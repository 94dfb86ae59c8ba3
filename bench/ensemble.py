"""A serving ensemble made from a seed, as an XGBoost JSON model.

The scoring and serving cells score with a model trained elsewhere, so no
training is paid in set-up: `random_model` builds an `xgboost.Booster` JSON
document (the `save_model("*.json")` schema) of full trees. Each split
feature is uniform over the columns, each threshold is a value of that
feature in a random training row (so both sides are taken), each default
direction is a coin flip and each leaf value is uniform in +-leaf_scale.
"""
from __future__ import annotations

import numpy as np


def random_model(x_train: np.ndarray, *, trees: int, depth: int,
                 leaf_scale: float, seed: int,
                 objective: str = "binary:logistic") -> dict:
    rng = np.random.default_rng(seed)
    n_rows, n_features = x_train.shape
    n_inner, n_nodes = 2 ** depth - 1, 2 ** (depth + 1) - 1
    feat = rng.integers(0, n_features, (trees, n_inner))
    rows = rng.integers(0, n_rows, (trees, n_inner))
    thr = x_train[rows, feat].astype(np.float32)
    dleft = rng.integers(0, 2, (trees, n_inner))
    leaves = rng.uniform(-leaf_scale, leaf_scale,
                         (trees, n_nodes - n_inner)).astype(np.float32)
    ids = np.arange(n_nodes)
    left = np.where(ids < n_inner, 2 * ids + 1, -1).tolist()
    right = np.where(ids < n_inner, 2 * ids + 2, -1).tolist()
    parents = [2147483647] + ((ids[1:] - 1) // 2).tolist()
    out = []
    for t in range(trees):
        cond = np.concatenate([thr[t], leaves[t]]).tolist()
        out.append({
            "base_weights": [0.0] * n_nodes,
            "categories": [], "categories_nodes": [],
            "categories_segments": [], "categories_sizes": [],
            "default_left": dleft[t].tolist() + [0] * (n_nodes - n_inner),
            "id": t,
            "left_children": left,
            "loss_changes": [0.0] * n_nodes,
            "parents": parents,
            "right_children": right,
            "split_conditions": cond,
            "split_indices": feat[t].tolist() + [0] * (n_nodes - n_inner),
            "split_type": [0] * n_nodes,
            "sum_hessian": [0.0] * n_nodes,
            "tree_param": {"num_deleted": "0",
                           "num_feature": str(n_features),
                           "num_nodes": str(n_nodes),
                           "size_leaf_vector": "1"},
        })
    return {
        "learner": {
            "attributes": {},
            "feature_names": [], "feature_types": [],
            "gradient_booster": {
                "model": {
                    "gbtree_model_param": {"num_parallel_tree": "1",
                                           "num_trees": str(trees)},
                    "iteration_indptr": list(range(trees + 1)),
                    "tree_info": [0] * trees,
                    "trees": out,
                },
                "name": "gbtree",
            },
            "learner_model_param": {"base_score": "5E-1",
                                    "num_class": "0",
                                    "num_feature": str(n_features),
                                    "num_target": "1"},
            "objective": {"name": objective},
        },
        "version": [2, 0, 0],
    }
