"""Pallas TPU kernel: fused ensemble traversal for batch inference.

The serving path (`repro.serve.traversal`) evaluates every split node of a
tree block densely over the rows and picks each leaf by a path one-hot,
in XLA elementwise operations with no per-row gather. This kernel walks
the trees level by level instead and, exactly as the histogram kernel
recasts atomicAdd scatter (DESIGN.md §4), recasts the walk's two per-row
lookups (the arena record of the row's node, the row's feature value) as
dense **one-hot matmuls on the MXU**:

    node one-hot  (TB, RB, A) @ arena field (TB, A)  -> per-pair select
    feat one-hot  (TB, RB, F) @ row block   (RB, F)  -> per-pair value

Per level that is four batched mat-vecs (feature id, threshold,
default-direction, leaf flag) plus one value select; after `max_depth`
levels a final one-hot select reads the leaf values and a small
(TB, K) class-assignment matmul folds the tree block's contribution into
the (RB, K) margin accumulator.

Blocking:
  grid = (row_blocks, tree_blocks)        tree axis innermost, sequential
  arena fields   (TREES_BLK, A) f32       A padded to a lane multiple
  row block      (ROWS_BLK, F) f32        values NaN-sanitised by wrapper
  out block      (ROWS_BLK, K) f32        accumulated across tree blocks
                                          (@pl.when(tb==0) init, += after)

NaN handling: 0 * NaN = NaN would poison the one-hot contraction, so the
wrapper splits the input into a zero-filled value plane and a {0,1}
missing-mask plane; the kernel reads missingness through the same one-hot
matmul as the values. Arena thresholds on inactive slots are sanitised to
finite placeholders for the same reason (leaf masking makes their value
irrelevant to routing).

Raw-threshold mode only: serving traffic arrives as float rows, and
imported XGBoost models carry no cut points. The bin-space fused path
stays on the XLA form in serve/traversal.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _select(noh: jax.Array, field: jax.Array) -> jax.Array:
    """One-hot arena select: (TB, RB, A) x (TB, A) -> (TB, RB)."""
    return jax.lax.dot_general(
        noh, field,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )


def _kernel(
    feature_ref,  # (TB, A) f32 (exact small ints)
    threshold_ref,  # (TB, A) f32, finite everywhere
    default_left_ref,  # (TB, A) f32 {0, 1}
    is_leaf_ref,  # (TB, A) f32 {0, 1}
    leaf_value_ref,  # (TB, A) f32
    class_oh_ref,  # (TB, K) f32; all-zero row = padding tree
    x_ref,  # (RB, F) f32, NaN replaced by 0
    miss_ref,  # (RB, F) f32 {0, 1} NaN mask
    out_ref,  # (RB, K) f32 margin accumulator
    *,
    max_depth: int,
):
    tb = pl.program_id(1)
    feature = feature_ref[...]
    threshold = threshold_ref[...]
    default_left = default_left_ref[...]
    is_leaf = is_leaf_ref[...]
    x = x_ref[...]
    miss = miss_ref[...]
    trees_blk, arena = feature.shape
    rows_blk, n_feat = x.shape

    iota_a = jnp.arange(arena, dtype=jnp.int32)[None, None, :]
    iota_f = jnp.arange(n_feat, dtype=jnp.float32)[None, None, :]

    def level(_, node):
        noh = (node[:, :, None] == iota_a).astype(jnp.float32)  # (TB, RB, A)
        f_id = _select(noh, feature)  # exact: small ints in f32
        foh = (f_id[:, :, None] == iota_f).astype(jnp.float32)  # (TB, RB, F)
        # Batch dims lead the dot_general output: (RB, TB) -> transpose.
        v = jax.lax.dot_general(
            foh, x,
            dimension_numbers=(((2,), (1,)), ((1,), (0,))),
            preferred_element_type=jnp.float32,
        ).T  # (TB, RB)
        is_missing = jax.lax.dot_general(
            foh, miss,
            dimension_numbers=(((2,), (1,)), ((1,), (0,))),
            preferred_element_type=jnp.float32,
        ).T > 0.5
        go_left = jnp.where(
            is_missing, _select(noh, default_left) > 0.5,
            v <= _select(noh, threshold),
        )
        child = jnp.where(go_left, 2 * node + 1, 2 * node + 2)
        return jnp.where(_select(noh, is_leaf) > 0.5, node, child)

    node = jnp.zeros((trees_blk, rows_blk), jnp.int32)
    node = jax.lax.fori_loop(0, max_depth, level, node)
    noh = (node[:, :, None] == iota_a).astype(jnp.float32)
    leaf = _select(noh, leaf_value_ref[...])  # (TB, RB)

    # Fold this tree block into per-class margins: (RB, TB) @ (TB, K).
    part = jnp.dot(
        leaf.T, class_oh_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(tb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += part


def ensemble_margins_kernel(
    feature: jax.Array,  # (T, A) int32
    threshold: jax.Array,  # (T, A) f32
    default_left: jax.Array,  # (T, A) bool
    leaf_value: jax.Array,  # (T, A) f32
    is_leaf: jax.Array,  # (T, A) bool
    x: jax.Array,  # (N, F) f32, NaN = missing
    n_classes: int,
    max_depth: int,
    *,
    trees_blk: int = 32,
    rows_blk: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Margins (n_rows, n_classes) WITHOUT base_score (caller adds it, as
    core.predict's _fold_classes does). Round-robin multiclass layout."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n_trees, arena = feature.shape
    n_rows, n_feat = x.shape

    trees_blk = min(trees_blk, max(n_trees, 1))
    n_tblk = -(-n_trees // trees_blk)
    n_rblk = -(-n_rows // rows_blk)
    t_pad = n_tblk * trees_blk - n_trees
    r_pad = n_rblk * rows_blk - n_rows
    a_pad = (-arena) % 128  # lane-align the one-hot contraction dim

    def pad_field(a, value, dtype):
        return jnp.pad(
            a.astype(dtype), ((0, t_pad), (0, a_pad)), constant_values=value
        )

    # Padding trees are all-leaf with zero class weight; padded arena slots
    # are unreachable leaves. Inactive-slot thresholds sanitised to 0 so the
    # one-hot contraction never multiplies 0 * inf.
    feature_p = pad_field(feature, 0, jnp.float32)
    threshold_p = pad_field(jnp.nan_to_num(threshold), 0.0, jnp.float32)
    default_p = pad_field(default_left, 0.0, jnp.float32)
    leaf_val_p = pad_field(jnp.nan_to_num(leaf_value), 0.0, jnp.float32)
    is_leaf_p = pad_field(is_leaf, 1.0, jnp.float32)

    # Round-robin class id per tree, zero row for padding trees.
    cls = jnp.arange(n_tblk * trees_blk, dtype=jnp.int32) % n_classes
    class_oh = (
        (cls[:, None] == jnp.arange(n_classes, dtype=jnp.int32)[None, :])
        & (jnp.arange(n_tblk * trees_blk)[:, None] < n_trees)
    ).astype(jnp.float32)

    x_p = jnp.pad(x.astype(jnp.float32), ((0, r_pad), (0, 0)))
    miss_p = jnp.isnan(x_p).astype(jnp.float32)
    x_p = jnp.nan_to_num(x_p)

    kern = functools.partial(_kernel, max_depth=max_depth)
    a_full = arena + a_pad
    out = pl.pallas_call(
        kern,
        grid=(n_rblk, n_tblk),
        in_specs=[
            pl.BlockSpec((trees_blk, a_full), lambda rb, tb: (tb, 0)),
            pl.BlockSpec((trees_blk, a_full), lambda rb, tb: (tb, 0)),
            pl.BlockSpec((trees_blk, a_full), lambda rb, tb: (tb, 0)),
            pl.BlockSpec((trees_blk, a_full), lambda rb, tb: (tb, 0)),
            pl.BlockSpec((trees_blk, a_full), lambda rb, tb: (tb, 0)),
            pl.BlockSpec((trees_blk, n_classes), lambda rb, tb: (tb, 0)),
            pl.BlockSpec((rows_blk, n_feat), lambda rb, tb: (rb, 0)),
            pl.BlockSpec((rows_blk, n_feat), lambda rb, tb: (rb, 0)),
        ],
        out_specs=pl.BlockSpec((rows_blk, n_classes), lambda rb, tb: (rb, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n_rblk * rows_blk, n_classes), jnp.float32
        ),
        name="ensemble_traversal",
        interpret=interpret,
    )(
        feature_p, threshold_p, default_p, is_leaf_p, leaf_val_p,
        class_oh, x_p, miss_p,
    )
    return out[:n_rows]
