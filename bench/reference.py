"""Plain references: a level-wise numpy histogram GBDT and a numpy traversal.

The trainer follows the algorithm of the repository's numpy baseline
(`benchmarks/baselines.py::train_numpy`): exact quantile cuts with linear
interpolation, searchsorted-left binning, a reserved missing bin, gains
1/2 [GL^2/(HL+lam) + GR^2/(HR+lam) - G^2/(H+lam)] - gamma over both routings
of the missing mass, a split where the best gain is > 0, leaf weight
-G/(H+lam) times eta. It grows one level of all nodes at a time, so a
histogram costs one `np.bincount` per feature per level over all rows.
Arithmetic is float64.

`precision="bfloat16"` is the control: gradients, histogram bins, leaf
weights and margins are held in bfloat16 (sums accumulate wider and are
stored in bfloat16), the step a lower-precision trainer would take.
`row_fraction=0.5` is a planted fault: histograms and leaf weights see a
seeded half of the rows, and the trees are applied to all rows.

Objectives: binary:logistic (g = p - y, h = p(1-p)), multi:softmax
(g_k = p_k - [y=k], h_k = p_k(1-p_k)) and reg:squarederror (g = m - y,
h = 1), each with margins starting at 0 (mean of y for regression).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _bf16(a):
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def _column_cuts(col: np.ndarray, max_bins: int) -> np.ndarray:
    nvb = max_bins - 1
    srt = np.sort(col[~np.isnan(col)]).astype(np.float64)
    if len(srt) == 0:
        return np.zeros(0)
    pos = np.arange(1, nvb) / nvb * (len(srt) - 1)
    lo = np.floor(pos).astype(np.int64)
    hi = np.minimum(lo + 1, len(srt) - 1)
    return np.unique(srt[lo] + (pos - lo) * (srt[hi] - srt[lo]))


def _column_bins(col: np.ndarray, cuts: np.ndarray, max_bins: int):
    b = np.searchsorted(cuts.astype(np.float32), col, side="left")
    return np.where(np.isnan(col), max_bins - 1, b)


def _per_column(fn, x: np.ndarray):
    """fn(contiguous column j) for every column, on a few threads (sort and
    searchsorted release the interpreter lock)."""
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as ex:
        return list(ex.map(lambda j: fn(j, np.ascontiguousarray(x[:, j])),
                           range(x.shape[1])))


def bin_matrix(x: np.ndarray, max_bins: int) -> np.ndarray:
    """(n_features, n_rows) bins. Per feature, the cuts are the
    max_bins - 2 interior quantiles of the finite values (np.quantile's
    linear rule), duplicates dropped; value bin b holds cuts[b-1] < x <=
    cuts[b]; NaN goes to the missing bin max_bins - 1."""
    dtype = np.uint8 if max_bins <= 256 else np.uint16
    return np.stack(_per_column(
        lambda j, col: _column_bins(col, _column_cuts(col, max_bins),
                                    max_bins).astype(dtype), x))


def _grad(objective: str, margins: np.ndarray, y: np.ndarray):
    """(g, h), each (n, k) float64."""
    if objective == "binary:logistic":
        p = 1.0 / (1.0 + np.exp(-margins[:, 0]))
        return (p - y)[:, None], (p * (1.0 - p))[:, None]
    if objective == "multi:softmax":
        z = margins - margins.max(axis=1, keepdims=True)
        e = np.exp(z)
        p = e / e.sum(axis=1, keepdims=True)
        onehot = np.zeros_like(p)
        onehot[np.arange(len(y)), y.astype(np.int64)] = 1.0
        return p - onehot, p * (1.0 - p)
    if objective == "reg:squarederror":
        return (margins[:, 0] - y)[:, None], np.ones((len(y), 1))
    raise ValueError(f"unsupported objective {objective!r}")


def loss(objective: str, margins: np.ndarray, y: np.ndarray) -> float:
    """Mean training loss of float64 margins (n, k)."""
    m = np.asarray(margins, np.float64)
    if objective == "binary:logistic":
        z = m[:, 0]
        return float(np.mean(np.logaddexp(0.0, z) - y * z))
    if objective == "multi:softmax":
        mx = m.max(axis=1)
        lse = mx + np.log(np.exp(m - mx[:, None]).sum(axis=1))
        return float(np.mean(lse - m[np.arange(len(y)), y.astype(np.int64)]))
    if objective == "reg:squarederror":
        return float(np.mean(0.5 * (m[:, 0] - y) ** 2))
    raise ValueError(f"unsupported objective {objective!r}")


def n_outputs(objective: str, num_class: int) -> int:
    return int(num_class) if objective == "multi:softmax" else 1


def base_margin(objective: str, y: np.ndarray) -> float:
    return float(np.mean(y)) if objective == "reg:squarederror" else 0.0


class Trainer:
    """Level-wise histogram GBDT on pre-binned rows.

    `bins` is (n_features, n_rows) from `binize`. `step()` grows one round
    (k trees from the round-start gradients) and returns the new margins.
    """

    def __init__(self, bins, y, *, objective, num_class=1, max_depth=6,
                 max_bins=256, eta=0.3, reg_lambda=1.0, gamma=0.0,
                 min_child_weight=1.0, precision="float64", row_fraction=1.0,
                 seed=0):
        if precision not in ("float64", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.bins, self.y = bins, np.asarray(y, np.float64)
        self.objective = objective
        self.k = n_outputs(objective, num_class)
        self.depth, self.max_bins = max_depth, max_bins
        self.eta, self.lam = eta, reg_lambda
        self.gamma, self.mcw = gamma, min_child_weight
        self.round = _bf16 if precision == "bfloat16" else (lambda a: a)
        n = bins.shape[1]
        self.margins = np.full((n, self.k), base_margin(objective, self.y))
        self.margins = self.round(self.margins)
        self.weight = None
        if row_fraction < 1.0:
            keep = np.random.default_rng(seed).random(n) < row_fraction
            self.weight = keep.astype(np.float64)

    def step(self) -> np.ndarray:
        g, h = _grad(self.objective, self.margins, self.y)
        g, h = self.round(g), self.round(h)
        if self.weight is not None:
            g, h = g * self.weight[:, None], h * self.weight[:, None]
        delta = np.stack([self._tree(g[:, c], h[:, c])
                          for c in range(self.k)], axis=1)
        self.margins = self.round(self.margins + delta)
        return self.margins

    def _hist(self, pos, nn, g, h, parent=None):
        """(nn, F, B) sums of g and of h per (node, feature, bin); rows
        with pos < 0 are out. Below the root only the left children are
        summed; a right child is its parent's histogram less its sibling's
        (`parent`: the previous level's pair)."""
        B, F = self.max_bins, self.bins.shape[0]
        if parent is None:
            sel, node, n_out = None, pos, nn
        else:
            sel = np.flatnonzero((pos >= 0) & (pos % 2 == 0))
            node, n_out = pos[sel] // 2, nn // 2
            g, h = g[sel], h[sel]
        key0 = node.astype(np.int64) * B
        G, H = np.empty((F, n_out, B)), np.empty((F, n_out, B))
        for f in range(F):
            key = key0 + (self.bins[f] if sel is None else self.bins[f][sel])
            G[f] = np.bincount(key, g, minlength=n_out * B).reshape(n_out, B)
            H[f] = np.bincount(key, h, minlength=n_out * B).reshape(n_out, B)
        G = self.round(G.transpose(1, 0, 2))
        H = self.round(H.transpose(1, 0, 2))
        if parent is None:
            return G, H
        out = []
        for left, up in ((G, parent[0]), (H, parent[1])):
            full = np.empty((nn, F, B))
            full[0::2], full[1::2] = left, self.round(up - left)
            out.append(full)
        return tuple(out)

    def _tree(self, g, h) -> np.ndarray:
        """One tree on gradient pairs (g, h); returns eta * leaf weight per
        row."""
        lam, B = self.lam, self.max_bins
        n = len(g)
        pos = np.zeros(n, np.int64)
        delta = np.zeros(n)
        parent = None
        for level in range(self.depth + 1):
            nn = 2 ** level
            node = np.where(pos >= 0, pos, nn)
            active = np.bincount(node, minlength=nn + 1)[:nn] > 0
            split = np.zeros(nn, bool)
            if level == self.depth:
                g_tot = self.round(np.bincount(node, g, minlength=nn + 1)[:nn])
                h_tot = self.round(np.bincount(node, h, minlength=nn + 1)[:nn])
            else:
                G, H = parent = self._hist(pos, nn, g, h, parent)
                g_tot = G[:, 0, :].sum(axis=1)  # every row sits in one bin
                h_tot = H[:, 0, :].sum(axis=1)
                feat, sbin, dleft, gain = self._best_splits(G, H, g_tot,
                                                            h_tot)
                split = active & np.isfinite(gain) & (gain > 0.0)
            leaf_w = self.round(-g_tot / (h_tot + lam))
            inner = pos >= 0
            leaf_rows = inner & ~split[np.where(inner, pos, 0)]
            delta[leaf_rows] = self.round(self.eta * leaf_w)[pos[leaf_rows]]
            if level == self.depth:
                break
            rows = np.flatnonzero(inner & ~leaf_rows)
            p = pos[rows]
            b = self.bins[feat[p], rows].astype(np.int64)
            left = np.where(b == B - 1, dleft[p], b <= sbin[p])
            pos[leaf_rows] = -1
            pos[rows] = 2 * p + np.where(left, 0, 1)
        return delta

    def _best_splits(self, G, H, g_tot, h_tot):
        lam, gamma, mcw = self.lam, self.gamma, self.mcw
        gt, ht = g_tot[:, None, None], h_tot[:, None, None]
        gl = np.cumsum(G[..., :-1], axis=-1)[..., :-1]  # split bins 0..B-3
        hl = np.cumsum(H[..., :-1], axis=-1)[..., :-1]
        gm, hm = G[..., -1:], H[..., -1:]
        parent = gt * gt / (ht + lam)

        def gain_of(gl_, hl_):
            gr_, hr_ = gt - gl_, ht - hl_
            with np.errstate(divide="ignore", invalid="ignore"):
                gn = 0.5 * (gl_ * gl_ / (hl_ + lam) + gr_ * gr_ / (hr_ + lam)
                            - parent) - gamma
            return np.where((hl_ >= mcw) & (hr_ >= mcw), gn, -np.inf)

        gain_r = gain_of(gl, hl)
        gain_l = gain_of(gl + gm, hl + hm)
        dl = gain_l > gain_r
        gain = np.maximum(gain_l, gain_r)
        nn = gain.shape[0]
        flat = gain.reshape(nn, -1)
        best = np.argmax(flat, axis=1)
        n_thresh = gain.shape[2]
        return (best // n_thresh, best % n_thresh,
                dl.reshape(nn, -1)[np.arange(nn), best],
                flat[np.arange(nn), best])


def train_rounds(x, y, cfg: dict, n_rounds: int, *, precision="float64",
                 row_fraction=1.0, seed=0) -> list[np.ndarray]:
    """Margins after each of the first n_rounds rounds (float64, (n, k))."""
    bins = bin_matrix(x, cfg["max_bin"])
    tr = Trainer(bins, y, objective=cfg["objective"],
                 num_class=cfg.get("num_class", 1), max_depth=cfg["max_depth"],
                 max_bins=cfg["max_bin"], eta=cfg["eta"],
                 reg_lambda=cfg["lambda"], gamma=cfg.get("gamma", 0.0),
                 min_child_weight=cfg.get("min_child_weight", 1.0),
                 precision=precision, row_fraction=row_fraction, seed=seed)
    return [tr.step().copy() for _ in range(n_rounds)]


# --- XGBoost JSON models: plain traversal ----------------------------------

def predict_json(model: dict, x: np.ndarray, *, precision="float64"):
    """Margins (n, n_groups) of an XGBoost JSON gbtree model on float rows:
    `x < split_condition` goes left, NaN follows `default_left`.

    `precision="bfloat16"` is the control: rows, thresholds and leaf values
    rounded to bfloat16, and the sum over trees held in bfloat16."""
    learner = model["learner"]
    gb = learner["gradient_booster"]["model"]
    n_groups = max(int(learner["learner_model_param"].get("num_class", "0")),
                   1)
    rnd = _bf16 if precision == "bfloat16" else (lambda a: a)
    xs = rnd(np.asarray(x, np.float64)) if precision == "bfloat16" \
        else np.asarray(x, np.float32).astype(np.float64)
    n = len(xs)
    out = np.zeros((n, n_groups))
    info = gb.get("tree_info", [0] * len(gb["trees"]))
    for tree, grp in zip(gb["trees"], info):
        lc = np.asarray(tree["left_children"])
        rc = np.asarray(tree["right_children"])
        sc = np.asarray(tree["split_conditions"], np.float32).astype(np.float64)
        si = np.asarray(tree["split_indices"])
        dl = np.asarray(tree["default_left"]).astype(bool)
        thr = rnd(sc)
        node = np.zeros(n, np.int64)
        while True:
            inner = lc[node] != -1
            if not inner.any():
                break
            r = np.flatnonzero(inner)
            nd = node[r]
            v = xs[r, si[nd]]
            go_left = np.where(np.isnan(v), dl[nd], v < thr[nd])
            node[r] = np.where(go_left, lc[nd], rc[nd])
        out[:, grp] = rnd(out[:, grp] + rnd(sc[node]))
    base = float(learner["learner_model_param"]["base_score"])
    obj = learner["objective"]["name"]
    if obj == "binary:logistic":
        base = np.log(base / (1.0 - base))
    return out + base


def transform(objective: str, margins: np.ndarray) -> np.ndarray:
    if objective == "binary:logistic":
        return 1.0 / (1.0 + np.exp(-margins[:, 0]))
    if objective == "reg:squarederror":
        return margins[:, 0]
    return margins
