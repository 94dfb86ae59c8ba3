"""Reference GBDT baselines for Table 2 comparisons.

The paper compares against LightGBM/CatBoost CPU+GPU; offline we implement
the two algorithmically-relevant baselines ourselves:

  * cpu_hist  — pure-numpy histogram GBDT (same quantised algorithm as the
                paper's xgb-cpu-hist row: one core, no JAX/XLA),
  * exact     — exact greedy split enumeration over sorted feature values
                (the classic pre-histogram xgboost method; the paper's
                motivation for quantisation is beating exactly this).

Both share the booster loop; only FindBestSplit differs. Binary logistic +
squared error + softmax supported (enough for the six datasets).
"""
from __future__ import annotations

import numpy as np


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _grad(objective, margins, y):
    if objective == "reg:squarederror":
        return margins[:, 0] - y, np.ones_like(y)
    if objective == "binary:logistic":
        p = _sigmoid(margins[:, 0])
        return p - y, p * (1 - p)
    raise ValueError(objective)


class _Node:
    __slots__ = ("feature", "thr", "left", "right", "value", "default_left",
                 "gain")

    def __init__(self):
        self.feature = -1
        self.thr = 0.0
        self.left = self.right = None
        self.value = 0.0
        self.default_left = False
        self.gain = 0.0


def _best_split_hist(x, g, h, idx, max_bins, cuts, bins, lam, mcw):
    best = (1e-12, -1, 0.0, False)
    g_tot, h_tot = g[idx].sum(), h[idx].sum()
    parent = g_tot**2 / (h_tot + lam)
    for f in range(x.shape[1]):
        b = bins[idx, f]
        gb = np.bincount(b, weights=g[idx], minlength=max_bins)
        hb = np.bincount(b, weights=h[idx], minlength=max_bins)
        gl = np.cumsum(gb[:-1])[:-1]
        hl = np.cumsum(hb[:-1])[:-1]
        gm, hm = gb[-1], hb[-1]
        for add_miss in (0, 1):
            gl2, hl2 = gl + add_miss * gm, hl + add_miss * hm
            gr2, hr2 = g_tot - gl2, h_tot - hl2
            ok = (hl2 >= mcw) & (hr2 >= mcw)
            gain = 0.5 * (gl2**2 / (hl2 + lam) + gr2**2 / (hr2 + lam) - parent)
            gain = np.where(ok, gain, -np.inf)
            j = int(np.argmax(gain))
            if gain[j] > best[0]:
                best = (float(gain[j]), f, float(cuts[f][j]) if j < len(cuts[f]) else np.inf,
                        bool(add_miss))
    return best


def _best_split_exact(x, g, h, idx, lam, mcw):
    best = (1e-12, -1, 0.0, False)
    g_tot, h_tot = g[idx].sum(), h[idx].sum()
    parent = g_tot**2 / (h_tot + lam)
    for f in range(x.shape[1]):
        v = x[idx, f]
        finite = ~np.isnan(v)
        order = np.argsort(v[finite])
        vs = v[finite][order]
        gs, hs = g[idx][finite][order], h[idx][finite][order]
        gm, hm = g[idx][~finite].sum(), h[idx][~finite].sum()
        glc, hlc = np.cumsum(gs)[:-1], np.cumsum(hs)[:-1]
        valid = vs[:-1] < vs[1:]  # split between distinct values
        for add_miss in (0, 1):
            gl = glc + add_miss * gm
            hl = hlc + add_miss * hm
            gr, hr = g_tot - gl, h_tot - hl
            ok = valid & (hl >= mcw) & (hr >= mcw)
            gain = 0.5 * (gl**2 / (hl + lam) + gr**2 / (hr + lam) - parent)
            gain = np.where(ok, gain, -np.inf)
            if len(gain) == 0:
                continue
            j = int(np.argmax(gain))
            if gain[j] > best[0]:
                best = (float(gain[j]), f, float((vs[j] + vs[j + 1]) / 2),
                        bool(add_miss))
    return best


def _grow(x, g, h, idx, depth, max_depth, lam, mcw, splitter):
    node = _Node()
    if depth >= max_depth or len(idx) < 2:
        node.value = -g[idx].sum() / (h[idx].sum() + lam)
        return node
    gain, f, thr, dl = splitter(idx)
    if f < 0 or gain <= 0:
        node.value = -g[idx].sum() / (h[idx].sum() + lam)
        return node
    v = x[idx, f]
    miss = np.isnan(v)
    left = (v <= thr) & ~miss
    if dl:
        left |= miss
    node.feature, node.thr, node.default_left, node.gain = f, thr, dl, gain
    node.left = _grow(x, g, h, idx[left], depth + 1, max_depth, lam, mcw, splitter)
    node.right = _grow(x, g, h, idx[~left], depth + 1, max_depth, lam, mcw, splitter)
    return node


def _predict_tree(node, x):
    out = np.empty(len(x))
    stack = [(node, np.arange(len(x)))]
    while stack:
        nd, idx = stack.pop()
        if nd.feature < 0:
            out[idx] = nd.value
            continue
        v = x[idx, nd.feature]
        miss = np.isnan(v)
        left = (v <= nd.thr) & ~miss
        if nd.default_left:
            left |= miss
        stack.append((nd.left, idx[left]))
        stack.append((nd.right, idx[~left]))
    return out


def train_numpy(x, y, *, method="hist", n_rounds=20, max_depth=6, lr=0.3,
                max_bins=256, objective="binary:logistic", lam=1.0, mcw=1.0):
    """Returns (predict_fn, margins) after training; `predict_fn.trees`
    holds the per-round root `_Node`s."""
    n = len(x)
    margins = np.zeros((n, 1), np.float64)
    if objective == "reg:squarederror":
        margins[:] = y.mean()

    if method == "hist":
        cuts, bins = [], np.empty(x.shape, np.int32)
        nvb = max_bins - 1
        for f in range(x.shape[1]):
            col = x[:, f]
            finite = col[~np.isnan(col)]
            qs = np.quantile(finite, np.linspace(0, 1, nvb + 1)[1:-1]) if len(finite) else np.array([])
            qs = np.unique(qs)
            cuts.append(qs)
            b = np.searchsorted(qs, col, side="left")
            bins[:, f] = np.where(np.isnan(col), max_bins - 1, b)

    trees = []
    for _ in range(n_rounds):
        g, h = _grad(objective, margins, y)
        if method == "hist":
            splitter = lambda idx: _best_split_hist(x, g, h, idx, max_bins, cuts, bins, lam, mcw)
        else:
            splitter = lambda idx: _best_split_exact(x, g, h, idx, lam, mcw)
        root = _grow(x, g, h, np.arange(n), 0, max_depth, lam, mcw, splitter)
        margins[:, 0] += lr * _predict_tree(root, x)
        trees.append(root)

    def predict(xq):
        m = np.zeros(len(xq))
        if objective == "reg:squarederror":
            m[:] = y.mean()
        for t in trees:
            m += lr * _predict_tree(t, xq)
        return m

    predict.trees = trees
    return predict, margins
