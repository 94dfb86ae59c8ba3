"""Pallas TPU kernel: fused split-gain prefix scan (paper §2.3 EvaluateSplit).

The paper computes split gain "with a parallel prefix sum operation" over
the gradient histogram (Harris et al. scan). On TPU the scan itself is a
few microseconds of VPU work; the perf value of a kernel is *fusion* — one
pass over the VMEM-resident histogram computes the prefix sums, both
missing-direction gain variants, validity masking and the per-feature
argmax, writing back 4 floats per (node, feature) instead of materialising
(n, F, B) gain tensors in HBM (that is what the XLA path does).

Output per (node, feature): [best_gain, best_bin, default_left, hl_at_best].
The cross-feature argmax is a tiny follow-up reduction done by the caller.

Blocking: grid = (n_nodes, feature_blocks); block = full bin axis, so the
scan never crosses a block boundary. VMEM: (F_BLK=8, B=256, 2) f32 = 16 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(hist_ref, parent_ref, out_ref, *, reg_lambda, min_child_weight):
    h = hist_ref[...]  # (1, F_BLK, B, 2)
    g, hh = h[0, ..., 0], h[0, ..., 1]  # (F_BLK, B)
    p = parent_ref[...]  # (1, 2)
    g_tot, h_tot = p[0, 0], p[0, 1]
    g_miss, h_miss = g[:, -1:], hh[:, -1:]  # (F_BLK, 1)

    gl = jnp.cumsum(g[:, :-1], axis=-1)[:, :-1]  # (F_BLK, B-2)
    hl = jnp.cumsum(hh[:, :-1], axis=-1)[:, :-1]
    parent_gain = g_tot * g_tot / (h_tot + reg_lambda)

    def gain_of(gl_, hl_):
        gr_, hr_ = g_tot - gl_, h_tot - hl_
        gain = 0.5 * (
            gl_ * gl_ / (hl_ + reg_lambda)
            + gr_ * gr_ / (hr_ + reg_lambda)
            - parent_gain
        )
        ok = (hl_ >= min_child_weight) & (hr_ >= min_child_weight)
        return jnp.where(ok, gain, -jnp.inf)

    gain_r = gain_of(gl, hl)
    gain_l = gain_of(gl + g_miss, hl + h_miss)
    dl = gain_l > gain_r
    gain = jnp.maximum(gain_l, gain_r)  # (F_BLK, B-2)

    best = jnp.argmax(gain, axis=-1)  # (F_BLK,)
    take = lambda a: jnp.take_along_axis(a, best[:, None], axis=-1)[:, 0]
    bg, bdl = take(gain), take(dl)
    hl_best = take(hl) + jnp.where(bdl, h_miss[:, 0], 0.0)
    out_ref[...] = jnp.stack(
        [bg, best.astype(jnp.float32), bdl.astype(jnp.float32), hl_best], axis=-1
    )[None]


def split_scan(
    hist: jax.Array,  # (n_nodes, F, B, 2) f32
    parent_sum: jax.Array,  # (n_nodes, 2) f32
    reg_lambda: float = 1.0,
    min_child_weight: float = 1.0,
    *,
    f_blk: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (n_nodes, F, 4): [gain, bin, default_left, hl] per feature."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n_nodes, f, b, _ = hist.shape
    n_fblk = -(-f // f_blk)
    f_pad = n_fblk * f_blk - f
    hist_p = jnp.pad(hist, ((0, 0), (0, f_pad), (0, 0), (0, 0)))

    kern = functools.partial(
        _kernel, reg_lambda=reg_lambda, min_child_weight=min_child_weight
    )
    out = pl.pallas_call(
        kern,
        grid=(n_nodes, n_fblk),
        in_specs=[
            pl.BlockSpec((1, f_blk, b, 2), lambda n, fb: (n, fb, 0, 0)),
            pl.BlockSpec((1, 2), lambda n, fb: (n, 0)),
        ],
        out_specs=pl.BlockSpec((1, f_blk, 4), lambda n, fb: (n, fb, 0)),
        out_shape=jax.ShapeDtypeStruct((n_nodes, n_fblk * f_blk, 4), jnp.float32),
        name="split_scan",
        interpret=interpret,
    )(hist_p, parent_sum)
    return out[:, :f]
