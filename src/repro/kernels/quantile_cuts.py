"""Pallas TPU kernel: quantile cut selection from sorted columns (paper §2.1).

The paper moves quantile sketch construction on-device because it is a
considerable preprocessing cost; profiling here agrees — cut construction
dominated DMatrix build time (BENCH `phases` section). The build splits
into two stages (DESIGN.md §16):

  sort      — per-feature ascending sort of the NaN->+inf-filled column.
              Stays outside the kernel: on CPU it dispatches to the host's
              cache-blocked `np.sort` (ops.sort_columns_op), on TPU to the
              XLA device sort.
  selection — weighted-rank selection + linear interpolation + dedup of
              the interior boundaries of `n_value_bins` equal-mass bins.
              THIS kernel: grid over feature blocks, one (n, F_BLK) sorted
              block resident in VMEM, rank gathers + the interpolation
              arithmetic of `core.quantile.select_cuts_from_sorted`
              executed per feature on the VPU.

The kernel reproduces the reference selection arithmetic operation for
operation (same f32 interpolation, same guards, same dedup); parity with
`select_cuts_from_sorted` is to ~1 ulp of arithmetic — compiled XLA may
contract `lo + frac*(hi-lo)` into an FMA where the kernel's evaluation
does not — which can additionally flip a floor() at an exact integer rank
boundary and select the neighbouring order statistic (still a valid
boundary for the same equal-mass bin). The final ascending re-sort of the
candidate vector is left to the caller, as in the reference.

No training path calls this kernel: `kernels.ops.compute_cuts_op` uses the
shared XLA selection on every backend. The TPU compiler refuses this
kernel as written — its (n, F_BLK=8) block is not lane-aligned and the
body gathers with `jnp.take` — so it runs only in interpret mode, in its
parity tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(
    srt_ref,  # (n, F_BLK) f32, each column ascending, +inf tail
    nv_ref,  # (1, F_BLK) i32, finite count per column
    out_ref,  # (F_BLK, n_cuts) f32, pre-sort candidate cuts
    *,
    max_bins: int,
):
    n, f_blk = srt_ref.shape
    nvb = max_bins - 1  # n_value_bins(max_bins)
    # iota (not arange) and roll (not concatenate): the kernel body may not
    # capture trace-time constant arrays, only generate values in-kernel.
    ranks = jax.lax.iota(jnp.float32, nvb - 1) + 1.0

    for fi in range(f_blk):  # static unroll: F_BLK small
        col = srt_ref[:, fi]  # (n,)
        nv = nv_ref[0, fi]
        # Identical arithmetic to core.quantile.select_cuts_from_sorted.
        qs = (ranks / nvb) * jnp.maximum(nv - 1, 1).astype(jnp.float32)
        lo = jnp.clip(jnp.floor(qs).astype(jnp.int32), 0, n - 1)
        hi = jnp.clip(lo + 1, 0, n - 1)
        frac = qs - lo.astype(jnp.float32)
        lov = jnp.take(col, lo)
        hiv = jnp.take(col, hi)
        hiv = jnp.where(jnp.isfinite(hiv), hiv, lov)
        cand = lov + frac * (hiv - lov)
        cand = jnp.where(jnp.isfinite(cand), cand, jnp.inf)
        # prev[0] = -inf, prev[i] = cand[i-1]: a one-step roll re-pinned at 0.
        prev = jnp.roll(cand, 1).at[0].set(-jnp.inf)
        cand = jnp.where(cand > prev, cand, jnp.inf)
        out_ref[fi, :] = cand


@functools.partial(jax.jit, static_argnames=("max_bins", "f_blk", "interpret"))
def quantile_cuts_from_sorted(
    srt: jax.Array,  # (n, F) f32 column-sorted, +inf at the tail
    n_valid: jax.Array,  # (F,) int finite count per column
    max_bins: int,
    *,
    f_blk: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Selection stage of compute_cuts on pre-sorted columns.

    Returns (F, n_value_bins - 1) f32 ascending cuts with +inf padding —
    the exact `compute_cuts` output format.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n, f = srt.shape
    nvb = max_bins - 1
    n_cuts = nvb - 1
    n_fblk = -(-f // f_blk)
    f_pad = n_fblk * f_blk - f

    # Padding features are all-+inf / zero-valid columns; their cuts come
    # out +inf and are sliced off.
    srt_p = jnp.pad(srt, ((0, 0), (0, f_pad)), constant_values=jnp.inf)
    nv_p = jnp.pad(n_valid.astype(jnp.int32), (0, f_pad))[None, :]

    kern = functools.partial(_kernel, max_bins=max_bins)
    out = pl.pallas_call(
        kern,
        grid=(n_fblk,),
        in_specs=[
            pl.BlockSpec((n, f_blk), lambda fb: (0, fb)),
            pl.BlockSpec((1, f_blk), lambda fb: (0, fb)),
        ],
        out_specs=pl.BlockSpec((f_blk, n_cuts), lambda fb: (fb, 0)),
        out_shape=jax.ShapeDtypeStruct((n_fblk * f_blk, n_cuts), jnp.float32),
        name="quantile_cuts",
        interpret=interpret,
    )(srt_p, nv_p)
    # Final ascending re-sort (pushes +inf dedup markers to the tail), same
    # as the reference's trailing jnp.sort.
    return jnp.sort(out[:f], axis=-1)
