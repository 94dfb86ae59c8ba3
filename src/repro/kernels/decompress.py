"""Pallas TPU kernel: runtime bit-unpack of the compressed matrix (paper §2.2).

"Values are packed and unpacked at runtime using bitwise operations ... the
small number of bitwise operations computed on the GPU incur no visible
performance penalty." The TPU story is identical: the VPU shifts/masks a
(F_BLK, W_BLK) word tile in VMEM into a (F_BLK, W_BLK*spw) bin tile. Used
standalone for prediction-side unpacking; the histogram kernel fuses the
same unpack inline (never materialising bins in HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(packed_ref, out_ref, *, bits: int):
    words = packed_ref[...]  # (F_BLK, W_BLK)
    spw = 32 // bits
    shifts = (jnp.arange(spw, dtype=jnp.uint32) * bits)[None, None, :]
    mask = jnp.uint32((1 << bits) - 1)
    bins = ((words[:, :, None] >> shifts) & mask)
    out_ref[...] = bins.reshape(words.shape[0], -1).astype(jnp.int32)


def decompress(
    packed: jax.Array,  # (F, W) uint32
    bits: int,
    n_rows: int,
    *,
    f_blk: int = 8,
    w_blk: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns bins (n_rows, F) int32 (transposed to row-major like unpack)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    f, w = packed.shape
    spw = 32 // bits
    n_fblk, n_wblk = -(-f // f_blk), -(-w // w_blk)
    packed_p = jnp.pad(packed, ((0, n_fblk * f_blk - f), (0, n_wblk * w_blk - w)))

    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        grid=(n_fblk, n_wblk),
        in_specs=[pl.BlockSpec((f_blk, w_blk), lambda fb, wb: (fb, wb))],
        out_specs=pl.BlockSpec((f_blk, w_blk * spw), lambda fb, wb: (fb, wb)),
        out_shape=jax.ShapeDtypeStruct(
            (n_fblk * f_blk, n_wblk * w_blk * spw), jnp.int32
        ),
        name="decompress",
        interpret=interpret,
    )(packed_p)
    return out[:f, :n_rows].T
