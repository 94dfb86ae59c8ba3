"""jit'd public wrappers + backend dispatch for the Pallas kernels.

Two hist_builder entry points for grow_tree(hist_builder=...):

* `build_histograms_kernel_packed` — the compressed-native path
  (BoosterConfig(use_kernel_histograms=True, compress_matrix=True)): the
  privatised Pallas kernel consumes the training matrix's packed uint32
  words directly, no unpack/repack round trip anywhere (DESIGN.md §2/§16).
* `build_histograms_kernel` — dense-input compatibility path
  (compress_matrix=False): packs once so the kernel still exercises its
  unpack-in-VMEM path; only sees uncompressed workloads.

This module is also where quantile-cut construction picks its backend
(`compute_cuts_op`): the sort stage goes to the host's np.sort on CPU (the
XLA CPU sort is ~an order of magnitude slower at 1M rows) and to the XLA
device sort elsewhere; the selection stage is the shared XLA selection
(`core.quantile.select_cuts_from_sorted`) on every backend. All paths emit
bit-identical cuts (tests/test_quantile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compress as C
from repro.core import quantile as Q
from repro.kernels.histogram import histogram_packed, build_histograms_packed_kernel
from repro.kernels.split_scan import split_scan
from repro.kernels.decompress import decompress
from repro.kernels.ensemble_traversal import ensemble_margins_kernel


@functools.partial(jax.jit, static_argnames=("n_nodes", "max_bins", "bits"))
def histogram_packed_op(packed, gh, positions, n_nodes: int, max_bins: int, bits: int):
    return histogram_packed(packed, gh, positions, n_nodes, max_bins, bits)


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "max_bins", "bits", "n_private", "buffer_depth"),
)
def histogram_private_op(
    packed, gh, positions, n_nodes: int, max_bins: int, bits: int,
    n_private: int = 8, buffer_depth: int = 2,
):
    """The privatised double-buffered kernel (DESIGN.md §16), jit'd."""
    return build_histograms_packed_kernel(
        packed, gh, positions, n_nodes, max_bins, bits,
        n_private=n_private, buffer_depth=buffer_depth,
    )


def build_histograms_kernel_packed(
    data: C.PackedBins,
    gh: jax.Array,
    positions: jax.Array,
    n_nodes: int,
    max_bins: int,
) -> jax.Array:
    """Packed-native drop-in for core.histogram.build_histograms_packed:
    feeds the training matrix's packed words straight to the privatised
    Pallas kernel."""
    return histogram_private_op(
        data.packed, gh, positions, n_nodes, max_bins, data.bits
    )


@functools.partial(jax.jit, static_argnames=("n_nodes", "max_bins"))
def build_histograms_kernel(
    bins: jax.Array,  # (n, f) int32 dense rows (compress_matrix=False path)
    gh: jax.Array,
    positions: jax.Array,
    n_nodes: int,
    max_bins: int,
) -> jax.Array:
    """Drop-in for core.histogram.build_histograms via the Pallas kernel.

    Packs the dense bins (cheap, fused by XLA) so the kernel exercises the
    same unpack-in-VMEM path it runs on TPU.
    """
    bits = C.bits_needed(max_bins - 1)
    packed = C.pack(bins, bits)
    return build_histograms_packed_kernel(
        packed, gh, positions, n_nodes, max_bins, bits
    )


@jax.jit
def _cuts_prep(x: jax.Array):
    """Missing-value fill + finite counts, shared by both sort backends."""
    x = x.astype(jnp.float32)
    finite = jnp.isfinite(x)
    return jnp.where(finite, x, jnp.inf), jnp.sum(finite, axis=0)


@functools.partial(jax.jit, static_argnames=("max_bins",))
def _compute_cuts_device(x: jax.Array, max_bins: int) -> jax.Array:
    """Fully on-device cut construction: XLA column sort, then the shared
    XLA selection."""
    filled, n_valid = _cuts_prep(x)
    srt = jnp.sort(filled, axis=0)
    return Q.select_cuts_from_sorted(srt, n_valid, max_bins)


def compute_cuts_op(x: jax.Array, max_bins: int) -> jax.Array:
    """Backend-dispatched compute_cuts (see module docstring). Bit-identical
    to core.quantile.compute_cuts_reference on every path.

    On CPU the sort stage runs on the HOST, at the Python level, between
    two jitted stages: numpy's cache-blocked introsort beats the XLA CPU
    sort by >10x at 1M rows and produces the identical array (same
    multiset per column; floats without NaN are totally ordered). It is
    deliberately NOT a pure_callback inside the jitted graph — a callback
    that materialises an intermediate of the executable that is invoking
    it (np.asarray on the operand) deadlocks the XLA CPU runtime, so the
    sort input is fetched only after `_cuts_prep` has fully completed.
    Under a jit trace (x is a Tracer) the eager host detour is impossible
    and the all-device path is used instead."""
    if isinstance(x, jax.core.Tracer) or jax.default_backend() != "cpu":
        return _compute_cuts_device(x, max_bins)
    filled, n_valid = _cuts_prep(x)
    srt = jnp.asarray(np.sort(np.asarray(filled), axis=0))
    return Q.select_cuts_from_sorted(srt, n_valid, max_bins)


def quantize_op(x: jax.Array, cuts: jax.Array) -> jax.Array:
    """Backend-dispatched quantize. Bit-identical to
    core.quantile.quantize_reference on every path.

    On CPU the per-column binary search runs on the host: numpy's
    searchsorted over the same ascending f32 cuts performs the identical
    sequence of exact float comparisons as the XLA lowering, but without
    XLA's gather/while overhead — ~15% faster at 1M rows and, more
    importantly for the DMatrix build, with zero compile time. NaN rows
    are overridden to the missing bin on both paths, so whatever either
    binary search returns for a NaN key never escapes. Under a jit trace
    (or off-CPU) the jitted reference runs instead."""
    if (
        isinstance(x, jax.core.Tracer)
        or isinstance(cuts, jax.core.Tracer)
        or jax.default_backend() != "cpu"
    ):
        return Q.quantize_reference(x, cuts)
    xn = np.asarray(x, np.float32)
    cn = np.asarray(cuts)
    n_cuts = cn.shape[1]
    out = np.empty(xn.shape, np.int32)
    for j in range(xn.shape[1]):
        col = xn[:, j]
        b = np.searchsorted(cn[j], col, side="left").astype(np.int32)
        out[:, j] = np.where(np.isnan(col), np.int32(n_cuts + 1), b)
    return jnp.asarray(out)


@functools.partial(jax.jit, static_argnames=("reg_lambda", "min_child_weight"))
def split_scan_op(hist, parent_sum, reg_lambda: float = 1.0, min_child_weight: float = 1.0):
    return split_scan(hist, parent_sum, reg_lambda, min_child_weight)


@functools.partial(jax.jit, static_argnames=("bits", "n_rows"))
def decompress_op(packed, bits: int, n_rows: int):
    return decompress(packed, bits, n_rows)


@functools.partial(jax.jit, static_argnames=("n_classes", "max_depth"))
def ensemble_margins_op(
    feature, threshold, default_left, leaf_value, is_leaf,
    x, n_classes: int, max_depth: int,
):
    """Raw-input serving margins (minus base_score) via the fused
    ensemble-traversal kernel (one launch for all trees x all rows)."""
    return ensemble_margins_kernel(
        feature, threshold, default_left, leaf_value, is_leaf,
        x, n_classes, max_depth,
    )
