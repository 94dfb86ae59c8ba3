"""Compiles for a described TPU v5e, with no chip attached.

Interpret mode cannot see what the TPU compiler refuses: blocks and DMA
slices that are not (8, 128)-aligned, layouts Mosaic cannot broadcast,
fast memory over the scoped VMEM limit. These tests compile the main
path's device programs at the Higgs width (28 features, 256 bins) for one
chip of a described `v5e:2x2`. Nothing runs; a compile that passes is not
a chip run.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import predict as PR
from repro.core import quantile as Q
from repro.kernels.histogram import build_histograms_packed_kernel
from repro.serve import traversal as ST

N_FEATURES, MAX_BINS, BITS = 28, 256, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_cut_selection_compiles_without_kernel(one_chip):
    """Cut selection as the off-CPU cut path runs it (the shared XLA
    selection), at the largest size the Pallas selection kernel used to
    be dispatched for."""
    srt = _spec((131072, N_FEATURES), jnp.float32, one_chip)
    n_valid = _spec((N_FEATURES,), jnp.int32, one_chip)
    compiled = Q.select_cuts_from_sorted.lower(
        srt, n_valid, max_bins=MAX_BINS).compile()
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("n_nodes", [1, 32])
def test_histogram_kernel_compiles(one_chip, n_nodes):
    """The privatised histogram kernel at the root and at depth 6's widest
    level, four row chunks per private group."""
    n_rows = 16384
    args = (
        _spec((N_FEATURES, n_rows * BITS // 32), jnp.uint32, one_chip),
        _spec((n_rows, 2), jnp.float32, one_chip),
        _spec((n_rows,), jnp.int32, one_chip),
    )
    fn = jax.jit(lambda packed, gh, pos: build_histograms_packed_kernel(
        packed, gh, pos, n_nodes, MAX_BINS, BITS, interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_rows", [275_000, 2_200_000])
def test_fused_traversal_compiles_at_the_scoring_size(one_chip, n_rows):
    """The dense fused traversal of 500 depth-6 trees over raw f32 rows at
    the Higgs width: one `higgs.score` call (275,000 rows) and the whole
    2.2M-row holdout each fit one chip (the compiler refuses a program over
    its memory)."""
    n_trees, depth = 500, 6
    arena = 2 ** (depth + 1) - 1
    field = {k: _spec((n_trees, arena), dt, one_chip) for k, dt in (
        ("feature", jnp.int32), ("split_bin", jnp.int32),
        ("threshold", jnp.float32), ("default_left", jnp.bool_),
        ("leaf_value", jnp.float32), ("is_leaf", jnp.bool_),
        ("gain", jnp.float32))}
    ens = PR.Ensemble(**field)
    x = _spec((n_rows, N_FEATURES), jnp.float32, one_chip)
    ST.predict_margins_fused.lower(ens, x, depth).compile()
