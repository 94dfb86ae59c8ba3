"""Device-sharded sketch construction (repro.dist.sketch, DESIGN.md §15).

Host-side properties of the log-depth tree merge (associativity / shard-count
invariance in the exact regime, rank-error bounds under pruning, push_sorted
equivalence) run in-process; the shard_map device-sort phase runs in an
8-virtual-device subprocess, mirroring tests/test_distributed.py.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantile as Q
from repro.core.dmatrix import ExternalDMatrix
from repro.dist import sharded_sketch_cuts, tree_merge

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    return res.stdout


def _shard_sketches(x, shards, max_bins=128, capacity=4096):
    out = []
    for part in np.array_split(x, shards):
        sk = Q.StreamingQuantileSketch(x.shape[1], max_bins, capacity)
        sk.push(part)
        out.append(sk)
    return out


def test_tree_merge_shard_count_invariance_exact(rng):
    """Exact summaries merge exactly, so 2/4/8-shard tree merges and the
    single sequential sketch all produce bitwise-identical cuts."""
    n, f = 1600, 5
    x = rng.normal(size=(n, f)).astype(np.float32)
    x[rng.random((n, f)) < 0.05] = np.nan
    x[:, 2] = rng.integers(0, 4, n)  # low cardinality

    ref = Q.StreamingQuantileSketch(f, 128, 4096).push(x).get_cuts()
    for shards in (2, 4, 8):
        merged = tree_merge(_shard_sketches(x, shards)).get_cuts()
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(merged))


def test_tree_merge_order_invariance_exact(rng):
    """Any permutation of the shard list tree-merges to the same cuts in
    the exact regime (associativity + commutativity of exact combine)."""
    n, f = 1200, 4
    x = rng.normal(size=(n, f)).astype(np.float32)
    base = tree_merge(_shard_sketches(x, 4)).get_cuts()
    for perm in ([3, 1, 0, 2], [2, 3, 0, 1], [1, 0, 3, 2]):
        sketches = _shard_sketches(x, 4)
        merged = tree_merge([sketches[i] for i in perm]).get_cuts()
        np.testing.assert_array_equal(np.asarray(base), np.asarray(merged))


def test_sharded_cuts_rank_error_bound(rng):
    """Cuts from a pruned sharded sketch stay within a GK-style rank-error
    bound of compute_cuts' exact quantiles: each finite cut's empirical
    rank deviates from its target by at most a small multiple of
    n/capacity per merge level."""
    n, capacity, shards = 40000, 256, 8
    col = (rng.standard_normal(n) ** 3).astype(np.float32)
    x = col[:, None]
    cuts = np.asarray(
        sharded_sketch_cuts(x, max_bins=64, capacity=capacity,
                            n_shards=shards)
    )[0]
    finite = cuts[np.isfinite(cuts)]
    assert finite.size == Q.n_value_bins(64) - 1  # all cuts used
    srt = np.sort(col)
    nvb = Q.n_value_bins(64)
    # Tree depth log2(8)=3 prune rounds + per-shard pushes; headroom x2.
    eps = 2.0 * (shards + 3) / capacity
    for b, v in enumerate(finite):
        target = (b + 1) / nvb * (n - 1)
        true_rank = np.searchsorted(srt, v)
        assert abs(true_rank - target) <= eps * n, (b, true_rank, target)


def test_push_sorted_equals_push(rng):
    """push_sorted on device-style presorted columns (NaN -> +inf tail)
    builds the same summaries as push on the raw rows."""
    n, f = 900, 6
    x = rng.normal(size=(n, f)).astype(np.float32)
    x[rng.random((n, f)) < 0.1] = np.nan
    x[:, 4] = np.nan  # all-missing feature

    a = Q.StreamingQuantileSketch(f, 64, 512).push(x)
    filled = np.where(np.isfinite(x), x, np.inf)
    b = Q.StreamingQuantileSketch(f, 64, 512).push_sorted(
        np.sort(filled, axis=0), np.isfinite(x).sum(axis=0)
    )
    np.testing.assert_array_equal(np.asarray(a.get_cuts()),
                                  np.asarray(b.get_cuts()))
    assert a.n_pushed == b.n_pushed

    with pytest.raises(ValueError, match="cols_sorted"):
        Q.StreamingQuantileSketch(f, 64, 512).push_sorted(
            np.zeros((4, f + 1), np.float32), np.zeros(f + 1)
        )
    with pytest.raises(ValueError, match="n_valid"):
        Q.StreamingQuantileSketch(f, 64, 512).push_sorted(
            np.zeros((4, f), np.float32), np.zeros(f - 1)
        )


def test_sharded_cuts_quantise_like_compute_cuts(rng):
    """With adequate capacity the host-sharded build reproduces
    compute_cuts exactly, so quantisation is bit-identical."""
    n, f = 2000, 5
    x = rng.normal(size=(n, f)).astype(np.float32)
    x[rng.random((n, f)) < 0.02] = np.nan
    exact = np.asarray(Q.compute_cuts(jnp.asarray(x), 64))
    sharded = np.asarray(
        sharded_sketch_cuts(x, max_bins=64, capacity=8192, n_shards=4)
    )
    np.testing.assert_allclose(exact, sharded, rtol=1e-6, atol=0)
    be = np.asarray(Q.quantize(jnp.asarray(x), jnp.asarray(exact)))
    bs = np.asarray(Q.quantize(jnp.asarray(x), jnp.asarray(sharded)))
    np.testing.assert_array_equal(be, bs)


def test_external_dmatrix_sketch_shards(rng):
    """ExternalDMatrix(sketch_shards=) routes cut generation through the
    tree merge; in the exact-capacity regime it matches the sequential
    sketch build bit for bit."""
    n, f = 3000, 4
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = rng.normal(size=n).astype(np.float32)
    seq = ExternalDMatrix.from_arrays(x, y, chunk_rows=500,
                                      sketch_capacity=8192)
    shd = ExternalDMatrix.from_arrays(x, y, chunk_rows=500,
                                      sketch_capacity=8192, sketch_shards=3)
    np.testing.assert_array_equal(np.asarray(seq.cuts), np.asarray(shd.cuts))
    with pytest.raises(ValueError, match="sketch_shards"):
        ExternalDMatrix.from_arrays(x, y, chunk_rows=500, sketch_shards=0)


def test_device_phase_sharded_sketch():
    """The shard_map device-sort phase: mesh-sharded sketch cuts match the
    host tree-merge and (at high capacity) compute_cuts, and a
    DeviceDMatrix(cuts=) fit on them trains normally."""
    out = _run("""
        import numpy as np, jax.numpy as jnp
        from repro.core import Booster, DeviceDMatrix
        from repro.core.quantile import compute_cuts
        from repro.dist import sharded_sketch_cuts
        from repro.dist import make_mesh
        rng = np.random.default_rng(11)
        n, f = 4096, 6
        x = rng.normal(size=(n, f)).astype(np.float32)
        x[rng.random((n, f)) < 0.03] = np.nan
        y = np.nan_to_num(x[:, 0] * 2 + x[:, 1]).astype(np.float32)
        mesh = make_mesh((8,), ("data",))
        dev = np.asarray(sharded_sketch_cuts(
            x, max_bins=64, capacity=8192, mesh=mesh))
        host = np.asarray(sharded_sketch_cuts(
            x, max_bins=64, capacity=8192, n_shards=8))
        np.testing.assert_array_equal(dev, host)
        exact = np.asarray(compute_cuts(jnp.asarray(x), 64))
        np.testing.assert_allclose(exact, dev, rtol=1e-6, atol=0)
        d = DeviceDMatrix(x, label=y, max_bins=64, cuts=dev)
        b = Booster(n_rounds=3, max_depth=3, max_bins=64).fit(d)
        p = np.asarray(b.predict(x))
        assert np.isfinite(p).all()
        print("DEVICE-SKETCH-OK")
    """)
    assert "DEVICE-SKETCH-OK" in out
