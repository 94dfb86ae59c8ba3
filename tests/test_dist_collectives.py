"""Pluggable collectives + compressed histogram allreduce (repro.dist,
DESIGN.md §15).

Multi-device equivalence and compression behaviour run in 8-virtual-device
subprocesses (mirroring tests/test_distributed.py); registry validation and
the analytic CommStats wire model run in-process.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro import dist
from repro.dist import make_mesh

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


def test_every_collective_matches_single_device():
    """fit(mesh=, collective=) in f32 mode: ring, hierarchical (1-axis
    factored and 2-axis mesh) all grow the same trees as the single-device
    fit — same features/split bins, leaf values to float tolerance."""
    out = _run("""
        import numpy as np, jax.numpy as jnp
        from repro.core import Booster, BoosterConfig, DeviceDMatrix
        from repro.dist import make_mesh
        rng = np.random.default_rng(5)
        n, f = 2048, 8
        x = rng.normal(size=(n, f)).astype(np.float32)
        y = (x @ rng.normal(size=f) > 0).astype(np.float32)
        cfg = BoosterConfig(n_rounds=4, max_depth=3, max_bins=32,
                            objective="binary:logistic")
        d = DeviceDMatrix(x, label=y, max_bins=cfg.max_bins)
        st = Booster(cfg).fit(d)
        mesh = make_mesh((8,), ("data",))
        mesh2 = make_mesh((4, 2), ("data", "pod"))
        runs = [
            (mesh, ("data",), "ring"),
            (mesh, ("data",), "hier"),
            (mesh2, ("data", "pod"), "hier"),
        ]
        for m, axes, name in runs:
            b = Booster(cfg).fit(d, mesh=m, data_axes=axes, collective=name)
            assert bool(jnp.all(st.ensemble.feature == b.ensemble.feature)), name
            assert bool(jnp.all(st.ensemble.split_bin
                                == b.ensemble.split_bin)), name
            diff = float(jnp.max(jnp.abs(st.ensemble.leaf_value
                                         - b.ensemble.leaf_value)))
            assert diff < 1e-4, (name, diff)
            cs = b.comm_stats
            assert cs["collective"] == name
            assert cs["compression"] is None
            assert cs["bytes_per_round"] > 0
            assert cs["fallback_events"] == 0
            # one hist allreduce per level + the root sum, per tree
            assert cs["collective_calls_per_round"] == cfg.max_depth + 1
        print("COLLECTIVES-F32-OK")
    """)
    assert "COLLECTIVES-F32-OK" in out


def test_compressed_allreduce_trains_within_tolerance():
    """f16/q16 compressed histogram allreduce: eval metric within tolerance
    of the exact fit, comm bytes/round at least halved on the ring, and the
    q16 integer reduction identical across ring and psum topologies."""
    out = _run("""
        import numpy as np, jax.numpy as jnp
        from repro.core import Booster, BoosterConfig, DeviceDMatrix
        from repro.dist import make_mesh
        rng = np.random.default_rng(7)
        n, f = 4096, 10
        x = rng.normal(size=(n, f)).astype(np.float32)
        y = (x[:, 0] * 2 + x[:, 1] + 0.1 * rng.normal(size=n)).astype(
            np.float32)
        cfg = BoosterConfig(n_rounds=5, max_depth=4, max_bins=64)
        d = DeviceDMatrix(x, label=y, max_bins=cfg.max_bins)
        mesh = make_mesh((8,), ("data",))
        exact = Booster(cfg).fit(d, mesh=mesh, collective="ring")
        p_exact = np.asarray(exact.predict(x))
        rmse_exact = float(np.sqrt(np.mean((p_exact - y) ** 2)))
        for comp in ("f16", "q16"):
            b = Booster(cfg).fit(d, mesh=mesh, collective="ring",
                                 compression=comp)
            p = np.asarray(b.predict(x))
            rmse = float(np.sqrt(np.mean((p - y) ** 2)))
            assert abs(rmse - rmse_exact) <= 0.05 * rmse_exact + 1e-4, (
                comp, rmse, rmse_exact)
            cs = b.comm_stats
            assert cs["compression"] == comp
            # the compressed histogram payload is exactly halved; the f32
            # side-channel scalars keep the TOTAL just under 2x
            hist = sum(cs["hist_bytes_per_level"])
            hist_f32 = 2 * hist  # 2-byte wire vs 4-byte wire, same model
            assert cs["bytes_per_round_f32"] - cs["bytes_per_round"] >= (
                hist_f32 - hist) * 0.999, cs
            assert cs["bytes_per_round_f32"] >= 1.95 * cs["bytes_per_round"], cs
            assert cs["fallback_events"] == 0, cs
        # q16 is an exact integer allreduce after shared scaling: the
        # reduction is order-independent, so ring and psum grow
        # bit-identical trees.
        rq = Booster(cfg).fit(d, mesh=mesh, collective="ring",
                              compression="q16")
        pq = Booster(cfg).fit(d, mesh=mesh, collective="psum",
                              compression="q16")
        assert bool(jnp.all(rq.ensemble.feature == pq.ensemble.feature))
        assert bool(jnp.all(rq.ensemble.split_bin == pq.ensemble.split_bin))
        assert bool(jnp.all(rq.ensemble.leaf_value == pq.ensemble.leaf_value))
        print("COMPRESSED-OK")
    """)
    assert "COMPRESSED-OK" in out


def test_fallback_on_adversarial_gradients():
    """Near-zero tolerance forces the on-device error check to reject the
    compressed payload every level: the fit falls back to exact f32
    (bit-identical trees to compression=None) and comm_stats counts every
    fallback. A loose tolerance on adversarial wide-range gradients still
    triggers at least one fallback for f16."""
    out = _run("""
        import numpy as np, jax.numpy as jnp
        from repro import dist
        from repro.core import Booster, BoosterConfig, DeviceDMatrix
        from repro.dist import make_mesh
        rng = np.random.default_rng(9)
        n, f = 2048, 6
        x = rng.normal(size=(n, f)).astype(np.float32)
        y = (x[:, 0] + 0.1 * rng.normal(size=n)).astype(np.float32)
        cfg = BoosterConfig(n_rounds=2, max_depth=3, max_bins=32)
        d = DeviceDMatrix(x, label=y, max_bins=cfg.max_bins)
        mesh = make_mesh((8,), ("data",))
        exact = Booster(cfg).fit(d, mesh=mesh, collective="ring")
        tight = dist.get_collective("ring", mesh, ("data",),
                                    compression="q16", tolerance=0.0)
        b = Booster(cfg).fit(d, mesh=mesh, collective=tight)
        # every hist allreduce fell back: rounds * levels
        assert b.comm_stats["fallback_events"] == cfg.n_rounds * cfg.max_depth, (
            b.comm_stats)
        assert bool(jnp.all(exact.ensemble.feature == b.ensemble.feature))
        assert bool(jnp.all(exact.ensemble.split_bin == b.ensemble.split_bin))
        assert bool(jnp.all(exact.ensemble.leaf_value
                            == b.ensemble.leaf_value))
        # Adversarial dynamic range: targets spanning ~6 orders of
        # magnitude give f16-unrepresentable bin sums -> fallbacks fire
        # even at a practical tolerance.
        y2 = (y * np.where(rng.random(n) < 0.01, 3e4, 1e-3)).astype(
            np.float32)
        d2 = DeviceDMatrix(x, label=y2, max_bins=cfg.max_bins)
        b2 = Booster(cfg).fit(d2, mesh=mesh, collective="ring",
                              compression="f16", comm_tolerance=1e-4)
        assert b2.comm_stats["fallback_events"] > 0, b2.comm_stats
        print("FALLBACK-OK")
    """)
    assert "FALLBACK-OK" in out


# --- in-process: registry + analytic wire model ----------------------------


def test_registry_resolution_and_errors():
    mesh = make_mesh((1,), ("data",))
    c = dist.get_collective("psum", mesh, ("data",))
    assert isinstance(c, dist.PsumCollective)
    assert dist.get_collective(c, mesh, ("data",)) is c  # instance passthrough
    c2 = dist.get_collective(dist.RingCollective, mesh, ("data",))
    assert isinstance(c2, dist.RingCollective)
    assert set(dist.collective_names()) >= {"psum", "ring", "hier"}

    with pytest.raises(ValueError, match="unknown collective"):
        dist.get_collective("allgather", mesh, ("data",))
    with pytest.raises(TypeError, match="collective must be"):
        dist.get_collective(42, mesh, ("data",))
    with pytest.raises(ValueError, match="compression"):
        dist.get_collective("psum", mesh, ("data",), compression="int4")
    with pytest.raises(ValueError, match="tolerance"):
        dist.get_collective("psum", mesh, ("data",), tolerance=-0.5)
    with pytest.raises(TypeError, match="subclass"):
        dist.register_collective("bad", int)

    class MyColl(dist.PsumCollective):
        name = "mine"

    dist.register_collective("mine", MyColl)
    assert isinstance(dist.get_collective("mine", mesh, ("data",)), MyColl)

    mesh2 = make_mesh((1, 1), ("data", "pod"))
    with pytest.raises(ValueError, match="one mesh axis"):
        dist.RingCollective(mesh2, ("data", "pod"))


def test_hier_group_geometry_validation():
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="divide"):
        dist.HierarchicalCollective(mesh, ("data",), group_size=3)
    mesh2 = make_mesh((1, 1), ("data", "pod"))
    with pytest.raises(ValueError, match="conflicts"):
        dist.HierarchicalCollective(mesh2, ("data", "pod"), group_size=7)
    c = dist.HierarchicalCollective(mesh2, ("data", "pod"))
    assert (c.n_hosts, c.group_size) == (1, 1)


def test_comm_stats_wire_model():
    """The analytic byte model: psum/ring move 2*(p-1)*N*B total; ring
    compression halves the hist payload; CommStats serialises cleanly."""

    class FakeMesh:  # duck-typed: only .shape is consulted
        shape = {"data": 8}

    mesh = FakeMesh()
    f32 = dist.get_collective("ring", mesh, ("data",))
    f16 = dist.get_collective("ring", mesh, ("data",), compression="f16")
    n_elems = 4 * 64 * 2  # one level: nodes * features-ish payload
    assert f32.bytes_allreduce(n_elems, 4) == 2 * 7 * 8 * (n_elems // 8) * 4
    assert f16.wire_bytes_elem() == 2
    s32 = dist.round_comm_stats(f32, max_depth=6, n_features=13, max_bins=256)
    s16 = dist.round_comm_stats(f16, max_depth=6, n_features=13, max_bins=256)
    assert s32.bytes_per_round == s32.bytes_per_round_f32
    assert s16.bytes_per_round_f32 == s32.bytes_per_round_f32
    # hist payload dominates, so halving the wire dtype ~halves the round
    assert s16.bytes_per_round < 0.51 * s32.bytes_per_round
    assert len(s16.hist_bytes_per_level) == 6
    assert s16.collective_calls_per_round > s32.collective_calls_per_round
    d = s16.as_dict()
    assert d["collective"] == "ring" and d["compression"] == "f16"
    assert isinstance(d["hist_bytes_per_level"], list)
    # q16 through plain psum cannot narrow the wire (int32 partials) — the
    # model reports no saving, steering users to ring/hier.
    q_psum = dist.get_collective("psum", mesh, ("data",), compression="q16")
    assert q_psum.wire_bytes_elem() == 4
    # hierarchical: intra stays f32, inter ring shrinks
    h16 = dist.get_collective("hier", mesh, ("data",), compression="f16")
    h32 = dist.get_collective("hier", mesh, ("data",))
    assert h16.bytes_allreduce(1024, 2) < h32.bytes_allreduce(1024, 4)
