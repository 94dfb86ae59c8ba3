"""Algorithm 1's multi-device path: shard_map + psum AllReduce equivalence.

These spawn subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest process keeps seeing 1 device (per the brief's carve-out).
"""
import os
import subprocess
import sys
import textwrap


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


def test_row_sharded_equals_single_device():
    """The distributed path is a strategy behind Booster.fit(mesh=...):
    same DeviceDMatrix in, same Booster object out, identical trees."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import Booster, BoosterConfig, DeviceDMatrix
        from repro.core.distributed import train_distributed
        rng = np.random.default_rng(2)
        n, f = 1024, 6
        x = rng.normal(size=(n, f)).astype(np.float32)
        y = (x @ rng.normal(size=f) > 0).astype(np.float32)
        cfg = BoosterConfig(n_rounds=4, max_depth=3,
                            objective="binary:logistic", max_bins=32)
        dtrain = DeviceDMatrix(x, label=y, max_bins=cfg.max_bins)
        st = Booster(cfg).fit(dtrain)
        from repro.dist import make_mesh
        mesh = make_mesh((8,), ("data",))
        bst = Booster(cfg).fit(dtrain, mesh=mesh)
        assert type(bst) is type(st)  # identical object shape out
        ens = bst.ensemble
        assert bool(jnp.all(st.ensemble.feature == ens.feature))
        assert bool(jnp.all(st.ensemble.split_bin == ens.split_bin))
        d = float(jnp.max(jnp.abs(st.ensemble.leaf_value - ens.leaf_value)))
        assert d < 1e-4, d
        # deprecated one-shot shim returns the same Booster type
        legacy = train_distributed(x, y, cfg, mesh)
        assert bool(jnp.all(legacy.ensemble.feature == ens.feature))
        print("ROW-SHARDED-OK")
    """)
    assert "ROW-SHARDED-OK" in out


def test_subsampled_row_sharded_equals_single_device():
    """Stochastic training under mesh=: shards derive the SAME row sample
    and feature masks from the shared (seed, round, class) key, so single-
    and multi-device subsampled fits grow identical trees (DESIGN.md §12)."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import Booster, BoosterConfig, DeviceDMatrix
        rng = np.random.default_rng(6)
        n, f = 2048, 6
        x = rng.normal(size=(n, f)).astype(np.float32)
        y = (x @ rng.normal(size=f) > 0).astype(np.float32)
        cfg = BoosterConfig(n_rounds=4, max_depth=3, max_bins=32,
                            objective="binary:logistic", subsample=0.5,
                            colsample_bytree=0.8, colsample_bylevel=0.9,
                            seed=13)
        dtrain = DeviceDMatrix(x, label=y, max_bins=cfg.max_bins)
        st = Booster(cfg).fit(dtrain)
        from repro.dist import make_mesh
        mesh = make_mesh((8,), ("data",))
        bst = Booster(cfg).fit(dtrain, mesh=mesh)
        assert bool(jnp.all(st.ensemble.feature == bst.ensemble.feature))
        assert bool(jnp.all(st.ensemble.split_bin == bst.ensemble.split_bin))
        d = float(jnp.max(jnp.abs(st.ensemble.leaf_value
                                  - bst.ensemble.leaf_value)))
        assert d < 1e-4, d
        # monotone constraints compute identically on every shard too
        cfg2 = BoosterConfig(n_rounds=3, max_depth=3, max_bins=32,
                             monotone_constraints=(1, 0, 0, 0, 0, -1))
        st2 = Booster(cfg2).fit(dtrain)
        bst2 = Booster(cfg2).fit(dtrain, mesh=mesh)
        assert bool(jnp.all(st2.ensemble.feature == bst2.ensemble.feature))
        assert bool(jnp.all(st2.ensemble.is_leaf == bst2.ensemble.is_leaf))
        print("SUBSAMPLED-SHARDED-OK")
    """)
    assert "SUBSAMPLED-SHARDED-OK" in out


def test_feature_sharded_equals_single_device():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core import tree as T
        from repro.core import quantile as Q
        import jax.nn
        rng = np.random.default_rng(3)
        n, f = 512, 8
        x = rng.normal(size=(n, f)).astype(np.float32)
        y = (x @ rng.normal(size=f) > 0).astype(np.float32)
        cuts = Q.compute_cuts(jnp.asarray(x), 32)
        bins = Q.quantize(jnp.asarray(x), cuts)
        p = jax.nn.sigmoid(jnp.zeros(n)); gh = jnp.stack([p - y, p*(1-p)], -1)
        ref = T.grow_tree(bins, gh, cuts, 4, 32)
        from repro.dist import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        fn = jax.jit(jax.shard_map(
            lambda b, g, c: T.grow_tree(b, g, c, 4, 32, axis_name="data",
                                        feature_axis="model"),
            mesh=mesh,
            in_specs=(P("data", "model"), P("data", None), P("model", None)),
            out_specs=P(), check_vma=False))
        tr = fn(bins, gh, cuts)
        assert bool(jnp.all(ref.feature == tr.feature))
        assert bool(jnp.all(ref.split_bin == tr.split_bin))
        assert bool(jnp.all(ref.is_leaf == tr.is_leaf))
        print("FEATURE-SHARDED-OK")
    """)
    assert "FEATURE-SHARDED-OK" in out


def test_hlo_analyzer_matches_analytic():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.dist import make_mesh
        from repro.launch.hlo_analysis import analyze
        mesh = make_mesh((2, 4), ("data", "model"))
        D, L, B = 64, 4, 8
        def fwd(x, ws):
            def body(c, w): return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return jnp.sum(y)
        xs = jax.ShapeDtypeStruct((B, D), jnp.float32)
        ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
        compiled = jax.jit(fwd, in_shardings=(
            NamedSharding(mesh, P("data", None)),
            NamedSharding(mesh, P(None, None, "model")),
        )).lower(xs, ws).compile()
        res = analyze(compiled.as_text())
        # per-device: L * (B/2) * D * (D/4) * 2
        assert res["dot_flops_per_device"] == L * (B // 2) * D * (D // 4) * 2, res
        assert res["collective_bytes_total"] > 0
        print("HLO-ANALYZER-OK")
    """)
    assert "HLO-ANALYZER-OK" in out
