"""GOSS (gradient-based one-side sampling) — DESIGN.md §17.

sampling_method="goss" keeps the top_rate fraction of rows by |gradient|
and uniformly samples other_rate of the remainder per tree, reweighting
the sampled rest by (1 - top_rate) / other_rate. The selection is a pure
function of (seed, round, class, global |g|), so it replays identically
across resume/update(), device counts, and the in-memory / resident /
streamed executors.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Booster, BoosterConfig, DeviceDMatrix, ExternalDMatrix
from repro.core import sampling as SMP

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENSEMBLE_FIELDS = (
    "feature",
    "split_bin",
    "threshold",
    "default_left",
    "leaf_value",
    "is_leaf",
)


def assert_boosters_identical(b1, b2):
    e1, e2 = b1.ensemble, b2.ensemble
    for f in ENSEMBLE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(e1, f)),
            np.asarray(getattr(e2, f)),
            err_msg=f"ensemble field {f} differs",
        )


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(17)
    n, f = 3000, 8
    x = rng.standard_normal((n, f)).astype(np.float32)
    w = rng.standard_normal(f).astype(np.float32)
    y = (x @ w + 0.3 * rng.standard_normal(n) > 0).astype(np.float32)
    x[rng.random((n, f)) < 0.05] = np.nan
    return x, y, w


def _goss_kw(**over):
    kw = dict(
        n_rounds=6,
        max_depth=3,
        objective="binary:logistic",
        sampling_method="goss",
        top_rate=0.2,
        other_rate=0.1,
        seed=5,
    )
    kw.update(over)
    return kw


# --- config validation ------------------------------------------------------


def test_goss_config_validation():
    ok = dict(n_rounds=2, max_depth=2, objective="binary:logistic")
    with pytest.raises(ValueError, match="sampling_method"):
        BoosterConfig(**ok, sampling_method="lossguide")
    with pytest.raises(ValueError, match="top_rate"):
        BoosterConfig(**ok, sampling_method="goss", top_rate=0.0)
    with pytest.raises(ValueError, match="other_rate"):
        BoosterConfig(**ok, sampling_method="goss", other_rate=1.0)
    with pytest.raises(ValueError, match="must be <= 1.0"):
        BoosterConfig(**ok, sampling_method="goss", top_rate=0.7,
                      other_rate=0.6)
    with pytest.raises(ValueError, match="subsample"):
        BoosterConfig(**ok, sampling_method="goss", subsample=0.5)
    # the rates are inert under uniform sampling: no validation applies
    BoosterConfig(**ok, top_rate=0.0, other_rate=1.0)


def test_goss_selection_properties():
    """Unit contract of the selection kernel: exact sizes, top rows always
    kept, rest disjoint from top, pure function of (key, |g|, sizes)."""
    key = jax.random.key(3)
    g = jax.random.normal(jax.random.key(9), (500,))
    m_top, m_other = SMP.goss_sizes(
        500, SMP.StochasticParams(sampling_method="goss", top_rate=0.1,
                                  other_rate=0.2)
    )
    assert (m_top, m_other) == (50, 100)
    sel, rest = SMP.goss_selection(key, jnp.abs(g), m_top, m_other)
    sel, rest = np.asarray(sel), np.asarray(rest)
    assert sel.sum() == m_top + m_other
    assert rest.sum() == m_other
    top_ids = np.argsort(-np.abs(np.asarray(g)))[:m_top]
    assert sel[top_ids].all()
    assert not rest[top_ids].any()
    sel2, rest2 = SMP.goss_selection(key, jnp.abs(g), m_top, m_other)
    np.testing.assert_array_equal(sel, np.asarray(sel2))
    np.testing.assert_array_equal(rest, np.asarray(rest2))


# --- end-to-end determinism and executor parity -----------------------------


def test_goss_fit_deterministic_and_seed_sensitive(data):
    x, y, _ = data
    d = DeviceDMatrix(x, label=y)
    b1 = Booster(**_goss_kw()).fit(d)
    b2 = Booster(**_goss_kw()).fit(d)
    assert_boosters_identical(b1, b2)
    b3 = Booster(**_goss_kw(seed=6)).fit(d)
    with pytest.raises(AssertionError):
        assert_boosters_identical(b1, b3)
    # and GOSS actually changes the model vs full-data training
    b4 = Booster(**_goss_kw(sampling_method="uniform")).fit(d)
    with pytest.raises(AssertionError):
        assert_boosters_identical(b1, b4)


def test_goss_external_and_streamed_match_in_memory(data):
    """The same GOSS fit bit for bit across all three executors on shared
    cuts: in-memory, external resident (compiled chunked scan), external
    streamed (async pager)."""
    x, y, _ = data
    ext = ExternalDMatrix.from_arrays(
        x, y, chunk_rows=700, cuts="exact", paging="resident"
    )
    b_mem = Booster(**_goss_kw()).fit(DeviceDMatrix(x, label=y, cuts=ext.cuts))
    b_res = Booster(**_goss_kw()).fit(ext)
    b_str = Booster(**_goss_kw()).fit(
        ExternalDMatrix.from_arrays(
            x, y, chunk_rows=700, cuts="exact", paging="stream"
        )
    )
    assert_boosters_identical(b_mem, b_res)
    assert_boosters_identical(b_res, b_str)


def test_goss_update_continuation_matches_longer_fit(data):
    """The per-round key folds the ABSOLUTE round index, so update() replays
    the same selections a single longer fit would draw."""
    x, y, _ = data
    d = DeviceDMatrix(x, label=y)
    long = Booster(**_goss_kw(n_rounds=8)).fit(d)
    short = Booster(**_goss_kw(n_rounds=5)).fit(d)
    short.update(d, 3)
    assert_boosters_identical(long, short)


def test_goss_streamed_skips_rows_and_holds_accuracy(data):
    """The perf claim at test scale: GOSS touches a small fraction of the
    rows per round (top 10% + 10% of the rest) while staying competitive
    with full-data training on a holdout."""
    x, y, w = data
    rng = np.random.default_rng(23)
    xv = rng.standard_normal((1500, x.shape[1])).astype(np.float32)
    yv = (xv @ w + 0.3 * rng.standard_normal(1500) > 0).astype(np.float32)
    touched, errs = {}, {}
    for name, over in (
        ("full", dict(sampling_method="uniform")),
        ("goss", dict(top_rate=0.1, other_rate=0.1)),
    ):
        ext = ExternalDMatrix.from_arrays(
            x, y, chunk_rows=500, cuts="exact", paging="stream"
        )
        b = Booster(**_goss_kw(n_rounds=20, max_depth=4, **over)).fit(ext)
        touched[name] = ext.stream_stats.rows_touched
        errs[name] = float(
            np.mean((np.asarray(b.predict(xv)) > 0.5) != yv)
        )
    # >= 3x reduction in histogram rows touched (ISSUE acceptance bar)
    assert touched["goss"] <= touched["full"] / 3, touched
    assert errs["full"] < 0.35, errs
    assert errs["goss"] < errs["full"] + 0.05, errs


def test_goss_sharded_equals_single_device():
    """8-device GOSS parity, to the repo's distributed-stochastic
    convention: identical tree structure, leaf values within 1e-4 (compact
    single-device build vs masked sharded build associate f32 sums
    differently)."""
    script = """
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import Booster, BoosterConfig, DeviceDMatrix
        from repro.dist import make_mesh
        rng = np.random.default_rng(4)
        n, f = 1024, 6
        x = rng.normal(size=(n, f)).astype(np.float32)
        y = (x @ rng.normal(size=f) > 0).astype(np.float32)
        cfg = BoosterConfig(n_rounds=4, max_depth=3,
                            objective="binary:logistic", max_bins=32,
                            sampling_method="goss", top_rate=0.2,
                            other_rate=0.1, seed=11)
        dtrain = DeviceDMatrix(x, label=y, max_bins=cfg.max_bins)
        st = Booster(cfg).fit(dtrain)
        mesh = make_mesh((8,), ("data",))
        bst = Booster(cfg).fit(dtrain, mesh=mesh)
        for fld in ("feature", "split_bin", "default_left", "is_leaf"):
            a = getattr(st.ensemble, fld)
            b = getattr(bst.ensemble, fld)
            assert bool(jnp.all(a == b)), fld
        d = float(jnp.max(jnp.abs(st.ensemble.leaf_value
                                  - bst.ensemble.leaf_value)))
        assert d < 1e-4, d
        print("GOSS-SHARDED-OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "GOSS-SHARDED-OK" in res.stdout
