"""chip_smoke.py's phases at tiny sizes on CPU (Pallas in interpret mode),
its refusal to run without a TPU, and the compile-cache placement."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
from repro import compile_cache  # noqa: E402


def _env(**kw):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.update(kw)
    return env


def test_phase_small_agrees_with_numpy_reference():
    rec = cs.phase_small(n_rows=3000, n_rounds=3)
    assert rec["label_agreement"] >= cs.AGREE_MIN
    assert 0.6 < rec["accuracy"] <= 1.0


def test_phase_kernel_agrees_with_default_fit():
    rec = cs.phase_kernel(n_rows=3000, n_rounds=3)
    assert set(rec["hist_rel_err_by_level"]) == {1, 2, 4, 8, 16, 32}
    assert rec["label_agreement"] >= cs.AGREE_MIN


def test_phase_full_tiny():
    rec = cs.phase_full(n_rows=20_000, n_rounds=2, n_batches=1)
    assert rec["train_rows"] == 16_000 and rec["features"] == 28
    assert len(rec["valid_accuracy_by_round"]) == 2


def test_first_divergence_finds_the_first_parted_node():
    leaf = np.array([False, True, True])
    a = [(np.array([3, 0, 0]), np.array([0.5, 0, 0]), leaf,
          np.array([2.0, 0, 0]))]
    assert cs._first_divergence(a, a) is None
    b = [(np.array([4, 0, 0]),) + a[0][1:]]
    assert cs._first_divergence(a, b) == (0, 0, 2.0, 2.0)
    rng = np.random.default_rng(0)
    y = (rng.random(50) < 0.5).astype(np.float64)
    with pytest.raises(AssertionError, match="not a near-tie"):
        c = [a[0][:3] + (np.array([1.0, 0, 0]),)]
        cs._agreement("t", b, c, y, y, y)


def test_phase_sharded_on_four_virtual_devices():
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        rec = cs.phase_sharded(n_rows=20_000, n_devices=4, n_rounds=3)
        print(json.dumps(rec))
    """)
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
    )
    assert res.returncode == 0, res.stderr[-3000:]
    rec = json.loads(res.stdout.strip().splitlines()[-1])
    assert rec["packed_devices"] == rec["margin_devices"] == 4
    assert rec["words_per_shard"] == [rec["packed_shape"][1] // 4]


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_main_refuses_without_tpu(args):
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, env=_env(),
    )
    assert res.returncode != 0
    assert res.stdout == ""
    assert "needs a TPU, found platform 'cpu'" in res.stderr


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    res = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, capture_output=True,
        text=True, timeout=300, env=env,
    )
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_compile_cache_dir_choice():
    default = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}) == "/elsewhere"
    assert compile_cache.compile_cache_dir({}) == default
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    res = subprocess.run(
        [sys.executable, "-c",
         "from repro.compile_cache import enable_compile_cache; "
         "enable_compile_cache(); import jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.strip() == default


def test_compile_cache_written_only_where_the_variable_says(tmp_path):
    default = os.path.join(ROOT, ".jax_cache")
    before = set(os.listdir(default)) if os.path.isdir(default) else set()
    code = textwrap.dedent("""
        from repro.compile_cache import enable_compile_cache
        path = enable_compile_cache()
        import jax, jax.numpy as jnp
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda v: v * 2 + 1)(jnp.ones(3)).block_until_ready()
        print(path)
    """)
    cache = tmp_path / "cache"
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.strip() == str(cache)
    assert any(cache.iterdir())
    after = set(os.listdir(default)) if os.path.isdir(default) else set()
    assert after == before
