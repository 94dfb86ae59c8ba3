"""Program observation: the named scopes of the round and of the fused
traversal reach the lowered program, and `repro.obs` nests spans, bounds
its call records and counts each program obtained under the open span."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import Booster, BoosterConfig, DeviceDMatrix
from repro.core import booster as B
from repro.serve import PredictEngine
from repro.serve import traversal as ST

OP_NAME = re.compile(r'op_name="([^"]+)"')
# Name-stack parts JAX adds itself (jit(...), loop and call bodies).
WRAPPER = re.compile(r"^(\w+\(.*\)|while|body|cond|closed_call|scan)$")


def _scope_paths(lowered) -> set[str]:
    """The program's scope path of every operation of the compiled module,
    as its metadata (and so a profiler trace's `tf_op`) carries it."""
    out = set()
    for name in OP_NAME.findall(lowered.compile().as_text()):
        parts = [p for p in name.split("/")[:-1] if not WRAPPER.match(p)]
        out.add("/".join(parts))
    return out


def _has(paths, scope: str) -> bool:
    """Some path holds `scope` ("a/b") as whole consecutive parts."""
    want = f"/{scope}/"
    return any(want in f"/{p}/" for p in paths)


@pytest.fixture(scope="module")
def small():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(600, 5)).astype(np.float32)
    y = (x[:, 0] - x[:, 2] > 0).astype(np.float32)
    return x, DeviceDMatrix(x, label=y, max_bins=16)


def test_lowered_round_names_its_phases(small):
    _, d = small
    cfg = BoosterConfig(n_rounds=1, max_depth=3, max_bins=16)
    bst = Booster(cfg).fit(d)
    fn = B._make_train_fn(cfg, bst.obj, d.cuts, None, (), False,
                          n_rounds=1)
    paths = _scope_paths(fn.func.lower(d.cuts, d.packed_bins(), bst.margins,
                                       d.label, {}))
    for scope in ("round/gradient", "round/margins", "level0/histogram",
                  "level1/histogram", "level2/split", "level1/repartition",
                  "split"):
        assert _has(paths, scope), scope


def test_lowered_traversal_names_route_and_lookup(small):
    x, d = small
    bst = Booster(BoosterConfig(n_rounds=3, max_depth=3, max_bins=16)).fit(d)
    paths = _scope_paths(ST.predict_margins_fused.lower(
        bst.ensemble, jnp.asarray(x), 3))
    for scope in ("traverse/route", "traverse/lookup", "traverse/leaf",
                  "traverse/fold"):
        assert _has(paths, scope), scope
    packed = _scope_paths(ST.predict_margins_fused_packed.lower(
        bst.ensemble, d.matrix.packed, d.bits, d.n_rows, 15, 3))
    assert _has(packed, "traverse/route") and _has(packed, "traverse/lookup")


def test_spans_nest_and_compiles_land_under_the_innermost():
    arg = jnp.arange(7.0)
    with obs.call("test.nest") as rec:
        with obs.span("outer"):
            with obs.span("inner"):
                jax.jit(lambda v: v * 3.0 + 1.0)(arg).block_until_ready()
            jax.jit(lambda v: v * 5.0 - 2.0)(arg).block_until_ready()
    assert rec["compiles"] == {"inner": 1, "outer": 1}
    assert obs.compiles(rec) == 2
    assert obs.calls("test.nest")[-1] is rec
    assert rec["seconds"] > 0


def test_a_program_loaded_from_the_cache_counts_once(tmp_path):
    """JAX reports a persistent-cache load twice (the obtain-duration event
    and a cache hit); the record counts the program once."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    hits = []

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    jax.monitoring.register_event_listener(on_event)
    try:
        for k, v in zip(keys, (str(tmp_path), 0.0, -1)):
            jax.config.update(k, v)
        cc.reset_cache()
        arg = jnp.arange(5.0)
        with obs.call("test.cache") as compiled:
            jax.jit(lambda v: v * 7.0 + 3.0)(arg).block_until_ready()
        jax.clear_caches()
        with obs.call("test.cache") as loaded:
            jax.jit(lambda v: v * 7.0 + 3.0)(arg).block_until_ready()
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert hits, "the second call did not load from the cache"
    assert compiled["compiles"] == {"test.cache": 1}
    assert loaded["compiles"] == {"test.cache": 1}


def test_no_call_open_counts_nothing():
    before = [dict(r["compiles"]) for r in obs.calls("test.idle")]
    jax.jit(lambda v: v - 11.0)(jnp.ones(3)).block_until_ready()
    assert [r["compiles"] for r in obs.calls("test.idle")] == before


def test_records_are_bounded():
    for i in range(obs.MAXLEN + 5):
        with obs.call("test.bounded") as rec:
            rec["i"] = i
    got = obs.calls("test.bounded")
    assert len(got) == obs.MAXLEN
    assert got[0]["i"] == 5 and got[-1]["i"] == obs.MAXLEN + 4


def test_a_call_that_raises_is_not_stored():
    n = len(obs.calls("test.raises"))
    with pytest.raises(ValueError):
        with obs.call("test.raises"):
            raise ValueError("no")
    assert len(obs.calls("test.raises")) == n


def test_update_records_its_compiles(small):
    _, d = small
    bst = Booster(BoosterConfig(n_rounds=5, max_depth=4, max_bins=16)).fit(d)
    n = len(obs.calls("update"))
    bst.update(d, 1)
    recs = obs.calls("update")
    assert len(recs) == n + 1 or len(recs) == obs.MAXLEN
    rec = recs[-1]
    # A fresh ensemble size: the concatenations compile; a fresh chunk
    # length: so does the round program.
    assert obs.compiles(rec) > 0
    assert set(rec["compiles"]) <= {"ensemble.append", "round.dispatch"}
    assert rec["compiles"].get("ensemble.append", 0) >= 1


def test_engine_records_are_bounded(small):
    x, d = small
    bst = Booster(BoosterConfig(n_rounds=2, max_depth=3, max_bins=16)).fit(d)
    eng = PredictEngine(bst, buckets=(16,)).warmup()
    for _ in range(obs.MAXLEN + 3):
        eng.predict(x[:4])
    assert len(eng.calls) == obs.MAXLEN
    s = eng.stats()
    assert set(s) == {"n_calls", "rows", "p50_ms", "p99_ms", "rows_per_s"}
    assert s["n_calls"] == obs.MAXLEN and s["rows"] == 4 * obs.MAXLEN
