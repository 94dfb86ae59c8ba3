"""The scope reduction (`bench/scopes.py`): device self time per program
scope, counted once through nested operations; idle time per host span;
the metadata decoder against `ProfileData`; the per-layer readers that
read it, on a scoped trace recorded on one v5e chip."""
import importlib.util
import os

import pytest

from bench import scopes, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SCOPED = os.path.join(DATA, "tpu_scoped.xplane.pb")
OLD = os.path.join(DATA, "tpu_small.xplane.pb")
NEW_READERS = ("round.histogram_ms", "round.repartition_ms",
               "round.split_ms", "round.margins_ms", "update.host_gap_ms",
               "traverse.route_ns_per_row", "traverse.lookup_ns_per_row")
OLD_READERS = ("build.cuts_s", "build.quantize_pack_s", "idle_share.train",
               "idle_share.score", "mfu.train", "mfu.score")


def _reader(name):
    path = os.path.join(ROOT, "bench", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("t_" + name.replace(
        ".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _bytes(path):
    with open(path, "rb") as fh:
        return fh.read()


def _synthetic():
    """A `while` (histogram) whose body runs an unscoped copy and a split,
    then a gradient and an unscoped op; host spans as one update makes."""
    return {
        "devices": [[("level0/histogram", 10, 50),       # the while
                     ("level0/histogram", 12, 20),
                     ("", 22, 30),                        # copy in the body
                     ("level0/split", 30, 45),
                     ("gradient", 60, 70),
                     ("", 80, 90)]],
        "spans": [("bench.window", 0, 100),
                  ("bench.update", 0, 100),
                  ("repro.update", 0, 95),
                  ("repro.round.dispatch", 0, 55),
                  ("repro.round.wait", 55, 92),
                  ("repro.ensemble.append", 72, 78)],
    }


def test_self_time_counts_nested_ops_once():
    red = scopes.reduce(_synthetic())
    ns = {k: v * 1e9 for k, v in red["self_s"].items()}
    assert ns == pytest.approx({"level0/histogram": 25, "level0/split": 15,
                                "gradient": 10, "": 10})
    assert red["busy_s"] * 1e9 == pytest.approx(60)
    assert sum(red["self_s"].values()) == pytest.approx(red["busy_s"])
    assert red["scoped_share"] == pytest.approx(50 / 60)
    assert scopes.under(red["self_s"], "histogram") * 1e9 == \
        pytest.approx(25)
    assert scopes.under(red["self_s"], "traverse") is None
    assert scopes.per_level(red["self_s"], "split") == \
        pytest.approx({"level0": 15e-9})


def test_an_unnamed_loop_takes_its_body_scope():
    """On the TPU a `while` carries no tf_op: its own time goes to the
    scope of its body; an unnamed op inside it takes the loop's."""
    trace = {"devices": [[("", 0, 40),                  # the while
                          ("", 2, 5),                   # a copy in it
                          ("level2/histogram", 5, 30),
                          ("", 50, 60)]],               # alone: unnamed
             "spans": [("bench.window", 0, 100)]}
    ns = {k: v * 1e9 for k, v in scopes.reduce(trace)["self_s"].items()}
    assert ns == pytest.approx({"level2/histogram": 40, "": 10})


def test_idle_time_split_at_span_edges():
    red = scopes.reduce(_synthetic())
    ns = {k: v * 1e9 for k, v in red["gap_s"].items()}
    assert ns == pytest.approx({"repro.round.dispatch": 15,
                                "repro.round.wait": 11,
                                "repro.ensemble.append": 6,
                                "repro.update": 3, "bench.update": 5})
    assert scopes.program_gaps(red, "repro.round.wait") * 1e9 == \
        pytest.approx(24)


def test_no_window_or_no_device_gives_nothing():
    t = _synthetic()
    assert scopes.reduce({"devices": [], "spans": t["spans"]}) is None
    assert scopes.reduce({"devices": t["devices"], "spans": []}) is None


def test_scope_of_drops_jax_wrappers_and_the_primitive():
    assert scopes.scope_of("jit(train_fn)/while/body/closed_call/level3/"
                           "histogram/jit(build)/while/body/scatter-add") \
        == "level3/histogram"
    assert scopes.scope_of("jit(<lambda>)/jit(sort)/sort") == ""
    assert scopes.scope_of("") == "" and scopes.scope_of(None) == ""


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(num, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _xspace(entries):
    """A serialized XSpace of one TPU plane whose event metadata are
    `entries`, (id, name, program id, tf_op) each."""
    stat_md = [(1, "tf_op"), (2, "program_id")]
    plane = _field(2, "/device:TPU:0")
    for mid, name, program, tf_op in entries:
        md = _field(1, mid) + _field(2, name)
        md += _field(5, _field(1, 2) + _field(3, program))
        md += _field(5, _field(1, 1) + _field(5, tf_op))
        plane += _field(4, _field(1, mid) + _field(2, md))
    for sid, name in stat_md:
        plane += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                   + _field(2, name)))
    return _field(1, plane)


def test_decoder_keys_metadata_by_program():
    """Two programs may name an instruction alike: each keeps its own
    scope; a key repeated with another tf_op is counted, not hidden."""
    buf = _xspace([(1, "%copy.1", 11, "jit(a)/level0/histogram/copy:"),
                   (2, "%copy.1", 22, "jit(b)/traverse/route/copy:"),
                   (3, "%fusion", 11, "jit(a)/margins/add:"),
                   (4, "%fusion", 11, "jit(a)/split/add:")])
    ops, conflicts = scopes.metadata_tf_ops(buf)["/device:TPU:0"]
    assert ops == {(11, "%copy.1"): "jit(a)/level0/histogram/copy",
                   (22, "%copy.1"): "jit(b)/traverse/route/copy",
                   (11, "%fusion"): "jit(a)/margins/add"}
    assert conflicts == 1


def test_an_op_belongs_to_the_module_it_runs_in():
    modules = [(0, 10, 11), (10, 30, 22), (40, 50, 11)]
    assert [scopes._program_of(modules, t) for t in (0, 9, 10, 29, 35, 45)] \
        == [11, 11, 22, 22, None, 11]
    assert scopes._program_of([], 5) is None


def test_decoder_on_the_old_trace():
    """A trace of a program that names no scope: every device event is
    matched to its metadata, no time is scoped, the readers give None."""
    tf_ops = scopes.metadata_tf_ops(_bytes(OLD))
    assert set(tf_ops) == {"/device:TPU:0"}
    ops, conflicts = tf_ops["/device:TPU:0"]
    assert "jit(<lambda>)/jit(sort)/sort" in ops.values() and conflicts == 0
    red = scopes.reduce(scopes.load_bytes(_bytes(OLD)))
    assert red["unmatched"] == 0 and red["conflicts"] == 0
    assert red["scoped_share"] == 0.0 and red["program_spans"] == 0
    ctx = {"result": {"rounds": 3, "rows": 10, "attempted": 3},
           "xplane": _bytes(OLD), "notes": {}}
    for name in NEW_READERS:
        assert _reader(name)(ctx) is None, name


def test_xplane_reduce_pinned_on_the_old_trace():
    """The existing reduction reads the recorded trace as it always has."""
    red = xplane.reduce(xplane.load(OLD))
    assert red["busy_s"] == pytest.approx(0.000174911, rel=1e-12)
    assert red["window_s"] == pytest.approx(0.011674199, rel=1e-12)
    assert red["device_ops"] == [
        ["sort %sort.6", pytest.approx(0.00015885)],
        ["copy-done %copy-done", pytest.approx(8.76e-06)],
        ["fusion %fusion", pytest.approx(3.648e-06)],
        ["copy %copy.3", pytest.approx(1.855e-06)],
        ["fusion %slice_reduce_fusion", pytest.approx(1.232e-06)],
        ["iota %iota.clone", pytest.approx(5.23e-07)],
        ["copy-start %copy-start", pytest.approx(4.3e-08)]]
    assert [n for n, _ in red["idle_gaps"]] == [
        "idle", "call", "idle", "idle", "idle", "idle", "idle", "idle",
        "idle", "idle"]
    assert red["idle_gaps"][0][1] == pytest.approx(0.003697549, rel=1e-9)


def test_existing_readers_ignore_the_trace_bytes():
    """Handing the readers the trace's bytes changes none of the six
    readers the benchmark already had."""
    trace = xplane.reduce(xplane.load(OLD))
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    class Spans:
        def first(self, name):
            return {"build.cuts": 1.5, "build.quantize_pack": 2.5}[name]

    cfg = {"features": 28, "objective": "binary:logistic", "num_class": 1,
           "max_bin": 256, "serve_model": {"trees": 500, "depth": 6}}
    result = {"rounds": 2, "rows": 1000, "wall_s": 3.0, "attempted": 2}
    for name in OLD_READERS:
        def ctx(**more):
            return dict({"cfg": cfg, "result": result, "trace": trace,
                         "spans": Spans(), "peaks": peaks, "notes": {}},
                        **more)
        plain, handed = ctx(), ctx(xplane=_bytes(OLD))
        assert _reader(name)(plain) == _reader(name)(handed), name
        assert plain["notes"] == handed["notes"], name


# --- the scoped trace recorded on a v5e (record_scoped_trace.py) ------------

@pytest.fixture(scope="module")
def scoped():
    buf = _bytes(SCOPED)
    return buf, scopes.load_bytes(buf)


def test_decoder_agrees_with_profile_data(scoped):
    """Every device event `ProfileData` reads has metadata of its program
    and name, no key comes with two tf_ops, and the names the program
    gives come back as scope paths."""
    buf, trace = scoped
    assert trace["unmatched"] == 0 and trace["conflicts"] == 0
    tf_ops = scopes.metadata_tf_ops(buf)
    assert list(tf_ops) == ["/device:TPU:0"]
    ops, conflicts = tf_ops["/device:TPU:0"]
    assert conflicts == 0
    # The window runs several programs (the round, the concatenations, the
    # traversal, the transform): each op is keyed by its own.
    assert len({p for p, _ in ops if p is not None}) > 5
    found = {scopes.scope_of(t) for t in ops.values()}
    for path in ("round/gradient", "round/margins", "round/level0/histogram",
                 "round/level1/repartition", "round/level2/split",
                 "traverse/route", "traverse/lookup", "traverse/leaf",
                 "traverse/fold"):
        assert path in found, path


def test_recorded_self_time_counts_each_instant_once(scoped):
    _, trace = scoped
    red = scopes.reduce(trace)
    assert sum(red["self_s"].values()) == pytest.approx(red["busy_s"],
                                                        rel=1e-9)
    assert red["busy_s"] == pytest.approx(
        xplane.reduce(xplane.load(SCOPED))["busy_s"], rel=1e-9)
    # Operations nest (a `while` holds its body's), so the sum of their
    # durations over-reads busy time; self time does not.
    w0, w1 = next((s, e) for n, s, e in trace["spans"]
                  if n == xplane.WINDOW_SPAN)
    total = sum(min(e, w1) - max(s, w0) for _, s, e in trace["devices"][0]
                if e > w0 and s < w1)
    assert total * 1e-9 > 1.05 * red["busy_s"]
    assert red["scoped_share"] > 0.95


def test_recorded_gaps_inside_update_are_named_by_the_program(scoped):
    """Of the idle time inside `bench.update`, at least 90% lies under a
    `repro.*` span."""
    _, trace = scoped
    w0, w1 = next((s, e) for n, s, e in trace["spans"]
                  if n == xplane.WINDOW_SPAN)
    _, _, idle = scopes.self_time(trace["devices"][0], w0, w1)
    updates = [(n, s, e) for n, s, e in trace["spans"]
               if n == "bench.update"]
    assert len(updates) == 2
    in_update = scopes.gaps_by_span(idle, updates, w0, w1)["bench.update"]
    inner = [sp for sp in trace["spans"]  # the updates and spans in them
             if any(sp[1] >= s and sp[2] <= e for _, s, e in updates)]
    named = scopes.gaps_by_span(idle, inner, w0, w1)
    program = sum(v for k, v in named.items()
                  if k.startswith(scopes.PROGRAM_PREFIX))
    assert in_update > 0
    assert program >= 0.9 * in_update
    assert "repro.ensemble.append" in named


def test_readers_on_the_recorded_trace(scoped):
    buf, _ = scoped
    # The window: a 2-round fit, two 1-round updates, a 1,024-row predict.
    ctx = {"result": {"rounds": 4, "rows": 1024, "attempted": 3},
           "xplane": buf, "notes": {}}
    got = {name: _reader(name)(ctx) for name in NEW_READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    red = ctx["notes"]["scopes"]
    assert got["round.histogram_ms"] == pytest.approx(
        1e3 * scopes.under(red["self_s"], "histogram") / 4)
    assert got["round.margins_ms"] == pytest.approx(
        1e3 * scopes.under(red["self_s"], "margins") / 4)
    assert got["traverse.route_ns_per_row"] == pytest.approx(
        1e9 * scopes.under(red["self_s"], "traverse", "route") / 1024)
    assert set(red["per_level"]["histogram"]) == {"level0", "level1",
                                                  "level2"}
    # Without the trace the readers give None, never 0.
    for name in NEW_READERS:
        assert _reader(name)({"result": ctx["result"], "notes": {}}) is None
