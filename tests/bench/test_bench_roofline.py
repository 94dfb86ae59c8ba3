"""Roofline counts checked by hand, and the per-layer readers."""
import importlib.util
import json
import os

import pytest

from bench import roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
V5E = roofline.peaks("TPU v5 lite")


def test_peaks_refuse_unknown_device():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_train_round_by_hand():
    # 1000 rows x 28 features at 8 bits: 28,000 B; labels 4,000 B; margins
    # read and written 8,000 B. Adds: 2 (g, h) x 1000 x 28 = 56,000.
    b = roofline.train_round(1000, 28, 1, 256, V5E)
    assert b["bytes"] == 40_000 and b["ops"] == 56_000
    assert b["bound"] == "bytes"
    assert b["seconds"] == pytest.approx(40_000 / 819e9)
    # 7 trees a round: 28,000 + 4,000 + 56,000 B; 392,000 adds.
    b7 = roofline.train_round(1000, 28, 7, 256, V5E)
    assert b7["bytes"] == 88_000 and b7["ops"] == 392_000


def test_predict_call_by_hand():
    # 100 rows x 28 f32 in, 100 f32 out; 100 x 500 trees x 6 levels compares.
    b = roofline.predict_call(100, 28, 1, 500, 6, V5E)
    assert b["bytes"] == 11_600 and b["ops"] == 300_000
    assert b["seconds"] == pytest.approx(max(11_600 / 819e9,
                                             300_000 / 197e12))


def _reader(name):
    path = os.path.join(ROOT, "bench", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _per_layer_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return [m["name"] for m in json.load(fh)["per_layer"]]


@pytest.mark.parametrize("name", _per_layer_names())
def test_every_reader_is_silent_without_its_input(name):
    from bench.drivers import Spans

    ctx = {"kind": "none", "cfg": {}, "traffic": {}, "spans": Spans(),
           "result": {}, "trace": None, "peaks": V5E, "notes": {}}
    assert _reader(name)(ctx) is None


@pytest.mark.parametrize("kind,name", [("train_rounds", "idle_share.train"),
                                       ("batch_score", "idle_share.score")])
def test_idle_share_readers(kind, name):
    ctx = {"kind": kind, "trace": {"idle_share": 0.25}}
    assert _reader(name)(ctx) == pytest.approx(25.0)


def test_mfu_train_reader():
    cfg = {"features": 28, "objective": "binary:logistic", "num_class": 1,
           "max_bin": 256}
    res = {"rows": 1000, "rounds": 4, "wall_s": 2.0}
    ctx = {"kind": "train_rounds", "cfg": cfg, "result": res, "peaks": V5E,
           "notes": {}}
    # 4 rounds of 40,000 B each in 2 s.
    assert _reader("mfu.train")(ctx) == pytest.approx(
        100 * 4 * (40_000 / 819e9) / 2.0)
    assert ctx["notes"]["mfu.train"]["bound"] == "bytes"
