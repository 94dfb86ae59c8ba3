"""The harness end to end on the CPU at a small size: every traffic kind,
traced and untraced runs, cells added by files alone (a configuration, a
traffic mix with its own program parameters, a traffic kind), the refusal
without a chip, and `correct` coming out false under the control and under
each fault a one-chip cell can have."""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import compare, drivers
from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2**31 + 4242


def _run(root, cell, trace=False, seconds=1.0):
    return run.run_cell(str(root), cell, SEED, seconds, trace, jax.devices())


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_run_exits_without_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "higgs.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(_env(), PYTHONPATH="src"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode == run.NO_CHIP
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "higgs.score",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""


def test_train_cell(small_root):
    out = _run(small_root, "higgs.train")
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"train_row_rounds_per_s",
                                   "dmatrix_build_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == len(jax.devices())
    assert list(out)[-1] == "check"
    assert any(v["limit"] is not None for v in out["check"].values())


def test_train_cell_traced(small_root):
    out = _run(small_root, "higgs.train", trace=True)
    assert out["correct"], out["check"]
    m = out["metrics"]
    # The CPU has no device plane: the idle share is left out, not 0.
    assert set(m) == {"build.cuts_s", "build.quantize_pack_s", "mfu.train"}
    assert 0.0 < m["mfu.train"]["value"] < 100.0


def test_multiclass_cell_runs(small_root):
    out = _run(small_root, "covtype.train")
    assert out["attempted"] >= 1
    # 20,000 rows of 54 columns tie often below the root; the first round's
    # loss still agrees with the reference's.
    assert out["check"]["loss_gap_r1"]["value"] < 1e-4


def test_score_cell(small_root):
    out = _run(small_root, "higgs.score")
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"score_rows_per_s", "setup_s"}
    traced = _run(small_root, "higgs.score", trace=True)
    assert set(traced["metrics"]) == {"mfu.score"}


def test_cell_added_by_files_alone(small_root):
    """A later PR adds a configuration file, a limits file and entries in
    BENCHMARK.json; the harness finds them by name."""
    cfg = json.loads((small_root / "bench/configs/higgs.json").read_text())
    cfg.update(name="year_prediction", dataset="year_prediction",
               features=90, task="reg", objective="reg:squarederror",
               rows=8000)
    (small_root / "bench/configs/year_prediction.json").write_text(
        json.dumps(cfg))
    shutil.copy(small_root / "bench/limits/higgs.train.json",
                small_root / "bench/limits/year_prediction.train.json")
    bench = json.loads((small_root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="year_prediction",
                                 file="bench/configs/year_prediction.json"))
    bench["workloads"].append({"name": "year_prediction.train",
                               "config": "year_prediction",
                               "traffic": "train_rounds", "chips": 1,
                               "why": "regression at 90 columns"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "higgs.train" in m.get("workloads", []):
            m["workloads"].append("year_prediction.train")
    (small_root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = _run(small_root, "year_prediction.train")
    assert out["correct"], out["check"]
    assert "train_row_rounds_per_s" in out["metrics"]


def test_booster_params_take_every_program_parameter():
    cfg = json.loads(open(os.path.join(ROOT, "bench/configs/higgs.json"))
                     .read())
    got = drivers.booster_params(dict(cfg, colsample_bytree=0.5),
                                 {"booster": {"eta": 0.1}})
    assert got == {"learning_rate": 0.1, "reg_lambda": 1.0, "max_bins": 256,
                   "n_classes": 1, "max_depth": 6, "gamma": 0.0,
                   "min_child_weight": 1.0, "objective": "binary:logistic",
                   "colsample_bytree": 0.5}


def test_unknown_kind_is_refused(small_root):
    (small_root / "bench/traffic/nothing.json").write_text(
        json.dumps({"kind": "no_such_kind"}))
    _enter_cell(small_root, "higgs.nothing", "nothing")
    with pytest.raises(KeyError, match="no_such_kind"):
        _run(small_root, "higgs.nothing")


def _enter_cell(root, name, traffic, like="higgs.train"):
    """BENCHMARK.json gains cell `name` (config higgs) and a copy of `like`'s
    limits; every metric of `like` is reported there too."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": "higgs",
                               "traffic": traffic, "chips": 1,
                               "why": "entered by a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copy(root / f"bench/limits/{like}.json",
                root / f"bench/limits/{name}.json")


def test_mix_with_program_parameters_added_by_files_alone(small_root,
                                                         monkeypatch):
    """A GOSS mix is a traffic file whose `booster` group reaches the
    program's BoosterConfig as it stands."""
    from repro.core import Booster

    (small_root / "bench/traffic/train_rounds_goss.json").write_text(
        json.dumps({"kind": "train_rounds", "rounds_per_call": 1,
                    "booster": {"sampling_method": "goss", "top_rate": 0.2,
                                "other_rate": 0.1}}))
    _enter_cell(small_root, "higgs.train-goss", "train_rounds_goss")
    seen = []
    plain = Booster.__init__

    def spy(self, cfg, *a, **k):
        seen.append(cfg)
        plain(self, cfg, *a, **k)

    monkeypatch.setattr(Booster, "__init__", spy)
    out = _run(small_root, "higgs.train-goss")
    assert out["attempted"] >= 1
    assert "train_row_rounds_per_s" in out["metrics"]
    (cfg,) = seen
    assert (cfg.sampling_method, cfg.top_rate, cfg.other_rate) == \
        ("goss", 0.2, 0.1)
    assert (cfg.max_bins, cfg.learning_rate, cfg.max_depth) == (256, 0.3, 6)


def test_kind_added_by_a_file_alone(small_root):
    """A traffic kind that builds the training matrix another way (host
    chunks streamed each round) is one new module and one traffic file."""
    (small_root / "bench/kinds/train_stream.py").write_text(
        "import numpy as np\n"
        "from bench.kinds.train_rounds import Driver as TrainRounds\n\n\n"
        "class Driver(TrainRounds):\n"
        "    def build_matrix(self, x, y, cuts):\n"
        "        from repro.core import ExternalDMatrix\n\n"
        "        return ExternalDMatrix(\n"
        "            [(np.asarray(x), y)], cuts=np.asarray(cuts),\n"
        "            max_bins=self.cfg['max_bin'],\n"
        "            **self.traffic['matrix'])\n")
    (small_root / "bench/traffic/train_stream.json").write_text(json.dumps(
        {"kind": "train_stream", "rounds_per_call": 1,
         "matrix": {"chunk_rows": 8192, "paging": "stream"}}))
    _enter_cell(small_root, "higgs.train-stream", "train_stream")
    out = _run(small_root, "higgs.train-stream")
    assert out["correct"], out["check"]
    assert out["metrics"]["dmatrix_build_s"]["value"] > 0


# --- the control and the faults -------------------------------------------

def _limits(cell):
    return compare.load_limits(cell, os.path.join(ROOT, "bench"))


def _small_cfg(small_root, name):
    return json.loads((small_root / f"bench/configs/{name}.json").read_text())


def test_control_fails_training_limits(small_root):
    kind = drivers.load_kind(str(small_root), "train_rounds")
    got = kind.control_readings(_small_cfg(small_root, "higgs"), {}, SEED)
    for name in ("control_bf16", "fault_half_rows", "fault_state_unchanged"):
        ok, shown = compare.judge(got[name], _limits("higgs.train"))
        assert not ok, (name, shown)


def test_control_fails_answer_limits(small_root):
    cfg = _small_cfg(small_root, "higgs")
    traffic = json.loads((small_root / "bench/traffic/batch_score.json")
                         .read_text())
    kind = drivers.load_kind(str(small_root), "batch_score")
    got = kind.control_readings(cfg, traffic, SEED)
    ok, shown = compare.judge(got["control_bf16"], _limits("higgs.score"))
    assert not ok, shown


def test_fault_state_unchanged(small_root, monkeypatch):
    from repro.core import Booster

    monkeypatch.setattr(Booster, "update",
                        lambda self, dtrain, n_rounds, *a, **k: self)
    assert not _run(small_root, "higgs.train")["correct"]


def test_fault_half_the_rows(small_root, monkeypatch):
    """Gradient pairs of every other row zeroed: the histograms and leaf
    weights see half of the batch."""
    from repro.core import Booster

    plain = Booster.obj.fget
    cache = {}

    def half_obj(self):
        o = plain(self)
        if o.name not in cache:
            def grad(m, y, **kw):
                gh = o.grad(m, y, **kw)
                keep = (jnp.arange(gh.shape[0]) % 2 == 0).astype(gh.dtype)
                return gh * keep[:, None, None]
            cache[o.name] = o._replace(grad=grad)
        return cache[o.name]

    monkeypatch.setattr(Booster, "obj", property(half_obj))
    assert not _run(small_root, "higgs.train")["correct"]


def test_fault_answer_altered_in_scoring(small_root, monkeypatch):
    from repro.core import Booster

    plain = Booster.predict

    def altered(self, x, *a, **k):
        out = plain(self, x, *a, **k)
        return out.at[len(out) // 2].add(1e-3)

    monkeypatch.setattr(Booster, "predict", altered)
    assert not _run(small_root, "higgs.score")["correct"]
