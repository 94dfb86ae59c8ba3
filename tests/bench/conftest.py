"""Fixtures of the benchmark's tests: the checkout root on sys.path, and a
copy of the benchmark's data files with the configurations cut to a size a
CPU test can run."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SMALL_ROWS = {"higgs": 40_000, "covtype": 20_000}
SMALL_TREES = 40


@pytest.fixture
def small_root(tmp_path):
    """A checkout-shaped directory holding BENCHMARK.json and the benchmark's
    data files, configurations cut to SMALL_ROWS rows, and a "cpu" row in
    the peaks table (the real table refuses any device it does not list)."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for name, rows in SMALL_ROWS.items():
        path = root / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg["rows"] = rows
        if "serve_model" in cfg:
            cfg["serve_model"]["trees"] = SMALL_TREES
        path.write_text(json.dumps(cfg))
    peaks_path = root / "bench" / "peaks.json"
    peaks = json.loads(peaks_path.read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test only")
    peaks_path.write_text(json.dumps(peaks))
    return root
