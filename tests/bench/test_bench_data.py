"""The benchmark's copies of the program's generator and reference trainer."""
import json
import os

import numpy as np
import pytest

from bench import data, reference as R

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", ["higgs", "covtype"])
@pytest.mark.parametrize("seed", [0, 1])
def test_generator_matches_program(name, seed):
    from repro.data import make_dataset

    x, y = data.make_dataset(_cfg(name), seed, n_rows=3000)
    x_p, y_p, _ = make_dataset(name, n_rows=3000, seed=seed)
    np.testing.assert_array_equal(x, x_p)
    np.testing.assert_array_equal(y, y_p)


def test_generator_takes_large_seeds():
    a, _ = data.make_dataset(_cfg("higgs"), 2**31 + 12345, n_rows=100)
    b, _ = data.make_dataset(_cfg("higgs"), 2**31 + 12345, n_rows=100)
    np.testing.assert_array_equal(a, b)


def test_reference_bins_follow_numpy_quantile():
    x = np.random.default_rng(0).standard_normal((5000, 3)).astype(np.float32)
    x[::9, 2] = np.nan
    bins = R.bin_matrix(x, 256)
    for f in range(3):
        col = x[:, f]
        cuts = np.unique(np.quantile(col[~np.isnan(col)].astype(np.float64),
                                     np.linspace(0, 1, 256)[1:-1]))
        want = np.searchsorted(cuts.astype(np.float32), col, side="left")
        np.testing.assert_array_equal(
            bins[f], np.where(np.isnan(col), 255, want))
    assert bins.shape == (3, 5000) and bins[:2].max() <= 254


def test_reference_matches_numpy_baseline():
    """The level-wise trainer grows the trees of the per-node baseline
    (`benchmarks/baselines.py::train_numpy`). Shallow trees on many rows,
    so that no two candidate splits tie to rounding."""
    from benchmarks.baselines import train_numpy

    cfg = dict(_cfg("higgs"), rows=20_000, max_depth=3)
    x, y = data.make_dataset(cfg, 3)
    got = R.train_rounds(x, y, cfg, 2)[-1][:, 0]
    _, want = train_numpy(x, y, n_rounds=2, max_depth=3, lr=0.3,
                          max_bins=256, objective="binary:logistic")
    np.testing.assert_allclose(got, want[:, 0], atol=1e-9)


def test_reference_matches_program_first_round():
    from repro.core import Booster, BoosterConfig, DeviceDMatrix

    cfg = _cfg("higgs")
    x, y = data.make_dataset(cfg, 5, n_rows=20_000)
    bst = Booster(BoosterConfig(n_rounds=1, objective="binary:logistic"))
    bst.fit(DeviceDMatrix(x, label=y, max_bins=256))
    ref = R.train_rounds(x, y, dict(cfg, rows=20_000), 1)[0]
    np.testing.assert_allclose(np.asarray(bst.margins), ref, atol=1e-6)


def test_json_traversal_matches_program():
    from bench import ensemble
    from repro.serve.interop import import_xgboost_json

    rng = np.random.default_rng(0)
    x = rng.standard_normal((2000, 6)).astype(np.float32)
    x[::5, 2] = np.nan
    model = ensemble.random_model(x, trees=20, depth=4, leaf_scale=0.1,
                                  seed=1)
    got = np.asarray(import_xgboost_json(model).predict(x))
    want = R.transform("binary:logistic", R.predict_json(model, x))
    np.testing.assert_allclose(got, want, atol=1e-6)
