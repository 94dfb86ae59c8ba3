"""Back-to-back `Booster.predict` over the holdout, held as a host array, in
slices of `batch_rows` rows; each call ends with its result on the host.
Offline batch scoring: the traversal does nearly all of the device work.

The model is the configuration's serving ensemble (`serve_model`), made
from the seed as an XGBoost JSON document and imported through
`import_xgboost_json`, so no training is paid in set-up. `check_rows`
holdout rows, drawn from the seed, are checked against the numpy
traversal wherever a window call scored them.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import compare, data, drivers, ensemble
from bench import reference as R


def _holdout_and_model(cfg: dict, seed: int):
    x, y = data.make_dataset(cfg, seed)
    x_tr, _, x_ho, _ = data.split_holdout(x, y, cfg["holdout_fraction"])
    sm = cfg["serve_model"]
    model = ensemble.random_model(
        x_tr, trees=sm["trees"], depth=sm["depth"],
        leaf_scale=sm["leaf_scale"], seed=seed, objective=cfg["objective"])
    return np.ascontiguousarray(x_ho), model


def _check_sample(traffic: dict, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 1])
    k = min(int(traffic["check_rows"]), n)
    return np.sort(rng.choice(n, k, replace=False))


class Driver(drivers.Driver):

    def setup(self):
        from repro.serve.interop import import_xgboost_json

        with self.spans("data"):
            self.x, self.model = _holdout_and_model(self.cfg, self.seed)
        with self.spans("import"):
            self.booster = import_xgboost_json(self.model)
        step = int(self.traffic["batch_rows"])
        self.batches = [slice(i, min(i + step, len(self.x)))
                        for i in range(0, len(self.x), step)]
        with self.spans("warmup"):
            for n in sorted({b.stop - b.start for b in self.batches}):
                np.asarray(self.booster.predict(self.x[:n]))
        self.sample = _check_sample(self.traffic, len(self.x), self.seed)

    def window(self, seconds):
        calls, rows, t0 = 0, 0, time.perf_counter()
        self.checked = []  # (holdout rows, their answers) of every call
        while True:
            b = self.batches[calls % len(self.batches)]
            with self.spans("predict"):
                out = np.asarray(self.booster.predict(self.x[b]))
            calls += 1
            rows += b.stop - b.start
            mine = self.sample[(self.sample >= b.start)
                               & (self.sample < b.stop)]
            self.checked.append((mine, out[mine - b.start]))
            wall = time.perf_counter() - t0
            if wall >= seconds:
                break
        return {"attempted": calls, "failed": 0, "wall_s": wall,
                "rows": rows, "score_rows_per_s": rows / wall}

    def check(self):
        self.booster = None
        gc.collect()
        ref = R.transform(self.cfg["objective"],
                          R.predict_json(self.model, self.x[self.sample]))
        ref = dict(zip(self.sample.tolist(), ref))
        return compare.answer_numbers(
            [out for _, out in self.checked],
            [np.array([ref[i] for i in rows.tolist()])
             for rows, _ in self.checked])


def control_readings(cfg: dict, traffic: dict, seed: int) -> dict:
    """`answer_gap` of the numpy traversal in bfloat16 put in the program's
    place, over the rows the harness checks in the window's first call."""
    x_ho, model = _holdout_and_model(cfg, seed)
    sample = _check_sample(traffic, len(x_ho), seed)
    rows = x_ho[sample[sample < int(traffic["batch_rows"])]]
    ref = R.transform(cfg["objective"], R.predict_json(model, rows))
    ctl = R.transform(cfg["objective"],
                      R.predict_json(model, rows, precision="bfloat16"))
    return {"control_bf16": compare.answer_numbers([ctl], [ref])}
