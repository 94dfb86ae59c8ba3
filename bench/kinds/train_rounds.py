"""Back-to-back `Booster.update(dtrain, rounds_per_call)` on cached margins:
the exact continuation `xgboost.train` runs.

Set-up: the dataset, the training matrix (cuts, then quantise and pack;
timed as `dmatrix_build_s`) and a `Booster.fit` of `rounds_per_call`
rounds, which compiles the round program that every window call runs. The
margins after the fit and after each of the first window calls are kept on
the device; `check()` compares them, through the first CHECK_ROUNDS rounds,
with the numpy reference trainer.

Traffic parameters: `rounds_per_call`, and an optional `booster` group of
program parameters laid over the configuration's. A kind that builds its
training matrix another way subclasses `Driver` and overrides
`build_matrix`.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import compare, data, drivers
from bench import reference as R

CHECK_ROUNDS = 3


class Driver(drivers.Driver):

    def build_matrix(self, x, y, cuts):
        """The training matrix from host rows and their cuts, ready."""
        from repro.core import DeviceDMatrix

        dtrain = DeviceDMatrix(x, label=y, max_bins=self.cfg["max_bin"],
                               cuts=cuts)
        drivers.ready((dtrain.matrix.packed, dtrain.label))
        return dtrain

    def setup(self):
        import jax

        from repro.core import Booster, BoosterConfig, compute_cuts

        cfg = self.cfg
        self.per_call = int(self.traffic["rounds_per_call"])
        with self.spans("data"):
            x, y = data.make_dataset(cfg, self.seed)
            self.x, self.y, _, _ = data.split_holdout(
                x, y, cfg["holdout_fraction"])
            del x, y
        with self.spans("build.cuts"):
            xd = jax.device_put(self.x)
            cuts = drivers.ready(compute_cuts(xd, cfg["max_bin"]))
        with self.spans("build.quantize_pack"):
            self.dtrain = self.build_matrix(xd, self.y, cuts)
        del xd, cuts
        self.build_s = (self.spans.first("build.cuts")
                        + self.spans.first("build.quantize_pack"))
        params = drivers.booster_params(cfg, self.traffic)
        self.booster = Booster(BoosterConfig(**dict(params,
                                                    n_rounds=self.per_call)))
        with self.spans("fit"):
            self.booster.fit(self.dtrain)
            drivers.ready(self.booster.margins)
        self.kept = [(self.per_call, self.booster.margins)]
        self.base = float(self.booster.base_score)

    def window(self, seconds):
        calls, t0 = 0, time.perf_counter()
        while True:
            with self.spans("update"):
                self.booster.update(self.dtrain, self.per_call)
                drivers.ready(self.booster.margins)
            calls += 1
            done = self.per_call * (calls + 1)
            if done <= CHECK_ROUNDS:
                self.kept.append((done, self.booster.margins))
            wall = time.perf_counter() - t0
            if wall >= seconds:
                break
        rounds, rows = calls * self.per_call, self.dtrain.n_rows
        return {"attempted": calls, "failed": 0, "wall_s": wall,
                "rounds": rounds, "rows": rows,
                "train_row_rounds_per_s": rows * rounds / wall,
                "dmatrix_build_s": self.build_s}

    def check(self):
        kept = [(r, np.asarray(m, np.float64)) for r, m in self.kept
                if r <= CHECK_ROUNDS]
        self.booster = self.dtrain = self.kept = None
        gc.collect()
        ref = R.train_rounds(self.x, self.y, self.cfg, kept[-1][0])
        return compare.training_numbers(
            self.cfg["objective"], self.y, self.base,
            [m for _, m in kept], [ref[r - 1] for r, _ in kept])


def control_readings(cfg: dict, traffic: dict, seed: int,
                     rounds: int = CHECK_ROUNDS) -> dict:
    """The numbers `check()` compares, for the reference in bfloat16 put in
    the program's place, and for two planted faults: half of the rows left
    out of the histograms and leaf weights, and the state left unchanged
    after the first round. All read against the float64 reference."""
    x, y = data.make_dataset(cfg, seed)
    x, y, _, _ = data.split_holdout(x, y, cfg["holdout_fraction"])
    base = R.base_margin(cfg["objective"], y)
    t0 = time.perf_counter()
    ref = R.train_rounds(x, y, cfg, rounds)
    ref_s = time.perf_counter() - t0
    runs = {
        "control_bf16": R.train_rounds(x, y, cfg, rounds,
                                       precision="bfloat16"),
        "fault_half_rows": R.train_rounds(x, y, cfg, rounds,
                                          row_fraction=0.5, seed=seed),
        "fault_state_unchanged": [ref[0]] * rounds,
    }
    out = {name: compare.training_numbers(cfg["objective"], y, base, m, ref)
           for name, m in runs.items()}
    out["reference_s"] = ref_s
    return out
