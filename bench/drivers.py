"""What every traffic kind shares, and how a kind is found.

A traffic file (`bench/traffic/<mix>.json`) names a `kind` and holds its
parameters; the kind is the module `bench/kinds/<kind>.py`, found by that
name, whose class `Driver` has three steps that `run.py` calls in order:

  setup()       make the inputs from the seed, build what the window drives,
                warm every shape the window uses;
  window(s)     drive the program for at least s seconds; returns the
                end-to-end measurements;
  check()       free the program's state, run the plain reference over what
                the window produced, return the numbers that decide
                `correct`.

A kind module may also define `control_readings(cfg, traffic, seed)`, the
readings of its control and planted faults (`bench/control.py`).

The program is reached only through its public path: `repro.core`
(`compute_cuts`, `DeviceDMatrix`, `ExternalDMatrix`, `Booster`) and
`repro.serve.interop` (`import_xgboost_json`).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import time
from contextlib import contextmanager

# XGBoost's parameter names, as the configuration files use them, and the
# program's names for them. Every other configuration or traffic key that
# names a `BoosterConfig` field is passed on as it is.
XGBOOST_NAMES = {"eta": "learning_rate", "lambda": "reg_lambda",
                 "max_bin": "max_bins", "num_class": "n_classes"}


class Spans:
    """Host-clock spans of the harness's calls into each layer, mirrored
    into the profiler's trace as `bench.<name>` annotations."""

    def __init__(self):
        self.seconds: dict[str, list[float]] = {}

    @contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.seconds.setdefault(name, []).append(time.perf_counter() - t0)

    def summary(self) -> dict:
        """{name: [count, total seconds, first seconds]}."""
        return {k: [len(v), sum(v), v[0]] for k, v in self.seconds.items()}

    def first(self, name: str):
        got = self.seconds.get(name)
        return got[0] if got else None


def ready(x):
    import jax

    return jax.block_until_ready(x)


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, spans: Spans):
        self.cfg, self.traffic, self.seed, self.spans = cfg, traffic, seed, spans


def booster_params(cfg: dict, traffic: dict) -> dict:
    """`BoosterConfig` keyword arguments: the configuration's keys, then the
    traffic file's `booster` group over them, each under the program's name
    for it. Keys that are no parameter of the program are left out."""
    from repro.core import BoosterConfig

    fields = {f.name for f in dataclasses.fields(BoosterConfig)}
    out = {}
    for src in (cfg, traffic.get("booster", {})):
        for k, v in src.items():
            k = XGBOOST_NAMES.get(k, k)
            if k in fields:
                out[k] = v
    return out


def load_kind(root: str, kind: str):
    """The module `bench/kinds/<kind>.py` under the checkout `root`."""
    path = os.path.join(root, "bench", "kinds", f"{kind}.py")
    if not os.path.exists(path):
        raise KeyError(f"no traffic kind {kind!r} (looked for {path})")
    spec = importlib.util.spec_from_file_location(f"bench_kind_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
