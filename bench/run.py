"""Run one cell of the chip benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (bench/configs/<config>.json), its traffic
(bench/traffic/<traffic>.json) and the kind that drives it
(bench/kinds/<kind>.py), the limits of its correctness numbers
(bench/limits/<cell>.json) and the readers of its per-layer metrics
(bench/layer_metrics/<metric>.py) are found by the names in BENCHMARK.json.

Steps: set-up (inputs from the seed, build, warm-up; `setup_s` runs from
process start to the window), the measured window, the peak device memory,
then the correctness check against the plain reference once the program's
state is freed. With `--trace 1` the window runs under the profiler and the
line carries the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device, breakdown (traced runs) and, last, check: each
compared number beside its limit, which are also the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = ROOT  # import the package, never shadow stdlib names
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import compare, drivers, xplane  # noqa: E402
from bench import roofline  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
CACHE_MIN_CAP = 2 * 2**30  # bytes
NO_CHIP = 3


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def find_cell(root: str, name: str):
    """(benchmark, cell, config, traffic) for the cell called `name`."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(cells: {sorted(cells)})")
    cell = cells[name]
    cfg = load_json(os.path.join(root, "bench", "configs",
                                 f"{cell['config']}.json"))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{cell['traffic']}.json"))
    return bench, cell, cfg, traffic


def load_reader(root: str, metric: str):
    path = os.path.join(root, "bench", "layer_metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, per_layer: bool) -> list[dict]:
    """The metrics this cell reports: end-to-end ones in untraced runs,
    per-layer ones in traced runs."""
    if not per_layer:
        return [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
    moved = {m["name"] for m in cell_metrics(bench, cell, False)}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved
                             else [])]


class CompileCounter:
    """Programs JAX compiled or loaded from its cache while `active`."""

    def __init__(self):
        import jax

        self.active, self.compiles, self.cache_hits = False, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_info(devices, count: int) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices[:count]]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             devices, t_start: float = T_START) -> dict:
    """Set-up, window, memory, check and metrics of one cell; returns the
    result object (the caller prints it)."""
    import jax

    bench, cell, cfg, traffic = find_cell(root, name)
    limits = compare.load_limits(name, os.path.join(root, "bench"))
    spans = drivers.Spans()
    kind = drivers.load_kind(root, traffic["kind"])
    driver = kind.Driver(cfg, traffic, seed, spans)
    driver.setup()
    setup_s = time.perf_counter() - t_start

    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(trace_dir)
        counter.active = True
        with spans("window"):
            res = driver.window(seconds)
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
        device = device_info(devices, cell["chips"])
        reduced = None
        if trace:
            path = xplane.find_xplane(trace_dir)
            reduced = xplane.reduce(xplane.load(path)) if path else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"cell": name, "seed": seed, "setup_s": setup_s,
                      "window": res, "spans": spans.summary(),
                      "window_compiles": counter.compiles,
                      "window_cache_hits": counter.cache_hits}), flush=True)

    numbers = driver.check()
    del driver
    gc.collect()
    correct, shown = compare.judge(numbers, limits)
    correct = correct and res["failed"] == 0
    print(json.dumps({"check_numbers": numbers}), flush=True)

    metrics = {}
    if not trace:
        for m in cell_metrics(bench, name, False):
            v = setup_s if m["name"] == "setup_s" else res[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        ctx = {"kind": traffic["kind"], "cfg": cfg, "traffic": traffic,
               "spans": spans, "result": res, "trace": reduced,
               "peaks": roofline.peaks(device["kind"], os.path.join(root,
                                                                     "bench")),
               "notes": {}}
        for m in cell_metrics(bench, name, True):
            v = load_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if ctx["notes"]:
            print(json.dumps({"roofline": ctx["notes"]}), flush=True)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["check"] = shown
    return out


def configure_jax():
    """Persistent compile cache at a fixed path in the checkout (or where
    JAX_COMPILATION_CACHE_DIR points), every program cached, room for at
    least CACHE_MIN_CAP bytes."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The multiclass round compiles to ~210 MB; a smaller cap would leave it
    # out of the cache and every run would compile it again.
    cap = jax.config.jax_compilation_cache_max_size
    if cap != -1 and cap < CACHE_MIN_CAP:
        jax.config.update("jax_compilation_cache_max_size", CACHE_MIN_CAP)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _, cell, _, _ = find_cell(ROOT, args.workload)
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as exc:
        print(f"bench: the system under test is missing: {exc}",
              file=sys.stderr)
        return 2
    jax = configure_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, found platform {devices[0].platform!r}",
              file=sys.stderr)
        return NO_CHIP
    if len(devices) < cell["chips"]:
        print(f"bench: cell {args.workload} needs {cell['chips']} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return NO_CHIP

    out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                   bool(args.trace), devices)
    for k, v in out["check"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
