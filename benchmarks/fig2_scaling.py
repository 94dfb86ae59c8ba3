"""Paper Figure 2: runtime scaling with device count (airline dataset).

A rows x devices grid recording rows/s and the per-round communication
profile (wire bytes, collective calls, compression fallbacks —
`Booster.comm_stats`, DESIGN.md §15) for each collective strategy
(psum / ring / hier) and compression mode (f32 / f16 / q16).

The whole grid runs in this one process: the cell with p devices trains on
a ("data",) mesh of the first p of `jax.devices()`. On a TPU host those are
chips; with JAX_PLATFORMS=cpu the script re-execs itself with
`--xla_force_host_platform_device_count` set to the largest p, and the
devices are virtual (then rows/s is not a speedup claim). A cell that asks
for more devices than exist, or that fails, fails the script.

`--merge-into BENCH_pipeline.json` folds the results into the shared BENCH
file as a `scaling` section, including the headline comm-bytes reduction of
the compressed histogram allreduce vs exact f32.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def allreduce_bytes_per_round(max_depth=6, n_features=13, max_bins=256):
    """Legacy single-number model: full-histogram f32 payload per round
    (sum over levels of 2^l * F * B * 2 * 4 bytes)."""
    total = 0
    for level in range(max_depth):
        total += (2**level) * n_features * max_bins * 2 * 4
    return total


def _cell(dtrain, objective, p, rounds, collective, compression):
    import jax

    from repro.core import Booster, BoosterConfig
    from repro.dist import make_mesh

    mesh = make_mesh((p,), ("data",), devices=jax.devices()[:p])
    fit_kw = dict(mesh=mesh, collective=collective, compression=compression)
    # untimed warm-up fit compiles the round program
    Booster(BoosterConfig(n_rounds=1, max_depth=6, max_bins=256,
                          objective=objective)).fit(dtrain, **fit_kw)
    cfg = BoosterConfig(n_rounds=rounds, max_depth=6, max_bins=256,
                        objective=objective)
    t0 = time.perf_counter()
    bst = Booster(cfg).fit(dtrain, **fit_kw)
    jax.block_until_ready(bst.margins)
    dt = time.perf_counter() - t0
    rows = dtrain.n_rows
    rec = dict(p=p, rows=rows, time_s=dt, rows_per_device=rows // p,
               rows_per_s=rows * rounds / dt, collective=collective,
               compression=compression)
    rec.update(bst.comm_stats)
    rec["hist_bytes_per_round"] = sum(rec.pop("hist_bytes_per_level"))
    return rec


def run(rows_list=(32_768,), rounds=5, device_counts=(1, 2, 4),
        collectives=("psum", "ring", "hier"),
        compressions=(None, "q16")):
    """The rows x devices x (collective, compression) grid.

    f32 runs cover every collective; compressed runs go through the ring
    (the strategy whose wire dtype actually narrows). p=1 runs only psum
    f32 (the single-device baseline row).
    """
    import jax

    from repro.core import DeviceDMatrix
    from repro.data import make_dataset

    n_dev = len(jax.devices())
    if max(device_counts) > n_dev:
        raise SystemExit(
            f"--devices {max(device_counts)} needs that many devices; found "
            f"{n_dev} {jax.devices()[0].platform} device(s)"
        )
    grid = []
    for rows in rows_list:
        x, y, spec = make_dataset("airline", n_rows=rows)
        dtrain = DeviceDMatrix(x, label=y)
        for p in device_counts:
            cells = [("psum", None)]
            if p > 1:
                cells += [(c, None) for c in collectives if c != "psum"]
                cells += [("ring", comp) for comp in compressions
                          if comp is not None]
            for coll, comp in cells:
                grid.append(_cell(dtrain, spec.objective, p, rounds, coll,
                                  comp))
    return grid


def summarise(grid):
    """Headline: compressed ring vs exact f32 ring at the largest grid cell
    — histogram-payload and total wire-byte reduction factors."""
    ring_f32 = {(g["rows"], g["p"]): g for g in grid
                if g["collective"] == "ring" and g["compression"] is None}
    best = None
    for g in grid:
        if g["compression"] is None:
            continue
        ref = ring_f32.get((g["rows"], g["p"]))
        if ref is None:
            continue
        red_total = ref["bytes_per_round"] / g["bytes_per_round"]
        red_hist = ref["hist_bytes_per_round"] / g["hist_bytes_per_round"]
        cand = {
            "rows": g["rows"], "devices": g["p"],
            "collective": g["collective"], "compression": g["compression"],
            "bytes_per_round": g["bytes_per_round"],
            "bytes_per_round_f32": ref["bytes_per_round"],
            "reduction_hist": round(red_hist, 4),
            "reduction_total": round(red_total, 4),
            "fallback_events": g["fallback_events"],
        }
        if best is None or (cand["devices"], cand["reduction_hist"]) > (
                best["devices"], best["reduction_hist"]):
            best = cand
    return best


def merge_into(path, section):
    """Fold the scaling section into an existing BENCH json (created if
    missing), leaving every other section untouched."""
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data["scaling"] = section
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, nargs="+", default=[32_768])
    ap.add_argument("--devices", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--compressions", nargs="+", default=["q16", "f16"])
    ap.add_argument("--out", default=None, help="write the grid json here")
    ap.add_argument("--merge-into", default=None,
                    help="BENCH json to receive the `scaling` section")
    args = ap.parse_args(argv)

    flag = "--xla_force_host_platform_device_count"
    if (os.environ.get("JAX_PLATFORMS") == "cpu"
            and flag not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            f"{os.environ.get('XLA_FLAGS', '')} {flag}={max(args.devices)}"
        ).strip()
        os.execv(sys.executable, [sys.executable, *sys.argv])

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    grid = run(rows_list=tuple(args.rows), rounds=args.rounds,
               device_counts=tuple(args.devices),
               compressions=tuple(args.compressions))
    dev = jax.devices()[0]
    print(f"# Figure 2 grid (airline-shaped) on {dev.platform} "
          f"{dev.device_kind} x{len(jax.devices())}:")
    print("rows,devices,collective,compression,time_s,rows_per_s,"
          "bytes_per_round,hist_bytes_per_round,fallbacks")
    for g in grid:
        print(f"{g['rows']},{g['p']},{g['collective']},"
              f"{g['compression']},{g['time_s']:.2f},"
              f"{g['rows_per_s']:.0f},{g['bytes_per_round']},"
              f"{g['hist_bytes_per_round']},{g['fallback_events']}")
    section = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "note": "on virtual CPU devices rows/s is NOT a speedup claim; "
                "comm bytes/round is the faithful signal "
                "(Booster.comm_stats, DESIGN.md §15)",
        "rounds": args.rounds,
        "grid": grid,
        "comm_reduction": summarise(grid),
    }
    if section["comm_reduction"]:
        cr = section["comm_reduction"]
        print(f"# comm reduction ({cr['collective']}+{cr['compression']}, "
              f"p={cr['devices']}): hist x{cr['reduction_hist']}, "
              f"total x{cr['reduction_total']}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(section, f, indent=1)
            f.write("\n")
    if args.merge_into:
        merge_into(args.merge_into, section)
        print(f"# merged `scaling` into {args.merge_into}")
    return grid


if __name__ == "__main__":
    main()
