"""Readings of the control and of planted faults at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

The control is the plain reference put in the program's place and computed
in bfloat16, the precision below the configuration's float32; beside it,
the faults the cell's traffic kind plants (`control_readings` in
bench/kinds/<kind>.py). Each line is the numbers that `run.py` compares,
read off the same float64 reference the harness uses; they set the upper
ends of the limits in bench/limits/<cell>.json. The benchmark's own runs
never run this; it needs no accelerator, only the host's memory for the
reference at the cell's size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = ROOT
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.drivers import load_kind  # noqa: E402
from bench.run import find_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    args = ap.parse_args(argv)
    _, _, cfg, traffic = find_cell(ROOT, args.workload)
    kind = load_kind(ROOT, traffic["kind"])
    for seed in (int(s) for s in args.seeds.split(",")):
        got = kind.control_readings(cfg, traffic, seed)
        print(json.dumps({"cell": args.workload, "seed": seed, **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
