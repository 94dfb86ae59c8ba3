"""Benchmark entrypoint: one section per paper table/figure.

  python -m benchmarks.run               # all (reduced sizes for 1-core CPU)
  python -m benchmarks.run --only table2 compression
  python -m benchmarks.run --rows 20000  # bigger table2

Prints CSV-ish lines per section; EXPERIMENTS.md cites these outputs.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    choices=["table2", "compression", "fig2", "kernels",
                             "pipeline", "roofline", "ablations"])
    ap.add_argument("--rows", type=int, default=8000)
    args = ap.parse_args()
    sections = args.only or ["compression", "kernels", "pipeline", "table2",
                             "fig2", "ablations", "roofline"]

    t0 = time.perf_counter()
    for sec in sections:
        print(f"\n=== {sec} ===", flush=True)
        if sec == "table2":
            from benchmarks import table2
            table2.main(rows=args.rows)
        elif sec == "compression":
            from benchmarks import compression_ratio
            compression_ratio.main()
        elif sec == "fig2":
            from benchmarks import fig2_scaling
            fig2_scaling.main()
        elif sec == "kernels":
            from benchmarks import kernels
            kernels.main()
        elif sec == "pipeline":
            from benchmarks import pipeline
            # Reduced size (pipeline.main's own defaults are the 1M-row
            # acceptance run), and write to /tmp so the committed
            # BENCH_pipeline.json artifact of record is never clobbered.
            pipeline.main(["--rows", str(max(args.rows, 20_000)),
                           "--features", "20",
                           "--out", "/tmp/BENCH_pipeline.json"])
        elif sec == "ablations":
            from benchmarks import ablations
            ablations.main()
        elif sec == "roofline":
            from benchmarks import roofline
            roofline.main()
    print(f"\n# total benchmark time: {time.perf_counter() - t0:.1f}s")


if __name__ == "__main__":
    main()
