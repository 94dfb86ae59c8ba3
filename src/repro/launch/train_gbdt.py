"""GBDT training driver — the paper's own end-to-end pipeline (Figure 1)
behind the two-noun API: DeviceDMatrix (quantise once) + Booster.fit.

Single-device by default; --devices N shards rows over a ("data",) mesh of
the first N devices and trains with the shard_map/psum strategy behind the
same Booster.fit signature (Algorithm 1's multi-GPU path). On a TPU host
those are N chips, all driven from this one process; with JAX_PLATFORMS=cpu
they are N virtual host devices (the script re-execs itself so that
XLA_FLAGS precedes jax init). Both paths produce the same Booster object.

Examples:
  PYTHONPATH=src python -m repro.launch.train_gbdt --dataset higgs \
      --rows 20000 --rounds 50
  PYTHONPATH=src python -m repro.launch.train_gbdt --dataset airline \
      --rows 100000 --rounds 100 --devices 8
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="higgs")
    ap.add_argument("--rows", type=int, default=20_000)
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--max-depth", type=int, default=6)
    ap.add_argument("--max-bins", type=int, default=256)
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--growth", default="depthwise", choices=["depthwise", "lossguide"])
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--use-kernel", action="store_true",
                    help="route histograms through the Pallas kernel")
    ap.add_argument("--early-stopping", type=int, default=0,
                    help="stop when the valid metric stalls for N rounds")
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args()

    flag = "--xla_force_host_platform_device_count"
    if (args.devices > 1 and os.environ.get("JAX_PLATFORMS") == "cpu"
            and flag not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            f"{os.environ.get('XLA_FLAGS', '')} {flag}={args.devices}".strip()
        )
        os.execv(sys.executable, [sys.executable, "-m", "repro.launch.train_gbdt", *sys.argv[1:]])

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from repro.core import Booster, BoosterConfig, DeviceDMatrix
    from repro.data import make_dataset

    x, y, spec = make_dataset(args.dataset, n_rows=args.rows)
    n_tr = int(0.8 * len(x))
    n_tr = (n_tr // args.devices) * args.devices  # shard-divisible (no-op at 1)
    cfg = BoosterConfig(
        n_rounds=args.rounds,
        max_depth=args.max_depth,
        max_bins=args.max_bins,
        learning_rate=args.lr,
        objective=spec.objective,
        n_classes=spec.n_classes,
        growth=args.growth,
        use_kernel_histograms=args.use_kernel,
    )

    t0 = time.perf_counter()
    dtrain = DeviceDMatrix(x[:n_tr], label=y[:n_tr], max_bins=args.max_bins)
    dval = DeviceDMatrix(x[n_tr:], label=y[n_tr:], ref=dtrain)
    t_build = time.perf_counter() - t0

    mesh = None
    if args.devices > 1:
        from repro.dist import make_mesh

        devices = jax.devices()
        if len(devices) < args.devices:
            raise SystemExit(
                f"--devices {args.devices} needs {args.devices} devices; "
                f"found {len(devices)} {devices[0].platform} device(s)"
            )
        mesh = make_mesh((args.devices,), ("data",),
                         devices=devices[: args.devices])

    t0 = time.perf_counter()
    bst = Booster(cfg).fit(
        dtrain,
        evals=[(dval, "valid")],
        early_stopping_rounds=args.early_stopping or None,
        verbose_every=max(args.rounds // 5, 1),
        callback=lambda r, rec: print(rec, flush=True),
        mesh=mesh,
    )
    t_fit = time.perf_counter() - t0

    metric_name, metric = next(iter(bst.eval(dval, "valid").items()))
    print(f"dataset={args.dataset} rows={args.rows} "
          f"rounds={bst.n_rounds_trained} devices={args.devices} "
          f"dmatrix={t_build:.1f}s fit={t_fit:.1f}s "
          f"{metric_name}={metric:.4f}")
    if args.checkpoint:
        bst.save(args.checkpoint)
        print("saved booster to", args.checkpoint)


if __name__ == "__main__":
    main()
