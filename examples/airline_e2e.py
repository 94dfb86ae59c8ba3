"""End-to-end driver (the paper's large-scale scenario, reduced for CPU):
airline-shaped data (13 features, binary), 200 boosting rounds, multi-
device row sharding with AllReduce histogram combination (Algorithm 1) as a
strategy behind the same Booster.fit signature.

Run single-device:
    PYTHONPATH=src python examples/airline_e2e.py
Across 8 virtual devices (Algorithm 1 multi-GPU path):
    PYTHONPATH=src python examples/airline_e2e.py --devices 8

(paper scale: 115M rows on 8 V100s in under 3 minutes; here 200k rows on
a 1-core CPU container — the algorithm and collectives are the same.)
"""
import argparse
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--devices", type=int, default=1)
ap.add_argument("--rows", type=int, default=200_000)
ap.add_argument("--rounds", type=int, default=200)
args = ap.parse_args()

if args.devices > 1 and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={args.devices}"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import time
import numpy as np
from repro.core import Booster, DeviceDMatrix
from repro.data import make_dataset

x, y, spec = make_dataset("airline", n_rows=args.rows)
n_tr = int(0.9 * args.rows)
n_tr = (n_tr // args.devices) * args.devices  # shard-divisible (no-op at 1)

mesh = None
if args.devices > 1:
    from repro.dist import make_mesh
    mesh = make_mesh((args.devices,), ("data",))

t0 = time.perf_counter()
dtrain = DeviceDMatrix(x[:n_tr], label=y[:n_tr])
t_build = time.perf_counter() - t0

bst = Booster(n_rounds=args.rounds, max_depth=6, max_bins=256,
              objective=spec.objective)
t0 = time.perf_counter()
bst.fit(dtrain, verbose_every=50, mesh=mesh,
        callback=lambda r, rec: print(rec, flush=True))
t_fit = time.perf_counter() - t0

p = np.asarray(bst.predict(x[n_tr:]))
acc = float(np.mean((p > 0.5) == y[n_tr:]))
print(f"rows={args.rows} rounds={args.rounds} devices={args.devices} "
      f"dmatrix={t_build:.1f}s fit={t_fit:.1f}s valid_accuracy={acc:.4f}")
