"""Distributed quickstart: multi-device training with pluggable collectives
(repro.dist, DESIGN.md §15). Runs on 8 virtual CPU devices so it works —
and means the same thing — on a laptop or an accelerator pod:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/dist_quickstart.py

Every collective at f32 grows bit-identical trees to the single-device
fit; compression (f16 / q16) narrows the histogram allreduce wire to 2
bytes/element with an on-device error check that falls back to exact f32
when the tolerance is exceeded.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import Booster, DeviceDMatrix  # noqa: E402
from repro.dist import sharded_sketch_cuts  # noqa: E402
from repro.dist import make_mesh  # noqa: E402

rng = np.random.default_rng(0)
n, f = 8_192, 10
x = rng.normal(size=(n, f)).astype(np.float32)
y = (x[:, 0] * 2 + np.sin(x[:, 1]) + 0.1 * rng.normal(size=n)).astype(
    np.float32
)

# --- device-sharded sketch: each shard sorts + sketches its rows, then a
# --- log-depth tree merge produces one mergeable-summary cut set ---------
mesh = make_mesh((8,), ("data",))
cuts = sharded_sketch_cuts(x, max_bins=64, capacity=4096, mesh=mesh)
dtrain = DeviceDMatrix(x, label=y, max_bins=64, cuts=np.asarray(cuts))

# --- single-device reference fit ----------------------------------------
ref = Booster(n_rounds=5, max_depth=4, max_bins=64).fit(dtrain)

# --- every collective strategy reproduces it bit-identically at f32 ------
for name in ("psum", "ring", "hier"):
    bst = Booster(n_rounds=5, max_depth=4, max_bins=64).fit(
        dtrain, mesh=mesh, collective=name
    )
    assert bool(jnp.all(bst.ensemble.feature == ref.ensemble.feature)), name
    assert bool(
        jnp.all(bst.ensemble.split_bin == ref.ensemble.split_bin)
    ), name
    leaf_diff = float(
        jnp.max(jnp.abs(bst.ensemble.leaf_value - ref.ensemble.leaf_value))
    )
    assert leaf_diff < 1e-4, (name, leaf_diff)
    cs = bst.comm_stats  # per-round communication accounting
    print(
        f"{name:5s} f32: identical trees, "
        f"{cs['bytes_per_round']:>9d} B/round, "
        f"{cs['collective_calls_per_round']} calls/round"
    )

# --- compressed allreduce: 2-byte wire, error-checked fallback to f32 ----
for comp in ("f16", "q16"):
    bst = Booster(n_rounds=5, max_depth=4, max_bins=64).fit(
        dtrain, mesh=mesh, collective="ring", compression=comp
    )
    cs = bst.comm_stats
    rmse = float(np.sqrt(np.mean((np.asarray(bst.predict(x)) - y) ** 2)))
    rmse0 = float(np.sqrt(np.mean((np.asarray(ref.predict(x)) - y) ** 2)))
    assert abs(rmse - rmse0) <= 0.05 * rmse0 + 1e-3, (comp, rmse, rmse0)
    print(
        f"ring  {comp}: rmse {rmse:.4f} (f32 {rmse0:.4f}), "
        f"{cs['bytes_per_round']:>9d} B/round "
        f"({cs['bytes_per_round_f32'] / cs['bytes_per_round']:.2f}x less), "
        f"{cs['fallback_events']} fallbacks"
    )

print("dist quickstart OK")
