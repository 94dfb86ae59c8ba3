"""Bring-up smoke test of the GBDT pipeline on a TPU.

    python chip_smoke.py             # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4   # four chips: the row-sharded phase only

Every phase drives the public path: `repro.data.make_dataset` ->
`DeviceDMatrix` -> `Booster.fit` -> `Booster.predict` / `PredictEngine`, on
Higgs-shaped data (28 features, binary:logistic, depth 6, 256 bins) made
from a seed.

  (a) small:   20,000 rows, 10 rounds; holdout probabilities agree with the
               numpy histogram trainer (`benchmarks/baselines.py`).
  (b) full:    the Higgs shape (11M rows, 80/20 split); timed DeviceDMatrix
               build, a 1-round warm-up fit, then twice a 5-round fit with
               the holdout in `evals` (the first compiles, the second is
               steady state), then PredictEngine over holdout batches.
  (c) kernel:  phase (a)'s data fitted with the Pallas histogram kernel
               (`use_kernel_histograms=True`); trees equal the default fit's.
  (d) sharded: `--chips 4` — a row-sharded fit over a ("data",) mesh of four
               chips against a one-chip fit of the same 2M rows.

Each phase prints one JSON line of results. The last line of stdout is
`{"ok": true, "device": {...}}` with the device as JAX reports it. Without a
TPU, `main()` exits non-zero before any phase runs; a failed phase raises
and the script exits non-zero. The phases are plain functions of their
sizes, so the tests run them on CPU at tiny sizes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# How a fit is held to its reference: the numpy trainer in (a), the default
# fit in (c), the one-device fit in (d). Trees are compared round by round
# in level order. Two correct trainers can still part at a node whose two
# best candidate splits have near-equal gain, because f32 sums in another
# order (or the reference's f64) pick the other one. So at the first node
# where the trees part, the two gains must agree within GAIN_TIE (relative),
# the mark of such a near-tie; a wrong histogram or split search moves the
# gain by far more. Every later round then sees other gradients, so from
# there on predictions are held to the aggregate bounds only.
# Set from the CPU rehearsal at phase (a)'s size, seeds 0 and 1, against
# the numpy reference. Seed 0: the trees part at round 0, node 61 (gains
# 2.958618 vs 2.958561, 1.9e-5 relative); after 10 rounds mean |dp| 0.0144,
# labels agree on 98.5% of the holdout, accuracy 0.92375 vs 0.9255.
# Seed 1: they part at round 0, node 35 (gains equal to 1e-7 relative) and
# no probability moves by more than 1.7e-5. The kernel fit (c) parts from
# the default fit at round 6, node 31 on seed 0 (gains equal to 2.2e-6
# relative) and not at all on seed 1.
# With equal trees, f32 sums in another order still move leaf values, the
# more so the more rows a leaf sums: max |dp| was 1.7e-5 at phase (a)'s
# size on CPU, 2.2e-4 for the sharded fit (d) at 1.6M rows on four v5e
# chips. A flipped split moves rows by 1e-2 or more.
GAIN_TIE = 1e-4
PRED_TOL = 1e-3  # max |p - p_ref| when no tree parts
MEAN_TOL = 0.03  # mean |p - p_ref| after a near-tie
AGREE_MIN = 0.97  # share of holdout rows with the same predicted label
ACC_TOL = 0.01  # |accuracy - accuracy_ref| after a near-tie

SMALL_ROWS = 20_000
HIGGS_ROWS = 11_000_000
SHARDED_ROWS = 2_000_000


def _check(ok, *what) -> None:
    """A failed check raises (and is never skipped, unlike `assert` under
    `python -O`)."""
    if not ok:
        raise AssertionError(*what)


def _emit(phase: str, **rec) -> dict:
    print(json.dumps({"phase": phase, **rec}), flush=True)
    return rec


def _config(n_rounds: int, **kw):
    from repro.core import BoosterConfig

    return BoosterConfig(n_rounds=n_rounds, max_depth=6, max_bins=256,
                         objective="binary:logistic", **kw)


def _split(n_rows: int, seed: int):
    from repro.data import make_dataset

    x, y, _ = make_dataset("higgs", n_rows=n_rows, seed=seed)
    n_tr = int(0.8 * n_rows)
    return x[:n_tr], y[:n_tr], x[n_tr:], y[n_tr:]


def _booster_trees(bst) -> list:
    """Per round: (feature, threshold, is_leaf, gain) over the node arena."""
    ens = bst.ensemble
    arrays = [np.asarray(a) for a in (ens.feature, ens.threshold, ens.is_leaf,
                                      ens.gain)]
    return [tuple(a[r] for a in arrays) for r in range(arrays[0].shape[0])]


def _numpy_trees(roots, n_arena: int) -> list:
    """The numpy trainer's trees laid out like `_booster_trees`."""
    out = []
    for root in roots:
        feat, thr = np.zeros(n_arena, np.int64), np.zeros(n_arena)
        leaf, gain = np.zeros(n_arena, bool), np.zeros(n_arena)
        stack = [(root, 0)]
        while stack:
            nd, i = stack.pop()
            if nd.feature < 0:
                leaf[i] = True
                continue
            feat[i], thr[i], gain[i] = nd.feature, nd.thr, nd.gain
            stack += [(nd.left, 2 * i + 1), (nd.right, 2 * i + 2)]
        out.append((feat, thr, leaf, gain))
    return out


def _first_divergence(a: list, b: list):
    """(round, node, gain_a, gain_b) where trees a and b first part, in
    round-major level order, or None. A leaf's gain counts as 0."""
    _check(len(a) == len(b), "round counts differ", len(a), len(b))
    for r, (ta, tb) in enumerate(zip(a, b)):
        queue = [0]
        for i in queue:  # grows while it is walked: level order
            (fa, xa, la, ga), (fb, xb, lb, gb) = (
                tuple(t[k][i] for k in range(4)) for t in (ta, tb))
            if la != lb or (not la and (fa != fb or not np.isclose(
                    xa, xb, rtol=1e-6, atol=1e-6))):
                return (r, i, 0.0 if la else float(ga),
                        0.0 if lb else float(gb))
            if not la:
                queue += [2 * i + 1, 2 * i + 2]
    return None


def _agreement(phase: str, trees, trees_ref, p, p_ref, y) -> dict:
    """Hold a fit to its reference (see GAIN_TIE); returns the record."""
    p, p_ref = np.asarray(p, np.float64), np.asarray(p_ref, np.float64)
    _check(p.shape == p_ref.shape == y.shape and np.all(np.isfinite(p)))
    d = np.abs(p - p_ref)
    rec = dict(
        max_abs_diff=float(d.max()), mean_abs_diff=float(d.mean()),
        label_agreement=float(np.mean((p > 0.5) == (p_ref > 0.5))),
        accuracy=float(np.mean((p > 0.5) == y)),
        accuracy_ref=float(np.mean((p_ref > 0.5) == y)),
    )
    div = _first_divergence(trees, trees_ref)
    rec["trees_equal"] = div is None
    if div is None:
        _check(rec["max_abs_diff"] <= PRED_TOL, phase, rec)
        return rec
    r, node, ga, gb = div
    rec.update(first_divergence=[r, node], gains_at_divergence=[ga, gb])
    _check(abs(ga - gb) <= GAIN_TIE * max(abs(ga), abs(gb)) + 1e-6,
           f"({phase}) trees part at round {r} node {node} with gains "
           f"{ga} vs {gb}: not a near-tie", rec)
    _check(rec["mean_abs_diff"] <= MEAN_TOL, phase, rec)
    _check(rec["label_agreement"] >= AGREE_MIN, phase, rec)
    _check(abs(rec["accuracy"] - rec["accuracy_ref"]) <= ACC_TOL, phase, rec)
    return rec


def phase_small(n_rows: int = SMALL_ROWS, n_rounds: int = 10, seed: int = 0):
    """(a) Default fit vs the numpy histogram trainer on the same data."""
    from benchmarks.baselines import train_numpy
    from repro.core import Booster, DeviceDMatrix
    from repro.core.tree import arena_size

    x_tr, y_tr, x_ho, y_ho = _split(n_rows, seed)
    dtrain = DeviceDMatrix(x_tr, label=y_tr, max_bins=256)
    bst = Booster(_config(n_rounds)).fit(dtrain)
    predict, _ = train_numpy(x_tr, y_tr, method="hist", n_rounds=n_rounds,
                             max_depth=6, lr=0.3, max_bins=256,
                             objective="binary:logistic")
    rec = _agreement(
        "a", _booster_trees(bst), _numpy_trees(predict.trees, arena_size(6)),
        bst.predict(x_ho), 1.0 / (1.0 + np.exp(-predict(x_ho))), y_ho)
    return _emit("small", rows=n_rows, rounds=n_rounds, **rec)


def phase_kernel(n_rows: int = SMALL_ROWS, n_rounds: int = 10, seed: int = 0):
    """(c) The Pallas histogram kernel against the default XLA histograms:
    per level on the same gradients, then as a whole fit."""
    import jax.numpy as jnp

    from repro.core import Booster, DeviceDMatrix
    from repro.core import histogram as H
    from repro.kernels import ops as KO

    x_tr, y_tr, x_ho, y_ho = _split(n_rows, seed)
    dtrain = DeviceDMatrix(x_tr, label=y_tr, max_bins=256)
    pb = dtrain.packed_bins()
    rng = np.random.default_rng(seed)
    gh = jnp.asarray(np.stack([rng.normal(size=len(x_tr)),
                               rng.random(len(x_tr))], -1), jnp.float32)
    hist_err = {}
    for n_nodes in (1, 2, 4, 8, 16, 32):
        pos = jnp.asarray(rng.integers(0, n_nodes + 1, len(x_tr)), jnp.int32)
        got = KO.build_histograms_kernel_packed(pb, gh, pos, n_nodes, 256)
        want = H.build_histograms_packed(pb.packed, gh, pos, n_nodes, 256,
                                         pb.bits, pb.n_rows)
        hist_err[n_nodes] = float(jnp.max(jnp.abs(got - want))
                                  / jnp.max(jnp.abs(want)))
        _check(hist_err[n_nodes] <= 1e-5, "c", n_nodes, hist_err)

    ref = Booster(_config(n_rounds)).fit(dtrain)
    kern = Booster(_config(n_rounds, use_kernel_histograms=True)).fit(dtrain)
    rec = _agreement("c", _booster_trees(kern), _booster_trees(ref),
                     kern.predict(x_ho), ref.predict(x_ho), y_ho)
    return _emit("kernel", rows=n_rows, rounds=n_rounds,
                 hist_rel_err_by_level=hist_err, **rec)


def phase_full(n_rows: int = HIGGS_ROWS, n_rounds: int = 5, seed: int = 0,
               n_batches: int = 4):
    """(b) Higgs width and rows: build, warm-up fit, timed fit, serving."""
    import jax

    from repro.core import Booster, DeviceDMatrix
    from repro.serve.engine import DEFAULT_BUCKETS, PredictEngine

    t0 = time.perf_counter()
    x_tr, y_tr, x_ho, y_ho = _split(n_rows, seed)
    gen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    dtrain = DeviceDMatrix(x_tr, label=y_tr, max_bins=256)
    dval = DeviceDMatrix(x_ho, label=y_ho, ref=dtrain)
    jax.block_until_ready((dtrain.matrix.packed, dval.matrix.packed))
    build_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    warm = Booster(_config(1)).fit(dtrain, evals=[(dval, "valid")])
    jax.block_until_ready(warm.margins)
    warmup_fit_s = time.perf_counter() - t0

    # The first n-round fit compiles its scan (unless the compile cache
    # holds it); the second, identical one is the steady state.
    fit_times, fits = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        bst = Booster(_config(n_rounds)).fit(dtrain, evals=[(dval, "valid")])
        jax.block_until_ready(bst.margins)
        fit_times.append(time.perf_counter() - t0)
        fits.append(bst)
    _check(fits[0].history == fits[1].history, "(b) refit differs",
           fits[0].history, fits[1].history)

    history_acc = [h["valid_accuracy"] for h in bst.history]
    holdout = bst.eval(dval, "valid")["valid_accuracy"]

    engine = PredictEngine(bst)
    t0 = time.perf_counter()
    engine.warmup()
    serve_warmup_s = time.perf_counter() - t0
    top = DEFAULT_BUCKETS[-1]
    batches = [x_ho[i * top:(i + 1) * top] for i in range(n_batches)]
    served = np.concatenate([engine.predict(b) for b in batches])
    direct = np.asarray(bst.predict(np.concatenate(batches)))
    serve_diff = float(np.max(np.abs(served - direct)))
    stats = engine.stats()

    _check(len(history_acc) == n_rounds and np.all(np.isfinite(history_acc)))
    _check(abs(history_acc[-1] - holdout) <= 1e-6, history_acc, holdout)
    _check(holdout > 0.6, f"(b) holdout accuracy {holdout} is near chance")
    _check(np.all(np.isfinite(served)) and served.shape == (len(direct),))
    _check(serve_diff <= 1e-6, f"(b) PredictEngine vs predict {serve_diff}")
    _check(engine.trace_count == len(DEFAULT_BUCKETS), engine.trace_count)
    mem = jax.devices()[0].memory_stats() or {}
    return _emit(
        "full", rows=n_rows, train_rows=len(x_tr), features=x_tr.shape[1],
        rounds=n_rounds, data_gen_s=gen_s, dmatrix_build_s=build_s,
        warmup_fit_s=warmup_fit_s, first_fit_s=fit_times[0],
        fit_s=fit_times[1],
        holdout_accuracy=holdout, valid_accuracy_by_round=history_acc,
        serve_warmup_s=serve_warmup_s, serve_p50_ms=stats.get("p50_ms"),
        serve_rows_per_s=stats.get("rows_per_s"),
        serve_max_abs_diff=serve_diff,
        peak_bytes_in_use=mem.get("peak_bytes_in_use"),
        bytes_limit=mem.get("bytes_limit"),
    )


def phase_sharded(n_rows: int = SHARDED_ROWS, n_devices: int = 4,
                  n_rounds: int = 5, seed: int = 0):
    """(d) Row-sharded fit over n_devices vs a one-device fit."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import Booster, DeviceDMatrix
    from repro.dist import make_mesh

    devices = jax.devices()
    _check(len(devices) >= n_devices,
           f"(d) needs {n_devices} devices, found {len(devices)}")
    x_tr, y_tr, x_ho, y_ho = _split(n_rows, seed)
    n_tr = len(x_tr) - len(x_tr) % n_devices
    dtrain = DeviceDMatrix(x_tr[:n_tr], label=y_tr[:n_tr], max_bins=256)

    t0 = time.perf_counter()
    single = Booster(_config(n_rounds)).fit(dtrain)
    jax.block_until_ready(single.margins)
    single_s = time.perf_counter() - t0

    mesh = make_mesh((n_devices,), ("data",), devices=devices[:n_devices])
    t0 = time.perf_counter()
    sharded = Booster(_config(n_rounds)).fit(dtrain, mesh=mesh)
    jax.block_until_ready(sharded.margins)
    sharded_s = time.perf_counter() - t0

    packed = dtrain.sharded_packed(
        n_devices, NamedSharding(mesh, P(None, "data")))
    spans = len(packed.sharding.device_set)
    shard_words = {s.data.shape[1] for s in packed.addressable_shards}
    margin_devices = len(sharded.margins.sharding.device_set)
    _check(mesh.devices.size == n_devices)
    _check(spans == n_devices and margin_devices == n_devices,
           spans, margin_devices)
    _check(shard_words == {packed.shape[1] // n_devices}, shard_words)
    rec = _agreement("d", _booster_trees(sharded), _booster_trees(single),
                     sharded.predict(x_ho), single.predict(x_ho), y_ho)
    return _emit(
        "sharded", rows=n_tr, devices=n_devices, rounds=n_rounds,
        packed_shape=list(packed.shape), packed_devices=spans,
        words_per_shard=sorted(shard_words), margin_devices=margin_devices,
        one_device_fit_s=single_s, sharded_fit_s=sharded_s, **rec,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the row-sharded phase over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    _emit("device", platform=dev.platform, kind=dev.device_kind,
          count=len(devices), compile_cache=cache)

    if args.chips == 4:
        phase_sharded(n_devices=4, seed=args.seed)
    else:
        phase_small(seed=args.seed)
        phase_kernel(seed=args.seed)
        phase_full(seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
