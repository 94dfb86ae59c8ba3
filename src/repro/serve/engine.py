"""PredictEngine — the serving front end (DESIGN.md §14).

Wraps a fitted (or imported) Booster behind a `predict(X)` call shaped for
request traffic rather than training:

  * Shape-bucketed compiled caches. XLA compiles one program per input
    shape; naive serving of mixed request sizes would recompile constantly.
    Incoming batches are padded up to a small static ladder of power-of-two
    row buckets, so after one warmup pass per bucket NO request size ever
    triggers a recompile (asserted by a trace counter the tests read).
    Padding rows are NaN — the legal missing marker, routed through default
    directions like any missing value — and are sliced off the output.
  * Persistent host staging. One preallocated float32 staging buffer per
    bucket: the request's rows are copied (and dtype-converted — the single
    float32 conversion on this path) into the buffer's head, the tail is
    NaN, and the device transfer always leaves from the same page-aligned
    allocation (the pinned-host pattern; on CPU it simply avoids per-call
    allocation).
  * Latency accounting. Every call records rows, wall seconds, and the
    programs it compiled, in a bounded store (`repro.obs`); `stats()` reduces the
    stored calls to p50/p99 latency and rows per summed latency second,
    with compile calls excluded (they are warmup, not steady state).

Validation mirrors DeviceDMatrix: inputs must be 2-D with the model's
feature count, ±inf is rejected with the same remedy message, NaN stays
legal missing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import predict as PR

DEFAULT_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)


class PredictEngine:
    """Batched-inference engine over a fitted Booster.

    Args:
      booster: a fitted `repro.core.Booster` (trained here or imported via
        `repro.serve.interop.import_xgboost_json`).
      buckets: ascending row-count ladder to pad batches onto. Requests
        larger than the top bucket are served in top-bucket slices.
      output_margin: serve raw margins instead of transformed predictions.
      iteration_range: XGBoost-style (a, b) round slice baked in at engine
        build (staged serving: one engine per stage, no per-call slicing).
      host_staging: keep one persistent staging buffer per bucket.

    `predict(X)` returns a numpy array of X's row count.
    """

    def __init__(
        self,
        booster,
        *,
        buckets: tuple[int, ...] = DEFAULT_BUCKETS,
        output_margin: bool = False,
        iteration_range: tuple[int, int] = (0, 0),
        host_staging: bool = True,
    ):
        if getattr(booster, "ensemble", None) is None:
            raise RuntimeError(
                "PredictEngine requires a fitted Booster — call fit() or "
                "import a model first"
            )
        buckets = tuple(sorted(int(b) for b in buckets))
        if not buckets or any(b <= 0 for b in buckets):
            raise ValueError(f"buckets must be positive ints, got {buckets!r}")

        ens = booster.ensemble
        if iteration_range != (0, 0):
            ens = PR.slice_rounds(ens, *iteration_range)
        self._ens = ens
        self._max_depth = booster.cfg.max_depth
        self._transform = None if output_margin else booster.obj.transform
        self._buckets = buckets
        self._host_staging = bool(host_staging)

        nf = getattr(booster, "n_features_in_", None)
        if nf is None and getattr(booster, "cuts", None) is not None:
            nf = int(booster.cuts.shape[0])
        if nf is None:
            raise ValueError(
                "cannot infer the model's feature count; booster has "
                "neither cuts nor n_features_in_"
            )
        self.n_features = int(nf)

        self._compiled: dict[int, object] = {}  # bucket -> jit'd fn
        self._staging: dict[int, np.ndarray] = {}
        self._trace_count = 0  # bumped at trace time; tests assert on it
        self.calls = obs.store()  # the last obs.MAXLEN calls' records

    # --- compiled cache ----------------------------------------------------
    @property
    def trace_count(self) -> int:
        """Number of XLA traces taken so far (one per bucket after warmup —
        a steady-state engine never increases this)."""
        return self._trace_count

    def _bucket_for(self, n_rows: int) -> int:
        for b in self._buckets:
            if n_rows <= b:
                return b
        return self._buckets[-1]

    def _compiled_for(self, bucket: int):
        fn = self._compiled.get(bucket)
        if fn is None:
            def traced(ens, block):
                # Trace-time side effect only: retraces are recompiles.
                self._trace_count += 1
                m = PR._fold_classes(
                    _traverse_raw(ens, block, self._max_depth), ens,
                    block.shape[0],
                )
                return m if self._transform is None else self._transform(m)

            fn = jax.jit(traced)
            self._compiled[bucket] = fn
        return fn

    def _stage(self, x: np.ndarray, bucket: int) -> np.ndarray:
        """Copy the batch into the bucket's persistent staging buffer (the
        single float32 conversion), NaN-fill the padding tail."""
        buf = self._staging.get(bucket)
        if buf is None:
            buf = np.empty((bucket, self.n_features), np.float32)
            if self._host_staging:
                self._staging[bucket] = buf
        n = x.shape[0]
        np.copyto(buf[:n], x, casting="unsafe")
        buf[n:] = np.nan
        return buf

    # --- serving -----------------------------------------------------------
    def warmup(self) -> "PredictEngine":
        """Compile every bucket up front so the first real request never
        pays a trace."""
        probe = np.zeros((1, self.n_features), np.float32)
        for b in self._buckets:
            fn = self._compiled_for(b)
            jax.block_until_ready(fn(self._ens, jnp.asarray(self._stage(probe, b))))
        return self

    def predict(self, x) -> np.ndarray:
        """Serve one request batch. Accepts any 2-D array-like; rows beyond
        the largest bucket are processed in largest-bucket slices."""
        with obs.call("engine.predict", into=self.calls) as rec:
            x = np.asarray(x)
            if x.ndim != 2:
                raise ValueError(
                    f"x must be 2-D (n_rows, n_features), got shape {x.shape}"
                )
            if x.shape[1] != self.n_features:
                raise ValueError(
                    f"x has {x.shape[1]} features, model expects "
                    f"{self.n_features}"
                )
            if x.shape[0] == 0:
                raise ValueError("x has 0 rows; nothing to predict")
            if np.isinf(x).any():
                raise ValueError(
                    "x contains infinite feature values; replace ±inf with "
                    "NaN (the legal missing marker) or a large finite value "
                    "before prediction"
                )

            top = self._buckets[-1]
            parts = []
            for s in range(0, x.shape[0], top):
                part = x[s : s + top]
                bucket = self._bucket_for(part.shape[0])
                fn = self._compiled_for(bucket)
                block = jnp.asarray(self._stage(part, bucket))
                out = fn(self._ens, block)
                parts.append(np.asarray(out)[: part.shape[0]])
            result = parts[0] if len(parts) == 1 else np.concatenate(parts)
            rec["rows"] = int(x.shape[0])
        return result

    # --- accounting --------------------------------------------------------
    def stats(self, include_warmup: bool = False) -> dict:
        """p50/p99 latency and throughput over the recorded calls (the last
        `obs.MAXLEN`). Calls that obtained a program (the record's
        `compiles`: compiled, or loaded from the persistent cache) are
        excluded unless include_warmup=True. `rows_per_s` is rows over the
        summed latency of those calls, not over wall time: idle time between
        requests does not count, and overlapping calls would count twice."""
        calls = [
            c for c in self.calls if include_warmup or not obs.compiles(c)
        ]
        if not calls:
            return {"n_calls": 0}
        lat = np.array([c["seconds"] for c in calls])
        rows = sum(c["rows"] for c in calls)
        return {
            "n_calls": len(calls),
            "rows": rows,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "rows_per_s": float(rows / lat.sum()),
        }

    def reset_stats(self) -> None:
        self.calls.clear()


def _traverse_raw(ens: PR.Ensemble, x: jax.Array, max_depth: int):
    from repro.serve.traversal import traverse_ensemble_raw

    return traverse_ensemble_raw(
        ens.feature, ens.threshold, ens.default_left, ens.leaf_value,
        ens.is_leaf, x, max_depth,
    )
