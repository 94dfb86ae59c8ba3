"""repro.serve — batched GBDT inference (DESIGN.md §14).

The training side of the paper got six PRs; this package is the serving
side: a dedicated batched-inference stack over the compact ensemble arena.

  * `traversal`  — fused ensemble traversal: blocks of trees evaluate every
    split node densely over a row block and pick each row's leaf by a path
    one-hot, with no per-row gather, replacing the per-tree `lax.scan` of
    `core.predict` for batch inference. Bin-space fast path when the model carries cut points,
    raw-threshold path otherwise; a Pallas kernel lives in
    `kernels.ensemble_traversal` with the XLA form as its parity oracle.
  * `engine`     — `PredictEngine`: shape-bucketed compiled predict caches
    (mixed request sizes pad up to a small static set of power-of-two row
    buckets, so serving traffic never recompiles), optional persistent
    host staging, and per-call latency accounting
    (p50/p99, rows/s).
  * `interop`    — XGBoost model-format interop: load a real
    `xgboost.Booster` JSON into our arena (matching its predictions) and
    export our Booster to that JSON, so the server can front models trained
    anywhere.
"""
from repro.serve.engine import PredictEngine
from repro.serve.interop import (
    export_xgboost_json,
    import_xgboost_json,
)

__all__ = [
    "PredictEngine",
    "export_xgboost_json",
    "import_xgboost_json",
]
