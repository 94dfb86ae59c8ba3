"""Least device time of one unit of work, from its shapes alone.

The counts are lower bounds that any implementation of the same work has
to meet, so the share they give cannot pass 100%:

* a boosting round reads each quantised feature value once (the root
  histogram needs all of them; ceil(log2(max_bins)) bits each), reads the
  labels and the margins and writes the margins, f32 each; it adds a
  gradient and a hessian per row, feature and tree into the root histogram;
* a batch `predict` reads the f32 rows and writes one f32 output per row
  and class; it makes one comparison per row, tree and level.

Operations are held against the chip's bf16 peak, bytes against its HBM
bandwidth; the larger of the two times bounds the work.
"""
from __future__ import annotations

import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def _bound(ops: float, nbytes: float, pk: dict) -> dict:
    t_ops, t_bytes = ops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"]
    return {"ops": ops, "bytes": nbytes, "seconds": max(t_ops, t_bytes),
            "bound": "bytes" if t_bytes >= t_ops else "ops"}


def train_round(rows: int, features: int, outputs: int, max_bins: int,
                pk: dict) -> dict:
    bits = math.ceil(math.log2(max_bins))
    nbytes = rows * features * bits / 8 + rows * 4 + 2 * rows * outputs * 4
    ops = 2 * rows * features * outputs
    return _bound(ops, nbytes, pk)


def predict_call(rows: int, features: int, outputs: int, trees: int,
                 depth: int, pk: dict) -> dict:
    nbytes = rows * features * 4 + rows * outputs * 4
    ops = rows * trees * depth
    return _bound(ops, nbytes, pk)
