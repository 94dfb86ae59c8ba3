"""Synthetic stand-ins for the paper's datasets, made from a seed.

A copy of the repository's dataset generator, kept here so that the
benchmark's inputs do not move when the program changes. Every parameter
comes from the configuration file, so a new dataset needs no edit here.
"""
from __future__ import annotations

import zlib

import numpy as np


def make_dataset(cfg: dict, seed: int, n_rows: int | None = None):
    """(x, y) as float32 arrays for the configuration `cfg`.

    Keys read: `dataset` (salts the seed), `rows`, `features`, `task`
    ("reg", "binary" or "multiclass"), `num_class`, `missing_frac`.
    """
    n = int(n_rows or cfg["rows"])
    f = int(cfg["features"])
    task = cfg["task"]
    # crc32, not hash(): string hashing is salted per process.
    rng = np.random.default_rng(
        seed + zlib.crc32(cfg["dataset"].encode()) % 2**31)

    x = rng.standard_normal((n, f), dtype=np.float32)
    # Learnable structure: a sparse linear signal on the first k columns
    # plus three pairwise interactions.
    k = max(3, min(f // 5, 24))
    w = np.zeros(f, np.float32)
    w[:k] = rng.standard_normal(k).astype(np.float32)
    signal = x @ w
    for _ in range(3):
        i, j = rng.integers(0, k, size=2)
        signal += 0.5 * x[:, i] * x[:, j]
    noise = 0.3 * rng.standard_normal(n).astype(np.float32)

    if task == "reg":
        y = (signal + noise).astype(np.float32)
    elif task == "binary":
        y = (signal + noise > 0).astype(np.float32)
    elif task == "multiclass":
        n_classes = int(cfg["num_class"])
        qs = np.quantile(signal, np.linspace(0, 1, n_classes + 1)[1:-1])
        y = np.digitize(signal + noise, qs).astype(np.float32)
    else:
        raise ValueError(f"unknown task {task!r}")

    missing = float(cfg.get("missing_frac", 0.0))
    if missing > 0:
        x[rng.random(x.shape) < missing] = np.nan
    return x, y


def split_holdout(x, y, holdout_fraction: float):
    """The first rows train, the last `holdout_fraction` are held out."""
    n_tr = int(round((1.0 - holdout_fraction) * len(x)))
    return x[:n_tr], y[:n_tr], x[n_tr:], y[n_tr:]
