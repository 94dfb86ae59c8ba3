"""The numbers that decide `correct`, and their limits.

Training cells (the program's margins after each of the first rounds
against the numpy reference trainer's, both from the same rows):

  loss_gap_r1   |L - L_ref| / L_ref of the mean training loss after round 1.
  loss_gap      the same, worst over the compared rounds.
  update1_gap   the first round's update as the trees apply it,
                d = m_1 - m_0, per margin column (class) k:
                median_i |d_ik - d_ref_ik| / median_i |d_ref_ik|, worst k.
  update1_norm_gap, change_norm_gap
                gaps of norms, not norms of differences: per column k,
                | ||d_k|| - ||d_ref_k|| | / max(||d_ref_k||, median_k
                ||d_ref_k||), worst k; for the first round's update and for
                the change over all compared rounds.

Scoring and serving cells (answers checked one by one):

  answer_gap    max |p - p_ref| over the sampled answers, in the
                objective's output space (probabilities); an answer that
                never came reads inf.

Limits sit in `bench/limits/<cell>.json`: {"<number>": {"limit": x, ...}}.
A number whose limit is null is printed and not compared.
"""
from __future__ import annotations

import json
import os

import numpy as np

from bench import reference as R

HERE = os.path.dirname(os.path.abspath(__file__))


def _norm_gap(d, d_ref) -> float:
    n, n_ref = np.linalg.norm(d, axis=0), np.linalg.norm(d_ref, axis=0)
    scale = np.maximum(n_ref, np.median(n_ref))
    return float(np.max(np.abs(n - n_ref) / scale))


def training_numbers(objective, y, base, margins, margins_ref) -> dict:
    """margins / margins_ref: (n, k) arrays after rounds 1, 2, ... ."""
    m0 = np.full_like(margins_ref[0], base)
    gaps = []
    for m, m_ref in zip(margins, margins_ref):
        l, l_ref = R.loss(objective, m, y), R.loss(objective, m_ref, y)
        gaps.append(abs(l - l_ref) / l_ref)
    d, d_ref = margins[0] - m0, margins_ref[0] - m0
    med = np.maximum(np.median(np.abs(d_ref), axis=0), 1e-30)
    return {
        "loss_gap_r1": gaps[0],
        "loss_gap": max(gaps),
        "update1_gap": float(np.max(np.median(np.abs(d - d_ref), axis=0)
                                    / med)),
        "update1_norm_gap": _norm_gap(d, d_ref),
        "change_norm_gap": _norm_gap(margins[-1] - m0, margins_ref[-1] - m0),
        "rounds_compared": len(margins),
    }


def answer_numbers(outputs, refs) -> dict:
    gap = 0.0
    for out, ref in zip(outputs, refs):
        if out is None or np.shape(out) != np.shape(ref) \
                or not np.all(np.isfinite(out)):
            return {"answer_gap": float("inf"), "answers_compared": len(refs)}
        gap = max(gap, float(np.max(np.abs(np.asarray(out, np.float64)
                                           - ref))))
    return {"answer_gap": gap, "answers_compared": len(refs)}


def load_limits(cell: str, root: str = HERE) -> dict:
    path = os.path.join(root, "limits", f"{cell}.json")
    with open(path) as fh:
        return json.load(fh)


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value": v, "limit": l}}) over every number with
    a limit entry; a null limit is reported and not compared."""
    shown, ok = {}, True
    for name, spec in limits.items():
        v, lim = numbers.get(name), spec.get("limit")
        shown[name] = {"value": v, "limit": lim}
        if lim is None:
            continue
        if v is None or not v <= lim:
            ok = False
    return ok, shown
