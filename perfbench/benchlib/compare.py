"""The comparison that decides ``correct``: served answers against the plain
reference (``reference.py``) on the same queries.

Each number is held to its own limit (``perfbench/limits/<cell>.json``):

* ``topk_gap`` -- what was selected: per query, the served top-k scores and
  the reference's own top-k scores, each sorted best first, compared
  position by position; the largest ``|served - reference|`` relative to
  ``max(1, |reference|)``. A query whose count of answers (finite scores)
  differs from the reference's reads ``inf``;
* ``score_gap`` -- that each returned id carries its own score: the largest
  ``|served score - reference score|`` over every returned id, the
  reference scoring that same (query, id) pair, relative as above. An id
  that is no point of the index reads ``inf``;
* ``ids_not_once`` (set by the harness) -- corpus ids the served cluster
  layout does not hold exactly once; exact, limit 0.
"""
from __future__ import annotations

import numpy as np


def _relative(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    return np.abs(got - want) / np.maximum(1.0, np.abs(want))


def topk_gap(scores: np.ndarray, ref_scores: np.ndarray, metric: str) -> float:
    """Largest relative gap between the sorted served and the sorted
    reference top-k scores (both (Q, k))."""
    sign = 1.0 if metric == "l2" else -1.0      # best first: l2 ascending
    got = np.sort(sign * scores.astype(np.float64), axis=1)
    want = np.sort(sign * ref_scores.astype(np.float64), axis=1)
    fin_got, fin_want = np.isfinite(got), np.isfinite(want)
    if (fin_got.sum(axis=1) != fin_want.sum(axis=1)).any():
        return float("inf")
    if not fin_want.any():
        return 0.0
    return float(_relative(got[fin_want], want[fin_want]).max())


def score_gap(scores: np.ndarray, ref_of_served: np.ndarray) -> float:
    """Largest relative gap between each served score and the reference's
    score of that same (query, id) (both (Q, k))."""
    fin = np.isfinite(scores)
    if not fin.any():
        return 0.0
    want = ref_of_served[fin]
    gap = np.where(np.isfinite(want),
                   _relative(scores[fin].astype(np.float64), want), np.inf)
    return float(gap.max())


def numbers(scores: np.ndarray, ref_scores: np.ndarray,
            ref_of_served: np.ndarray, metric: str) -> dict:
    """``{"topk_gap", "score_gap"}`` of served scores (Q, k) against the
    reference's own top-k scores and its scores of the served ids."""
    return {"topk_gap": topk_gap(scores, ref_scores, metric),
            "score_gap": score_gap(scores, ref_of_served)}


def judge(values: dict, limits: dict) -> list[dict]:
    """One ``{"name", "value", "limit", "ok"}`` per limited number; a number
    the run could not produce fails."""
    out = []
    for name, limit in limits.items():
        v = values.get(name)
        ok = v is not None and np.isfinite(v) and v <= limit
        out.append({"name": name, "value": v, "limit": limit, "ok": bool(ok)})
    return out
