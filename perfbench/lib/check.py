"""The comparison that decides ``correct``.

For one propose call the program returns its picks (candidate indices, best
first) and the aggregate rank it gave each. The float64 reference
(``reference/propose_ref.py``) scores the same pool from the same forests.

Two candidates whose expected improvements, in one source, differ but lie
within ``EI_TIE_REL`` times that source's incumbent of each other (or both
below ``EI_TIE_ABS``) may take either order: float64 on the chip is a pair
of float32 (about 48 bits, float32's range), and its EI differs from the
host's by up to a few 1e-9 seconds at incumbents of 1e4 seconds.
Candidates whose leaves carry equal statistics in every tree get equal EI on
any device and keep candidate order. So each candidate gets an interval of
admissible aggregates
``[lo, hi]``, the weighted sums of the lowest and highest rank it may take
in each source.
The numbers compared:

``topk_gap``   rank units: how far the program's i-th pick, at its best, lies
               above the worst of the reference's first i picks. 0 when the
               selection is one the reference admits.
``agg_excess`` relative: how far the aggregate the program reports for a pick
               lies outside that pick's interval, over the interval's top.

Each is the maximum over the picks and over the calls checked.
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

from perfbench.reference import propose_ref as R

EI_TIE_REL = 1e-11
EI_TIE_ABS = float(np.finfo(np.float32).tiny)


def admissible(forests, X, incumbents, weights):
    """The reference aggregate of every candidate and its admissible
    interval ``(agg, lo, hi)``."""
    N = len(X)
    agg = np.zeros(N)
    lo = np.zeros(N)
    hi = np.zeros(N)
    for f, inc, w in zip(forests, incumbents, weights):
        stats = R.leaf_stats(f, X)
        e = R.expected_improvement(*R.forest_predict(f, X, stats=stats), inc)
        order = np.argsort(-e, kind="stable")
        rank = np.empty(N)
        rank[order] = np.arange(N)
        # candidates in leaves of equal statistics in every tree get equal
        # EI on every device and keep candidate order among themselves
        _, group, size = np.unique(np.concatenate(stats).T, axis=0,
                                   return_inverse=True, return_counts=True)
        group = group.reshape(-1)
        by_group = np.argsort(group, kind="stable")
        first = np.searchsorted(group[by_group], group[by_group], side="left")
        same_before = np.empty(N, dtype=np.int64)
        same_before[by_group] = np.arange(N) - first
        tol = EI_TIE_REL * abs(inc) + EI_TIE_ABS
        srt = np.sort(e)
        far_above = N - np.searchsorted(srt, e + tol, side="right")
        within = np.searchsorted(srt, e + tol, side="right") - np.searchsorted(srt, e - tol, side="left")
        r_lo = far_above + same_before
        agg += w * rank
        lo += w * r_lo
        hi += w * (r_lo + within - size[group])
    return agg, lo, hi


def selection_numbers(agg, lo, hi, got_idx, got_agg) -> Dict[str, float]:
    """``topk_gap`` and ``agg_excess`` of one call's picks."""
    got_idx = np.asarray(got_idx, dtype=np.int64)
    got_agg = np.asarray(got_agg, dtype=float)
    k = len(got_idx)
    N = len(agg)
    if (k == 0 or got_idx.min() < 0 or got_idx.max() >= N
            or len(np.unique(got_idx)) != k or not np.all(np.isfinite(got_agg))):
        return {"topk_gap": float("inf"), "agg_excess": float("inf")}
    want = R.top_k(agg, k)
    gap = np.max(np.maximum(lo[got_idx] - np.maximum.accumulate(hi[want]), 0.0))
    l, h = lo[got_idx], hi[got_idx]
    out = np.maximum(np.maximum(l - got_agg, got_agg - h), 0.0)
    return {"topk_gap": float(gap),
            "agg_excess": float(np.max(out / np.maximum(h, 1.0)))}


def check_call(forests, X, incumbents, weights, got_idx, got_agg) -> Dict[str, float]:
    """Both numbers of one call."""
    agg, lo, hi = admissible(forests, X, incumbents, weights)
    return selection_numbers(agg, lo, hi, got_idx, got_agg)


def control_call(forests, X, incumbents, weights, k, dtype=np.float32):
    """The reference computed in ``dtype``, in the program's place: its
    picks and their aggregates."""
    a = R.score_pool(forests, X, incumbents, weights, dtype)
    idx = R.top_k(a, k)
    return idx, a[idx].astype(float)


def worst(numbers: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """The largest reading of each number over several calls."""
    out: Dict[str, float] = {}
    for d in numbers:
        for key, v in d.items():
            out[key] = max(out.get(key, 0.0), v)
    return out


def verdict(readings: Sequence[Dict[str, float]], limits: Dict[str, float]) -> bool:
    """``correct``: some call was checked, and each number's worst reading
    is within its limit (a number that no call read fails)."""
    numbers = worst(readings)
    return bool(readings) and all(numbers.get(k, float("inf")) <= v
                                  for k, v in limits.items())
