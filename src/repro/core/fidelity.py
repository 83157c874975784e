"""Query-based fidelity partitioning (paper §6.1, Algorithm 2).

A delta-fidelity proxy is a subset Q_delta of the workload's queries whose
aggregate latency rank-correlates with the full workload across
configurations, subject to Cost(Q_delta) <= delta * Cost(Q). The greedy
solver starts from the empty set and repeatedly adds the query that
maximizes the weighted Kendall-tau correlation score while respecting the
cost budget. Correlations are computed on historical observations of
source tasks with the *same query set* (Eq. 8), weighted by task
similarity; the current task's own full-fidelity observations can serve as
a source (degradation path, §6.3). A greedy step scores all its admissible
candidates at once: per source, each candidate's aggregate is compared with
every other configuration's, and the pair signs against the full
aggregate's give scipy's exact tau-b, so the step picks what a scalar
``subset_correlation`` per candidate would.

Also provides the two proxy baselines the paper evaluates in Fig. 1b
(data-volume scaling and SQL early stop) so the comparison is reproducible.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from .knowledge import TaskRecord
from .similarity import kendall_tau

__all__ = [
    "QueryStats",
    "collect_query_stats",
    "query_cost_ratios",
    "subset_correlation",
    "greedy_query_subset",
    "FidelityPartition",
    "partition_fidelities",
    "early_stop_subset",
]


@dataclass
class QueryStats:
    """Per-source-task observation matrices aligned to the query list.

    perf: (n_configs, n_queries) latency of each query under each config.
    cost: (n_configs, n_queries) evaluation cost (elapsed time here).
    weight: the task's transfer weight w_i.
    """

    task_id: str
    perf: np.ndarray
    cost: np.ndarray
    weight: float


def collect_query_stats(
    tasks: Sequence[TaskRecord], weights: Dict[str, float], min_configs: int = 3
) -> List[QueryStats]:
    out: List[QueryStats] = []
    with obs.span("fidelity_query_stats") as sp:
        for t in tasks:
            rows = t.with_query_vectors()
            if len(rows) < min_configs:
                continue
            w = weights.get(t.task_id, 0.0)
            if t.task_id == "__target__":
                w = weights.get("__target__", 0.0)
            if w <= 0:
                continue
            perf = np.array([o.per_query_perf for o in rows], dtype=float)
            cost = np.array(
                [o.per_query_cost if o.per_query_cost is not None else o.per_query_perf
                 for o in rows],
                dtype=float,
            )
            out.append(QueryStats(task_id=t.task_id, perf=perf, cost=cost, weight=w))
        sp.set(sources=len(out), queries=out[0].perf.shape[1] if out else 0)
    return out


def query_cost_ratios(stats: Sequence[QueryStats]) -> np.ndarray:
    """Weighted average cost ratio c(q) of each query (Alg. 2 line 2)."""
    total_w = sum(s.weight for s in stats)
    m = stats[0].cost.shape[1]
    c = np.zeros(m)
    for s in stats:
        per_cfg_total = s.cost.sum(axis=1, keepdims=True)  # (n,1)
        ratios = (s.cost / np.maximum(per_cfg_total, 1e-12)).mean(axis=0)
        c += (s.weight / total_w) * ratios
    return c


def subset_correlation(stats: Sequence[QueryStats], subset: Sequence[int]) -> float:
    """tau(Q_delta, Q) = sum_i w_i KendallTau(A_i^{Q_delta}, A_i^{Q})  (Eq. 8)."""
    if not subset:
        return 0.0
    idx = np.asarray(list(subset), dtype=int)
    total_w = sum(s.weight for s in stats)
    score = 0.0
    for s in stats:
        agg_sub = s.perf[:, idx].sum(axis=1)
        agg_full = s.perf.sum(axis=1)
        tau, _ = kendall_tau(agg_sub, agg_full)
        score += (s.weight / total_w) * tau
    return score


# A batch of candidates compares at most this many ordered pairs of
# configurations, so a source of hundreds of rows (LOCAT's QCSA, the
# degradation path's ``__target__``) is scored in batches of a few MB.
_BATCH_PAIRS = 1 << 21


@dataclass
class _PairTable:
    """One source's configurations ranked by their full aggregate.

    sign: (n*n,) int8, ``sign(full_j - full_i)`` at ``i*n + j``; None when
    ``perf`` holds a non-finite value, which scipy ranks and the pair signs
    do not, so such a source is scored per candidate by ``kendall_tau``.
    ytie: tied pairs of the full aggregate; tot: pairs, n(n-1)/2.
    """

    full: np.ndarray
    sign: Optional[np.ndarray]
    ytie: int
    tot: int


def _pair_table(s: QueryStats) -> _PairTable:
    full = s.perf.sum(axis=1)
    n = len(full)
    tot = n * (n - 1) // 2
    if not np.isfinite(s.perf).all():
        return _PairTable(full, None, 0, tot)
    gt = full[None, :] > full[:, None]
    sign = gt.view(np.int8) - gt.T.view(np.int8)
    return _PairTable(full, sign.ravel(), tot - int(np.count_nonzero(gt)), tot)


def _batched_tau(s: QueryStats, t: _PairTable, idx: np.ndarray) -> np.ndarray:
    """``kendall_tau(perf[:, row].sum(axis=1), full)[0]`` for each row of
    ``idx``, by scipy's tau-b formula on integer pair counts."""
    tau = np.zeros(len(idx))
    if t.ytie == t.tot:  # n < 2 or a constant full aggregate
        return tau
    n = len(t.full)
    step = max(1, _BATCH_PAIRS // (n * n))
    for lo in range(0, len(idx), step):
        agg = s.perf[:, idx[lo:lo + step]].sum(axis=-1).T  # (k, n)
        gt = (agg[:, None, :] > agg[:, :, None]).reshape(len(agg), -1)
        # concordant minus discordant pairs; untied pairs of the candidate
        cmd = np.einsum("kp,p->k", gt.view(np.int8), t.sign, dtype=np.int64)
        untied = np.count_nonzero(gt, axis=1)
        ok = untied > 0
        tau[lo:lo + step][ok] = (
            cmd[ok] / np.sqrt(untied[ok]) / np.sqrt(t.tot - t.ytie))
    return np.clip(tau, -1.0, 1.0)


def _scalar_tau(s: QueryStats, t: _PairTable, idx: np.ndarray) -> np.ndarray:
    return np.array([kendall_tau(s.perf[:, row].sum(axis=1), t.full)[0] for row in idx])


def _candidate_scores(
    stats: Sequence[QueryStats], tables: Sequence[_PairTable],
    subset: Sequence[int], cands: np.ndarray,
) -> np.ndarray:
    """``subset_correlation(stats, subset + [q])`` for each q in ``cands``."""
    idx = np.empty((len(cands), len(subset) + 1), dtype=int)
    idx[:, :-1] = subset
    idx[:, -1] = cands
    total_w = sum(s.weight for s in stats)
    score = np.zeros(len(cands))
    for s, t in zip(stats, tables):
        tau = (_scalar_tau if t.sign is None else _batched_tau)(s, t, idx)
        score += (s.weight / total_w) * tau
    return score


def greedy_query_subset(
    stats: Sequence[QueryStats], delta: float
) -> Tuple[List[int], float, float]:
    """Algorithm 2. Returns (subset indices, correlation score, cost ratio)."""
    return _greedy(stats, [_pair_table(s) for s in stats], delta)


def _greedy(
    stats: Sequence[QueryStats], tables: Sequence[_PairTable], delta: float
) -> Tuple[List[int], float, float]:
    if not stats:
        raise ValueError("no source stats for fidelity partitioning")
    subset: List[int] = []
    r = 0.0
    current_tau = 0.0
    evals = 0
    with obs.span("fidelity_greedy", delta=delta) as sp:
        c = query_cost_ratios(stats)
        m = len(c)
        remaining = np.ones(m, dtype=bool)
        while True:
            cands = np.flatnonzero(remaining & (r + c <= delta + 1e-12))
            if not len(cands):
                break
            score = _candidate_scores(stats, tables, subset, cands)
            evals += len(cands)
            k = int(np.argmax(score))  # the first maximum, as a strict > over ascending q
            best_q = int(cands[k])
            subset.append(best_q)
            remaining[best_q] = False
            r += c[best_q]
            current_tau = float(score[k])
            if current_tau >= 1.0 - 1e-12:
                break
        sp.set(queries=m, chosen=len(subset), evals=evals,
               scalar_sources=sum(t.sign is None for t in tables))
    return subset, current_tau, r


@dataclass
class FidelityPartition:
    """Mapping fidelity delta -> selected query indices (+ diagnostics)."""

    subsets: Dict[float, List[int]]
    scores: Dict[float, float]
    cost_ratios: Dict[float, float]

    def queries_for(self, delta: float) -> List[int]:
        if delta >= 1.0:
            # full fidelity: all queries (total count inferred from any subset)
            return []  # sentinel: empty means "all"
        key = min(self.subsets.keys(), key=lambda d: abs(d - delta))
        return self.subsets[key]


def partition_fidelities(
    stats: Sequence[QueryStats], deltas: Sequence[float]
) -> FidelityPartition:
    subsets: Dict[float, List[int]] = {}
    scores: Dict[float, float] = {}
    ratios: Dict[float, float] = {}
    tables = [_pair_table(s) for s in stats]
    for d in deltas:
        if d >= 1.0:
            continue
        s, tau, r = _greedy(stats, tables, d)
        subsets[d] = s
        scores[d] = tau
        ratios[d] = r
    return FidelityPartition(subsets=subsets, scores=scores, cost_ratios=ratios)


def early_stop_subset(n_queries: int, delta: float) -> List[int]:
    """SQL Early Stop baseline: first ceil(delta * m) queries (Fig. 1b)."""
    k = max(1, int(np.ceil(delta * n_queries)))
    return list(range(min(k, n_queries)))
