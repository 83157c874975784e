"""The batched greedy step of Algorithm 2 against the scalar search it
replaced.

``reference_greedy`` is the pre-batching greedy loop, kept verbatim as the
oracle: it scores each admissible candidate with one ``subset_correlation``
(one ``scipy.stats.kendalltau`` per source). ``greedy_query_subset`` and
``partition_fidelities`` must give the same subsets, scores, cost ratios and
number of candidates scored, compared with ``==``. The cases tie values
(rounded to tenths, so that the summation order decides near-ties; the
``long`` cases grow subsets past eight queries, where numpy's pairwise
summation would part from the sequential one), weight sources unequally,
and take a source whose full aggregate is constant, one holding ``inf``
(scored on the scalar path) and one of 400 configurations (scored in
several batches).
"""

import numpy as np
import pytest

from repro import obs
from repro.core import fidelity as F

DELTAS = (1 / 9, 1 / 3)


def reference_greedy(stats, delta):
    """Algorithm 2 as one ``subset_correlation`` per admissible candidate.
    Returns (subset, score, cost ratio, candidates scored)."""
    if not stats:
        raise ValueError("no source stats for fidelity partitioning")
    subset = []
    r = 0.0
    current_tau = 0.0
    evals = 0
    c = F.query_cost_ratios(stats)
    m = len(c)
    remaining = set(range(m))
    while True:
        best_q, best_tau = None, -np.inf
        for q in sorted(remaining):
            if r + c[q] > delta + 1e-12:
                continue
            tau = F.subset_correlation(stats, subset + [q])
            evals += 1
            if tau > best_tau:
                best_q, best_tau = q, tau
        if best_q is None:
            break
        subset.append(best_q)
        remaining.discard(best_q)
        r += c[best_q]
        current_tau = best_tau
        if current_tau >= 1.0 - 1e-12:
            break
    return subset, current_tau, r, evals


def make_stats(m, S, n, seed, decimals=1, cost_sigma=0.5, special=None):
    """S sources of n configurations over m queries, with unequal weights and
    each query's cost scaled by a lognormal of ``cost_sigma`` (a wide one
    leaves many cheap queries, so the subsets grow long). ``special`` turns
    the first source into a ``"constant"`` full aggregate (each row a
    permutation of the same integers) or an ``"inf"`` row."""
    rng = np.random.default_rng(seed)
    stats = []
    for i in range(S):
        perf = np.round(rng.random((n, m)) * 2, decimals)
        cost = (rng.lognormal(0.0, cost_sigma, size=m)
                * rng.lognormal(0.0, 0.5, size=(n, m)))
        stats.append(F.QueryStats(task_id=f"t{i}", perf=perf, cost=cost,
                                  weight=float(rng.uniform(0.2, 3.0))))
    if special == "constant":
        stats[0].perf = np.array([rng.permutation(m) for _ in range(n)], dtype=float)
    elif special == "inf":
        stats[0].perf[rng.integers(n)] = np.inf
    return stats


# id: (m, S, n, decimals, cost_sigma, special)
CASES = {
    "m9-S3-n12": (9, 3, 12, 1, 0.5, None),
    "m9-S8-n3": (9, 8, 3, 1, 0.5, None),
    "m22-S8-n50": (22, 8, 50, 1, 0.5, None),
    "m22-S3-n50-int": (22, 3, 50, 0, 0.5, None),
    "m22-S1-n50-long": (22, 1, 50, 1, 2.0, None),
    "m22-S3-n12-long": (22, 3, 12, 1, 2.0, None),
    "m22-S1-n400-chunks": (22, 1, 400, 1, 0.5, None),
    "m22-S3-n12-constant": (22, 3, 12, 1, 0.5, "constant"),
    "m22-S3-n12-inf": (22, 3, 12, 1, 0.5, "inf"),
    "m99-S1-n8": (99, 1, 8, 1, 0.5, None),
    "m99-S3-n3": (99, 3, 3, 1, 0.5, None),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_batched_greedy_matches_scalar_reference(case):
    m, S, n, decimals, cost_sigma, special = CASES[case]
    stats = make_stats(m, S, n, seed=sum(map(ord, case)), decimals=decimals,
                       cost_sigma=cost_sigma, special=special)
    tr = obs.Tracer("greedy")
    with obs.tracing(tr):
        part = F.partition_fidelities(stats, [*DELTAS, 1.0])
        direct = {d: F.greedy_query_subset(stats, d) for d in DELTAS}
    spans = [e for e in obs.trace_events(tr)
             if e["type"] == "span" and e["name"] == "fidelity_greedy"]
    assert [s["args"]["delta"] for s in spans] == [*DELTAS, *DELTAS]
    for d in DELTAS:
        subset, tau, r, evals = reference_greedy(stats, d)
        assert subset, "every case chooses at least one query"
        assert direct[d] == (subset, tau, r)
        assert (part.subsets[d], part.scores[d], part.cost_ratios[d]) == (subset, tau, r)
        for s in spans:
            if s["args"]["delta"] == d:
                assert s["args"]["evals"] == evals
                assert s["args"]["chosen"] == len(subset)
                assert s["args"]["scalar_sources"] == (special == "inf")
    if special == "constant":
        assert F.subset_correlation(stats[:1], part.subsets[DELTAS[1]]) == 0.0
    if case.endswith("chunks"):
        assert n * n * m > F._BATCH_PAIRS, "the first step spans several batches"
