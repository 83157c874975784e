"""With the timed path broken underneath, a run's check comes out false:
once for each fault the cells can have (an answer altered where it is made,
half of the pool left out, the wrong picks returned with their own true
aggregates). The harness's look for a chip is skipped; the rest of the run
is the real one."""

import numpy as np
import pytest

from perfbench import run


def _alter_answer(monkeypatch):
    from repro.kernels.forest_eval import propose as P

    orig = P.propose_step

    def step(*a, **kw):
        idx, X, agg = orig(*a, **kw)
        picked = set(np.asarray(idx).tolist())
        other = next(i for i in range(kw["n_pool"]) if i not in picked)
        return idx.at[0].set(other), X, agg

    monkeypatch.setattr(P, "propose_step", step)


def _half_pool(monkeypatch):
    from repro.kernels.forest_eval import propose as P

    orig = P.propose_step

    def step(*a, **kw):
        kw["n_valid"] = kw["n_pool"] // 2
        return orig(*a, **kw)

    monkeypatch.setattr(P, "propose_step", step)


def _wrong_picks(monkeypatch):
    """The candidates the program ranks k to 2k - 1 in place of its top k,
    each with the aggregate the program gives it: only the selection is
    wrong."""
    from repro.kernels.forest_eval import propose as P

    orig = P.propose_step

    def step(*a, **kw):
        k = kw["k"]
        assert 2 * k <= kw["n_pool"]
        idx, X, agg = orig(*a, **dict(kw, k=2 * k))
        return idx[k:], X[k:], agg[k:]

    monkeypatch.setattr(P, "propose_step", step)


FAULTS = {"alter_answer": _alter_answer, "half_pool": _half_pool,
          "wrong_picks": _wrong_picks}
CASES = [
    ("tpcds600_A.score_131k", "alter_answer"),
    ("tpcds600_A.score_131k", "half_pool"),
    ("tpcds600_A.score_131k", "wrong_picks"),
    ("tpch100_F.score_4k", "alter_answer"),
    ("tpch100_F.score_4k", "half_pool"),
    ("tpch100_F.score_4k", "wrong_picks"),
]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_fails_the_check(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run.run_cell(cell, 3000000021, 1.0, trace=False, rehearse=True)
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("cell", ["tpch100_F.score_4k", "tpch100_F.tune"])
def test_program_that_bypasses_the_probe_stops_the_run(cell, monkeypatch):
    """A ``score_topk`` that answers without the module's fused step leaves
    the benchmark nothing to compare: the run stops instead of checking
    stale aggregates."""
    from perfbench.lib.loads import ProbeMissed
    from repro.core.propose import ProposeEngine

    monkeypatch.setattr(ProposeEngine, "score_topk",
                        lambda self, models, X, incs, ws, n, **kw: np.arange(n))
    with pytest.raises(ProbeMissed):
        run.run_cell(cell, 3000000022, 1.0, trace=False, rehearse=True)
