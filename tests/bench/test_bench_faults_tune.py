"""The faults of ``test_bench_faults.py`` planted under whole tuning
sessions: the check of the tune cells comes out false."""

import pytest

from perfbench import run

from test_bench_faults import FAULTS

CASES = [
    ("tpcds600_A.tune", "alter_answer"),
    ("tpcds600_A.tune", "half_pool"),
    ("tpcds600_A.tune", "wrong_picks"),
    ("tpch100_F.tune", "alter_answer"),
    ("tpch100_F.tune", "half_pool"),
    ("tpch100_F.tune", "wrong_picks"),
]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_fails_the_session_check(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run.run_cell(cell, 3000000021, 1.0, trace=False, rehearse=True)
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
