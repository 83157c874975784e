"""The ``shapley_chain_ms.tune`` reader, and the 128-knob tune cell: a traced
CPU rehearsal reports the chain walk and the cell's other layers, and the
faults of ``test_bench_faults.py`` fail its session check."""

import pytest

from perfbench import run
from perfbench.lib import spec

from test_bench_faults import FAULTS

CELL = "tpcds600_A_k128.tune"
METRIC = "shapley_chain_ms.tune"


@pytest.mark.parametrize("ctx,want", [
    ({"steps": 4, "spans": [("shapley_chain", 0.25), ("forest_fit", 9.0),
                            ("shapley_chain", 0.05)]}, 75.0),
    ({"steps": 2, "spans": [("shapley_composite", 0.5), ("space_compression", 1.0)]}, None),
    ({"steps": 3}, None),
    ({"steps": 0, "spans": [("shapley_chain", 0.25)]}, None),
])
def test_reader_sums_chain_spans_per_iteration(ctx, want):
    got = spec.reader(METRIC)(ctx)
    assert got == pytest.approx(want) if want is not None else got is None


def test_traced_rehearsal_reports_the_chain_walk():
    listed = {m["name"] for m in spec.benchmark()["per_layer"]
              if CELL in m.get("workloads", [])}
    assert {METRIC, "space_compression_ms.tune", "forest_fit_ms.tune",
            "similarity_ms.tune", "surrogate_fit_ms.tune",
            "device_idle_pct.tune"} <= listed
    res = run.run_cell(CELL, 3000000017, 1.0, trace=True, rehearse=True)
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got[METRIC] > 0.0
    # the chain walk lies inside the compression stage
    assert got[METRIC] < got["space_compression_ms.tune"]


@pytest.mark.parametrize("fault", ["alter_answer", "wrong_picks"])
def test_fault_fails_the_wide_session_check(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run.run_cell(CELL, 3000000021, 1.0, trace=False, rehearse=True)
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
