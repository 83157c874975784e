"""The per-layer metrics that read the propose call's phase spans and the
tuner's fit, partition and similarity spans report in a traced CPU
rehearsal of a score cell and of a tune cell."""

import pytest

from perfbench import run
from perfbench.lib import spec

SCORE = ["propose_prepare_ms.propose", "propose_upload_ms.propose",
         "propose_dispatch_ms.propose", "propose_fetch_ms.propose"]
TUNE = ["forest_fit_ms.tune", "forest_fits_per_iter.tune",
        "fidelity_greedy_ms.tune", "similarity_self_weight_ms.tune"]


@pytest.mark.parametrize("cell,names", [("tpch100_F.score_4k", SCORE),
                                        ("tpch100_F.tune", TUNE)])
def test_traced_rehearsal_reports_span_metrics(cell, names):
    listed = {m["name"] for m in spec.benchmark()["per_layer"]
              if cell in m.get("workloads", [])}
    assert set(names) <= listed
    res = run.run_cell(cell, 3000000014, 1.0, trace=True, rehearse=True)
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    for name in names:
        if name == "propose_dispatch_ms.propose":
            # the dispatch returns unready arrays, so it may read 0
            assert got[name] >= 0.0
        else:
            assert got[name] > 0.0, name
