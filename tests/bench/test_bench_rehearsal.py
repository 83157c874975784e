"""Every cell runs end to end in CPU rehearsal mode and prints no result
line; without a chip the measuring path exits 1 and prints nothing."""

import pytest

from perfbench import run
from perfbench.lib import spec

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearses_without_result_line(name, capsys):
    rc = run.main(["--workload", name, "--seed", "3000000011", "--seconds", "1",
                   "--trace", "0", "--rehearse"])
    out, err = capsys.readouterr()
    assert rc == 0
    assert out == ""
    assert "correct: True" in err


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc == 1
    assert out == ""
    assert "no TPU" in err


def test_traced_rehearsal_reports_layers():
    res = run.run_cell(CELLS[0], 3000000012, 1.0, trace=True, rehearse=True)
    assert res["correct"] is True
    assert 0.0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    names = set(res["metrics"])
    assert {"device_idle_pct.propose", "program_device_ms.propose",
            "compile_s"} <= names
    # no peaks for the CPU: the roofline share is left out, never 0
    assert "propose_roofline" not in names
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert list(res)[-1] == "checks"
