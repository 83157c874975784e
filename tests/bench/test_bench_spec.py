"""The benchmark finds every cell, configuration, traffic mix, limit file and
per-layer metric by its name, and its files keep their documented shape."""

import json
import os

import pytest

from perfbench.lib import spec

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, p))


@pytest.mark.parametrize("name", CELLS)
def test_cell_parts_found_by_name(name):
    cell = spec.cell(BENCH, name)
    cfg = spec.config(BENCH, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    limits = spec.limits(name)
    assert cfg["name"] == cell["config"]
    assert traffic["mode"] in ("score_topk", "tune")
    assert limits and all(v >= 0 for v in limits.values())
    e2e = {m["name"] for m in spec.metrics_of(BENCH, name, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = spec.metrics_of(BENCH, name, "per_layer")
    assert layer
    for m in layer:
        assert m["moves"] in e2e
        assert callable(spec.reader(m["name"]))


def test_every_metric_has_a_reader_and_cells_exist():
    for m in BENCH["per_layer"]:
        spec.reader(m["name"])
        for w in m.get("workloads", []):
            assert w in CELLS
    for m in BENCH["end_to_end"]:
        for w in m.get("workloads", []):
            assert w in CELLS


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        spec.cell(BENCH, "no_such_cell")
    with pytest.raises(KeyError):
        spec.config(BENCH, "no_such_config")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_knob_table_is_the_program_space(entry):
    """The configuration states the 60-knob space the program tunes, and
    its ``reduced`` keys are the entry's."""
    from repro.sparksim import spark_space

    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["space"] == {"name": "spark_space", "knobs": len(spark_space().knobs)}
    assert set(entry["reduced"]) <= set(cfg) and cfg["reduced"] == entry["reduced"]
