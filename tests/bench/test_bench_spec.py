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
    """The space the configuration states resolves to a knob space of the
    program with the stated number of knobs, and its ``reduced`` keys are
    the entry's."""
    from perfbench.lib.setup_data import space_of

    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    space = space_of(cfg)
    assert space.dim == cfg["space"]["knobs"] == len(set(space.names))
    assert set(entry["reduced"]) <= set(cfg) and cfg["reduced"] == entry["reduced"]


def _cfg(**changes):
    cfg = spec.config(BENCH, "tpch100_F")
    cfg.update(changes)
    return cfg


@pytest.mark.parametrize("name", ["no_such_space", "_private", "SCENARIOS",
                                  "SparkWorkload", "make_task_id",
                                  "build_knowledge_base"])
def test_unknown_space_name_raises(name, monkeypatch):
    """A name that ``repro.sparksim`` does not export as a knob space; no
    other callable it exports is called."""
    import functools
    import inspect

    import repro.sparksim
    from perfbench.lib.setup_data import space_of

    called = []
    real = getattr(repro.sparksim, name, None)
    if inspect.isfunction(real):
        @functools.wraps(real)
        def spy(*args, **kwargs):
            called.append(name)
            return real(*args, **kwargs)

        monkeypatch.setattr(repro.sparksim, name, spy)
    with pytest.raises(KeyError):
        space_of(_cfg(space={"name": name, "knobs": 60}))
    assert called == []


def test_space_of_another_knob_count_raises():
    from perfbench.lib.setup_data import space_of

    with pytest.raises(ValueError):
        space_of(_cfg(space={"name": "spark_space", "knobs": 61}))


@pytest.mark.parametrize("change", [{"trees": 20}, {"max_depth": 8},
                                    {"min_samples_split": 2}, {"bootstrap": False},
                                    {"kind": "gaussian_process"}])
def test_space_of_another_surrogate_raises(change):
    """Every forest the program grows is the PRF at its defaults, so a
    configuration that states another surrogate is refused."""
    from perfbench.lib.setup_data import space_of

    cfg = _cfg()
    cfg["surrogate"] = {**cfg["surrogate"], **change}
    with pytest.raises(ValueError):
        space_of(cfg)
