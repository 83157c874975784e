"""Every input of a cell is built in the knob space its configuration
names: a configuration that names another space runs both loads in it, and
the configurations that name ``spark_space`` get the inputs that
``spark_space()`` itself gives, bit for bit."""

import numpy as np
import pytest

from perfbench.lib import check as C
from perfbench.lib import loads, spec
from perfbench.lib import setup_data as sd
from perfbench.run import rehearsal_sizes
from repro.core.space import ConfigSpace, IntKnob

BENCH = spec.benchmark()
WIDE = "perfbench_test_wide_space"


def _wide_space() -> ConfigSpace:
    """``spark_space`` and two long-tail knobs of Spark 3.5: 62 knobs, under
    the 64 that one prefix mask holds."""
    from repro.sparksim import spark_space

    return ConfigSpace(spark_space().knobs + [
        IntKnob("spark.sql.adaptive.advisoryPartitionSizeInBytes", 16, 1024,
                log=True, default=64),
        IntKnob("spark.shuffle.io.maxRetries", 1, 10, default=3)])


@pytest.fixture
def wide_cfg(monkeypatch):
    """``tpch100_F`` as it would read if it named the 62-knob space."""
    import repro.sparksim

    monkeypatch.setattr(repro.sparksim, WIDE, _wide_space, raising=False)
    cfg = spec.config(BENCH, "tpch100_F")
    cfg["space"] = {"name": WIDE, "knobs": 62}
    return cfg


def test_score_topk_runs_in_the_named_space(wide_cfg):
    traffic = spec.traffic("score_4k")
    rehearsal_sizes(wide_cfg, traffic)
    load = loads.make(wide_cfg, traffic, 3000000041)
    assert load.eng.space.names == _wide_space().names
    assert all(p.shape == (traffic["pool"], 62) for p in load.pools)
    assert all(m.X_.shape[1] == 62 for m in load.models)
    load.warm()
    load.step()
    load.step()
    assert C.verdict(load.check([0, 1]), spec.limits("tpch100_F.score_4k"))


def test_tune_runs_in_the_named_space(wide_cfg, monkeypatch):
    import repro.core

    targets = []

    class Recorded(repro.core.MFTune):
        def __init__(self, wl, *a, **kw):
            targets.append(wl)
            super().__init__(wl, *a, **kw)

    monkeypatch.setattr(repro.core, "MFTune", Recorded)
    traffic = spec.traffic("tune")
    rehearsal_sizes(wide_cfg, traffic)
    load = loads.make(wide_cfg, traffic, 3000000042)
    names = _wide_space().names
    assert all(sorted(o.config) == sorted(names)
               for r in load.records for o in r.observations)
    _, calls = load.session(load.records, load.tuner_seed)
    assert [wl.space.names for wl in targets] == [names]
    assert all(X.shape[1] == 62 for _, X, *_ in calls)
    assert C.verdict([
        C.check_call([sd.forest_data(m) for m in models], X, incs, ws, idx,
                     np.asarray(agg)[: len(idx)])
        for models, X, incs, ws, idx, agg in calls], spec.limits("tpch100_F.tune"))


def _inputs(cfg, seed):
    """The sources, two pools and the knowledge base of ``cfg``, as plain
    arrays and lists."""
    models, incs, ws = sd.fit_sources(cfg, seed)
    forests = [sd.forest_data(m) for m in models]
    pools = sd.host_pools(sd.space_of(cfg), 2, 512, seed)
    kb = [(r.task_id, r.meta_features,
           [(o.config, o.performance, o.per_query_perf, o.per_query_cost)
            for o in r.observations])
          for r in sd.knowledge_base(cfg, seed)]
    return forests, incs, ws, pools, kb


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_inputs_are_those_of_the_program_space(name, monkeypatch):
    """Through the resolver, the inputs equal those built directly in
    ``spark_space()``, with task workloads as ``TaskSpec.workload`` makes
    them."""
    import repro.sparksim
    from repro.sparksim import TaskSpec, spark_space

    cfg = spec.config(BENCH, name)
    rehearsal_sizes(cfg, spec.traffic("score_4k"))
    got = _inputs(cfg, 3000000043)

    monkeypatch.setattr(sd, "space_of", lambda cfg: spark_space())
    monkeypatch.setattr(repro.sparksim, "SparkWorkload",
                        lambda b, gb, hw, seed, space: TaskSpec(b, gb, hw).workload())
    want = _inputs(cfg, 3000000043)

    for f_got, f_want in zip(got[0], want[0], strict=True):
        assert np.array_equal(f_got["y"], f_want["y"])
        for t_got, t_want in zip(f_got["trees"], f_want["trees"], strict=True):
            assert t_got.keys() == t_want.keys()
            assert all(np.array_equal(t_got[k], t_want[k]) for k in t_got)
    assert got[1:3] == want[1:3]
    assert all(np.array_equal(a, b) for a, b in zip(got[3], want[3], strict=True))
    assert got[4] == want[4]
