"""The session memo of fitted forests (``ForestMemo``): a hit hands back the
forest a fresh fit would give, any change to what fixes the trees misses,
its counters count, and a tuning session runs the same path with it as
without it, one memo per tuner."""

import numpy as np
import pytest

from repro import obs
from repro.core import (
    CandidateGenerator,
    ForestMemo,
    KnowledgeBase,
    MFTune,
    MFTuneOptions,
    SimilarityEngine,
    make_forest,
)
from repro.core.compression import extract_promising_regions
from repro.core.similarity import TaskWeights


def _data(n=40, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.random((n, d))
    y = 3.0 * X[:, 0] - X[:, 1] + 0.1 * rng.standard_normal(n)
    return X, y


def _fit_spans(tr):
    return sum(1 for e in tr.events if e["type"] == "span" and e["name"] == "forest_fit")


@pytest.mark.parametrize("backend", ["numpy", "loop"])
def test_hit_is_the_fresh_fit(backend):
    X, y = _data()
    memo = ForestMemo()
    first = memo.fit(X, y, seed=5, backend=backend)
    hit = memo.fit(X.copy(), y.copy(), seed=5, backend=backend)
    assert hit is first
    assert memo.stats["hits"] == 1 and memo.stats["misses"] == 1
    fresh = make_forest(seed=5, backend=backend).fit(X, y)
    got, want = hit.pack(), fresh.pack()
    for field in ("feat", "thr", "child", "mean", "var", "roots"):
        assert np.array_equal(getattr(got, field), getattr(want, field)), field
    assert (got.depth, got.y_mean, got.y_std) == (want.depth, want.y_mean, want.y_std)
    Xq = np.random.default_rng(1).random((64, X.shape[1]))
    for a, b in zip(hit.predict(Xq), fresh.predict(Xq)):
        assert np.array_equal(a, b)


def _ulp_x(X, y):
    X = X.copy()
    X[3, 2] = np.nextafter(X[3, 2], np.inf)
    return X, y


def _ulp_y(X, y):
    y = y.copy()
    y[7] = np.nextafter(y[7], -np.inf)
    return X, y


@pytest.mark.parametrize("change", [
    pytest.param(lambda X, y: (X, y, {"seed": 6}), id="seed"),
    pytest.param(lambda X, y: (*_ulp_x(X, y), {}), id="x_ulp"),
    pytest.param(lambda X, y: (*_ulp_y(X, y), {}), id="y_ulp"),
    pytest.param(lambda X, y: (X, y, {"n_trees": 9}), id="n_trees"),
    pytest.param(lambda X, y: (X, y, {"max_depth": 11}), id="max_depth"),
    pytest.param(lambda X, y: (X, y, {"min_samples_split": 5}), id="min_samples_split"),
    pytest.param(lambda X, y: (X, y, {"min_samples_leaf": 2}), id="min_samples_leaf"),
    pytest.param(lambda X, y: (X, y, {"bootstrap": False}), id="bootstrap"),
    pytest.param(lambda X, y: (X, y, {"backend": "loop"}), id="backend"),
    pytest.param(lambda X, y: (X[:-1], y[:-1], {}), id="rows"),
])
def test_any_change_misses(change):
    X, y = _data()
    memo = ForestMemo()
    base = memo.fit(X, y, seed=5, backend="numpy")
    X2, y2, kw = change(X, y)
    kw = {"seed": 5, "backend": "numpy", **kw}
    other = memo.fit(X2, y2, **kw)
    assert other is not base
    assert memo.stats == {"hits": 0, "misses": 2, "evictions": 0, "size": 2}
    fresh = make_forest(**kw).fit(X2, y2)
    Xq = np.random.default_rng(2).random((32, X.shape[1]))
    assert np.array_equal(other.predict(Xq)[0], fresh.predict(Xq)[0])


def test_counters_and_spans():
    fits = [_data(seed=s) for s in range(3)]
    memo = ForestMemo()
    memo.max_entries = 2
    with obs.tracing() as tr:
        a = memo.fit(*fits[0])            # miss
        memo.fit(*fits[1])                # miss
        assert memo.fit(*fits[0]) is a    # hit; 1 is now the oldest
        memo.fit(*fits[2])                # miss, evicts 1
        assert memo.fit(*fits[0]) is a    # hit
        memo.fit(*fits[1])                # miss, evicts 2
    assert memo.stats == {"hits": 2, "misses": 4, "evictions": 2, "size": 2}
    assert _fit_spans(tr) == 4


def test_owners_share_one_forest():
    """Similarity, compression and the generator fit a source task's forest
    on the same inputs: with one memo, the first fit serves the others."""
    from repro.core import ConfigSpace, FloatKnob, Observation, TaskRecord

    space = ConfigSpace([FloatKnob(f"x{i}", 0.0, 1.0) for i in range(5)])
    rng = np.random.default_rng(0)
    src = TaskRecord(task_id="src", queries=["q1"])
    for cfg in space.sample(rng, 30):
        src.observations.append(Observation(
            config=cfg, performance=1.0 + cfg["x0"] + 0.5 * cfg["x1"], fidelity=1.0))
    target = TaskRecord(task_id="tgt", queries=["q1"])
    kb = KnowledgeBase()
    kb.add_task(src, persist=False)
    kb.add_task(target, persist=False)
    memo = ForestMemo()
    model = SimilarityEngine(space, kb, seed=3, memo=memo).source_model("src")
    gen = CandidateGenerator(space, seed=3, memo=memo)
    w = TaskWeights(weights={"src": 1.0}, similarities={"src": 1.0}, used_meta=False)
    sources = gen.build_sources(w, {"src": src}, target, [1.0])
    assert sources[0].model is model
    assert extract_promising_regions(space, src, 1.0, seed=3, memo=memo) is not None
    assert memo.stats["misses"] == 1 and memo.stats["hits"] == 2


CONFIGS = ["tpch100_F", "tpcds600_A", "tpcds600_A_k128"]


def _rehearsal(name):
    from perfbench.lib import setup_data as sd
    from perfbench.lib import spec
    from perfbench.run import rehearsal_sizes

    cfg = spec.config(spec.benchmark(), name)
    traffic = spec.traffic("tune")
    rehearsal_sizes(cfg, traffic)
    return cfg, traffic, sd.knowledge_base(cfg, 3000000018)


def _session(cfg, traffic, records):
    from perfbench.lib import setup_data as sd
    from repro.sparksim import SparkWorkload
    from repro.tuneapi import Budget

    kb = KnowledgeBase()
    for r in records:
        kb.add_task(r, persist=False)
    t = cfg["target"]
    wl = SparkWorkload(t["benchmark"], t["scale_factor_gb"], t["cluster"],
                       space=sd.space_of(cfg))
    tune = MFTune(wl, kb, MFTuneOptions(seed=18))
    tune.gen.pool_size = traffic["pool_size"]
    with obs.tracing() as tr:
        res = tune.run(Budget(traffic["budget_hours"] * 3600.0))
    return tune, res, _fit_spans(tr)


def _summary(res):
    return ([(p.time, p.best, p.fidelity, p.config) for p in res.trajectory],
            res.n_evaluations, res.n_full_evaluations, res.best_performance)


@pytest.fixture(scope="module", params=CONFIGS)
def sessions(request):
    """Two sessions through their tuners' memos, and one with every memo
    fit made fresh, on one rehearsal-size knowledge base."""
    cfg, traffic, records = _rehearsal(request.param)
    memo_runs = [_session(cfg, traffic, records) for _ in range(2)]
    orig = ForestMemo.fit

    def fresh(self, X, y, seed=0, backend=None, **hyper):
        self.misses += 1
        return make_forest(seed=seed, backend=backend, **hyper).fit(X, y)

    ForestMemo.fit = fresh
    try:
        fresh_run = _session(cfg, traffic, records)
    finally:
        ForestMemo.fit = orig
    return memo_runs, fresh_run


def test_session_runs_as_without_memo(sessions):
    (tune, res, spans), _ = sessions[0]
    _, fresh_res, fresh_spans = sessions[1]
    assert _summary(res) == _summary(fresh_res)
    counters = res.metrics["counters"]
    assert spans == counters["forest_memo/misses"] == tune.memo.misses
    assert counters["forest_memo/hits"] > 0
    assert spans + counters["forest_memo/hits"] == fresh_spans


def test_tuners_share_no_entries(sessions):
    (first, res1, spans1), (second, res2, spans2) = sessions[0]
    assert first.memo is not second.memo
    assert _summary(res1) == _summary(res2)
    assert second.memo.stats == first.memo.stats
    assert spans2 == spans1 == res2.metrics["counters"]["forest_memo/misses"]
