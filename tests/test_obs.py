"""Unified tracing & metrics plane (ISSUE 9).

Invariants pinned here:
  * span nesting/ordering: parent ids resolve through the per-thread
    stack, spans are emitted in close order, ids are unique, nested
    durations fit inside their parents;
  * histogram bins: fixed log-spaced edges, one-searchsorted recording,
    underflow/overflow buckets;
  * the disabled path is a true no-op and tracing alters nothing: the
    observation stream + trajectory of a full MFTune run are bit-identical
    tracer-on vs tracer-off at a fixed seed;
  * exporters: JSONL and Chrome/Perfetto JSON both round-trip back to
    schema-valid canonical events, and the Perfetto file is plain
    ``json.load``-able (what ui.perfetto.dev requires);
  * back-compat: ``TuningResult.overheads`` / ``surrogate_cache`` /
    ``plane_cache`` are now views over the typed Metrics registry but keep
    their historical shapes and dtypes;
  * baselines route through the same tracer vocabulary.
"""

import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import KnowledgeBase, MFTune, MFTuneOptions
from repro.obs.metrics import HIST_BINS, HIST_HI, HIST_LO
from repro.sparksim import SparkWorkload, TaskSpec, generate_history
from repro.tuneapi import Budget


@pytest.fixture(autouse=True)
def _no_global_tracer():
    # tests install tracers explicitly; never leak one across tests
    obs.set_tracer(None)
    yield
    obs.set_tracer(None)


def _warm_kb():
    kb = KnowledgeBase()
    kb.add_task(
        generate_history(
            TaskSpec("tpch", 100, "A").workload(), n_obs=12, n_init=5, seed=3
        ),
        persist=False,
    )
    return kb


def _spans(tracer):
    return [e for e in obs.trace_events(tracer) if e["type"] == "span"]


# ------------------------------------------------------------ span invariants


def test_span_nesting_and_ordering():
    tr = obs.Tracer("t")
    obs.set_tracer(tr)
    with obs.span("outer", a=1) as so:
        with obs.span("inner") as si:
            assert si.parent == so.id
        with obs.span("inner2") as s2:
            s2.set(k="v")
    spans = _spans(tr)
    # spans are emitted when they close: inner, inner2, outer
    assert [s["name"] for s in spans] == ["inner", "inner2", "outer"]
    inner, inner2, outer = spans
    assert outer["parent"] == -1
    assert inner["parent"] == outer["id"] and inner2["parent"] == outer["id"]
    assert inner2["args"]["k"] == "v" and outer["args"]["a"] == 1
    ids = [s["id"] for s in spans]
    assert len(set(ids)) == len(ids)
    # children fit inside the parent window
    for ch in (inner, inner2):
        assert ch["ts"] >= outer["ts"]
        assert ch["ts"] + ch["dur"] <= outer["ts"] + outer["dur"] + 1e-9
    assert inner2["ts"] >= inner["ts"] + inner["dur"] - 1e-9  # sequential siblings


def test_span_stack_is_per_thread():
    tr = obs.Tracer("t")
    obs.set_tracer(tr)
    seen = {}

    def worker():
        with obs.span("in_thread") as s:
            seen["parent"] = s.parent

    with obs.span("main_span"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    # the thread's span must NOT adopt the main thread's open span
    assert seen["parent"] == -1
    tids = {s["tid"] for s in _spans(tr)}
    assert len(tids) == 2


def test_disabled_path_is_noop():
    assert obs.get_tracer() is None
    with obs.span("x", a=1) as s:
        s.set(b=2)
        assert s.id == -1
    obs.count("c")
    obs.observe("h", 1.0)
    obs.gauge("g", 3.0)
    obs.instant("i")
    assert obs.get_tracer() is None


def test_mis_nested_close_unwinds():
    tr = obs.Tracer("t")
    obs.set_tracer(tr)
    a = obs.span("a").__enter__()
    obs.span("b").__enter__()  # never exited (leaked)
    a.__exit__(None, None, None)  # closing the outer unwinds past it
    with obs.span("c"):
        pass
    spans = {s["name"]: s for s in _spans(tr)}
    assert set(spans) == {"a", "c"}  # leaked span dropped, not emitted
    assert spans["c"]["parent"] == -1  # stack fully unwound — no stale parent


def test_buffer_cap_drops_not_grows():
    tr = obs.Tracer("t", max_events=5)
    obs.set_tracer(tr)
    for i in range(20):
        obs.instant(f"e{i}")
    assert len(tr.events) == 5
    assert tr.dropped == 15


# ---------------------------------------------------------------- histograms


def test_histogram_log_spaced_edges_and_overflow():
    m = obs.Metrics()
    h = m.histogram("lat")
    assert len(h.edges) == HIST_BINS + 1
    np.testing.assert_allclose(
        h.edges, np.logspace(np.log10(HIST_LO), np.log10(HIST_HI), HIST_BINS + 1)
    )
    # ratio between consecutive edges is constant (log-spaced)
    r = h.edges[1:] / h.edges[:-1]
    np.testing.assert_allclose(r, r[0])
    h.observe(1e-9)   # underflow -> bucket 0
    h.observe(1e9)    # overflow  -> bucket len(edges)
    h.observe(1.0)
    assert h.counts[0] == 1 and h.counts[-1] == 1
    assert h.n == 3 and h.counts.sum() == 3
    snap = h.snapshot()
    assert snap["min"] == 1e-9 and snap["max"] == 1e9
    assert snap["total"] == pytest.approx(1e-9 + 1e9 + 1.0)
    # recorded bucket matches a direct searchsorted
    k = int(np.searchsorted(h.edges, 1.0, side="right"))
    assert h.counts[k] >= 1


def test_metrics_registry_views():
    m = obs.Metrics()
    m.counter("overhead/similarity").add(0.5)
    m.counter("overhead/similarity").add(0.25)
    m.counter("store/hits").add(3)
    assert m.counters_view("overhead/", coerce_int=False) == {"similarity": 0.75}
    view = m.counters_view("store/")
    assert view == {"hits": 3} and isinstance(view["hits"], int)
    m.absorb_counters("pc/", {"hits": 7, "misses": 2})
    assert m.counters_view("pc/") == {"hits": 7, "misses": 2}
    snap = m.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["overhead/similarity"] == 0.75


# ------------------------------------------------- tracing alters no numerics


def _identity_run(traced: bool):
    kb = _warm_kb()
    wl = SparkWorkload("tpch", 100, "A")
    tuner = MFTune(wl, kb, MFTuneOptions(seed=0))
    if traced:
        tracer = obs.Tracer("identity")
        with obs.tracing(tracer):
            res = tuner.tune(Budget(8 * 3600.0))
    else:
        tracer = None
        res = tuner.tune(Budget(8 * 3600.0))
    sig = [
        (o.performance, o.fidelity, tuple(sorted(o.config.items())))
        for o in kb.get(wl.task_id).observations
    ]
    traj = [
        (p.time, p.best, p.fidelity, p.rung, tuple(sorted(p.config.items())))
        for p in res.trajectory
    ]
    return sig, traj, res, tracer


def test_tracer_on_off_bit_identical():
    sig_off, traj_off, res_off, _ = _identity_run(traced=False)
    sig_on, traj_on, res_on, tracer = _identity_run(traced=True)
    assert sig_on == sig_off
    assert traj_on == traj_off
    assert res_on.best_performance == res_off.best_performance
    assert res_on.overheads.keys() == res_off.overheads.keys()
    # the traced run actually traced something
    assert len(tracer.events) > 50


def test_trace_covers_tuner_stages_and_rungs():
    _, _, res, tracer = _identity_run(traced=True)
    events = obs.trace_events(tracer)
    assert obs.validate_events(events) == []
    names = {e["name"] for e in events if e["type"] == "span"}
    for required in ("pool_gen", "surrogate_fit", "surrogate_eval", "bo_recommend",
                     "rung_eval", "space_compression", "workload_eval", "evaluate"):
        assert required in names, f"missing span {required}"
    rungs = [e for e in events if e["type"] == "span" and e["name"] == "rung_eval"]
    for r in rungs:
        a = r["args"]
        assert a["evaluated"] >= a.get("survivors", 0)
        assert a["cost"] >= 0
    # per-run metrics exported under the task scope
    scopes = {e.get("scope") for e in events if e["type"] == "counter"}
    assert "tpch-100gb-A" in scopes


def test_trajectory_wall_time_and_rung():
    # cold start: the warm-history recipe seeds the target's own record, so
    # nothing improves on it; with an empty KB the first full eval always does
    wl = SparkWorkload("tpch", 100, "A")
    res = MFTune(wl, KnowledgeBase(), MFTuneOptions(seed=0)).tune(Budget(4 * 3600.0))
    assert res.trajectory
    for p in res.trajectory:
        assert p.wall_time > 1e9  # real epoch seconds
        assert p.fidelity == 1.0 and p.rung is not None


# ------------------------------------------------------------------ back-compat


def test_tuning_result_views_back_compat():
    _, _, res, _ = _identity_run(traced=False)
    assert res.overheads and all(isinstance(v, float) for v in res.overheads.values())
    for key in ("similarity", "space_compression", "bo_recommend"):
        assert key in res.overheads
    for cache in (res.surrogate_cache, res.plane_cache):
        assert cache and all(isinstance(v, int) for v in cache.values())
    assert {"hits", "misses"} <= res.surrogate_cache.keys()
    assert {"hits", "misses"} <= res.plane_cache.keys()
    # the raw registry snapshot is also exposed
    assert res.metrics["counters"]["overhead/similarity"] == pytest.approx(
        res.overheads["similarity"]
    )


def test_rung_table_rows_carry_trace_ids():
    from repro.core import hyperband_backend

    with hyperband_backend("table"):
        kb = _warm_kb()
        wl = SparkWorkload("tpch", 100, "A")
        tuner = MFTune(wl, kb, MFTuneOptions(seed=0))
        tracer = obs.Tracer("rt")
        with obs.tracing(tracer):
            res = tuner.tune(Budget(8 * 3600.0))
    tables = [t for t in res.rung_tables if len(t) > 0]
    assert tables
    span_ids = {e["id"] for e in tracer.events
                if e["type"] == "span" and e["name"] == "rung_eval"}
    for table in tables:
        ids = table.trace_id[: len(table)]
        assert (ids > 0).all()  # every recorded row links to its rung span
        assert set(np.unique(ids)) <= span_ids


# ------------------------------------------------------------------- exporters


def test_perfetto_round_trip(tmp_path):
    _, _, _, tracer = _identity_run(traced=True)
    canonical = obs.trace_events(tracer)
    pf = tmp_path / "trace.json"
    jl = tmp_path / "trace.jsonl"
    obs.export_perfetto(tracer, str(pf))
    obs.export_jsonl(tracer, str(jl))

    with open(pf) as f:
        doc = json.load(f)  # plain JSON, ui.perfetto.dev-openable
    assert isinstance(doc["traceEvents"], list)
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert "X" in phases and "C" in phases  # durations + counters

    back_pf = obs.read_events(str(pf))
    back_jl = obs.read_events(str(jl))
    assert obs.validate_events(back_pf) == []
    assert obs.validate_events(back_jl) == []
    assert len(back_pf) == len(back_jl) == len(canonical)
    # span stream survives both encodings losslessly
    key = lambda e: (e["name"], round(e["ts"], 6), e["id"], e["parent"])
    spans = sorted(key(e) for e in canonical if e["type"] == "span")
    assert sorted(key(e) for e in back_pf if e["type"] == "span") == spans
    assert sorted(key(e) for e in back_jl if e["type"] == "span") == spans


def test_schema_validator_flags_bad_events():
    good = {"type": "instant", "name": "x", "ts": 0.0, "tid": 1, "args": {}}
    assert obs.validate_events([good]) == []
    bad = [
        {"type": "span", "name": "x"},                     # missing required
        {"type": "instant", "name": 3, "ts": 0.0, "tid": 1, "args": {}},  # wrong type
        {"type": "nope", "name": "x"},                     # unknown type
        {"type": "span", "name": "x", "ts": 0.0, "dur": -1.0, "id": 1,
         "parent": -1, "tid": 1, "args": {}},              # negative duration
    ]
    for ev in bad:
        assert obs.validate_events([ev]), f"validator accepted {ev}"


# ------------------------------------------------------------------- baselines


def test_baselines_share_tracer_vocabulary():
    from repro.baselines import LOCAT, VanillaBO

    for cls in (VanillaBO, LOCAT):
        kb = _warm_kb()
        wl = SparkWorkload("tpch", 100, "A")
        tracer = obs.Tracer("bl")
        with obs.tracing(tracer):
            res = cls(wl, kb=kb, seed=0).run(Budget(12 * 3600.0))
        names = {e["name"] for e in tracer.events if e["type"] == "span"}
        assert "bo_recommend" in names and "workload_eval" in names
        assert "bo_recommend" in res.overheads
        assert res.metrics["counters"]["budget/full_fidelity_s"] > 0
        scopes = {e.get("scope") for e in tracer.events if e["type"] == "counter"}
        assert f"{cls.name}:tpch-100gb-A" in scopes
        for p in res.trajectory:
            assert p.wall_time > 1e9 and p.rung is None


# ------------------------------------------------ spans inside the slow stages


def _propose_setup(n_sources=3, n_trees=10, seed0=0):
    from repro.core import (BoolKnob, CatKnob, ConfigSpace, FloatKnob, IntKnob,
                            ProbabilisticRandomForest)

    space = ConfigSpace([
        FloatKnob("f1", 0.1, 10.0, log=True),
        FloatKnob("f2", -5.0, 5.0),
        IntKnob("i1", 1, 64, log=True),
        CatKnob("c1", ["a", "b", "c"]),
        BoolKnob("b1"),
    ])
    rng = np.random.default_rng(seed0)
    models = [ProbabilisticRandomForest(n_trees=n_trees, seed=s).fit(
                  rng.random((30, space.dim)), rng.random(30) * 10 + s)
              for s in range(n_sources)]
    incs = [5.0 + s for s in range(n_sources)]
    ws = list(np.full(n_sources, 1.0 / n_sources))
    return space, models, incs, ws


PHASES = {"score_topk": ("propose_upload", "propose_dispatch", "propose_fetch"),
          "propose": ("propose_dispatch", "propose_fetch")}


@pytest.mark.parametrize("call", sorted(PHASES))
def test_propose_phase_spans(call):
    pytest.importorskip("jax")
    from repro.core import ProposeEngine

    space, models, incs, ws = _propose_setup()
    eng = ProposeEngine(space, seed=0, pool_size=300)
    pool = np.random.default_rng(1).random((300, space.dim))

    def go():
        if call == "score_topk":
            eng.score_topk(models, pool, incs, ws, 4)
        else:
            eng.propose(models, incs, ws, 4)

    go()  # compiles outside the trace
    tr = obs.Tracer("phases")
    with obs.tracing(tr):
        go()
    spans = _spans(tr)
    by = {s["name"]: s for s in spans}
    assert [s["name"] for s in spans] == ["propose_prepare", *PHASES[call], "propose_step"]
    step, prep = by["propose_step"], by["propose_prepare"]
    assert prep["parent"] == step["parent"] == -1
    assert prep["ts"] + prep["dur"] <= step["ts"]
    inner = [by[n] for n in PHASES[call]]
    assert all(s["parent"] == step["id"] for s in inner)
    assert sum(s["dur"] for s in inner) <= step["dur"]
    assert step["args"]["compile"] is False


def test_propose_step_compile_reads_the_jit_cache():
    """A second engine calling a shape the process has run reads
    ``compile=False``: the argument follows the jitted function's executable
    cache, not the engine's own memory of its signatures."""
    pytest.importorskip("jax")
    from repro.core import ProposeEngine

    # 2 sources of 7 trees: a signature no other test of this file compiles
    space, models, incs, ws = _propose_setup(n_sources=2, n_trees=7, seed0=5)
    pool = np.random.default_rng(2).random((260, space.dim))
    tr = obs.Tracer("compile")
    with obs.tracing(tr):
        for seed in (0, 1):
            ProposeEngine(space, seed=seed).score_topk(models, pool, incs, ws, 3)
    flags = [s["args"]["compile"] for s in _spans(tr) if s["name"] == "propose_step"]
    assert flags == [True, False]


def test_forest_fit_spans_match_fits(monkeypatch):
    from repro.core import ProbabilisticRandomForest

    fits = []
    orig = ProbabilisticRandomForest.fit

    def counting_fit(self, X, y):
        if obs.get_tracer() is not None:  # fits of the tuner's own run
            fits.append(len(y))
        return orig(self, X, y)

    monkeypatch.setattr(ProbabilisticRandomForest, "fit", counting_fit)
    _, _, _, tracer = _identity_run(traced=True)
    spans = _spans(tracer)
    fit_spans = [s for s in spans if s["name"] == "forest_fit"]
    assert fits and len(fit_spans) == len(fits)
    assert [s["args"]["n_obs"] for s in fit_spans] == fits
    assert all(s["args"]["trees"] > 0 and s["args"]["dim"] > 0 for s in fit_spans)
    # the partition is attempted until it forms; each try counts itself
    attempts = [s["args"]["attempt"] for s in spans if s["name"] == "fidelity_partition"]
    assert attempts == list(range(1, len(attempts) + 1))
    names = {s["name"] for s in spans}
    assert {"similarity_eq2", "similarity_self_weight"} <= names


def test_fidelity_greedy_counts_correlation_evals():
    from repro.core import fidelity as F
    from test_fidelity_batched import reference_greedy

    rng = np.random.default_rng(0)
    stats = [F.QueryStats(task_id=f"t{i}", perf=rng.random((12, 9)),
                          cost=rng.random((12, 9)) + 0.1, weight=1.0 + i)
             for i in range(3)]
    tr = obs.Tracer("greedy")
    with obs.tracing(tr):
        part = F.partition_fidelities(stats, [1 / 9, 1 / 3, 1.0])
    greedy = [s for s in _spans(tr) if s["name"] == "fidelity_greedy"]
    assert [s["args"]["delta"] for s in greedy] == [1 / 9, 1 / 3]
    # evals counts the candidate subsets scored, as the scalar search's
    # subset_correlation calls did
    ref_evals = [reference_greedy(stats, s["args"]["delta"])[3] for s in greedy]
    assert [s["args"]["evals"] for s in greedy] == ref_evals
    assert sum(ref_evals) > 0
    for s in greedy:
        assert s["args"]["queries"] == 9
        assert s["args"]["chosen"] == len(part.subsets[s["args"]["delta"]])
        assert s["args"]["scalar_sources"] == 0


def test_spans_mirror_on_the_profiler_clock(tmp_path):
    """Each span's profiler annotation starts within 0.5 ms of the span put
    on the profiler's clock by the benchmark's one-mark offset."""
    import time

    jax = pytest.importorskip("jax")
    from perfbench.lib import trace_reduce as T
    from repro.core import ProposeEngine

    space, models, incs, ws = _propose_setup()
    eng = ProposeEngine(space, seed=0)
    pool = np.random.default_rng(3).random((300, space.dim))
    eng.score_topk(models, pool, incs, ws, 4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tr = obs.Tracer("clock")
        with obs.tracing(tr):
            clock_pc = time.perf_counter()
            with jax.profiler.TraceAnnotation(T.CLOCK_MARK):
                pass
            for _ in range(3):
                with obs.span("outer"):
                    eng.score_topk(models, pool, incs, ws, 4)
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    pd = T.load(T.xplane_file(str(tmp_path)))
    shift = T.host_marks(pd, T.CLOCK_MARK)[0][0] - clock_pc
    spans = _spans(tr)
    assert len(spans) == 3 * 6
    for name in {s["name"] for s in spans}:
        moved = sorted(tr.epoch + s["ts"] + shift for s in spans if s["name"] == name)
        marks = [a for a, _ in T.host_marks(pd, name)]
        assert len(marks) == len(moved), name
        assert max(abs(a - b) for a, b in zip(marks, moved)) <= 0.5e-3, name


def test_no_annotation_without_tracer(monkeypatch):
    from repro.obs import trace

    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("/" + self.name)

    monkeypatch.setattr(trace, "_ANNOTATION", Annotation)
    with obs.span("off"):
        pass
    assert entered == []
    with obs.tracing(obs.Tracer("on")):
        with obs.span("on"):
            pass
    assert entered == ["on", "/on"]


def test_lowered_step_names_every_stage(monkeypatch):
    jax = pytest.importorskip("jax")
    from repro.core import ProposeEngine
    from repro.kernels.forest_eval import propose as P

    space, models, incs, ws = _propose_setup()
    seen = []
    orig = P._propose_jit

    def capture(*a, **kw):
        seen.append((a, kw))
        return orig(*a, **kw)

    capture._cache_size = orig._cache_size
    monkeypatch.setattr(P, "_propose_jit", capture)
    ProposeEngine(space, seed=0).propose(models, incs, ws, 4)
    (a, kw), = seen
    with jax.enable_x64(True):
        text = orig.lower(*a, **kw).as_text(debug_info=True)
    for stage in ("draw", "descent", "combine", "ei", "rank", "topk"):
        assert f"/{stage}/" in text, stage


def _compression_pass(d, tracer):
    """One ``SpaceCompressor.compress`` over two source tasks in a ``d``-knob
    space, under ``tracer``."""
    from repro.core import ConfigSpace, FloatKnob, Observation, SpaceCompressor, TaskRecord
    from repro.core.similarity import TaskWeights

    space = ConfigSpace([FloatKnob(f"x{i}", 0.0, 1.0) for i in range(d)])
    tasks = {}
    for t in range(2):
        rec = TaskRecord(task_id=f"s{t}", queries=["q1"])
        for cfg in space.sample(np.random.default_rng(t), 40):
            perf = (cfg["x0"] - 0.2) ** 2 + (cfg[f"x{d - 1}"] - 0.7) ** 2 + 1.0
            rec.observations.append(Observation(config=cfg, performance=perf, fidelity=1.0))
        tasks[rec.task_id] = rec
    weights = TaskWeights(weights={k: 0.5 for k in tasks}, similarities={}, used_meta=False)
    with obs.tracing(tracer):
        SpaceCompressor(space, alpha=0.65, seed=0).compress(weights, tasks)


def test_shapley_chain_spans_past_64_knobs():
    tr = obs.Tracer("compress")
    _compression_pass(128, tr)
    spans = _spans(tr)
    by_id = {s["id"]: s for s in spans}
    chain = [s for s in spans if s["name"] == "shapley_chain"]
    assert chain and not [s for s in spans if s["name"] == "shapley_composite"]
    for s in chain:
        assert by_id[s["parent"]]["name"] == "shapley_attribution"
        assert s["args"]["knobs"] == 128 and s["args"]["chains"] > 0
    counts = tr.metrics.counters_view("shapley/")
    assert counts["chain_kernel"] == len(chain) and "composite_fallback" not in counts


def test_shapley_composite_span_on_leaf_overflow():
    from repro.core import ProbabilisticRandomForest, draw_permutations, shapley_values_batch

    rng = np.random.default_rng(0)
    m = ProbabilisticRandomForest(seed=0, n_trees=3, max_depth=12,
                                  min_samples_split=2).fit(rng.random((600, 4)), rng.random(600))
    tr = obs.Tracer("overflow")
    with obs.tracing(tr):
        shapley_values_batch(m.predict_mean, rng.random((2, 4)), rng.random((5, 4)),
                             perms=draw_permutations(4, 4, rng), model=m)
    names = [s["name"] for s in _spans(tr)]
    assert names.count("shapley_composite") == 1 and "shapley_chain" not in names
    assert tr.metrics.counters_view("shapley/") == {"composite_fallback": 1}


def test_space_compression_span_names_the_width():
    _, _, _, tracer = _identity_run(traced=True)
    comp = [s for s in _spans(tracer) if s["name"] == "space_compression"]
    assert comp
    for s in comp:
        assert s["args"]["knobs"] == 60 and 0 < s["args"]["kept"] <= 60
