"""Chip smoke run: MFTune's fused propose path on one accelerator chip.

    python chip_smoke.py [--seed 0]

It first reports whether the device's float64 is IEEE binary64. Phase A
tunes ``SparkWorkload("tpcds", 600, "A")`` end to end through
``MFTune.run`` with the fused on-device propose step (device pool), under a
tracer, on a knowledge base of sparksim histories generated from the seed.
It fails unless at least three fused ``propose_step`` spans ran and no
``recommend`` call fell back to the staged numpy path
(``propose/declined``).

Phase B scores a 131072-candidate host pool with ``ProposeEngine`` (the
pool bucket where QuickScorer descent is auto-selected) over 12 fitted
sources x 10 trees on the 60-knob ``spark_space``, and checks the selected
top-k against the staged numpy reference (``score_sources`` ->
``aggregate_ranks`` -> stable argsort).

Every data set is generated from ``--seed``. Compilation goes through the
persistent cache of ``repro.compile_cache``; the run prints compile
seconds per pool bucket with the cache hits it saw, each program's
``memory_analysis()``, the steady propose latency (host clock around a
blocking call, after warm-up) and ``peak_bytes_in_use``, each labelled
with the device. The last line is one JSON object. Without a TPU the run
fails before any phase; ``--tiny`` shrinks every size and also runs on the
CPU backend, as a rehearsal that prints no result line.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

N_SOURCES = 12
PHASE_A_MIN_STEPS = 3

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Backend compile seconds and persistent-cache hits, per label. A
    cache hit's duration is its retrieval time."""

    def __init__(self):
        self.label = "setup"
        self.seconds: dict = {}
        self.programs: dict = {}
        self.hits: dict = {}

    def on_duration(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self.seconds[self.label] = self.seconds.get(self.label, 0.0) + secs
            self.programs[self.label] = self.programs.get(self.label, 0) + 1

    def on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.hits[self.label] = self.hits.get(self.label, 0) + 1

    def line(self, label, device):
        return (f"[chip_smoke] compile {label} on {device}: "
                f"{self.programs.get(label, 0)} programs, "
                f"{self.seconds.get(label, 0.0)!r} s backend compile, "
                f"{self.hits.get(label, 0)} persistent-cache hits")


def _device_label(dev) -> str:
    return f"{dev.platform}:{dev.device_kind}"


def f64_probe(dev, device: str) -> None:
    """Whether the device's float64 is IEEE binary64: values that only
    binary64 holds (a subnormal, 1e-300, 1 + 2**-52) read back after a
    round trip and after one multiply by 1 on the device."""
    import jax
    import jax.numpy as jnp

    vals = np.array([1e-300, 5e-324, 1.0 + 2.0**-52, 1.0 / 3.0])
    with jax.enable_x64(True):
        x = jax.device_put(vals, dev)
        back = np.asarray(x)
        prod = np.asarray(jax.jit(jnp.multiply)(x, jnp.ones_like(x)))
    def same(a):
        return (a.view(np.uint64) == vals.view(np.uint64)).tolist()

    print(f"[chip_smoke] f64 on {device}: sent {vals.tolist()}; read back "
          f"{back.tolist()} (bit-exact {same(back)}); times one on the "
          f"device {prod.tolist()} (bit-exact {same(prod)})")


def phase_a(seed: int, tiny: bool, log: CompileLog, device: str) -> dict:
    """MFTune end to end with the fused device-pool propose step."""
    from repro import obs
    from repro.core import KnowledgeBase, MFTune, MFTuneOptions
    from repro.sparksim import SparkWorkload, TaskSpec, generate_history
    from repro.tuneapi import Budget

    specs = [TaskSpec("tpcds", 100, "A"), TaskSpec("tpch", 600, "A"),
             TaskSpec("tpcds", 600, "B"), TaskSpec("tpcds", 100, "C")]
    n_obs = 12 if tiny else 30
    kb = KnowledgeBase()
    for i, spec in enumerate(specs[: 2 if tiny else 4]):
        kb.add_task(generate_history(spec.workload(), n_obs=n_obs, seed=seed + i),
                    persist=False)
    wl = SparkWorkload("tpcds", 600, "A")
    opts = MFTuneOptions(seed=seed, acquisition_backend="jax",
                         acquisition_pool="device")
    log.label = "phase_a"
    t0 = time.perf_counter()
    with obs.tracing(name="chip_smoke_phase_a") as tr:
        res = MFTune(wl, kb, opts).run(Budget((8 if tiny else 24) * 3600.0))
    wall = time.perf_counter() - t0
    log.label = "setup"
    steps = [e for e in tr.events
             if e.get("type") == "span" and e["name"] == "propose_step"]
    counters = tr.metrics.snapshot()["counters"]
    declined = counters.get("propose/declined", 0.0)
    buckets = sorted({e["args"]["bucket"] for e in steps})
    steady = [e["dur"] for e in steps if not e["args"].get("compile")]
    print(f"[chip_smoke] phase A on {device}: {len(steps)} propose_step spans "
          f"(buckets {buckets}), propose/declined={declined!r}, "
          f"{res.n_evaluations} evaluations, best {res.best_performance!r} s "
          f"(virtual), {wall!r} s wall")
    if steady:
        print(f"[chip_smoke] phase A steady propose_step latency on {device} "
              f"(device pool, bucket {buckets}): median "
              f"{statistics.median(steady)!r} s over {len(steady)} calls")
    print(log.line("phase_a", device))
    if len(steps) < PHASE_A_MIN_STEPS:
        raise AssertionError(f"phase A ran {len(steps)} fused propose steps, "
                             f"expected >= {PHASE_A_MIN_STEPS}")
    if declined:
        raise AssertionError(f"phase A: {declined!r} recommend calls declined "
                             f"the fused path: {sorted(k for k in counters if k.startswith('propose/declined/'))}")
    if not np.isfinite(res.best_performance):
        raise AssertionError("phase A found no finite configuration")
    return {"steps": len(steps), "declined": declined,
            "steady_s": steady, "best": res.best_performance}


def _sources(space, seed: int, n_obs: int):
    """12 PRFs (10 trees each) fitted to sparksim latencies of LHS configs
    on 12 different tasks, with their incumbents and source weights."""
    from repro.core import make_forest
    from repro.sparksim import all_task_specs

    rng = np.random.default_rng(seed)
    specs = all_task_specs()
    picks = rng.choice(len(specs), size=N_SOURCES, replace=False)
    models, incs = [], []
    for s, j in enumerate(picks):
        wl = specs[int(j)].workload()
        batch = space.lhs_sample(rng, n_obs)
        res = wl.evaluate_many(batch.materialize())
        y = np.array([r.aggregate if not r.failed else np.nan for r in res])
        ok = np.isfinite(y)
        X = batch.unit()[ok]
        models.append(make_forest(seed=seed + s).fit(X, y[ok]))
        incs.append(float(y[ok].min()))
    weights = rng.dirichlet(np.ones(N_SOURCES))
    return models, incs, weights


def phase_b(seed: int, tiny: bool, log: CompileLog, device: str) -> dict:
    """ProposeEngine host-pool top-k at the top pool bucket vs numpy."""
    import jax

    from repro.core import ProposeEngine
    from repro.core.acquisition import aggregate_ranks, score_sources
    from repro.kernels.forest_eval import propose as P
    from repro.sparksim import spark_space

    n_pool = 2048 if tiny else P.POOL_BUCKET_MAX
    k = 64
    space = spark_space()
    models, incs, weights = _sources(space, seed, 24 if tiny else 64)
    assert ProposeEngine.fusable(models)
    rng = np.random.default_rng(seed + 1)
    Xu = space.complete_batch(space.sample(rng, n_pool)).unit()

    t0 = time.perf_counter()
    scores = score_sources(models, Xu, incs)
    agg = aggregate_ranks(scores, weights)
    want = np.argsort(agg, kind="stable")[:k]
    t_ref = time.perf_counter() - t0

    eng = ProposeEngine(space, seed=seed)
    # the auto rule picks QuickScorer at this bucket on the chip; the tiny
    # rehearsal asks for it so that it runs the same descent
    descent = "qs" if tiny else "auto"
    calls = []
    run_jit = P._propose_jit

    def capture(*a, **kw):
        calls.append((a, kw))
        return run_jit(*a, **kw)

    log.label = f"bucket_{n_pool}"
    P._propose_jit = capture
    try:
        t0 = time.perf_counter()
        got = eng.score_topk(models, Xu, incs, weights, k, descent=descent)
        first = time.perf_counter() - t0
    finally:
        P._propose_jit = run_jit
    log.label = "setup"
    a, kw = calls[0]
    with jax.enable_x64(True):
        mem = run_jit.lower(*a, **kw).compile().memory_analysis()
    times = []
    for _ in range(2 if tiny else 5):
        t0 = time.perf_counter()
        eng.score_topk(models, Xu, incs, weights, k, descent=descent)
        times.append(time.perf_counter() - t0)
    print(f"[chip_smoke] phase B on {device}: bucket {n_pool}, descent "
          f"{kw['descent']}, rank {kw['rank_impl']}, {N_SOURCES} sources x "
          f"{models[0].n_trees} trees, k={k}")
    print(log.line(f"bucket_{n_pool}", device))
    print(f"[chip_smoke] phase B memory_analysis on {device}: {mem}")
    print(f"[chip_smoke] phase B first call (trace+compile+run) on {device}: "
          f"{first!r} s; steady score_topk latency on {device}: median "
          f"{statistics.median(times)!r} s over {len(times)} calls "
          f"(host pool upload included); numpy reference on the host: "
          f"{t_ref!r} s")
    same = np.array_equal(want, got)
    print(f"[chip_smoke] phase B top-{k} selection on {device} vs numpy: "
          f"{'identical' if same else 'DIFFERENT'}")
    if not same:
        from repro.core.acquisition import predict_sources

        means, vars_ = predict_sources(models, Xu)
        ei_dev = P.ei_host(means, vars_, np.asarray(incs)[:, None])
        print(f"[chip_smoke] numpy top-{k}: {want.tolist()}")
        print(f"[chip_smoke] {device} top-{k}: {got.tolist()}")
        print(f"[chip_smoke] largest |EI(device) - EI(numpy)| = "
              f"{float(np.max(np.abs(ei_dev - scores)))!r}")
        diff = sorted(set(want.tolist()) ^ set(got.tolist()))
        print(f"[chip_smoke] numpy aggregate of the {len(diff)} candidates "
              f"chosen by one side only: "
              f"{ {i: float(agg[i]) for i in diff} }; numpy k-th "
              f"aggregate {float(agg[want[-1]])!r}")
        raise AssertionError("phase B: device top-k differs from numpy")
    return {"n_pool": n_pool, "steady_s": times, "first_s": first}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="small sizes; also runs on the CPU backend, where "
                         "it prints no result line")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = _device_label(dev)
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"[chip_smoke] no TPU: JAX found {device}; nothing was run",
              file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)
    print(f"[chip_smoke] device {device} x{len(jax.devices())}, "
          f"compile cache {cache_dir}, seed {args.seed}")

    f64_probe(dev, device)
    phase_a(args.seed, args.tiny, log, device)
    phase_b(args.seed, args.tiny, log, device)

    stats = dev.memory_stats() or {}
    print(f"[chip_smoke] peak_bytes_in_use on {device}: "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    if not on_tpu:
        print(f"[chip_smoke] rehearsal on {device} passed; not a chip run")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
