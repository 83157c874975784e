"""Propose-step latency vs candidate-pool size: staged numpy vs fused jax.

The PR 7 headline: one jitted program runs the whole BO propose iteration
(device pool draw, merged-QuickScorer forest descent, per-source combine,
EI, weighted rank aggregation, top-k) against the staged numpy path
(``space.sample`` -> unit encode -> ``score_sources`` ->
``aggregate_ranks`` -> stable argsort), at MFTune's combined-surrogate
scale (12 sources) over pool sizes 256 .. 131072. Both sides draw a fresh
pool per call — the real per-iteration cost, not a cached-pool microloop.

Before timing, host-pool mode is equivalence-gated: the fused program must
select bit-identical candidate indices to the staged numpy path. After the
sweep a jit-cache-growth guard asserts the engine compiled at most one
program per pool bucket (+1 for the host-mode gate) — the bucketed-shape
protocol's contract.

The speedup reported at 131072 is the measured number on the current
host. The 10x target assumes an accelerator; on a single-core CPU the
fused path is sort- and gather-bound, which historically capped the
ratio around 4x there. PR 10 replaced the rank-aggregation stage's
u64 stable sort with a radix-rank kernel (``rank_impl="callback"`` on
CPU: an LSD counting sort behind a raw XLA custom-call), cutting that
stage ~5x at 12 x 131072. The pallas-descent row (REPRO_BENCH_PALLAS=1)
and the callback rank rows run on the CPU backend only: Mosaic refuses
the descent kernel on a TPU v5e, and the callback is the host radix.

Per-stage rows decompose the top pool size: the rank-aggregation and
top-k stage programs are timed standalone (they are the exact programs
the engine dispatches), the end-to-end number is the ``propose_step``
span duration captured by a tracer, and the descent+combine+EI residual
is their difference — the fused program is one jit, so there is no
in-program stage boundary to instrument directly.

``--smoke`` (or REPRO_BENCH_SMOKE=1) sweeps two small pools, 1
repetition, and gates the radix rank kernel against the pinned
``np.argsort(-scores, kind="stable")`` permutation on a tie- and
special-heavy fixture.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from benchmarks.common import cached

N_SOURCES = 12   # MFTune combined surrogate: source tasks + fidelity levels
N_OBS = 64
D = 16
K = 16           # candidates returned per propose call
POOLS = [256, 1024, 4096, 16384, 65536, 131072]
SMOKE_POOLS = [256, 2048]


def _best(fn, repeats: int) -> float:
    fn()  # warm up (pack, jit, numpy dispatch)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _space():
    from repro.core import ConfigSpace, FloatKnob, IntKnob

    knobs = []
    for j in range(D):
        if j % 4 == 0:
            knobs.append(FloatKnob(f"f{j}", 0.1, 10.0, log=True))
        elif j % 4 == 1:
            knobs.append(FloatKnob(f"f{j}", -5.0, 5.0))
        elif j % 4 == 2:
            knobs.append(IntKnob(f"i{j}", 1, 1024, log=True))
        else:
            knobs.append(IntKnob(f"i{j}", 0, 99))
    return ConfigSpace(knobs)


def _run():
    try:
        import jax  # noqa: F401
    except ImportError:
        return [{"name": "pool_scaling_skipped", "us_per_call": 0.0,
                 "derived": "jax unavailable"}]

    from repro.core import ProposeEngine, make_forest
    from repro.core.acquisition import aggregate_ranks, score_sources

    smoke = os.environ.get("REPRO_BENCH_SMOKE") == "1"
    pools = SMOKE_POOLS if smoke else POOLS
    rng = np.random.default_rng(0)
    space = _space()
    models = []
    for s in range(N_SOURCES):
        X = rng.random((N_OBS, D))
        y = 3 * X[:, 0] - X[:, 1] ** 2 + 0.1 * rng.normal(size=N_OBS)
        models.append(make_forest(seed=s).fit(X, y))
    assert ProposeEngine.fusable(models)
    incs = list(rng.random(N_SOURCES))
    ws = list(rng.random(N_SOURCES))
    eng = ProposeEngine(space, seed=0)

    seed_ctr = [0]

    def staged(n):
        # fresh pool per call, exactly the staged recommend scoring path
        seed_ctr[0] += 1
        pool = space.sample(np.random.default_rng(seed_ctr[0]), n)
        Xu = space.complete_batch(pool).unit()
        scores = score_sources(models, Xu, incs)
        agg = aggregate_ranks(scores, np.asarray(ws))
        return np.argsort(agg, kind="stable")[:K]

    def fused(n, descent="auto"):
        # fresh device pool per call via the engine's threaded PRNG key
        return eng.propose(models, incs, ws, K, pool_size=n, descent=descent)

    # equivalence gate: host-pool mode must select bit-identical indices
    n_gate = min(4096, max(pools))
    pool = space.sample(np.random.default_rng(99), n_gate)
    Xu = space.complete_batch(pool).unit()
    ref = np.argsort(
        aggregate_ranks(score_sources(models, Xu, incs), np.asarray(ws)),
        kind="stable",
    )[:K]
    got = eng.score_topk(models, Xu, incs, ws, K)
    assert np.array_equal(ref, got), "fused host-mode selection diverged"

    rows = []
    ratios = {}
    for n in pools:
        reps = 1 if smoke else (5 if n <= 16384 else 2)
        t_np = _best(lambda: staged(n), reps)
        t_fx = _best(lambda: fused(n), reps)
        ratios[n] = t_np / t_fx
        rows.append({
            "name": f"staged_numpy_{n}", "us_per_call": t_np * 1e6,
            "derived": f"{n / t_np:.0f} cand/s",
        })
        rows.append({
            "name": f"fused_jax_{n}", "us_per_call": t_fx * 1e6,
            "derived": f"speedup {ratios[n]:.2f}x vs staged; {n / t_fx:.0f} cand/s",
        })
    # the pallas descent runs interpreted on the CPU backend only; Mosaic
    # refuses the kernel on a TPU v5e (docs/KERNELS.md)
    on_cpu = jax.default_backend() == "cpu"
    if on_cpu and os.environ.get("REPRO_BENCH_PALLAS") == "1":
        n = max(pools)
        t = _best(lambda: fused(n, descent="pallas"), 1 if smoke else 2)
        rows.append({
            "name": f"fused_pallas_{n}", "us_per_call": t * 1e6,
            "derived": f"pallas descent ({jax.default_backend()})",
        })

    # ---------------------------------------------------------- per-stage
    # Decompose the top pool size into rank-agg / top-k / descent. Rank
    # aggregation and top-k are timed through the exact stage programs the
    # fused step embeds; the end-to-end number is the propose_step span
    # captured by a tracer (non-compile calls only); descent+combine+EI is
    # the residual. A fresh engine keeps the main engine's jit-cache guard
    # meaningful (per-stage runs compile extra rank_impl signatures).
    from repro import obs
    from repro.kernels.forest_eval import propose as P
    from repro.kernels.forest_eval import rank as R

    n_top = max(pools)
    reps_st = 1 if smoke else 3
    scores_fix = rng.standard_normal((N_SOURCES, n_top))
    scores_fix[rng.random(scores_fix.shape) < 0.1] = 0.0  # tie clusters
    w_fix = np.asarray(ws)

    # the callback rank impl is the host radix: it exists on the CPU only
    impls = ("sort", "callback") if on_cpu else ("sort",)
    t_rank = {}
    for impl in impls:
        t_rank[impl] = _best(
            lambda: P.aggregate_ranks_host(scores_fix, w_fix, rank_impl=impl),
            reps_st,
        )
        rows.append({
            "name": f"stage_rank_{impl}_{n_top}",
            "us_per_call": t_rank[impl] * 1e6,
            "derived": f"rank-aggregation stage alone ({N_SOURCES} x {n_top})",
        })
    if on_cpu:
        rank_speedup = t_rank["sort"] / t_rank["callback"]
        rows.append({
            "name": f"stage_rank_speedup_{n_top}", "us_per_call": rank_speedup,
            "derived": (f"radix-rank callback vs fused stable sort at "
                        f"{N_SOURCES} x {n_top} (acceptance: >= 2x on CPU)"),
        })
        if not smoke:
            assert rank_speedup >= 2.0, (
                f"rank-aggregation stage speedup regressed: {rank_speedup:.2f}x"
            )

    import jax.numpy as jnp

    with P._x64():
        topk_fn = jax.jit(lambda a: P._sort_perm_asc1d(a)[:K])
        agg_fix = jnp.asarray(rng.random(n_top))
        t_topk = _best(lambda: np.asarray(topk_fn(agg_fix)), reps_st)
    rows.append({
        "name": f"stage_topk_{n_top}", "us_per_call": t_topk * 1e6,
        "derived": "top-k stage alone (monotone-key argsort, take k)",
    })

    eng_st = ProposeEngine(space, seed=0)
    t_total = {}
    for impl in impls:
        with obs.tracing() as tr:
            for _ in range(reps_st + 1):
                eng_st.propose(models, incs, ws, K, pool_size=n_top,
                               rank_impl=impl)
        durs = [e["dur"] for e in tr.events
                if e.get("name") == "propose_step"
                and e["args"].get("rank") == impl
                and not e["args"].get("compile")]
        t_total[impl] = min(durs)
        rows.append({
            "name": f"propose_span_{impl}_{n_top}",
            "us_per_call": t_total[impl] * 1e6,
            "derived": f"end-to-end propose_step span, rank_impl={impl}",
        })
    t_resid = min(t_total[i] - t_rank[i] - t_topk for i in t_total)
    rows.append({
        "name": f"stage_descent_residual_{n_top}",
        "us_per_call": max(t_resid, 0.0) * 1e6,
        "derived": ("pool draw + descent + combine + EI residual "
                    "(propose_step span minus rank-agg and top-k stages)"),
    })

    if smoke:
        # radix rank vs pinned stable argsort on a tie/special-heavy fixture
        s = rng.standard_normal((4, 3000))
        s[rng.random(s.shape) < 0.3] = 0.25
        s[0, :8] = [0.0, -0.0, 5e-324, -5e-324, np.inf, -np.inf, 1e-310, 0.0]
        want = np.argsort(-s, axis=-1, kind="stable")
        assert np.array_equal(R.radix_argsort(s), want), (
            "radix rank kernel diverged from the pinned stable argsort"
        )
        rows.append({
            "name": "smoke_radix_identity", "us_per_call": 1.0,
            "derived": "radix_argsort == np.argsort(-s, kind='stable'): OK",
        })

    crossover = next((n for n in pools if ratios[n] >= 1.0), None)
    rows.append({
        "name": "crossover_pool", "us_per_call": float(crossover or 0),
        "derived": ("fused beats staged from this pool size up"
                    if crossover else "fused never crossed staged in sweep"),
    })
    n_top = max(pools)
    rows.append({
        "name": f"headline_speedup_{n_top}", "us_per_call": ratios[n_top],
        "derived": (f"measured fused/staged ratio at {n_top}-candidate pools "
                    f"(single-device {jax.default_backend()}; 10x target assumes "
                    f"an accelerator — XLA:CPU's rank-agg sort and descent "
                    f"gathers are the floor here)"),
    })

    # jit-cache-growth guard: one program per pool bucket, +1 for the
    # host-mode equivalence gate — the bucketed-shape protocol's contract
    n_buckets = len({eng._pow2(max(n, 256)) for n in pools})
    assert len(eng.compiled) <= n_buckets + 1, (
        f"jit cache grew past the bucket bound: {sorted(eng.compiled)}"
    )
    rows.append({
        "name": "jit_cache_guard", "us_per_call": float(len(eng.compiled)),
        "derived": f"compiled signatures <= {n_buckets} buckets + 1 gate: OK",
    })
    return rows


def run(force: bool = False):
    return cached("pool_scaling", force, _run)


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        # smoke validates the selection-identity gates, the radix-rank
        # permutation gate, and the jit-cache guard without overwriting
        # the committed multi-repetition baseline JSON
        os.environ["REPRO_BENCH_SMOKE"] = "1"
        for r in _run():
            print(r)
    else:
        for r in run(force=True):
            print(r)
