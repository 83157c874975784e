"""Surrogate/acquisition throughput: per-tree loop vs the packed forest plane.

The acquisition bottleneck PR 2 attacks: ``CandidateGenerator.recommend``
scoring a 256-candidate pool against 8 surrogate sources (MFTune's combined
surrogate — one PRF per source task plus one per fidelity level, §6.2).
Reports per-pass latency for the legacy per-tree loop, the per-forest packed
numpy descent, the fused multi-source ``ForestPlane``, the jax kernel
backend, and the fused EI/rank acquisition program, plus speedups vs the
loop; the cached JSON under results/bench/ is the baseline later PRs track.
Every timed path is equivalence-checked against the loop before timing.

``--smoke`` (or REPRO_BENCH_SMOKE=1) runs 1 repetition for CI.
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
POOL = 256
REPEATS = 30


def _best(fn, repeats: int) -> float:
    fn()  # warm up (pack, jit, numpy dispatch)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _run():
    from repro.core import ForestPlane, make_forest
    from repro.core.acquisition import aggregate_ranks, ei_scores, score_sources

    repeats = 1 if os.environ.get("REPRO_BENCH_SMOKE") == "1" else REPEATS
    rng = np.random.default_rng(0)
    forests = []
    for s in range(N_SOURCES):
        X = rng.random((N_OBS, D))
        y = 3 * X[:, 0] - X[:, 1] ** 2 + 0.1 * rng.normal(size=N_OBS)
        forests.append(make_forest(seed=s).fit(X, y))
    pool = rng.random((POOL, D))
    incumbents = list(rng.random(N_SOURCES))
    weights = list(rng.random(N_SOURCES))

    def loop():
        return [m.predict_loop(pool) for m in forests]

    def packed_numpy():
        return [m.pack().predict(pool) for m in forests]

    def plane_numpy():
        plane = ForestPlane.from_forests([m.pack() for m in forests])
        return plane.predict(pool)

    def acq_legacy():
        # the pre-refactor acquisition verbatim: per-tree predict loop,
        # EI pushed through np.vectorize(erf), sequential rank aggregation
        import math

        agg = np.zeros(POOL)
        for m, inc, w in zip(forests, incumbents, weights):
            mean, var = m.predict_loop(pool)
            std = np.sqrt(np.maximum(var, 1e-12))
            z = (inc - mean) / std
            phi = np.exp(-0.5 * z**2) / np.sqrt(2 * np.pi)
            Phi = 0.5 * (1.0 + np.vectorize(math.erf)(z / np.sqrt(2.0)))
            scores = np.maximum((inc - mean) * Phi + std * phi, 0.0)
            order = np.argsort(-scores, kind="stable")
            ranks = np.empty(POOL)
            ranks[order] = np.arange(POOL, dtype=float)
            agg += w * ranks
        return agg

    def acq_modern_unfused():  # new EI, per-source loop (exact-equality gate)
        return aggregate_ranks(
            np.stack([ei_scores(m, pool, inc) for m, inc in zip(forests, incumbents)]),
            weights,
        )

    def acq_fused():
        return aggregate_ranks(score_sources(forests, pool, incumbents), weights)

    # equivalence gate before timing
    ref = loop()
    ms, vs = plane_numpy()
    for i, (m_ref, v_ref) in enumerate(ref):
        assert np.array_equal(ms[i], m_ref) and np.array_equal(vs[i], v_ref)
    assert np.array_equal(acq_modern_unfused(), acq_fused())
    # vs the erf-ulp legacy only rank *order* is meaningful (EI clamps at 0,
    # so stable-sort tie blocks shuffle under last-ulp CDF differences)
    agg_legacy, agg_fused = acq_legacy(), acq_fused()
    assert int(np.argmin(agg_legacy)) == int(np.argmin(agg_fused))
    assert np.corrcoef(agg_legacy, agg_fused)[0, 1] > 0.999

    t_loop = _best(loop, repeats)
    rows = [{
        "name": f"loop_{N_SOURCES}src_{POOL}pool", "us_per_call": t_loop * 1e6,
        "derived": f"legacy per-tree loop; {N_SOURCES * POOL / t_loop:.0f} cand-src/s",
    }]
    for name, fn in [("packed_numpy", packed_numpy), ("plane_numpy", plane_numpy)]:
        t = _best(fn, repeats)
        rows.append({
            "name": f"{name}_{N_SOURCES}src_{POOL}pool", "us_per_call": t * 1e6,
            "derived": f"speedup {t_loop / t:.1f}x vs loop",
        })
    try:
        import jax  # noqa: F401

        plane = ForestPlane.from_forests([m.pack() for m in forests])
        mj, vj = plane.predict(pool, backend="jax")
        for i, (m_ref, v_ref) in enumerate(ref):
            assert np.allclose(mj[i], m_ref, atol=1e-9) and np.allclose(vj[i], v_ref, atol=1e-9)
        t = _best(lambda: plane.predict(pool, backend="jax"), repeats)
        rows.append({
            "name": f"plane_jax_{N_SOURCES}src_{POOL}pool", "us_per_call": t * 1e6,
            "derived": f"speedup {t_loop / t:.1f}x vs loop",
        })
        # the pallas kernel path is correctness-tested in interpret mode
        # (tests/test_surrogate_packed.py); Mosaic refuses the kernel on a
        # TPU v5e (docs/KERNELS.md), so the row runs on the CPU backend only
        if jax.default_backend() == "cpu" and os.environ.get("REPRO_BENCH_PALLAS") == "1":
            t = _best(lambda: plane.predict(pool, backend="pallas"), max(1, repeats // 10))
            rows.append({
                "name": f"plane_pallas_{N_SOURCES}src_{POOL}pool", "us_per_call": t * 1e6,
                "derived": f"speedup {t_loop / t:.1f}x vs loop ({jax.default_backend()})",
            })
    except ImportError:
        pass
    t_acq_old = _best(acq_legacy, repeats)
    t_acq = _best(acq_fused, repeats)
    rows.append({
        "name": f"acq_legacy_{N_SOURCES}src_{POOL}pool", "us_per_call": t_acq_old * 1e6,
        "derived": "per-tree loop + np.vectorize(erf) EI + sequential ranks",
    })
    rows.append({
        "name": f"acq_fused_{N_SOURCES}src_{POOL}pool", "us_per_call": t_acq * 1e6,
        "derived": f"score_sources + aggregate_ranks; speedup {t_acq_old / t_acq:.1f}x",
    })
    return rows


def run(force: bool = False):
    return cached("surrogate", force, _run)


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    for r in run(force=True):
        print(r)
