"""Batched Shapley/compression plane equivalence suite.

Asserts that (1) the batched masked-evaluation plane reproduces the legacy
per-chain loop bit-for-bit under shared permutation draws, across
dimensionalities, permutation counts (odd included) and background sizes,
(2) the batch explainer equals sequential per-config calls with a shared
rng, (3) the Monte-Carlo error bound against exact enumeration is retained
and additivity holds exactly, (4) the proportional residual correction
keeps surrogate-ignored knobs at phi == 0.0, (5) ``SpaceCompressor``
invalidates stale cached regions and reuses KDE fits across calls, (6) the
bitvector chain kernel (``model=`` opt-in) reproduces the loop bit-for-bit
and falls back to the generic path when a tree overflows its uint64 word,
and (7) MFTune incumbent trajectories are identical across Shapley
backends at a fixed seed.
"""

import numpy as np
import pytest

from repro.core import (
    ConfigSpace,
    FloatKnob,
    KnowledgeBase,
    Observation,
    SpaceCompressor,
    TaskRecord,
    draw_permutations,
    make_forest,
    shapley_values,
    shapley_values_batch,
    shapley_values_exact,
)
from repro.core.compression import extract_promising_regions
from repro.core.similarity import TaskWeights


def _poly(d, seed=0):
    w = np.random.default_rng(seed).normal(size=d)
    return lambda Z: (Z * w).sum(axis=1) + 2.0 * Z[:, 0] * Z[:, 1 % d]


def _forest_f(d, seed=0, n=48):
    rng = np.random.default_rng(seed)
    X = rng.random((n, d))
    y = 3 * X[:, 0] - X[:, 1 % d] ** 2 + 0.1 * rng.normal(size=n)
    m = make_forest(seed=seed).fit(X, y)
    return m.predict_mean


# ----------------------------------------------------------- backend identity


@pytest.mark.parametrize(
    "d,n_perm,nb",
    [(3, 4, 1), (6, 8, 12), (6, 1, 5), (9, 3, 16), (16, 32, 16), (24, 32, 16)],
)
def test_batched_matches_loop_bitwise(d, n_perm, nb):
    rng = np.random.default_rng(d + n_perm)
    x = rng.random(d)
    bg = rng.random((nb, d))
    for f in (_poly(d, seed=1), _forest_f(d, seed=2)):
        a = shapley_values(
            f, x, bg, n_permutations=n_perm, rng=np.random.default_rng(7), backend="loop"
        )
        b = shapley_values(
            f, x, bg, n_permutations=n_perm, rng=np.random.default_rng(7), backend="batched"
        )
        assert np.array_equal(a, b)


def test_batched_invariant_to_chunking():
    d, nb = 8, 6
    rng = np.random.default_rng(0)
    x, bg = rng.random(d), rng.random((nb, d))
    f = _forest_f(d, seed=3)
    perms = draw_permutations(d, 8, np.random.default_rng(1))
    full = shapley_values(f, x, bg, perms=perms, backend="batched")
    tiny = shapley_values(f, x, bg, perms=perms, backend="batched", max_eval_rows=1)
    assert np.array_equal(full, tiny)


def test_batch_matches_sequential_shared_rng():
    d, nb, n_cfg = 7, 10, 9
    rng = np.random.default_rng(3)
    X = rng.random((n_cfg, d))
    bg = rng.random((nb, d))
    f = _forest_f(d, seed=4)
    r = np.random.default_rng(11)
    seq = np.stack(
        [shapley_values(f, xi, bg, n_permutations=6, rng=r, backend="loop") for xi in X]
    )
    bat = shapley_values_batch(
        f, X, bg, n_permutations=6, rng=np.random.default_rng(11), backend="batched"
    )
    assert np.array_equal(seq, bat)
    # the loop backend of the batch explainer is the same pinned path
    lop = shapley_values_batch(
        f, X, bg, n_permutations=6, rng=np.random.default_rng(11), backend="loop"
    )
    assert np.array_equal(seq, lop)


def test_odd_permutation_count_runs_exactly_n_chains():
    d, nb = 5, 4
    rng = np.random.default_rng(0)
    x, bg = rng.random(d), rng.random((nb, d))
    calls = {"rows": 0}

    def f(Z):
        calls["rows"] += len(Z)
        return Z.sum(axis=1)

    shapley_values(f, x, bg, n_permutations=1, rng=np.random.default_rng(1), backend="loop")
    # 1 chain * (d+1) prefixes * nb rows, plus the two residual anchors
    assert calls["rows"] == (d + 1) * nb + 1 + nb
    assert len(draw_permutations(d, 3, np.random.default_rng(0))) == 3


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        shapley_values(lambda Z: Z.sum(1), np.zeros(3), np.zeros((2, 3)), backend="vmap")


# --------------------------------------------------------- estimator quality


def test_mc_matches_exact_batched():
    rng = np.random.default_rng(0)
    d = 4
    w = np.array([2.0, -1.0, 0.5, 0.0])
    f = lambda Z: Z @ w + 3 * Z[:, 0] * Z[:, 1]
    x = rng.random(d)
    bg = rng.random((12, d))
    exact = shapley_values_exact(f, x, bg)
    mc = shapley_values(
        f, x, bg, n_permutations=64, rng=np.random.default_rng(1), backend="batched"
    )
    assert np.abs(exact - mc).max() < 0.05
    assert abs(mc.sum() - (f(x[None])[0] - f(bg).mean())) < 1e-9


@pytest.mark.parametrize("seed", [0, 1, 17, 123])
def test_additivity_property_batched(seed):
    rng = np.random.default_rng(seed)
    d = 6
    A = rng.normal(size=(d, d)) / d
    f = lambda Z: np.einsum("ni,ij,nj->n", Z, A, Z)
    X = rng.random((3, d))
    bg = rng.random((8, d))
    phis = shapley_values_batch(f, X, bg, n_permutations=8, rng=rng, backend="batched")
    for i in range(len(X)):
        assert abs(phis[i].sum() - (f(X[i][None])[0] - f(bg).mean())) < 1e-9


def test_proportional_residual_keeps_ignored_knob_zero():
    """A knob the model ignores must keep phi == 0.0 exactly; the old
    uniform resid/d spread injected spurious attribution into it."""
    d = 6
    rng = np.random.default_rng(2)
    x, bg = rng.random(d), rng.random((9, d))
    f = lambda Z: 3.0 * Z[:, 0] + Z[:, 1] ** 2  # ignores knobs 2..5
    for backend in ("loop", "batched"):
        phi = shapley_values(
            f, x, bg, n_permutations=8, rng=np.random.default_rng(3), backend=backend
        )
        assert all(phi[j] == 0.0 for j in range(2, d))
        # additivity still exact after the proportional distribution
        assert abs(phi.sum() - (f(x[None])[0] - f(bg).mean())) < 1e-9


def test_uniform_fallback_on_all_zero_attribution():
    d = 4
    rng = np.random.default_rng(0)
    x, bg = rng.random(d), rng.random((5, d))
    f = lambda Z: np.full(len(Z), 2.5)  # constant model: every phi exactly 0
    phi = shapley_values(f, x, bg, n_permutations=4, rng=rng)
    assert np.all(np.isfinite(phi)) and np.array_equal(phi, np.zeros(d))


# ----------------------------------------------------- compression integration


def _space(d=6):
    return ConfigSpace([FloatKnob(f"x{i}", 0.0, 1.0) for i in range(d)])


def _record(task_id, space, f, n=40, seed=0):
    rng = np.random.default_rng(seed)
    rec = TaskRecord(task_id=task_id, queries=["q1"])
    for cfg in space.sample(rng, n):
        rec.observations.append(
            Observation(config=cfg, performance=f(cfg), fidelity=1.0)
        )
    return rec


def _space_sig(space):
    sig = []
    for k in space.knobs:
        iv = k.active_intervals() if hasattr(k, "active_intervals") else None
        sig.append((k.name, tuple(iv.intervals) if iv is not None else None))
    return tuple(sig)


def test_extract_identical_across_backends():
    space = _space(5)
    f = lambda c: (c["x0"] - 0.2) ** 2 + (c["x1"] - 0.7) ** 2 + 1.0
    task = _record("s0", space, f, n=40, seed=0)
    regions = [
        extract_promising_regions(space, task, 1.0, seed=3, backend=b)
        for b in ("loop", "batched")
    ]
    assert regions[0] is not None and regions[1] is not None
    assert regions[0].importance == regions[1].importance
    assert regions[0].values == regions[1].values


def test_compression_identical_across_backends():
    space = _space(6)
    f = lambda c: (c["x0"] - 0.1) ** 2 + (c["x1"] - 0.9) ** 2 + 1.0
    tasks = {f"s{i}": _record(f"s{i}", space, f, n=50, seed=i) for i in range(3)}
    weights = TaskWeights(
        weights={k: 1 / 3 for k in tasks}, similarities={}, used_meta=False
    )
    sigs = []
    for backend in ("loop", "batched"):
        comp = SpaceCompressor(space, alpha=0.65, seed=0, backend=backend)
        sigs.append(_space_sig(comp.compress(weights, tasks)))
    assert sigs[0] == sigs[1]


def test_stale_region_cache_invalidated():
    space = _space(4)
    f = lambda c: c["x0"] + 0.5
    comp = SpaceCompressor(space, alpha=0.65, seed=0)
    target = _record("tgt", space, f, n=8, seed=1)
    assert comp._region(target, 1.0) is not None
    assert "tgt" in comp._cache
    # the target briefly drops below 4 full-fidelity observations
    target.observations = target.observations[:3]
    assert comp._region(target, 1.0, refresh=True) is None
    assert "tgt" not in comp._cache  # stale entry must not survive
    assert comp._region(target, 1.0) is None  # and must not be served later


def test_range_cache_reused_across_compress_calls(monkeypatch):
    space = _space(6)
    f = lambda c: (c["x0"] - 0.1) ** 2 + (c["x1"] - 0.9) ** 2 + 1.0
    tasks = {f"s{i}": _record(f"s{i}", space, f, n=50, seed=i) for i in range(2)}
    weights = TaskWeights(
        weights={k: 0.5 for k in tasks}, similarities={}, used_meta=False
    )
    comp = SpaceCompressor(space, alpha=0.65, seed=0)
    fits = {"n": 0}
    import repro.core.compression as cmod

    real_kde = cmod.WeightedKDE

    def counting_kde(*a, **kw):
        fits["n"] += 1
        return real_kde(*a, **kw)

    monkeypatch.setattr(cmod, "WeightedKDE", counting_kde)
    s1 = comp.compress(weights, tasks)
    cold = fits["n"]
    assert cold > 0
    s2 = comp.compress(weights, tasks)  # unchanged weights: all cache hits
    assert fits["n"] == cold
    assert _space_sig(s1) == _space_sig(s2)


def test_extract_deterministic_and_decoupled_streams():
    space = _space(5)
    f = lambda c: (c["x0"] - 0.3) ** 2 + 1.0
    # > 16 observations so the background subsample path is exercised
    task = _record("s0", space, f, n=30, seed=5)
    r1 = extract_promising_regions(space, task, 1.0, seed=9)
    r2 = extract_promising_regions(space, task, 1.0, seed=9)
    assert r1 is not None and r1.values == r2.values and r1.importance == r2.importance


# ----------------------------------------------------- bitvector chain kernel


def _forest(d, seed=0, n=48):
    rng = np.random.default_rng(seed)
    X = rng.random((n, d))
    y = 3 * X[:, 0] - X[:, 1 % d] ** 2 + 0.1 * rng.normal(size=n)
    return make_forest(seed=seed).fit(X, y)


@pytest.mark.parametrize(
    "d,n_perm,nb",
    [(3, 4, 1), (6, 8, 12), (6, 1, 5), (9, 3, 16), (24, 32, 16)],
)
def test_chain_kernel_matches_loop_bitwise(d, n_perm, nb):
    m = _forest(d, seed=2)
    rng = np.random.default_rng(d + nb)
    x, bg = rng.random(d), rng.random((nb, d))
    a = shapley_values(
        m.predict_mean, x, bg, n_permutations=n_perm,
        rng=np.random.default_rng(7), backend="loop",
    )
    b = shapley_values(
        m.predict_mean, x, bg, n_permutations=n_perm,
        rng=np.random.default_rng(7), backend="batched", model=m,
    )
    assert np.array_equal(a, b)


def test_chain_kernel_batch_matches_sequential():
    d, nb, n_cfg = 8, 10, 7
    m = _forest(d, seed=5)
    rng = np.random.default_rng(1)
    X, bg = rng.random((n_cfg, d)), rng.random((nb, d))
    r = np.random.default_rng(11)
    seq = np.stack(
        [
            shapley_values(m.predict_mean, xi, bg, n_permutations=6, rng=r, backend="loop")
            for xi in X
        ]
    )
    bat = shapley_values_batch(
        m.predict_mean, X, bg, n_permutations=6,
        rng=np.random.default_rng(11), backend="batched", model=m,
    )
    assert np.array_equal(seq, bat)


def test_chain_kernel_invariant_to_chunking():
    d, nb = 7, 5
    m = _forest(d, seed=6)
    rng = np.random.default_rng(0)
    x, bg = rng.random(d), rng.random((nb, d))
    perms = draw_permutations(d, 8, np.random.default_rng(1))
    full = shapley_values(m.predict_mean, x, bg, perms=perms, backend="batched", model=m)
    tiny = shapley_values(
        m.predict_mean, x, bg, perms=perms, backend="batched", model=m, max_eval_rows=1
    )
    assert np.array_equal(full, tiny)


def test_chain_plan_cached_on_arena():
    from repro.kernels.forest_eval.chain import build_chain_plan

    m = _forest(6, seed=0)
    p1 = build_chain_plan(m, 6)
    p2 = build_chain_plan(m, 6)
    assert p1 is not None and p1 is p2


def test_chain_plan_fallback_on_large_trees():
    """Trees past 64 leaves don't fit a uint64 word: the plan builder must
    decline and the batched backend must fall back to the generic composite
    path — still bit-identical to the loop."""
    from repro.kernels.forest_eval.chain import build_chain_plan

    d = 6
    rng = np.random.default_rng(0)
    X = rng.random((600, d))
    y = rng.normal(size=600)  # pure noise: splits keep refining to depth 12
    m = make_forest(seed=0).fit(X, y)
    assert build_chain_plan(m, d) is None
    x, bg = rng.random(d), rng.random((8, d))
    a = shapley_values(
        m.predict_mean, x, bg, n_permutations=4,
        rng=np.random.default_rng(3), backend="loop",
    )
    b = shapley_values(
        m.predict_mean, x, bg, n_permutations=4,
        rng=np.random.default_rng(3), backend="batched", model=m,
    )
    assert np.array_equal(a, b)


def test_chain_plan_guards():
    from repro.kernels.forest_eval.chain import build_chain_plan

    m = _forest(5, seed=1)
    assert max(int(f) for f in m.pack().feat) >= 3
    assert build_chain_plan(m, 3) is None  # splits outside a 3-dim space
    assert build_chain_plan(object(), 5) is None  # not a packable forest
    # model= on a non-forest callable silently uses the generic path
    f = _poly(5, seed=2)
    rng = np.random.default_rng(4)
    x, bg = rng.random(5), rng.random((6, 5))
    a = shapley_values(f, x, bg, n_permutations=4, rng=np.random.default_rng(5), backend="loop")
    b = shapley_values(
        f, x, bg, n_permutations=4, rng=np.random.default_rng(5),
        backend="batched", model=object(),
    )
    assert np.array_equal(a, b)


def _wide_forest(d, seed=0, n=200):
    """A forest over ``d`` features whose signal lies past the 64th."""
    rng = np.random.default_rng(seed)
    X = rng.random((n, d))
    y = 3 * X[:, d - 1] - X[:, d // 2] ** 2 + X[:, 0] + 0.1 * rng.normal(size=n)
    return make_forest(seed=seed).fit(X, y)


@pytest.mark.parametrize("d", [65, 128, 200])
def test_chain_kernel_past_64_features(d):
    """The chain plan keeps per-feature tables and walks permutations by
    index, so it builds at any width and stays bit-identical to the loop."""
    from repro import obs
    from repro.kernels.forest_eval.chain import build_chain_plan_ex

    m = _wide_forest(d, seed=d)
    plan, reason = build_chain_plan_ex(m, d)
    assert plan is not None and reason == ""
    assert max(int(f) for f in m.pack().feat) >= 64  # splits past one word
    rng = np.random.default_rng(d + 1)
    X, bg = rng.random((3, d)), rng.random((6, d))
    perms = draw_permutations(d, 4, rng)
    loop = shapley_values_batch(m.predict_mean, X, bg, perms=perms, backend="loop")
    with obs.tracing() as tr:
        chained = shapley_values_batch(m.predict_mean, X, bg, perms=perms, model=m)
    assert np.array_equal(loop, chained)
    counts = tr.metrics.counters_view("shapley/")
    assert counts.get("chain_kernel", 0) == 1 and "composite_fallback" not in counts


# ------------------------------------------------- end-to-end backend identity


def _traj(shapley_backend):
    from repro.core import MFTune, MFTuneOptions
    from repro.sparksim import SparkWorkload, TaskSpec, generate_history
    from repro.tuneapi import Budget

    kb = KnowledgeBase()
    kb.add_task(
        generate_history(TaskSpec("tpch", 100, "A").workload(), n_obs=12, n_init=5, seed=3),
        persist=False,
    )
    wl = SparkWorkload("tpch", 600, "A")
    opts = MFTuneOptions(seed=0, shapley_backend=shapley_backend)
    res = MFTune(wl, kb, opts).run(Budget(6 * 3600.0))
    return [(p.time, p.best, tuple(sorted(p.config.items()))) for p in res.trajectory]


def test_mftune_trajectory_identical_across_shapley_backends():
    assert _traj("batched") == _traj("loop")
