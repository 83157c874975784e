"""Chain-delta pool scoring: bit-identity with descent, and generator wiring.

``ForestPlane.predict(..., delta=(bases_unit, base_of))`` may factor a
mutation-heavy pool through the bitvector chain plan (shared-coordinate AND
once per base, re-AND only mutated coordinates per candidate). The contract
is bit-identity with the packed gather descent, and that turning the path on
never changes what the generator recommends.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro import obs
from repro.core.space import ConfigBatch, ConfigSpace, FloatKnob, IntKnob
from repro.core.surrogate import ForestPlane, ProbabilisticRandomForest


def _plane(d: int = 6, seed: int = 11, n_trees: int = 4, depth: int = 5):
    rng = np.random.default_rng(seed)
    Xf = rng.random((80, d))
    models = [
        ProbabilisticRandomForest(
            n_trees=n_trees, max_depth=depth, seed=s, backend="numpy"
        ).fit(Xf, rng.random(80))
        for s in range(3)
    ]
    return ForestPlane([m.pack() for m in models]), rng


def _mutation_pool(rng, d: int, n_free: int = 10, n_mut: int = 30, n_bases: int = 4):
    bases = rng.random((n_bases, d))
    N = n_free + n_mut
    base_of = np.concatenate(
        [np.full(n_free, -1), rng.integers(0, n_bases, n_mut)]
    )
    X = np.empty((N, d))
    for i in range(N):
        if base_of[i] < 0:
            X[i] = rng.random(d)
        else:
            X[i] = bases[base_of[i]]
            nm = rng.integers(1, d)
            cols = rng.choice(d, size=nm, replace=False)
            X[i, cols] = rng.random(nm)
    return X, bases, base_of


def test_delta_predict_bit_identical():
    plane, rng = _plane()
    X, bases, base_of = _mutation_pool(rng, d=6)
    m0, v0 = plane.predict(X, backend="numpy")
    m1, v1 = plane.predict(X, backend="numpy", delta=(bases, base_of))
    np.testing.assert_array_equal(m0, m1)
    np.testing.assert_array_equal(v0, v1)


@pytest.mark.parametrize("d", [65, 128, 200])
def test_delta_predict_past_64_features(d):
    """The pool plan builds at any width and its delta scoring equals the
    plain descent bit for bit."""
    from repro.kernels.forest_eval.chain import build_pool_plan_ex

    plane, rng = _plane(d=d, seed=d, depth=8)
    plan, reason = build_pool_plan_ex(plane, d)
    assert plan is not None and reason == ""
    assert max(int(f) for f in plane.feat) >= 64
    X, bases, base_of = _mutation_pool(rng, d=d)
    m0, v0 = plane.predict(X, backend="numpy")
    with obs.tracing() as tr:
        m1, v1 = plane.predict(X, backend="numpy", delta=(bases, base_of))
    assert tr.metrics.counters_view("forest_plane/").get("chain_delta", 0) == 1
    np.testing.assert_array_equal(m0, m1)
    np.testing.assert_array_equal(v0, v1)


def test_delta_predict_degenerate_pools():
    plane, rng = _plane(seed=12)
    X, bases, base_of = _mutation_pool(rng, d=6)
    N = len(X)
    m0, v0 = plane.predict(X, backend="numpy")
    # all-free: every row scored through the plain chain walk
    m2, v2 = plane.predict(X, backend="numpy", delta=(bases, np.full(N, -1)))
    np.testing.assert_array_equal(m0, m2)
    np.testing.assert_array_equal(v0, v2)
    # all-based on one base, every coordinate mutated: pure re-AND path
    Xall = rng.random((N, X.shape[1]))
    ma, va = plane.predict(Xall, backend="numpy")
    mb, vb = plane.predict(
        Xall, backend="numpy", delta=(bases, np.zeros(N, dtype=np.int64))
    )
    np.testing.assert_array_equal(ma, mb)
    np.testing.assert_array_equal(va, vb)


def test_delta_dispatch_counter():
    plane, rng = _plane(seed=13)
    X, bases, base_of = _mutation_pool(rng, d=6)
    with obs.tracing() as tr:
        plane.predict(X, backend="numpy", delta=(bases, base_of))
        plane.predict(X, backend="numpy")
    view = tr.metrics.counters_view("forest_plane/")
    assert view.get("chain_delta", 0) == 1
    assert view.get("numpy", 0) == 1


def _space(d: int = 5):
    knobs = [FloatKnob(f"f{i}", 0.0, 1.0) for i in range(d - 1)]
    knobs.append(IntKnob("i0", 1, 32))
    return ConfigSpace(knobs)


def test_candidate_pool_sets_delta_provenance():
    from repro.core.generator import CandidateGenerator

    space = _space()
    gen = CandidateGenerator(space, seed=7, pool_size=64)
    incs = [space.sample(np.random.default_rng(3), 1)[0] for _ in range(3)]
    pool = gen._candidate_pool(incs)
    delta = pool.delta
    assert delta is not None
    bases, base_of = delta
    assert base_of.shape == (len(pool),)
    n_mut = int((base_of >= 0).sum())
    assert n_mut > 0 and np.all(base_of[: len(pool) - n_mut] == -1)
    assert bases.shape[0] >= int(base_of.max()) + 1
    # every based row differs from its base only where a mutation landed;
    # at least the shared coordinates must match the base row exactly.
    U = pool.unit()
    for i in np.flatnonzero(base_of >= 0)[:8]:
        shared = U[i] == bases[base_of[i]]
        assert shared.any()  # gate p<1 keeps some coords untouched w.h.p.

    # a pool with no incumbents carries no delta
    assert gen._candidate_pool([]).delta is None


def test_recommend_unchanged_by_delta_path(monkeypatch):
    import repro.core.generator as GEN
    from repro.core.generator import CandidateGenerator, SurrogateSource

    space = _space()
    rng = np.random.default_rng(2)
    X = space.sample(rng, 30).unit()
    models = [
        ProbabilisticRandomForest(n_trees=3, max_depth=4, seed=s).fit(
            X, rng.random(30)
        )
        for s in range(2)
    ]
    srcs = [
        SurrogateSource(name=f"s{i}", model=m, weight=0.5, incumbent=0.4)
        for i, m in enumerate(models)
    ]
    incs = [space.sample(np.random.default_rng(9), 1)[0] for _ in range(3)]

    got_delta = CandidateGenerator(space, seed=5, pool_size=64).recommend(
        4, srcs, incumbents=incs
    )

    orig = GEN.score_sources
    monkeypatch.setattr(
        GEN,
        "score_sources",
        lambda models, X, incs, delta=None: orig(models, X, incs, delta=None),
    )
    got_plain = CandidateGenerator(space, seed=5, pool_size=64).recommend(
        4, srcs, incumbents=incs
    )
    assert got_delta == got_plain
