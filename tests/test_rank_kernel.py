"""Rank-kernel identity tests: radix rank == pinned stable argsort, exactly.

The rank aggregation contract is that every fast path (numpy radix, jitted
callback, fused lax.sort, pallas histogram kernel) produces the *same
permutation* ``np.argsort(-scores, kind="stable")`` would — including on the
IEEE-754 edge cases that break float-domain key remaps under FTZ/DAZ:
signed zeros, subnormals, infinities, and fully tied rows.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.kernels.forest_eval import rank as R

jax = pytest.importorskip("jax")


SPECIALS = np.array(
    [
        0.0,
        -0.0,
        5e-324,          # smallest positive subnormal
        -5e-324,
        1e-310,          # mid-range subnormal
        -1e-310,
        np.finfo(np.float64).tiny,      # smallest normal
        -np.finfo(np.float64).tiny,
        np.inf,
        -np.inf,
        np.finfo(np.float64).max,
        -np.finfo(np.float64).max,
        1.0,
        -1.0,
        3.5,
        -3.5,
    ],
    dtype=np.float64,
)


def _special_rows(seed: int = 0, n_rows: int = 6, n: int = 64) -> np.ndarray:
    rng = np.random.default_rng(seed)
    rows = SPECIALS[rng.integers(0, len(SPECIALS), size=(n_rows, n))]
    # splice in ordinary values so ties and specials interleave
    mask = rng.random((n_rows, n)) < 0.5
    rows = np.where(mask, rng.standard_normal((n_rows, n)), rows)
    return np.ascontiguousarray(rows)


def test_monotone_keys_total_order_on_specials():
    # keys are *descending*-order: larger score -> smaller u64 key, so an
    # ascending stable key sort yields the best-first rank permutation.
    v = np.sort(SPECIALS)  # ascending float order (±0 adjacent, order tied)
    k = R.monotone_keys(v[None, :])[0]
    assert np.all(np.diff(k.astype(object)) <= 0)
    # both zeros map to the same key — a genuine tie, resolved stably
    z = R.monotone_keys(np.array([[0.0, -0.0]]))[0]
    assert z[0] == z[1]


def test_radix_argsort_matches_stable_argsort_specials():
    scores = _special_rows(seed=1)
    for row in scores:
        want = np.argsort(-row, kind="stable")
        got = R.radix_argsort(row[None, :])[0]
        np.testing.assert_array_equal(got, want)


def test_rank_rows_radix_matches_reference():
    for seed in range(3):
        scores = _special_rows(seed=seed, n_rows=4, n=97)
        np.testing.assert_array_equal(
            R.rank_rows_radix(scores), R.rank_rows_reference(scores)
        )


def test_rank_rows_all_tied():
    scores = np.zeros((3, 33))
    out = R.rank_rows(scores)
    # every element keeps its original position's rank (stable on full tie)
    want = np.broadcast_to(np.arange(33, dtype=np.float64), (3, 33))
    np.testing.assert_array_equal(out, want)


def test_rank_rows_dispatch_crossover():
    # below RADIX_MIN_N the argsort path runs; above, the radix path — both
    # must agree with the pinned reference regardless.
    small = _special_rows(seed=2, n_rows=2, n=R.RADIX_MIN_N // 4)
    big = _special_rows(seed=3, n_rows=2, n=R.RADIX_MIN_N + 7)
    for scores in (small, big):
        np.testing.assert_array_equal(
            R.rank_rows(scores), R.rank_rows_reference(scores)
        )


@pytest.mark.parametrize("impl", R.RANK_IMPLS)
def test_rank_rows_traced_identity(impl):
    scores = _special_rows(seed=4, n_rows=3, n=129)
    want = R.rank_rows_reference(scores)
    with jax.enable_x64(True):
        got = np.asarray(R.rank_rows_traced(jax.numpy.asarray(scores), impl))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("impl", R.RANK_IMPLS)
def test_rank_rows_traced_random_property(impl):
    rng = np.random.default_rng(11)
    for _ in range(3):
        n = int(rng.integers(5, 400))
        s = int(rng.integers(1, 6))
        scores = rng.standard_normal((s, n))
        # force tie clusters
        scores[rng.random((s, n)) < 0.3] = 0.25
        want = R.rank_rows_reference(scores)
        with jax.enable_x64(True):
            got = np.asarray(R.rank_rows_traced(jax.numpy.asarray(scores), impl))
        np.testing.assert_array_equal(got, want)


def test_aggregate_ranks_host_impl_agreement():
    from repro.kernels.forest_eval import propose as P

    scores = _special_rows(seed=5, n_rows=3, n=257)
    w = np.array([0.5, 0.3, 0.2])
    ref = None
    for impl in ("sort", "callback"):
        agg = P.aggregate_ranks_host(scores, w, rank_impl=impl)
        if ref is None:
            ref = agg
        else:
            np.testing.assert_array_equal(agg, ref)
    # and against the pure-numpy aggregation
    ranks = R.rank_rows(scores)
    np.testing.assert_array_equal(ref, (w[:, None] * ranks).sum(axis=0))
