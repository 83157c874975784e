"""The device path's off-CPU formulations, checked on the CPU.

A program lowered for a TPU sorts by split float32 keys
(``rank.split_keys_argsort``) and draws pools through padded, all-columns
tables (``propose.pack_draw_tables`` / ``propose._unit_cols``). Both are
called directly here, on data where the CPU's FTZ/DAZ cannot interfere,
and pinned to the numpy references they replace. Host pools and the
surrogate's device descent route leaves on order keys
(``rank.monotone_keys``, ``rank.keys_from_bits``), checked here against
float64 compares.
"""

import numpy as np
import pytest

from repro.core import BoolKnob, CatKnob, ConfigSpace, FloatKnob, IntKnob, Intervals
from repro.kernels.forest_eval import rank as R

jax = pytest.importorskip("jax")


def _space():
    return ConfigSpace([
        FloatKnob("f1", 0.1, 10.0, log=True),
        FloatKnob("f2", -5.0, 5.0),
        IntKnob("i1", 1, 64, log=True),
        IntKnob("i2", 0, 9),
        CatKnob("c1", ["a", "b", "c"]),
        BoolKnob("b1"),
    ])


@pytest.mark.parametrize("descending", [False, True])
def test_split_keys_argsort_matches_stable_argsort(descending):
    # the off-CPU key (three float32 pieces, uint32 LSD passes) is exact
    # for finite values in float32's normal range; near ties one ulp of
    # float64 apart, ±0, ±inf and tie clusters must keep numpy's order
    rng = np.random.default_rng(21 + descending)
    for _ in range(4):
        s, n = int(rng.integers(1, 5)), int(rng.integers(2, 700))
        x = rng.standard_normal((s, n)) * 10.0 ** float(rng.integers(-20, 20))
        x[rng.random(x.shape) < 0.2] = 0.25
        x[rng.random(x.shape) < 0.05] = -0.0
        x[rng.random(x.shape) < 0.05] = 0.0
        x[rng.random(x.shape) < 0.03] = -np.inf
        x[rng.random(x.shape) < 0.03] = np.inf
        near = rng.random(x.shape) < 0.1
        x[near] = 1.0 + rng.integers(0, 4, size=int(near.sum())) * 2.0**-52
        want = np.argsort(-x if descending else x, axis=-1, kind="stable")
        with jax.enable_x64(True):
            got = np.asarray(jax.jit(
                lambda v: R.split_keys_argsort(v, descending)
            )(jax.numpy.asarray(x)))
            got_1d = np.asarray(jax.jit(
                lambda v: R.split_keys_argsort(v, descending)
            )(jax.numpy.asarray(x[0])))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got_1d, want[0])


@pytest.mark.parametrize("restricted", [False, True])
def test_device_unit_cols_match_host_quantile(restricted):
    # the padded, all-columns-at-once device transform replays the host's
    # per-column quantile map + clipped unit encode
    from repro.kernels.forest_eval import propose as P

    space = _space()
    if restricted:
        space = space.restrict(
            keep=["f1", "f2", "i1", "c1", "b1"],
            ranges={"f1": Intervals([(0.5, 1.0), (4.0, 8.0)]),
                    "i1": Intervals([(2, 2), (16, 32)])},
            cat_subsets={"c1": ["a", "c"]},
        )
    plane = space.plane()
    U = np.random.default_rng(3).random((512, space.dim))
    U[:4] = [[0.0], [0.5], [0.999999], [1.0 - 2.0**-53]]
    want = np.stack([
        np.clip(plane._to_unit_col(j, plane._quantile_col(j, U[:, j])), 0, 1)
        for j in range(space.dim)
    ], axis=1)
    tabs = P.pack_draw_tables(*plane.device_tables())
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(P._unit_cols)(
            {k: jax.numpy.asarray(v) for k, v in tabs.items()},
            jax.numpy.asarray(U)))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)


def _f32_pair(x):
    """float64 as XLA:TPU holds it: a float32 high part and a float32 low
    part, about 48 significant bits."""
    hi = x.astype(np.float32).astype(np.float64)
    return hi + (x - hi).astype(np.float32).astype(np.float64)


@pytest.mark.parametrize("ulps", [1, 2, 8])
def test_order_keys_route_like_binary64(ulps):
    # descent compares candidates with split thresholds; values a few ulps
    # apart (a grid value and a midpoint threshold) compare as binary64
    # does through the keys, and not after rounding to an f32 pair
    rng = np.random.default_rng(ulps)
    t = np.concatenate([rng.random(2000), [0.0, -0.0, 1.0, np.inf]])
    x = t.copy()
    for _ in range(ulps):
        x = np.nextafter(x, np.where(rng.random(t.shape) < 0.5, -2.0, 2.0))
    x[-4:] = [-0.0, 0.0, 1.0, 0.5]
    want = x > t
    kx = R.monotone_keys(x, descending=False)
    kt = R.monotone_keys(t, descending=False)
    np.testing.assert_array_equal(kx > kt, want)
    np.testing.assert_array_equal(np.argsort(kx, kind="stable"),
                                  np.argsort(x, kind="stable"))
    # the program keys an uploaded pool from its bit patterns
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(lambda b: R.keys_from_bits(b, False))(
            jax.numpy.asarray(x.view(np.uint64))))
    np.testing.assert_array_equal(got, kx)
    assert not np.array_equal(_f32_pair(x[:-4]) > _f32_pair(t[:-4]), want[:-4])
