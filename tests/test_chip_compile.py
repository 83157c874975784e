"""The fused propose programs compile for a TPU v5e, without the chip.

Each test lowers one jitted program of the tuner's device path for a
described (not attached) v5e chip and compiles it with the TPU compiler,
at the 60-knob ``spark_space`` shapes with 12 sources x 10 trees and the
default pool bucket. That catches what the CPU backend cannot: XLA:TPU
refuses f64 -> integer bitcasts, and some formulations compile for minutes
on it. Nothing runs; results and times need the chip (``chip_smoke.py``).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import ProposeEngine, make_forest  # noqa: E402
from repro.kernels.forest_eval import propose as P  # noqa: E402
from repro.sparksim import spark_space  # noqa: E402

N_SOURCES, N_POOL = 12, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but never read back without it
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def engine_inputs():
    space = spark_space()
    rng = np.random.default_rng(0)
    models = [make_forest(seed=s).fit(rng.random((64, space.dim)),
                                      rng.random(64) * 10 + s)
              for s in range(N_SOURCES)]
    eng = ProposeEngine(space)
    with jax.enable_x64(True):
        plane = eng._plane(models)
        arena, ystats, _, _ = eng._arena_for(plane)
        arena_k, _, qs_k, _ = eng._arena_for(plane, keyed=True)
        tabs = eng._tables_for(space)
    return plane, arena, ystats, tabs, arena_k, qs_k


def _spec(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(lowered):
    """Compile for the chip; no f64 -> integer bitcast may reach it."""
    bad = [ln for ln in lowered.as_text().splitlines()
           if "bitcast_convert" in ln and "f64" in ln.split("->")[0]]
    assert not bad, bad[:3]
    compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30
    return compiled


def test_ei_compiles_for_v5e(one_chip):
    with jax.enable_x64(True):
        sn = jax.ShapeDtypeStruct((N_SOURCES, N_POOL), jnp.float64,
                                  sharding=one_chip)
        best = jax.ShapeDtypeStruct((N_SOURCES, 1), jnp.float64,
                                    sharding=one_chip)
        zi = jax.ShapeDtypeStruct((), jnp.uint64, sharding=one_chip)
        _compile(P._ei_pad_jit.lower(sn, sn, best, zi))


def test_rank_sort_compiles_for_v5e(one_chip):
    with jax.enable_x64(True):
        sn = jax.ShapeDtypeStruct((N_SOURCES, N_POOL), jnp.float64,
                                  sharding=one_chip)
        w = jax.ShapeDtypeStruct((N_SOURCES,), jnp.float64, sharding=one_chip)
        zi = jax.ShapeDtypeStruct((), jnp.uint64, sharding=one_chip)
        _compile(P._ranks_pad_jit.lower(sn, w, zi, n_sources=N_SOURCES,
                                        rank_impl="sort"))


def test_device_pool_propose_compiles_for_v5e(one_chip, engine_inputs):
    plane, arena, ystats, tabs, _, _ = engine_inputs
    with jax.enable_x64(True):
        s = jax.ShapeDtypeStruct((N_SOURCES,), jnp.float64, sharding=one_chip)
        lowered = P._propose_jit.lower(
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
            _spec(tabs, one_chip), None, _spec(arena, one_chip), None,
            _spec(ystats, one_chip), s, s,
            jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.uint64, sharding=one_chip),
            n_pool=N_POOL, depth=plane.depth, n_sources=N_SOURCES,
            tps=plane.uniform_tree_count, k=128, rank_impl="sort",
            descent="jax",
        )
        _compile(lowered)


def test_host_pool_qs_propose_compiles_for_v5e(one_chip, engine_inputs):
    # score_topk's program: an uploaded pool of uint64 order keys routed
    # through the keyed QuickScorer tables
    plane, _, ystats, _, arena_k, qs_k = engine_inputs
    assert qs_k is not None
    with jax.enable_x64(True):
        s = jax.ShapeDtypeStruct((N_SOURCES,), jnp.float64, sharding=one_chip)
        lowered = P._propose_jit.lower(
            None, None,
            jax.ShapeDtypeStruct((N_POOL, spark_space().dim),
                                 jnp.uint64, sharding=one_chip),
            _spec(arena_k, one_chip), _spec(qs_k, one_chip),
            _spec(ystats, one_chip), s, s,
            jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.uint64, sharding=one_chip),
            n_pool=N_POOL, depth=plane.depth, n_sources=N_SOURCES,
            tps=plane.uniform_tree_count, k=64, rank_impl="sort",
            descent="qs",
        )
        _compile(lowered)
