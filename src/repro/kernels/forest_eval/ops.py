"""Backend dispatch for packed-forest evaluation.

``forest_eval`` evaluates a packed node arena (see
``repro.core.surrogate.PackedForest``) over a candidate matrix and returns
per-tree leaf stats, shape (n_trees, n_points) each. Backends:

  numpy   — the core level-synchronous descent (always available)
  jax     — jitted jnp reference (``ref.forest_eval_ref``)
  pallas  — candidate-blocked gather kernel (``kernel.forest_eval_pallas``)
  auto    — jax when importable, else numpy

The jax/pallas paths run under a scoped ``enable_x64`` and compare
candidates with thresholds as monotone uint64 order keys of their float64
values (``rank.monotone_keys``), made on the host: leaf routing, and
therefore (mean, var), is bit-identical to the numpy plane on any device,
including XLA:TPU, whose float64 is a pair of float32 that cannot hold
every binary64 value. Arena sizes change on every refit, so
node/root arrays are padded to power-of-two buckets (padding nodes are
self-loop leaves) and the descent depth to a multiple of 4, keeping the
jit cache small across Hyperband rungs.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Tuple

import numpy as np

try:
    import jax

    _HAS_JAX = True
except Exception:  # pragma: no cover - jax is baked into this image
    _HAS_JAX = False

__all__ = ["forest_eval", "forest_plane_eval", "available_backends"]

# Padded device-resident arenas, keyed by the identity of the arena's feat
# array (arenas are immutable once packed, so identity is a sound key; the
# stored reference also guards against id() reuse after gc). Bounded LRU —
# forests refit every rung, so stale arenas age out.
_DEVICE_CACHE: "OrderedDict[int, tuple]" = OrderedDict()
_DEVICE_CACHE_MAX = 32


def available_backends() -> Tuple[str, ...]:
    return ("numpy", "jax", "pallas") if _HAS_JAX else ("numpy",)


def _pad_pow2(n: int) -> int:
    return 1 << max(3, int(n - 1).bit_length())


def pad_nodes(feat, thr, child, mean, var):
    """Pad the node arrays to a power-of-two bucket with unreachable
    self-loop leaves, so a refit recompiles only on a size-class change."""
    n = len(feat)
    n_pad = _pad_pow2(n)
    if n_pad != n:
        extra = n_pad - n
        self_idx = np.arange(n, n_pad, dtype=feat.dtype)
        feat = np.concatenate([feat, np.zeros(extra, feat.dtype)])
        thr = np.concatenate([thr, np.full(extra, np.inf)])
        child = np.concatenate([child, np.stack([self_idx, self_idx], axis=1).reshape(-1)])
        mean = np.concatenate([mean, np.zeros(extra)])
        var = np.concatenate([var, np.zeros(extra)])
    return feat, thr, child, mean, var


def depth_bucket(depth: int) -> int:
    """Descent depth rounded up to a multiple of 4 (leaves self-loop, so
    extra rounds are no-ops)."""
    return -(-max(depth, 1) // 4) * 4


def _pad_arena(feat, thr, child, mean, var, roots, depth):
    """Bucket the arena so recompiles only happen on size-class changes."""
    feat, thr, child, mean, var = pad_nodes(feat, thr, child, mean, var)
    t = len(roots)
    t_pad = _pad_pow2(t)
    if t_pad != t:
        roots = np.concatenate([roots, np.full(t_pad - t, roots[0], roots.dtype)])
    return feat, thr, child, mean, var, roots, depth_bucket(depth)


def _pad_pool(X):
    """Bucket the candidate axis too — recommend() dedups its pool, so N
    drifts call-to-call and would otherwise recompile the jitted descent."""
    n = X.shape[0]
    n_pad = _pad_pow2(n)
    if n_pad != n:
        X = np.concatenate([X, np.zeros((n_pad - n, X.shape[1]))])
    return X, n


def _keys(a):
    from .rank import monotone_keys

    return monotone_keys(a, descending=False)


def _device_arena(feat, thr, child, mean, var, roots, depth):
    """Pad and upload an arena once (thresholds as order keys, see
    :func:`_keys`); reuse device buffers across predicts."""
    import jax.numpy as jnp

    key = id(feat)
    entry = _DEVICE_CACHE.get(key)
    if entry is not None and entry[0] is feat:
        _DEVICE_CACHE.move_to_end(key)
        return entry[1], entry[2]
    padded = _pad_arena(feat, thr, child, mean, var, roots, depth)
    dev = (
        jnp.asarray(padded[0], jnp.int64),
        jnp.asarray(_keys(padded[1])),
        jnp.asarray(padded[2], jnp.int64),
        jnp.asarray(padded[3], jnp.float64),
        jnp.asarray(padded[4], jnp.float64),
        jnp.asarray(padded[5], jnp.int64),
    )
    _DEVICE_CACHE[key] = (feat, dev, padded[6])
    while len(_DEVICE_CACHE) > _DEVICE_CACHE_MAX:
        _DEVICE_CACHE.popitem(last=False)
    return dev, padded[6]


def forest_eval(feat, thr, child, mean, var, roots, X, depth,
                backend: str = "auto", block_n: int = 128,
                chunk_n: int = None) -> Tuple[np.ndarray, np.ndarray]:
    """Per-tree (mean, var) over the packed arena, each (n_trees, n_points).

    ``chunk_n`` bounds the candidate rows handled per backend dispatch:
    oversized pools (the batched Shapley plane builds hundreds of thousands
    of composite rows) are split into row blocks and the results
    concatenated. Per-point descent is independent, so chunking never
    changes a result; on the jax path it also pins the pool-padding bucket
    to one size class instead of jitting a fresh giant bucket per call.
    """
    if backend == "auto":
        backend = "jax" if _HAS_JAX else "numpy"
    X = np.atleast_2d(np.asarray(X, dtype=float))
    if chunk_n is not None and X.shape[0] > chunk_n:
        parts = [
            forest_eval(feat, thr, child, mean, var, roots, X[a:a + chunk_n],
                        depth, backend=backend, block_n=block_n)
            for a in range(0, X.shape[0], chunk_n)
        ]
        return (np.concatenate([p[0] for p in parts], axis=1),
                np.concatenate([p[1] for p in parts], axis=1))
    if backend == "numpy":
        from ...core.surrogate import packed_descend

        nid = packed_descend(feat, thr, child, roots, X, depth)
        return np.take(mean, nid), np.take(var, nid)
    if not _HAS_JAX:
        raise RuntimeError(f"backend {backend!r} requires jax; use 'numpy'")
    if backend not in ("jax", "pallas"):
        raise ValueError(f"unknown forest_eval backend {backend!r}")
    T = len(roots)
    X, n = _pad_pool(X)
    with jax.enable_x64(True):
        import jax.numpy as jnp

        dev, depth = _device_arena(feat, thr, child, mean, var, roots, depth)
        Xd = jnp.asarray(_keys(X))
        if backend == "jax":
            from .ref import forest_eval_ref

            m_t, v_t = forest_eval_ref(*dev, Xd, depth)
        else:
            from .kernel import forest_eval_pallas

            m_t, v_t = forest_eval_pallas(*dev, Xd, depth, block_n=block_n)
        return np.asarray(m_t)[:T, :n], np.asarray(v_t)[:T, :n]


def forest_plane_eval(feat, thr, child, mean, var, roots, X, depth,
                      y_means, y_stds, trees_per_source: int) -> Tuple[np.ndarray, np.ndarray]:
    """Fully fused multi-source evaluation on the jax backend.

    Descent *and* the per-source ensemble combine (law of total variance +
    denormalization) run on device; only (S, N) results are transferred.
    Requires a uniform tree count per source; raises RuntimeError without
    jax (callers check :func:`available_backends` first).
    """
    if not _HAS_JAX:
        raise RuntimeError("forest_plane_eval requires jax; use the numpy plane")
    n_sources = len(roots) // trees_per_source
    X = np.atleast_2d(np.asarray(X, dtype=float))
    X, n = _pad_pool(X)
    with jax.enable_x64(True):
        import jax.numpy as jnp

        from .ref import forest_plane_eval_ref

        dev, depth = _device_arena(feat, thr, child, mean, var, roots, depth)
        means, vars_ = forest_plane_eval_ref(
            *dev,
            jnp.asarray(_keys(X)),
            jnp.asarray(y_means, jnp.float64),
            jnp.asarray(y_stds, jnp.float64),
            depth,
            n_sources,
            trees_per_source,
        )
        return np.asarray(means)[:, :n], np.asarray(vars_)[:, :n]
