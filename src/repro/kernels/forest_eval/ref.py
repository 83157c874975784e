"""jax oracle for the packed-forest gather descent.

Same node encoding as ``repro.core.surrogate.packed_descend``: leaves have
``thr = +inf`` and self-loop children, so the descent needs no active-lane
masking — every lane converges to its leaf and then spins in place. It only
compares candidates with thresholds, so it runs on whatever ordered dtype
the inputs carry; the ops dispatcher feeds it uint64 order keys of the
float64 values (x64-scoped) so leaf routing is bit-identical to the numpy
plane on every device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["forest_eval_ref", "forest_plane_eval_ref"]


def _descend(feat, thr, child, roots, X, depth):
    T = roots.shape[0]
    N, D = X.shape
    xflat = X.reshape(-1)
    col = jnp.arange(N, dtype=roots.dtype) * D
    nid = jnp.broadcast_to(roots[:, None], (T, N))

    def body(_, nid):
        f = feat[nid]
        xv = xflat[col[None, :] + f]
        go_right = (xv > thr[nid]).astype(nid.dtype)
        return child[2 * nid + go_right]

    return jax.lax.fori_loop(0, depth, body, nid)


@functools.partial(jax.jit, static_argnames=("depth",))
def forest_eval_ref(feat, thr, child, mean, var, roots, X, depth):
    """Per-tree leaf stats for a packed arena: returns (mean, var), each (T, N)."""
    nid = _descend(feat, thr, child, roots, X, depth)
    return mean[nid], var[nid]


@functools.partial(jax.jit, static_argnames=("depth", "n_sources", "trees_per_source"))
def forest_plane_eval_ref(feat, thr, child, mean, var, roots, X, y_mean, y_std,
                          depth, n_sources, trees_per_source):
    """Descent + per-source ensemble combine fused on device.

    For a plane whose forests all hold ``trees_per_source`` trees: returns
    denormalized (means, vars), each (n_sources, N) — only the combined
    stats cross back to the host, not the per-tree matrices.
    """
    nid = _descend(feat, thr, child, roots, X, depth)
    T = n_sources * trees_per_source
    m_t = mean[nid[:T]].reshape(n_sources, trees_per_source, -1)
    v_t = var[nid[:T]].reshape(n_sources, trees_per_source, -1)
    mean_s = m_t.mean(axis=1)
    var_s = jnp.maximum(v_t.mean(axis=1) + m_t.var(axis=1), 1e-10)
    return (
        mean_s * y_std[:, None] + y_mean[:, None],
        var_s * y_std[:, None] ** 2,
    )
