"""Pallas gather-descent kernel over a packed forest arena.

Candidate-blocked: each program instance descends *all* trees for a
(block_n)-wide slab of the candidate pool, keeping the whole node arena
(feature / threshold / interleaved-children / leaf stats) resident in VMEM —
the arena is O(10^3-10^4) nodes, far under the VMEM budget, while the
candidate axis is the one that scales with pool size. The descent itself is
``depth`` rounds of four gathers (feature, x-value, threshold, child); leaf
self-loops make the loop body branch-free.

Both kernels run interpreted on the CPU backend and through Mosaic on any
other (:func:`interpret_mode`); there is no silent interpreter fallback on
a TPU. Mosaic refuses both on a TPU v5e today, so there they raise their
compile error: ``forest_eval_pallas`` with "Only 2D gather is supported",
``chain_ordinals_pallas`` because its ``(1, d)`` permutation block breaks
the (8, 128) tiling rule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["interpret_mode", "forest_eval_pallas", "chain_ordinals_pallas"]


def interpret_mode() -> bool:
    """Pallas interpret mode on the CPU backend, compiled kernels elsewhere."""
    return jax.default_backend() == "cpu"


def _forest_kernel(feat_ref, thr_ref, child_ref, mean_ref, var_ref, roots_ref,
                   x_ref, m_ref, v_ref, *, depth):
    feat = feat_ref[...]
    thr = thr_ref[...]
    child = child_ref[...]
    roots = roots_ref[...]
    X = x_ref[...]
    T = roots.shape[0]
    Nb, D = X.shape
    xflat = X.reshape(-1)
    col = jax.lax.broadcasted_iota(roots.dtype, (1, Nb), 1) * D
    nid = jnp.broadcast_to(roots[:, None], (T, Nb))

    def body(_, nid):
        f = feat[nid]
        xv = xflat[col + f]
        go_right = (xv > thr[nid]).astype(nid.dtype)
        return child[2 * nid + go_right]

    nid = jax.lax.fori_loop(0, depth, body, nid)
    m_ref[...] = mean_ref[...][nid]
    v_ref[...] = var_ref[...][nid]


def forest_eval_pallas(feat, thr, child, mean, var, roots, X, depth,
                       block_n: int = 128):
    """Per-tree leaf stats via the Pallas descent: (mean, var), each (T, N)."""
    T = roots.shape[0]
    N, D = X.shape
    n_nodes = feat.shape[0]
    block_n = min(block_n, N)
    while N % block_n:
        block_n //= 2
    return pl.pallas_call(
        functools.partial(_forest_kernel, depth=depth),
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((n_nodes,), lambda i: (0,)),
            pl.BlockSpec((n_nodes,), lambda i: (0,)),
            pl.BlockSpec((2 * n_nodes,), lambda i: (0,)),
            pl.BlockSpec((n_nodes,), lambda i: (0,)),
            pl.BlockSpec((n_nodes,), lambda i: (0,)),
            pl.BlockSpec((T,), lambda i: (0,)),
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((T, block_n), lambda i: (0, i)),
            pl.BlockSpec((T, block_n), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, N), mean.dtype),
            jax.ShapeDtypeStruct((T, N), var.dtype),
        ],
        interpret=interpret_mode(),
    )(feat, thr, child, mean, var, roots, X)


def _chain_kernel(wx_ref, wb_ref, perm_ref, idx_ref, *, d, n_words):
    """Prefix/suffix-AND walk for one Shapley chain (QuickScorer exit).

    Statically unrolled over the d permutation levels: build the running
    prefix-AND of the chain's x-term words, then walk levels d..0 keeping
    the suffix-AND of background-term words; the exit leaf of
    (level, background row) is the lowest set bit of prefix & suffix —
    word 0 scanned first for two-word trees. Pure uint64 bit ops; the
    float leaf gather stays on the host so values match the numpy walk
    bit-for-bit.
    """
    wx = wx_ref[...][0]          # (d, T, W)
    wb = wb_ref[...]             # (nb, d, T, W)
    perm = perm_ref[...][0]      # (d,)
    ones = ~jnp.uint64(0)

    pref = [jnp.full(wx.shape[1:], ones, dtype=jnp.uint64)]
    for k in range(d):
        pref.append(pref[k] & jnp.take(wx, perm[k], axis=0))

    suf = jnp.full(wb.shape[:1] + wb.shape[2:], ones, dtype=jnp.uint64)
    for k in range(d, -1, -1):
        acc = pref[k][None] & suf                       # (nb, T, W)
        lsb = acc & (jnp.uint64(0) - acc)
        pc = jax.lax.population_count(lsb - jnp.uint64(1)).astype(jnp.int32)
        o = pc[..., 0]
        for w in range(1, n_words):
            o = jnp.where(acc[..., w - 1] != 0, o, 64 * w + pc[..., w])
        idx_ref[0, k] = o
        if k > 0:
            suf = suf & jnp.take(wb, perm[k - 1], axis=1)


def chain_ordinals_pallas(word_x, word_b, perms):
    """(C, d+1, nb, T) exit-leaf ordinals via the Pallas chain walk.

    Accepts the ``ChainPlan.row_words`` layouts — (n, d, T) one-word or
    (n, d, T, W) two-word — and returns exactly what the numpy
    ``_leaf_ordinals`` walk would. One program instance per chain; the
    background word block is shared by every instance.
    """
    import numpy as np

    if word_x.ndim == 3:
        word_x = word_x[..., None]
        word_b = word_b[..., None]
    C, d, T, W = word_x.shape
    nb = word_b.shape[0]
    with jax.enable_x64(True):
        idx = pl.pallas_call(
            functools.partial(_chain_kernel, d=d, n_words=W),
            grid=(C,),
            in_specs=[
                pl.BlockSpec((1, d, T, W), lambda c: (c, 0, 0, 0)),
                pl.BlockSpec((nb, d, T, W), lambda c: (0, 0, 0, 0)),
                pl.BlockSpec((1, d), lambda c: (c, 0)),
            ],
            out_specs=pl.BlockSpec((1, d + 1, nb, T), lambda c: (c, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((C, d + 1, nb, T), jnp.int32),
            interpret=interpret_mode(),
        )(jnp.asarray(word_x), jnp.asarray(word_b),
          jnp.asarray(perms, dtype=jnp.int32))
        return np.asarray(idx).astype(np.intp)
