"""Fused on-device BO propose step (jax, x64).

One jitted program runs an entire propose iteration with zero host round
trips until the chosen candidate indices come back: pool draw (uniform +
LHS halves in unit space, replaying ``SpacePlane._quantile_col`` /
``_to_unit_col`` from the uploaded transform tables), packed-forest descent
(merged QuickScorer bitvector tables across all sources' trees by default;
the ``forest_eval`` gather or pallas kernel otherwise), per-source ensemble
combine, EI, weighted rank aggregation, and stable top-k.

Bit-equivalence contract (vs the numpy acquisition reference), held on
XLA:CPU:

* Descent does no float arithmetic — leaf routing is bit-exact (PR 2).
* The combine unrolls ``PackedForest.combine``'s numpy op sequence over
  the tree axis at trace time, for all sources at once: numpy's axis-0
  mean/var reduce rows *sequentially*, so the jax side accumulates tree
  rows in the same order.
* EI instantiates the same portable Cephes expression tree as the numpy
  reference (``acquisition.make_portable_kernels``).
* Rank aggregation dispatches on a static ``rank_impl`` (see ``rank.py``):
  the default CPU path ranks each row with the host radix kernel through a
  raw ``emit_python_callback`` custom call (~5x the sort path at 131072),
  while ``"sort"`` keeps the ``lax.sort`` + scatter-add reference
  (``rank.stable_argsort``). Every impl produces the exact stable-argsort
  ranks and accumulates w_s * rank_s in source order — numpy's exact
  per-element add sequence — so the aggregate is bit-identical across
  impls.
* Every product that can feed an add is routed through an XOR-seal
  (:func:`seal`) — a bitcast round trip XORed with a *runtime* uint64 zero
  argument. XLA cannot constant-fold it (the zero is a parameter) and LLVM
  cannot contract a multiply with an integer XOR in between into an FMA,
  which is the one source of 1-ulp divergence on XLA:CPU. Overhead ~2%.

On a TPU the program holds no f64 -> integer bitcast (XLA:TPU refuses
them): the seal is the identity and sorts use split float32 keys, both
chosen with ``lax.platform_dependent`` when the program is lowered. The
device keeps its own f64 arithmetic (a float32 pair on XLA:TPU), and the
contract there is the top-k selection (see docs/KERNELS.md); an uploaded
host pool is routed on integer order keys of its binary64 bit patterns,
so its leaves are the host's on every device.

Pool shapes are padded to power-of-two buckets (256 … 131072) so a tuning
run compiles a handful of programs, not one per pool size. Padding rows
are appended *after* the real rows and forced to EI = -1 (< any real EI,
which is >= 0), so under a stable descending sort every real row keeps its
exact unpadded rank; aggregate ranks of padding are masked to +inf before
the final stable top-k argsort.

``propose_scan`` wraps the same step body in ``lax.scan``, splitting the
PRNG key per step.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

try:
    import jax
    import jax.numpy as jnp
    from jax import lax
except ImportError as _e:  # pragma: no cover - jax ships with the image
    jax = None
    _jax_err = _e

from ...core import acquisition as _acq
from . import rank as _rank
from .ref import _descend

__all__ = [
    "POOL_BUCKET_MIN",
    "POOL_BUCKET_MAX",
    "pool_bucket",
    "seal",
    "build_qs_plan",
    "build_qs_plan_ex",
    "pack_draw_tables",
    "propose_step",
    "propose_scan",
    "ei_host",
    "aggregate_ranks_host",
]

# Bucketed-shape protocol: pool sizes pad up to the next power of two in
# [256, 131072]; larger pools keep padding to powers of two (the jit cache
# then grows logarithmically, guarded by the bench).
POOL_BUCKET_MIN = 256
POOL_BUCKET_MAX = 131072


def pool_bucket(n: int) -> int:
    """Power-of-two pool bucket for ``n`` candidates (>= POOL_BUCKET_MIN)."""
    return max(POOL_BUCKET_MIN, 1 << (max(int(n), 1) - 1).bit_length())


def _require_jax():
    if jax is None:  # pragma: no cover
        raise RuntimeError(f"jax is required for the fused propose step: {_jax_err}")


def _x64():
    _require_jax()
    return jax.enable_x64(True)


# ---------------------------------------------------------------------------
# FMA barrier + portable-kernel plumbing
# ---------------------------------------------------------------------------


def _xor_seal(x, zi):
    bits = lax.bitcast_convert_type(x, jnp.uint64)
    return lax.bitcast_convert_type(bits ^ zi, jnp.float64)


def seal(x, zi):
    """FMA barrier: bitcast -> XOR with runtime-zero ``zi`` -> bitcast back.

    Value-preserving, but opaque to both XLA's algebraic simplifier (zi is
    a parameter, not a constant) and LLVM's fmul+fadd contraction (integer
    ops break the float dataflow). Apply to any product that may feed an
    add/sub when bit-identity with numpy matters.

    Only the XLA:CPU lowering carries the barrier. XLA:TPU refuses any
    f64 -> integer bitcast, and its emulated f64 is not the host's
    binary64, so on every other platform the seal is the identity and the
    device is held to the top-k selection instead (docs/KERNELS.md).
    """
    return lax.platform_dependent(x, zi, cpu=_xor_seal,
                                  default=lambda x, zi: x)


def _seal_mul(zi):
    def mul(a, b):
        return seal(jnp.multiply(a, b), zi)

    return mul


def _seal_div(zi):
    # sealing the denominator keeps XLA from rewriting division by a
    # constant into multiplication by its rounded reciprocal
    def div(a, b):
        return jnp.divide(a, seal(jnp.asarray(b, dtype=jnp.float64), zi))

    return div


def _pow2_bits(k):
    """Exact 2**k for integral float k in normal range (exponent bitcast)."""
    ki = (k.astype(jnp.int64) + 1023) << 52
    return lax.bitcast_convert_type(ki, jnp.float64)


def _kernels(zi):
    return _acq.make_portable_kernels(jnp, _seal_mul(zi), _pow2_bits,
                                      div=_seal_div(zi))


# ---------------------------------------------------------------------------
# numpy-replay building blocks (traced)
# ---------------------------------------------------------------------------


def _combine(m_leaf, v_leaf, ystats, n_sources, tps, mul, div):
    """Replay ``PackedForest.combine`` for every source at once on the
    (n_sources * tps, N) leaf stats: (means, vars), each (n_sources, N).

    numpy's axis-0 reductions add a source's tree rows sequentially in
    index order; the trace-time unroll over the tree axis reproduces that
    order for all sources together, with sealed squares/denorms (and sealed
    /T divisions — T is a trace-time constant).
    """
    m_t = m_leaf.reshape(n_sources, tps, -1)
    v_t = v_leaf.reshape(n_sources, tps, -1)
    y_mean, y_std, y_std2 = (a[:, None] for a in ystats)
    ms = m_t[:, 0]
    for t in range(1, tps):
        ms = ms + m_t[:, t]
    mean = div(ms, tps)
    vs = v_t[:, 0]
    for t in range(1, tps):
        vs = vs + v_t[:, t]
    vmean = div(vs, tps)
    dev = m_t[:, 0] - mean
    acc = mul(dev, dev)
    for t in range(1, tps):
        dev = m_t[:, t] - mean
        acc = acc + mul(dev, dev)
    var = jnp.maximum(vmean + div(acc, tps), 1e-10)
    return mul(mean, y_std) + y_mean, mul(var, y_std2)


def _sort_perm_desc(scores):
    """The permutation ``jnp.argsort(-scores, axis=1, stable=True)`` would
    return, int32 (see ``rank.stable_argsort``)."""
    return _rank.stable_argsort(scores, descending=True)


def _sort_perm_asc1d(v):
    """``jnp.argsort(v, stable=True)`` for a 1-D float vector, int32."""
    return _rank.stable_argsort(v)


def _aggregate_ranks_traced(scores, weights, n_sources, mul, rank_impl="sort"):
    """Replay ``acquisition.aggregate_ranks`` on an (S, N) score matrix.

    With ``rank_impl="sort"`` (the pure-XLA reference): ranks_s is the
    inverse permutation of the stable descending argsort; instead of
    materializing it (a second argsort), each source's weighted ranks
    scatter directly into the aggregate at its sorted positions. The
    scatters run in source order with a data dependency between them, so
    every element accumulates w_s * rank_s in numpy's exact add sequence
    (s = 0 initializes via set, preserving the sign of a +/-0 first term).

    Other impls ("callback", "pallas" — see ``rank.rank_rows_traced``)
    materialize the rank matrix directly and accumulate elementwise in
    source order: the ranks are the exact same integers, the sealed
    products are the same floats, and the per-element add sequence is
    numpy's, so every impl returns the bit-identical aggregate. On
    XLA:CPU the callback radix is ~5x the sort+scatter path at 131072.
    """
    if rank_impl != "sort":
        ranks = _rank.rank_rows_traced(scores, rank_impl)
        agg = mul(weights[0], ranks[0])
        for s in range(1, n_sources):
            agg = agg + mul(weights[s], ranks[s])
        return agg
    perm = _sort_perm_desc(scores)
    n = scores.shape[1]
    iota_f = jnp.arange(n, dtype=jnp.float64)
    agg = jnp.zeros(n, dtype=jnp.float64)
    agg = agg.at[perm[0]].set(mul(weights[0], iota_f), unique_indices=True)
    for s in range(1, n_sources):
        agg = agg.at[perm[s]].add(mul(weights[s], iota_f), unique_indices=True)
    return agg


# ---------------------------------------------------------------------------
# device-side pool draw from SpacePlane transform tables
# ---------------------------------------------------------------------------

_K_FLOAT, _K_INT, _K_CAT, _K_BOOL, _K_CONST = 0, 1, 2, 3, 4


def _pow2_at_least(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def pack_draw_tables(sig, cols) -> dict:
    """One set of (D, ...) arrays for :func:`_draw_unit_pool` from per-knob
    sampler tables (the ``SpacePlane.device_tables`` layout, plus
    ``_K_CONST`` columns whose one table holds the unit value).

    Each knob's signature becomes data, and the piece and choice axes pad
    to powers of two, so a restricted space compiles to the same program
    as the full one unless a table outgrows its padding.
    """
    D = len(sig)
    num = [s[5] for s in sig if s[0] in (_K_FLOAT, _K_INT)]
    cat = [len(c[0]) for s, c in zip(sig, cols) if s[0] in (_K_CAT, _K_BOOL)]
    n_piece = _pow2_at_least(max(num, default=1))
    n_act = _pow2_at_least(max(cat, default=1))
    t = {
        "kind": np.array([s[0] for s in sig], dtype=np.int32),
        # is_log, transformed, degenerate, zero_span
        "flags": np.array([s[1:5] for s in sig], dtype=bool).reshape(D, 4),
        "size": np.ones(D, dtype=np.int32),
        "cum": np.full((D, n_piece + 1), np.inf),
        "ga": np.zeros((D, n_piece)),
        "gb": np.zeros((D, n_piece)),
        "mid": np.zeros((D, n_piece)),
        "scal": np.tile(np.array([0.0, 1.0, 0.0, 1.0]), (D, 1)),
        "act": np.zeros((D, n_act)),
        "n_choices": np.ones(D),
        "const": np.zeros(D),
    }
    for j, (sj, cj) in enumerate(zip(sig, cols)):
        if sj[0] in (_K_FLOAT, _K_INT):
            P = sj[5]
            t["size"][j] = P
            ga, gb, cum, mid, scal = cj
            t["ga"][j, :P], t["gb"][j, :P], t["mid"][j, :P] = ga, gb, mid
            t["cum"][j, :P + 1] = cum
            t["scal"][j] = scal
        elif sj[0] in (_K_CAT, _K_BOOL):
            act = cj[0]
            t["size"][j] = len(act)
            t["act"][j, :len(act)] = act
            t["n_choices"][j] = sj[5]
        else:
            t["const"][j] = cj[0][0]
    return t


def _select(tab, i):
    """``tab[j, i[:, j]]`` for a (D, P) table and (n, D) indices, as a
    select chain over the short P axis: exact, and XLA:TPU compiles it in
    seconds where the element gather takes minutes at large n."""
    out = jnp.broadcast_to(tab[:, 0], i.shape)
    for p in range(1, tab.shape[1]):
        out = jnp.where(i == p, tab[:, p], out)
    return out


def _unit_cols(t, U):
    """Unit draws -> restriction-CDF values -> unit encode, every column of
    the (n, D) draw ``U`` at once.

    Replays ``SpacePlane._quantile_col`` followed by the clipped
    ``_to_unit_col`` (the exact host pool construction, so device pools
    have the host pools' distribution — the draws themselves come from the
    jax PRNG, see the CHANGES SEED NOTE). Every branch is computed on every
    column and the knob's kind and flags select; the lanes a knob does not
    select may hold inf or NaN.
    """
    kind = t["kind"][None, :]
    size = t["size"][None, :]
    is_log, transformed, degenerate, zero_span = (
        t["flags"][None, :, q] for q in range(4))
    t_lo, t_span, lo, hi = (t["scal"][:, q] for q in range(4))
    # searchsorted(cum, u, side="right") over each column's own table
    i = jnp.sum(t["cum"][None] <= U[..., None], axis=-1) - 1
    i = jnp.clip(i, 0, size - 1)
    c0 = _select(t["cum"], i)
    span = _select(t["cum"], i + 1) - c0
    frac = jnp.where(span > 0, (U - c0) / jnp.where(span > 0, span, 1.0), 0.0)
    ga = _select(t["ga"], i)
    g = ga + frac * (_select(t["gb"], i) - ga)
    v = jnp.where(transformed, jnp.exp(g), g)
    pick = jnp.minimum((U * size).astype(jnp.int32), size - 1)
    v = jnp.where(degenerate, _select(t["mid"], pick), v)
    v = jnp.where(kind == _K_INT, jnp.clip(jnp.round(v), lo, hi), v)
    tv = jnp.where(is_log, jnp.log(v), v)
    num = jnp.where(zero_span, 0.0, jnp.clip((tv - t_lo) / t_span, 0.0, 1.0))
    a = _select(t["act"], pick)
    out = jnp.where(kind == _K_CAT, (a + 0.5) / t["n_choices"],
                    jnp.where(a != 0, 0.75, 0.25))
    out = jnp.where(kind == _K_CONST, t["const"], out)
    return jnp.where(kind <= _K_INT, num, out)


def _draw_unit_pool(key, tabs, n):
    """(n, D) unit-space pool: uniform half + per-knob-stratified LHS half.

    LHS strata are shuffled by a random LCG bijection ``p(i) = (a*i + b)
    mod m`` per knob (a odd => a bijection on Z_m for the power-of-two
    strata count the bucket protocol guarantees) — a rank-1-lattice-style
    stratification ~45x cheaper than ``jax.random.permutation`` on XLA:CPU
    while keeping exactly one sample per stratum per knob. Non-bucketed
    strata counts fall back to true per-knob permutations.
    """
    D = tabs["kind"].shape[0]
    n_lhs = n // 2
    n_uni = n - n_lhs
    k_uni, k_ab, k_frac = jax.random.split(key, 3)
    u_uni = jax.random.uniform(k_uni, (n_uni, D), dtype=jnp.float64)
    frac = jax.random.uniform(k_frac, (n_lhs, D), dtype=jnp.float64)
    if n_lhs > 0 and (n_lhs & (n_lhs - 1)) == 0:
        ab = jax.random.bits(k_ab, (2, D), dtype=jnp.uint32)
        i = jnp.arange(n_lhs, dtype=jnp.uint32)[:, None]
        p = (i * (ab[0] | jnp.uint32(1)) + ab[1]) & jnp.uint32(n_lhs - 1)
        strata = p.astype(jnp.float64)
    else:
        keys = jax.random.split(k_ab, max(D, 1))
        strata = jnp.stack(
            [jax.random.permutation(keys[j], n_lhs) for j in range(D)], axis=1
        ).astype(jnp.float64)
    lhs = (strata + frac) / n_lhs
    return _unit_cols(tabs, jnp.concatenate([u_uni, lhs], axis=0))


# ---------------------------------------------------------------------------
# merged QuickScorer descent (bitvector tables across every source's trees)
# ---------------------------------------------------------------------------

_ONES = np.uint64(0xFFFFFFFFFFFFFFFF)


def build_qs_plan_ex(feat, thr, child, mean, var, roots, d):
    """Host-side QuickScorer tables for a fused multi-source arena.

    Same encoding as ``chain.build_chain_plan`` — and literally the same
    packer (``chain.pack_leaf_spans`` / ``chain.build_false_tables``):
    leaf ordinals left-to-right in one or two uint64 leaf words per tree,
    per-node masks clearing the left subtree's leaf span, per-feature
    sorted thresholds prefix-ANDed into false-set tables — but merged
    across ALL sources' trees into one table set: the tree axis spans
    every source, so a single searchsorted + AND chain per feature routes
    the whole pool through the whole arena. Rank ``r = #(thr < v)``
    replays the descent's exact ``v > thr`` float comparisons, so leaf
    routing — and therefore every downstream float — is bit-identical to
    the gather descent.

    Returns ``((thr, table, leaf_mean, leaf_var), "")`` — every feature's
    sorted thresholds padded with +inf to one power-of-two count
    ``n_thr`` and stacked, ``thr`` (d, n_thr) and ``table``
    (d, n_thr + 1, T) one-word or (d, n_thr + 1, T, 2) two-word (padding
    never ranks, so refits keep the compiled shapes), and the leaf stats
    laid out per tree, (T, 64 * n_words) by leaf ordinal — or
    ``(None, reason)`` when a tree exceeds 128 leaves or splits outside the
    d-dim space; callers fall back to the gather/pallas descent.
    """
    from .chain import build_false_tables, pack_leaf_spans

    packed, reason = pack_leaf_spans(feat, thr, child, mean, var, roots, d)
    if packed is None:
        return None, reason
    nodes_by_feat, leaf_mean, leaf_var, leaf_offs, n_words = packed
    T = len(roots)
    thrs, tables = build_false_tables(nodes_by_feat, T, n_words)
    n_thr = _pow2_at_least(max(len(t) for t in thrs))
    thr_all = np.full((d, n_thr), np.inf)
    tab_all = np.empty((d, n_thr + 1) + tables[0].shape[1:], dtype=np.uint64)
    for j, (tj, bj) in enumerate(zip(thrs, tables)):
        thr_all[j, :len(tj)] = tj
        tab_all[j, :len(bj)] = bj
        tab_all[j, len(bj):] = bj[-1]
    ends = np.append(leaf_offs[1:], len(leaf_mean))
    lm = np.zeros((T, 64 * n_words))
    lv = np.zeros((T, 64 * n_words))
    for t in range(T):
        n_t = ends[t] - leaf_offs[t]
        lm[t, :n_t] = leaf_mean[leaf_offs[t]:ends[t]]
        lv[t, :n_t] = leaf_var[leaf_offs[t]:ends[t]]
    return (thr_all, tab_all, lm, lv), ""


def build_qs_plan(feat, thr, child, mean, var, roots, d):
    """Back-compat wrapper over :func:`build_qs_plan_ex` (drops the
    decline reason)."""
    return build_qs_plan_ex(feat, thr, child, mean, var, roots, d)[0]


def _thr_rank(thr, x):
    """``#(thr < x)`` per element of ``x``: a binary search on XLA:CPU,
    where it runs ~3x faster; a compare-all count elsewhere, which XLA:TPU
    compiles in seconds where 60 binary searches take minutes."""
    def search(method):
        return lambda t, v: jnp.searchsorted(t, v, side="left", method=method)

    return lax.platform_dependent(thr, x, cpu=search("scan"),
                                  default=search("compare_all"))


def _qs_leaf_stats(qs, X):
    """Traced QuickScorer eval: (T, N) leaf means/vars for a unit pool.

    One threshold rank per feature ranks the whole column, the prefix
    tables turn ranks into per-tree false-node words, and the AND chain
    isolates each tree's exit leaf as the lowest set bit (ordinal via
    popcount of ``lsb - 1``). Replaces O(T * depth) random gathers with D
    cache-resident table lookups + D word-ANDs per row. Two-word trees
    (65..128 leaves, tables with a trailing word axis) scan word 0 first:
    an empty word 0 underflows ``lsb - 1`` to all-ones (popcount 64), so
    the select picks 64 + the word-1 ordinal. The per-tree leaf tables are
    read along their rows, a gather XLA:TPU compiles quickly, unlike an
    element gather over the whole (N, T) index set.
    """
    thr, tab, lm, lv = qs
    w = tab[0][_thr_rank(thr[0], X[:, 0])]
    for j in range(1, thr.shape[0]):
        w = w & tab[j][_thr_rank(thr[j], X[:, j])]
    if w.ndim == 3:  # two leaf words per tree
        w0, w1 = w[..., 0], w[..., 1]
        lsb0 = w0 & (jnp.uint64(0) - w0)
        lsb1 = w1 & (jnp.uint64(0) - w1)
        leaf = jnp.where(
            w0 != 0,
            lax.population_count(lsb0 - jnp.uint64(1)),
            jnp.uint64(64) + lax.population_count(lsb1 - jnp.uint64(1)),
        )
    else:
        lsb = w & (jnp.uint64(0) - w)
        leaf = lax.population_count(lsb - jnp.uint64(1))
    leaf = leaf.astype(jnp.int32).T
    return (jnp.take_along_axis(lm, leaf, axis=1),
            jnp.take_along_axis(lv, leaf, axis=1))


# ---------------------------------------------------------------------------
# the fused step
# ---------------------------------------------------------------------------


def _leaf_stats(arena, X, depth, descent):
    feat, thr, child, mean, var, roots = arena
    if descent == "pallas":
        from .kernel import forest_eval_pallas

        return forest_eval_pallas(feat, thr, child, mean, var, roots, X,
                                  depth)
    nid = _descend(feat, thr, child, roots, X, depth)
    return mean[nid], var[nid]


def _step_body(key, tabs, X, arena, qs, ystats, incumbents, weights, n_valid,
               zi, *, n_pool, depth, n_sources, tps, k, descent,
               rank_impl="sort"):
    # the stage scopes name the device operations in HLO metadata, so a
    # profile groups them by stage whatever XLA names the fusions
    scope = jax.named_scope
    with scope("draw"):
        if X is None:
            X = Xr = _draw_unit_pool(key, tabs, n_pool)
        else:
            # an uploaded pool is routed on the order keys of its binary64 bits
            Xr = _rank.keys_from_bits(X, descending=False)
    mul = _seal_mul(zi)
    div = _seal_div(zi)
    kern = _kernels(zi)
    with scope("descent"):
        if descent == "qs":
            m_leaf, v_leaf = _qs_leaf_stats(qs, Xr)
        else:
            m_leaf, v_leaf = _leaf_stats(arena, Xr, depth, descent)
    with scope("combine"):
        means, vars_ = _combine(m_leaf, v_leaf, ystats, n_sources, tps, mul, div)
    with scope("ei"):
        scores = kern["ei"](means, vars_, incumbents[:, None])
        valid = jnp.arange(X.shape[0]) < n_valid
        # padding: EI = -1 < 0 <= any real EI, appended after real rows =>
        # real rows keep their exact unpadded ranks under the stable sort
        scores = jnp.where(valid[None, :], scores, -1.0)
    with scope("rank"):
        agg = _aggregate_ranks_traced(scores, weights, n_sources, mul, rank_impl)
        agg = jnp.where(valid, agg, jnp.inf)
    with scope("topk"):
        idx = _sort_perm_asc1d(agg)[:k]
        return idx, jnp.take(X, idx, axis=0), jnp.take(agg, idx)


@functools.partial(
    jax.jit if jax is not None else lambda f, **kw: f,
    static_argnames=("n_pool", "depth", "n_sources", "tps", "k",
                     "rank_impl", "descent"),
)
def _propose_jit(key, tabs, X, arena, qs, ystats, incumbents, weights,
                 n_valid, zi, *, n_pool, depth, n_sources, tps, k,
                 rank_impl, descent):
    return _step_body(key, tabs, X, arena, qs, ystats, incumbents, weights,
                      n_valid, zi, n_pool=n_pool, depth=depth,
                      n_sources=n_sources, tps=tps, k=k,
                      descent=descent, rank_impl=rank_impl)


@functools.partial(
    jax.jit if jax is not None else lambda f, **kw: f,
    static_argnames=("n_pool", "depth", "n_sources", "tps", "k",
                     "rank_impl", "descent", "steps"),
)
def _propose_scan_jit(key, tabs, arena, qs, ystats, incumbents, weights, zi,
                      *, n_pool, depth, n_sources, tps, k, rank_impl,
                      descent, steps):
    n_valid = jnp.asarray(n_pool, dtype=jnp.int64)

    def body(carry, _):
        carry, sub = jax.random.split(carry)
        out = _step_body(sub, tabs, None, arena, qs, ystats, incumbents,
                         weights, n_valid, zi, n_pool=n_pool, depth=depth,
                         n_sources=n_sources, tps=tps, k=k,
                         descent=descent, rank_impl=rank_impl)
        return carry, out

    key, outs = lax.scan(body, key, None, length=steps)
    return key, outs


def propose_step(key, tabs, arena, ystats, incumbents, weights, zi,
                 *, n_pool, depth, n_sources, tps, k, descent="jax",
                 rank_impl=None, X=None, n_valid=None, qs=None):
    """One fused propose step. ``X=None`` draws the pool on device from
    ``key`` over the uploaded :func:`pack_draw_tables` set ``tabs``; an
    uploaded ``X`` (host pool mode) pins the candidates so the selection is
    bit-identical to the staged numpy path. It comes as the pool's binary64
    bit patterns (uint64, ``X.view(np.uint64)``) and descent compares their
    order keys (``rank.keys_from_bits``) with the thresholds of ``arena``
    and ``qs`` keyed on the host (``rank.monotone_keys(...,
    descending=False)``); the returned rows are those bit patterns.
    ``descent="qs"`` routes leaves through the merged QuickScorer tables in
    ``qs`` (from :func:`build_qs_plan`, uploaded). ``rank_impl`` picks the rank-matrix
    kernel (``rank.RANK_IMPLS``; None = backend default). Returns
    (idx, X[idx], agg[idx]), each length ``k``."""
    if n_valid is None:
        n_valid = n_pool
    if rank_impl is None:
        rank_impl = _rank.default_rank_impl()
    return _propose_jit(key, tabs, X, arena, qs, ystats, incumbents, weights,
                        jnp.asarray(n_valid, dtype=jnp.int64), zi,
                        n_pool=n_pool, depth=depth, n_sources=n_sources,
                        tps=tps, k=k, rank_impl=rank_impl, descent=descent)


def propose_scan(key, tabs, arena, ystats, incumbents, weights, zi, *,
                 n_pool, depth, n_sources, tps, k, descent="jax",
                 rank_impl=None, steps=1, qs=None):
    """``steps`` fused propose iterations under one ``lax.scan``, splitting
    the PRNG key per step. Returns (next_key, (idx, X_sel, agg_sel)) with a
    leading ``steps`` axis on each output."""
    if rank_impl is None:
        rank_impl = _rank.default_rank_impl()
    return _propose_scan_jit(key, tabs, arena, qs, ystats, incumbents,
                             weights, zi, n_pool=n_pool, depth=depth,
                             n_sources=n_sources, tps=tps, k=k,
                             rank_impl=rank_impl, descent=descent, steps=steps)


# ---------------------------------------------------------------------------
# host-callable, bucket-padded wrappers (bit-equivalence surface for tests)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit if jax is not None else lambda f: f)
def _ei_pad_jit(mean, var, best, zi):
    return _kernels(zi)["ei"](mean, var, best)


@functools.partial(
    jax.jit if jax is not None else lambda f, **kw: f,
    static_argnames=("n_sources", "rank_impl"),
)
def _ranks_pad_jit(scores, weights, zi, *, n_sources, rank_impl="sort"):
    return _aggregate_ranks_traced(scores, weights, n_sources, _seal_mul(zi),
                                   rank_impl)


def ei_host(mean, var, best) -> np.ndarray:
    """Jax EI, padded to the pool bucket; bit-identical (x64) to
    ``acquisition.expected_improvement``."""
    mean = np.asarray(mean, dtype=float)
    var = np.asarray(var, dtype=float)
    best = np.asarray(best, dtype=float)
    shape = np.broadcast_shapes(mean.shape, var.shape, best.shape)
    mf = np.broadcast_to(mean, shape).reshape(-1)
    vf = np.broadcast_to(var, shape).reshape(-1)
    bf = np.broadcast_to(best, shape).reshape(-1)
    n = max(mf.size, 1)
    bucket = pool_bucket(n)
    mp = np.zeros(bucket)
    vp = np.ones(bucket)
    bp = np.zeros(bucket)
    mp[:mf.size], vp[:vf.size], bp[:bf.size] = mf, vf, bf
    with _x64():
        zi = jnp.zeros((), dtype=jnp.uint64)
        out = _ei_pad_jit(jnp.asarray(mp), jnp.asarray(vp), jnp.asarray(bp), zi)
        return np.asarray(out)[:mf.size].reshape(shape)


def aggregate_ranks_host(scores, weights, rank_impl=None) -> np.ndarray:
    """Jax rank aggregation, padded to the pool bucket with -inf scores
    (strictly below any finite score, appended last => real columns keep
    their exact unpadded ranks); bit-identical to
    ``acquisition.aggregate_ranks`` for finite scores under every
    ``rank_impl`` (None = backend default)."""
    scores = np.atleast_2d(np.asarray(scores, dtype=float))
    if scores.size == 0:
        raise ValueError("no scores to aggregate")
    s, n = scores.shape
    bucket = pool_bucket(n)
    sp = np.full((s, bucket), -np.inf)
    sp[:, :n] = scores
    w = np.asarray(weights, dtype=float)
    if rank_impl is None:
        rank_impl = _rank.default_rank_impl()
    with _x64():
        zi = jnp.zeros((), dtype=jnp.uint64)
        agg = _ranks_pad_jit(jnp.asarray(sp), jnp.asarray(w), zi, n_sources=s,
                             rank_impl=rank_impl)
        return np.asarray(agg)[:n]
