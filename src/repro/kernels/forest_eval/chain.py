"""Bitvector chain evaluator for the batched Shapley plane.

The §5.1 attribution path evaluates, per (config, permutation) chain, the
(d+1) prefix-composite rows ``z_S`` (x on the prefix set S, background
elsewhere) averaged over every background row. A gather descent costs
O(trees * depth) random accesses per composite row; this module replaces it
with a QuickScorer-style bitvector evaluation (Lucchese et al., SIGIR'15)
that exploits the chain structure:

* Each tree's leaves get ordinals in left-to-right order, packed into
  ``W`` uint64 leaf words per tree (W = 1 up to 64 leaves, W = 2 up to
  128 — leaf L lives in word L // 64, bit L % 64). Every internal node
  carries masks clearing its left subtree's leaf bits; a row's exit leaf
  is the lowest set bit across the ANDed word vector of all *false* nodes
  (``v > thr``, i.e. the row goes right) — word 0 scanned first.
* Which nodes are false depends only on per-feature threshold *ranks*, so
  per feature we sort the split thresholds and prefix-AND their masks:
  ``table[j][r]`` = AND of masks of the r smallest thresholds — the false
  set of any value v with rank r = #(thr < v). Rank compares replay the
  descent's exact float comparisons, so the exit leaf is identical.
* A composite row's value vector mixes x and background coordinates by the
  prefix mask, so its AND factorizes along the permutation: AND of x-term
  words over the prefix, AND of background-term words over the suffix.
  Prefix/suffix cumulative ANDs turn the whole chain into ~1 word-AND per
  (level, background row) instead of a fresh descent.

Leaf means are the exact arena floats and the ensemble reduction replays
``PackedForest.combine``'s mean ops on the same (trees, rows) layout, so
chain values are bit-identical to evaluating the materialized composite
tensor through ``PackedForest.predict`` (see tests/test_shapley_batched.py).

The leaf-ordinal walk and prefix-AND table construction are shared with
the fused propose step's merged QuickScorer plan
(``propose.build_qs_plan``) via :func:`pack_leaf_spans` /
:func:`build_false_tables`.

``build_chain_plan_ex`` returns ``(plan, reason)`` — ``(None, why)`` when
the encoding does not apply (not a packable forest, or a tree with more
than 128 leaves); callers fall back to the generic composite-tensor path.
The feature count is not bounded: tables are kept per feature and chains
walk their permutation by index, so no word holds a set of features.
Values must be NaN-free (threshold ranks come from ``np.searchsorted``).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

__all__ = [
    "ChainPlan",
    "PoolPlan",
    "pack_leaf_spans",
    "build_false_tables",
    "build_chain_plan",
    "build_chain_plan_ex",
    "build_pool_plan_ex",
    "chain_decline_reason",
]

_ONES = np.uint64(0xFFFFFFFFFFFFFFFF)
_PLAN_ATTR = "_chain_plan_cache"
_POOL_PLAN_ATTR = "_pool_plan_cache"

# the widest supported leaf word vector: 2 x uint64 = 128 leaves per tree
MAX_LEAF_WORDS = 2

# why the most recent build_chain_plan call returned None ("" after a success).
# Back-compat only: the reason now travels on the (plan, reason) return of
# ``build_chain_plan_ex`` so interleaved builds can't clobber each other;
# this slot just mirrors the latest call for the legacy accessor.
_DECLINE_REASON = ""


def chain_decline_reason() -> str:
    """Reason the last ``build_chain_plan`` call declined, "" on success.

    Back-compat shim over the module-global last-call slot — prefer the
    ``reason`` returned by :func:`build_chain_plan_ex`, which is immune to
    interleaved builds.
    """
    return _DECLINE_REASON


# ---------------------------------------------------------------------------
# shared packer: leaf-ordinal walk + per-feature false-set tables
# ---------------------------------------------------------------------------


def pack_leaf_spans(feat, thr, child, mean, var, roots, d):
    """Walk every tree of a packed arena, assigning leaf ordinals
    left-to-right and collecting per-feature split spans.

    Returns ``(payload, reason)`` where payload is ``None`` with a decline
    reason, or ``(nodes_by_feat, leaf_mean, leaf_var, leaf_offs, n_words)``:

    * nodes_by_feat[j] — list of ``(thr, tree, lo, mid)`` spans: the node
      splits feature j at thr, and its false mask clears leaf ordinals
      [lo, mid) of that tree.
    * leaf_mean / leaf_var — flat float64 leaf stats, ordinal-indexed via
      leaf_offs (T,).
    * n_words — uint64 leaf words per tree (1 or 2) for the widest tree.
    """
    T = len(roots)
    nodes_by_feat: List[List[Tuple[float, int, int, int]]] = [[] for _ in range(d)]
    leaf_mean: List[float] = []
    leaf_var: List[float] = []
    leaf_offs = np.empty(T, dtype=np.int64)
    n_leaves_max = 0
    for t in range(T):
        base = len(leaf_mean)
        leaf_offs[t] = base
        stack = [(int(roots[t]), False)]
        spans = {}  # node -> (lo, hi) leaf-ordinal range within this tree
        while stack:
            n, expanded = stack.pop()
            if child[2 * n] == n:  # leaf: self-loop encoding
                spans[n] = (len(leaf_mean) - base, len(leaf_mean) - base + 1)
                leaf_mean.append(float(mean[n]))
                leaf_var.append(float(var[n]))
                continue
            if not expanded:
                stack.append((n, True))
                stack.append((int(child[2 * n + 1]), False))
                stack.append((int(child[2 * n]), False))
                continue
            lo, mid = spans[int(child[2 * n])]
            _, hi = spans[int(child[2 * n + 1])]
            spans[n] = (lo, hi)
            if int(feat[n]) >= d:
                return None, (
                    f"tree {t} splits on feature {int(feat[n])} outside the "
                    f"{d}-dim space"
                )
            if hi > 64 * MAX_LEAF_WORDS:
                return None, (
                    f"tree {t} has {hi} leaves > "
                    f"{64 * MAX_LEAF_WORDS}-bit leaf words"
                )
            n_leaves_max = max(n_leaves_max, hi)
            nodes_by_feat[int(feat[n])].append((float(thr[n]), t, lo, mid))
    n_words = 1 if n_leaves_max <= 64 else 2
    return (
        nodes_by_feat,
        np.asarray(leaf_mean),
        np.asarray(leaf_var),
        leaf_offs,
        n_words,
    ), ""


def _span_mask(lo: int, mid: int, w: int) -> np.uint64:
    """uint64 word ``w`` of the mask clearing leaf ordinals [lo, mid)."""
    a = min(max(lo - 64 * w, 0), 64)
    b = min(max(mid - 64 * w, 0), 64)
    if b <= a:
        return _ONES
    return np.uint64(~(((1 << (b - a)) - 1) << a) & int(_ONES))


def build_false_tables(nodes_by_feat, T: int, n_words: int):
    """Per-feature sorted thresholds + prefix-ANDed false-set tables.

    Returns ``(thrs, tables)``: tables[j] has shape (n_thr + 1, T) for one
    leaf word, (n_thr + 1, T, n_words) otherwise — row r is the AND of the
    masks of the r smallest thresholds on that feature.
    """
    thrs, tables = [], []
    for nds in (sorted(f, key=lambda z: z[0]) for f in nodes_by_feat):
        shape = (len(nds) + 1, T) if n_words == 1 else (len(nds) + 1, T, n_words)
        tab = np.full(shape, _ONES, dtype=np.uint64)
        for r, (_, t, lo, mid) in enumerate(nds):
            tab[r + 1] = tab[r]
            if n_words == 1:
                tab[r + 1, t] &= _span_mask(lo, mid, 0)
            else:
                for w in range(n_words):
                    tab[r + 1, t, w] &= _span_mask(lo, mid, w)
        thrs.append(np.array([z[0] for z in nds]))
        tables.append(tab)
    return thrs, tables


def _lowbit_ordinal(acc: np.ndarray) -> np.ndarray:
    """Ordinal of the lowest set bit of each uint64 (via the float64
    exponent of the isolated bit — exact for powers of two); an all-zero
    word yields a negative garbage value the caller must mask."""
    low = acc & (np.uint64(0) - acc)
    return (
        (low.astype(np.float64).view(np.uint64) >> np.uint64(52))
        - np.uint64(1023)
    ).astype(np.intp)


class ChainPlan:
    """Per-forest precompute: feature threshold tables + leaf ordinals."""

    def __init__(self, forest, d: int,
                 thrs: List[np.ndarray], tables: List[np.ndarray],
                 leaf_mean: np.ndarray, leaf_offs: np.ndarray,
                 n_words: int = 1):
        self.forest = forest          # PackedForest (for the y denorm)
        self.d = d
        self.thrs = thrs              # per feature: sorted split thresholds
        self.tables = tables          # per feature: (n_thr + 1, T[, W]) prefix-ANDs
        self.leaf_mean = leaf_mean    # flat leaf means, ordinal-indexed
        self.leaf_offs = leaf_offs    # (T,) offsets into the flat leaf array
        self.n_words = n_words        # uint64 leaf words per tree (1 or 2)
        self.decline_reason = ""      # always "" on a built plan

    @property
    def n_trees(self) -> int:
        return len(self.leaf_offs)

    def row_words(self, V: np.ndarray) -> np.ndarray:
        """Per-row false-node words, shape (n, d, T) or (n, d, T, W).

        ``word[i, j]`` is the AND of the masks of every node on feature j
        that row i's value makes false — rank r = #(thr < v) via
        ``searchsorted(..., 'left')``, the exact ``v > thr`` comparison of
        the packed descent.
        """
        V = np.asarray(V, dtype=float)
        shape = (len(V), self.d, self.n_trees)
        if self.n_words > 1:
            shape += (self.n_words,)
        out = np.empty(shape, dtype=np.uint64)
        for j in range(self.d):
            out[:, j] = self.tables[j][
                np.searchsorted(self.thrs[j], V[:, j], side="left")
            ]
        return out

    def _leaf_ordinals(self, word_x, word_b, perms):
        """(C, d+1, nb, T) exit-leaf ordinals for every (chain, level, bg).

        Prefix-AND of x-term words along each chain, then a level walk
        d..0 keeping the running suffix-AND of background-term words; the
        exit leaf of row (chain, level, bg) is the lowest set bit of
        pref & suffix (QuickScorer) — word 0 first for two-word trees.
        """
        C, d, T = word_x.shape[:3]
        nb = word_b.shape[0]
        two = self.n_words > 1
        tail = (T, self.n_words) if two else (T,)
        pidx = perms[:, :, None, None] if two else perms[:, :, None]

        pref = np.empty((C, d + 1) + tail, dtype=np.uint64)
        pref[:, 0] = _ONES
        for k in range(d):
            pref[:, k + 1] = pref[:, k] & np.take_along_axis(
                word_x, pidx[:, k][:, None], axis=1
            )[:, 0]

        idx = np.empty((C, d + 1, nb, T), dtype=np.intp)
        suf = np.broadcast_to(_ONES, (C, nb) + tail).copy()
        for k in range(d, -1, -1):
            acc = pref[:, k][:, None] & suf
            if two:
                o0 = _lowbit_ordinal(acc[..., 0])
                o1 = _lowbit_ordinal(acc[..., 1])
                idx[:, k] = np.where(acc[..., 0] != 0, o0, 64 + o1)
            else:
                idx[:, k] = _lowbit_ordinal(acc)
            if k > 0:
                wb = word_b[:, perms[:, k - 1]]  # (nb, C, ...) fancy-indexed
                suf &= np.moveaxis(wb, 0, 1)
        return idx

    def eval_chains(
        self,
        X: np.ndarray,
        background: np.ndarray,
        perms: np.ndarray,
        x_of_chain: np.ndarray,
        backend: str = "numpy",
    ) -> np.ndarray:
        """Chain values for (chain, level): E_b[f(z_{S_k})], shape (C, d+1).

        perms: (C, d) permutation per chain; x_of_chain: (C,) row of X each
        chain explains. Matches the composite-tensor path bit-for-bit: the
        exact mean ops of ``PackedForest.combine`` over the full (T, rows)
        block, then the same contiguous-axis mean over background rows.

        ``backend="pallas"`` runs the integer prefix/suffix-AND walk in
        the pallas chain-ordinal kernel (``kernel.chain_ordinals_pallas``);
        the leaf ordinals are integers either way, so the float tail is
        shared and the values stay bit-identical.
        """
        d, nb, T = self.d, len(background), self.n_trees
        C = len(perms)
        word_x = self.row_words(X)[x_of_chain]        # (C, d, T[, W])
        word_b = self.row_words(background)           # (nb, d, T[, W])

        if backend == "pallas":
            from .kernel import chain_ordinals_pallas
            idx = chain_ordinals_pallas(word_x, word_b,
                                        np.asarray(perms, dtype=np.int32))
        else:
            idx = self._leaf_ordinals(word_x, word_b, perms)

        flat = np.ascontiguousarray((idx + self.leaf_offs).reshape(-1, T).T)
        m_t = self.leaf_mean.take(flat)               # (T, rows) C-contiguous
        # ``PackedForest.combine``'s mean output never reads the variance
        # stats: replaying its exact mean ops here (sequential tree-axis
        # reduction on the C-contiguous (T, rows) block, then denorm) keeps
        # bit-identity while skipping the leaf-variance gather entirely
        mean_rows = m_t.mean(axis=0) * self.forest.y_std + self.forest.y_mean
        return mean_rows.reshape(C, d + 1, nb).mean(axis=2)


def _pack_of(model):
    """PackedForest from a PRF/PackedForest-like model, else None."""
    pack = getattr(model, "pack", None)
    if callable(pack):
        try:
            return pack()
        except Exception:
            return None
    return model if hasattr(model, "roots") and hasattr(model, "combine") else None


def build_chain_plan_ex(model, d: int) -> Tuple[Optional[ChainPlan], str]:
    """Build (and cache on the packed arena) a ChainPlan.

    Returns ``(plan, "")`` on success and ``(None, reason)`` when the
    model is not a packable forest or a tree exceeds 64 * MAX_LEAF_WORDS
    leaves.
    """
    pf = _pack_of(model)
    if pf is None:
        return None, "not a packable forest"
    cached = getattr(pf, _PLAN_ATTR, None)
    if cached is not None and cached[0] == d:
        return cached[1], ""

    packed, reason = pack_leaf_spans(pf.feat, pf.thr, pf.child, pf.mean,
                                     pf.var, pf.roots, d)
    if packed is None:
        return None, reason
    nodes_by_feat, leaf_mean, _leaf_var, leaf_offs, n_words = packed
    thrs, tables = build_false_tables(nodes_by_feat, pf.n_trees, n_words)

    plan = ChainPlan(pf, d, thrs, tables, leaf_mean,
                     leaf_offs.astype(np.intp), n_words)
    try:
        setattr(pf, _PLAN_ATTR, (d, plan))
    except Exception:
        pass  # frozen/slotted arena: just skip the cache
    return plan, ""


def build_chain_plan(model, d: int) -> Optional[ChainPlan]:
    """Back-compat wrapper over :func:`build_chain_plan_ex`; the decline
    reason lands in the legacy ``chain_decline_reason()`` slot."""
    global _DECLINE_REASON
    plan, _DECLINE_REASON = build_chain_plan_ex(model, d)
    return plan


# ---------------------------------------------------------------------------
# delta pool scoring: per-base shared-coordinate AND reuse
# ---------------------------------------------------------------------------


class PoolPlan:
    """False-word tables over a (possibly fused multi-forest) arena for
    whole-pool leaf routing with per-base delta reuse.

    Mutation pools share most coordinates with their base incumbent: a
    candidate's QuickScorer word is the AND of its d per-feature false
    words, and every unmutated coordinate contributes the *base's* word.
    Per base this plan precomputes a doubling (sparse) range-AND table
    over the feature axis — AND is idempotent, so any feature segment is
    two overlapping power-of-two lookups — and per candidate re-ANDs only
    the mutated coordinates plus one segment per gap between them. Leaf
    routing is bit-identical to the gather descent (the rank compare
    replays ``v > thr`` exactly), so ``predict`` through this plan returns
    the descent's exact leaf stats.
    """

    def __init__(self, d: int,
                 thrs: List[np.ndarray], tables: List[np.ndarray],
                 leaf_mean: np.ndarray, leaf_var: np.ndarray,
                 leaf_offs: np.ndarray, n_words: int = 1):
        self.d = d
        self.thrs = thrs
        self.tables = tables
        self.leaf_mean = leaf_mean
        self.leaf_var = leaf_var
        self.leaf_offs = leaf_offs
        self.n_words = n_words
        self.decline_reason = ""

    @property
    def n_trees(self) -> int:
        return len(self.leaf_offs)

    # same per-row false-word gather as the chain plan
    row_words = ChainPlan.row_words

    def _ordinals(self, acc: np.ndarray) -> np.ndarray:
        """Exit-leaf ordinals from ANDed word vectors (word 0 first)."""
        if self.n_words == 1:
            return _lowbit_ordinal(acc)
        o0 = _lowbit_ordinal(acc[..., 0])
        o1 = _lowbit_ordinal(acc[..., 1])
        return np.where(acc[..., 0] != 0, o0, 64 + o1)

    def leaf_stats(self, X: np.ndarray, bases: np.ndarray,
                   base_of: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(m_t, v_t), each (T, N) — the descent's exact per-tree leaf stats.

        ``bases`` is the (B, d) matrix of base rows; ``base_of[i]`` names
        candidate i's base (-1 = no base: a fresh random sample, evaluated
        by the vectorized full-row AND instead). Which coordinates mutated
        is recovered by exact value comparison against the base row — a
        mutation that lands back on the base value is simply shared.
        """
        X = np.asarray(X, dtype=float)
        N = len(X)
        T = self.n_trees
        base_of = np.asarray(base_of)
        m_t = np.empty((T, N))
        v_t = np.empty((T, N))

        free = np.flatnonzero(base_of < 0)
        if free.size:
            acc = np.bitwise_and.reduce(self.row_words(X[free]), axis=1)
            flat = self._ordinals(acc) + self.leaf_offs  # (nf, T)
            m_t[:, free] = self.leaf_mean.take(flat).T
            v_t[:, free] = self.leaf_var.take(flat).T

        mut = np.flatnonzero(base_of >= 0)
        if mut.size:
            bases = np.asarray(bases, dtype=float)
            bw = self.row_words(bases)                   # (B, d, T[, W])
            # doubling range-AND table: lvl[l][:, j] = AND of the words of
            # features [j, j + 2^l); idempotence lets two overlapping
            # power-of-two segments cover any [a, b)
            lvls = [bw]
            span = 1
            while span < self.d:
                prev = lvls[-1]
                nxt = prev.copy()
                nxt[:, : self.d - span] &= prev[:, span:]
                lvls.append(nxt)
                span *= 2

            def seg(b: int, a: int, e: int) -> np.ndarray:
                l = (e - a).bit_length() - 1
                return lvls[l][b, a] & lvls[l][b, e - (1 << l)]

            for i in mut:
                b = int(base_of[i])
                changed = np.flatnonzero(X[i] != bases[b])
                acc = np.broadcast_to(
                    _ONES, self.tables[0].shape[1:]
                ).copy()
                prev_j = 0
                for j in changed:
                    j = int(j)
                    if j > prev_j:
                        acc &= seg(b, prev_j, j)
                    acc &= self.tables[j][
                        int(np.searchsorted(self.thrs[j], X[i, j], side="left"))
                    ]
                    prev_j = j + 1
                if prev_j < self.d:
                    acc &= seg(b, prev_j, self.d)
                flat = self._ordinals(acc) + self.leaf_offs
                m_t[:, i] = self.leaf_mean[flat]
                v_t[:, i] = self.leaf_var[flat]
        return m_t, v_t


def build_pool_plan_ex(arena, d: int) -> Tuple[Optional["PoolPlan"], str]:
    """Build (and cache on the arena object) a PoolPlan.

    ``arena`` is anything carrying the packed node arrays (a PackedForest
    or a fused ForestPlane). Returns ``(plan, "")`` or ``(None, reason)``
    under the same decline conditions as :func:`build_chain_plan_ex`.
    """
    for attr in ("feat", "thr", "child", "mean", "var", "roots"):
        if not hasattr(arena, attr):
            return None, "not a packed arena"
    cached = getattr(arena, _POOL_PLAN_ATTR, None)
    if cached is not None and cached[0] == d:
        return cached[1], cached[2]

    packed, reason = pack_leaf_spans(arena.feat, arena.thr, arena.child,
                                     arena.mean, arena.var, arena.roots, d)
    if packed is None:
        plan = None
    else:
        nodes_by_feat, leaf_mean, leaf_var, leaf_offs, n_words = packed
        thrs, tables = build_false_tables(nodes_by_feat, len(arena.roots),
                                          n_words)
        plan = PoolPlan(d, thrs, tables, leaf_mean, leaf_var,
                        leaf_offs.astype(np.intp), n_words)
        reason = ""
    try:
        setattr(arena, _POOL_PLAN_ATTR, (d, plan, reason))
    except Exception:
        pass  # slotted arena: just skip the cache
    return plan, reason
