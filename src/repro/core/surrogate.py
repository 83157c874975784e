"""Surrogate models for Bayesian optimization.

The primary surrogate is a Probabilistic Random Forest (paper §3.3 —
"Probabilistic Random Forest [12]", i.e. the SMAC-style forest): an ensemble
of randomized regression trees over the unit-cube encoding; the predictive
mean is the mean of per-tree leaf means and the predictive variance combines
across-tree disagreement with within-leaf empirical variance (law of total
variance, as in Hutter et al. 2011).

A small exact Gaussian Process (Matérn-5/2) is also provided — it is *not*
used by MFTune itself but by the Tuneful baseline's multi-task GP.

Ensemble inference runs on a *packed* representation: ``pack()`` stacks all
trees of a forest into one struct-of-arrays :class:`PackedForest` (feature /
threshold / child / leaf-stat arrays with per-tree root offsets) so predict
is a single level-synchronous gather descent over (n_trees × n_points)
instead of a per-tree Python loop. :class:`ForestPlane` extends the same
arena across *several* forests (one per source task / fidelity level) so the
combined surrogate of §6.2 is evaluated in one fused pass. The descent also
has jax and pallas backends (``repro.kernels.forest_eval``); all backends
route points to identical leaves, so (mean, var) agree bit-for-bit with the
legacy loop, which is kept as ``predict_loop`` for equivalence tests.

Fitting mirrors inference: on every packed backend trees grow through a
*level-synchronous frontier builder* (one vectorized best-split scan over
all active nodes per depth, against a shared presorted feature order) that
feeds ``pack()`` directly; the ``"loop"`` backend keeps the legacy
node-by-node recursion. Per-node feature subsets come from a
traversal-order-independent seed chain and the split arithmetic replays the
recursion's exact op sequence, so both builders produce bit-identical
trees — backend choice never changes a fitted forest.

The default path is pure numpy; data sets here are O(10^2-10^3) points.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import obs as _obs

__all__ = [
    "RegressionTree",
    "ProbabilisticRandomForest",
    "PackedForest",
    "ForestPlane",
    "GaussianProcess",
    "Surrogate",
    "make_forest",
    "set_forest_backend",
    "get_forest_backend",
    "forest_backend",
    "packed_descend",
]


class Surrogate:
    """Minimal interface all surrogates implement."""

    def fit(self, X: np.ndarray, y: np.ndarray) -> "Surrogate":
        raise NotImplementedError

    def predict(self, X: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Return (mean, variance), each shape (n,)."""
        raise NotImplementedError

    def predict_mean(self, X: np.ndarray) -> np.ndarray:
        return self.predict(X)[0]


# ---------------------------------------------------------------------------
# Regression trees / random forest
# ---------------------------------------------------------------------------


@dataclass
class _Node:
    feature: int = -1            # -1 => leaf
    threshold: float = 0.0
    left: int = -1
    right: int = -1
    mean: float = 0.0
    var: float = 0.0
    n: int = 0


_MASK64 = (1 << 64) - 1
_GOLDEN = 0x9E3779B97F4A7C15


def _splitmix64(z: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 finalizer over uint64 arrays (wrapping mod 2^64)."""
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _child_seeds(seeds: np.ndarray, right: int) -> np.ndarray:
    """Traversal-order-independent per-node seed chain (splitmix64-style),
    derived for a whole frontier of parent seeds in one array pass.

    Both tree builders derive each node's feature-subset stream from this
    chain, so the recursive (depth-first) and frontier (level-synchronous)
    builders draw identical subsets regardless of node processing order.
    """
    z = np.asarray(seeds, dtype=np.uint64) + np.uint64((_GOLDEN * (right + 1)) & _MASK64)
    return _splitmix64(z) & np.uint64((1 << 63) - 1)


def _child_seed(seed: int, right: int) -> int:
    """Scalar view of the chain for the recursive reference builder."""
    return int(_child_seeds(np.asarray([seed], dtype=np.uint64), right)[0])


def _feature_subsets(seeds: np.ndarray, d: int, k: int) -> np.ndarray:
    """Per-node random k-of-d feature subsets for a whole frontier at once.

    A partial Fisher-Yates driven by a splitmix64 counter stream per node:
    k vectorized swap steps replace one ``Generator`` construction plus a
    ``permutation`` call *per node* — the dominant Python cost of a frontier
    level. Deterministic in the node seed and shared by both builders
    (modulo bias at d <= 64 vs 2^64 states is negligible).
    """
    seeds = np.asarray(seeds, dtype=np.uint64)
    W = len(seeds)
    perm = np.broadcast_to(np.arange(d), (W, d)).copy()
    rows = np.arange(W)
    state = seeds
    for i in range(min(k, d)):
        state = state + np.uint64(_GOLDEN)
        draw = _splitmix64(state)
        j = i + (draw % np.uint64(d - i)).astype(np.int64)
        pi = perm[rows, i].copy()
        perm[rows, i] = perm[rows, j]
        perm[rows, j] = pi
    return perm[:, :k]


class RegressionTree:
    """CART regression tree with random feature subsetting at each split.

    Two equivalent builders: ``"frontier"`` (default) grows the tree one
    *level* at a time — a vectorized best-split scan over all active nodes
    per depth against a shared presorted feature order — while
    ``"recursive"`` is the legacy node-by-node Python recursion kept as the
    equivalence reference. Both consume the per-node seed chain and compute
    split SSEs with the identical op sequence (padded per-node row cumsums),
    so they produce bit-identical trees.
    """

    def __init__(
        self,
        max_depth: int = 12,
        min_samples_split: int = 4,
        min_samples_leaf: int = 2,
        max_features: Optional[int] = None,
        rng: Optional[np.random.Generator] = None,
        builder: str = "frontier",
        root_seed: Optional[int] = None,
    ):
        self.max_depth = max_depth
        self.min_samples_split = min_samples_split
        self.min_samples_leaf = min_samples_leaf
        self.max_features = max_features
        self.rng = rng or np.random.default_rng()
        if builder not in ("frontier", "recursive"):
            raise ValueError(f"unknown tree builder {builder!r}")
        self.builder = builder
        # explicit root of the per-node seed chain (forest fits derive all
        # tree roots in one array pass); None = draw from self.rng
        self.root_seed = root_seed
        self.nodes: List[_Node] = []

    def _n_features(self, d: int) -> int:
        k = self.max_features or max(1, int(np.ceil(d / 1.5)))
        return min(k, d)

    def fit(self, X: np.ndarray, y: np.ndarray) -> "RegressionTree":
        X = np.asarray(X, dtype=float)
        y = np.asarray(y, dtype=float)
        self.nodes = []
        root_seed = self.root_seed if self.root_seed is not None else int(self.rng.integers(2**63))
        if self.builder == "recursive":
            self._build(X, y, np.arange(len(y)), 0, root_seed)
        else:
            self._build_frontier(X, y, root_seed)
        self._freeze()
        return self

    def _new_node(self, ysub: np.ndarray) -> int:
        node = _Node()
        # raw ufunc reduces replay numpy's _mean/_var op sequence (pairwise
        # umr_sum, then the same subtract/square/divide) without the method
        # dispatch overhead — bit-identical to ysub.mean()/ysub.var(), which
        # dominates per-node cost in both builders
        n = len(ysub)
        m = np.add.reduce(ysub) / n
        dev = ysub - m
        node.mean = float(m)
        node.var = float(np.add.reduce(dev * dev) / n)
        node.n = n
        self.nodes.append(node)
        return len(self.nodes) - 1

    def _build(self, X: np.ndarray, y: np.ndarray, idx: np.ndarray, depth: int, seed: int) -> int:
        nid = self._new_node(y[idx])
        node = self.nodes[nid]
        ysub = y[idx]
        if depth >= self.max_depth or len(idx) < self.min_samples_split or np.ptp(ysub) == 0:
            return nid
        d = X.shape[1]
        feats = _feature_subsets(np.asarray([seed], np.uint64), d, self._n_features(d))[0]
        best = None  # (score, feat, thr)
        for f in feats:
            xs = X[idx, f]
            order = np.argsort(xs, kind="stable")
            xs_sorted = xs[order]
            ys_sorted = ysub[order]
            # candidate split positions between distinct values
            csum = np.cumsum(ys_sorted)
            csum2 = np.cumsum(ys_sorted**2)
            n = len(idx)
            pos = np.arange(self.min_samples_leaf, n - self.min_samples_leaf + 1)
            pos = pos[(pos >= 1) & (pos <= n - 1)]  # both sides non-empty
            if len(pos) == 0:
                continue
            valid = xs_sorted[pos - 1] < xs_sorted[pos]  # split between distinct values
            pos = pos[valid]
            if len(pos) == 0:
                continue
            nl = pos.astype(float)
            nr = n - nl
            sl, sr = csum[pos - 1], csum[-1] - csum[pos - 1]
            s2l, s2r = csum2[pos - 1], csum2[-1] - csum2[pos - 1]
            sse = (s2l - sl**2 / nl) + (s2r - sr**2 / nr)
            j = int(np.argmin(sse))
            if best is None or sse[j] < best[0]:
                thr = 0.5 * (xs_sorted[pos[j] - 1] + xs_sorted[pos[j]])
                best = (float(sse[j]), int(f), float(thr))
        if best is None:
            return nid
        _, f, thr = best
        mask = X[idx, f] <= thr
        li, ri = idx[mask], idx[~mask]
        if len(li) < self.min_samples_leaf or len(ri) < self.min_samples_leaf:
            return nid
        node.feature = f
        node.threshold = thr
        node.left = self._build(X, y, li, depth + 1, _child_seed(seed, 0))
        node.right = self._build(X, y, ri, depth + 1, _child_seed(seed, 1))
        return nid

    def _build_frontier(self, X: np.ndarray, y: np.ndarray, root_seed: int) -> None:
        """Level-synchronous builder: one vectorized split scan per depth.

        Per level, the samples of every splittable node are grouped (via one
        stable argsort against the shared presorted feature order) into
        padded (node, position) matrices, and the SSE of every candidate
        split of every node is computed in a few whole-frontier array ops.
        Per-node Python work shrinks to the feature-subset draw and the
        child bookkeeping. Arithmetic is arranged to be bit-identical to the
        recursion: padded rows reproduce each node's own cumsum sequence,
        and argmins keep the recursion's first-strict-min tie-breaking.
        """
        n, d = X.shape
        k = self._n_features(d)
        msl = self.min_samples_leaf
        mss = self.min_samples_split
        sorted_mat = np.argsort(X, axis=0, kind="stable") if n else np.zeros((0, d), np.int64)
        root_idx = np.arange(n)
        self._new_node(y[root_idx])
        # frontier entries: (nid, idx, seed, splittable) — the splittable
        # flag (count and ptp gates, same booleans as the recursion's) is
        # computed when the node is created, from the y-gather it needs
        # anyway, so the level filter does no per-node array work
        root_ok = bool(
            n >= mss and n > 0 and np.maximum.reduce(y) != np.minimum.reduce(y)
        )
        frontier: List[Tuple[int, np.ndarray, int, bool]] = [(0, root_idx, root_seed, root_ok)]
        level = 0
        cols = np.arange(d)
        # one errstate for the whole build (padded lanes divide by zero
        # before they are masked invalid) instead of one context per level
        with np.errstate(divide="ignore", invalid="ignore"):
            self._frontier_levels(X, y, frontier, sorted_mat, cols, k, msl, mss, level)

    def _frontier_levels(self, X, y, frontier, sorted_mat, cols, k, msl, mss, level) -> None:
        n, d = X.shape
        while frontier and level < self.max_depth:
            active = [t for t in frontier if t[3]]
            if not active:
                break
            W = len(active)
            counts = np.array([len(t[1]) for t in active], dtype=np.int64)
            M = int(counts.max())
            n_act = int(counts.sum())
            starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
            slot_rep = np.repeat(np.arange(W), counts)
            cat = np.concatenate([t[1] for t in active])  # node-order sample ids
            # group every feature column by node in ONE stable argsort of the
            # (n, d) slot matrix: inactive samples carry sentinel W and sink
            # to the bottom; ties (same node) keep the presorted x-order
            slot_of = np.full(n, W, dtype=np.int64)
            slot_of[cat] = slot_rep
            gorder = np.argsort(slot_of[sorted_mat], axis=0, kind="stable")[:n_act]
            gidx = sorted_mat[gorder, cols[None, :]]  # (n_act, d)
            rowpos = np.arange(n_act) - starts[slot_rep]
            best_sse = np.full((W, d), np.inf)
            best_thr = np.zeros((W, d))
            # padded (node, position, feature) blocks: each (w, :, f) lane is
            # that node's feature-sorted value/target sequence, so the lane
            # cumsums replay the recursion's per-node cumsum bit-for-bit;
            # scatter by flat row index (node * M + position)
            dst = slot_rep * M + rowpos
            xs3 = np.zeros((W * M, d))
            ys3 = np.zeros((W * M, d))
            xs3[dst] = X[gidx, cols[None, :]]
            ys3[dst] = y[gidx]
            xs3 = xs3.reshape(W, M, d)
            ys3 = ys3.reshape(W, M, d)
            if M > 1:
                rows = np.arange(W)[:, None]
                pos = np.arange(1, M)
                nl = pos.astype(float)[None, :, None]
                cs = np.cumsum(ys3, axis=1)
                cs2 = np.cumsum(ys3**2, axis=1)
                sl = cs[:, :-1, :]
                s2l = cs2[:, :-1, :]
                tot = cs[rows[:, 0], counts - 1, :][:, None, :]
                tot2 = cs2[rows[:, 0], counts - 1, :][:, None, :]
                nr = counts[:, None, None] - nl
                sse = (s2l - sl**2 / nl) + ((tot2 - s2l) - (tot - sl) ** 2 / nr)
                valid = (
                    (pos[None, :, None] >= max(msl, 1))
                    & (pos[None, :, None] <= (counts[:, None] - max(msl, 1))[:, :, None])
                    & (xs3[:, :-1, :] < xs3[:, 1:, :])
                )
                sse = np.where(valid, sse, np.inf)
                j = np.argmin(sse, axis=1)  # (W, d): first minimum per lane
                # pos = arange(1, M), so lane argmin j maps to split position
                # j + 1; direct fancy gathers replace take_along_axis
                best_sse = sse[rows, j, cols[None, :]]
                bp = j + 1
                best_thr = 0.5 * (xs3[rows, bp - 1, cols[None, :]] + xs3[rows, bp, cols[None, :]])
            # whole-frontier feature pick + child masks: the per-node seed
            # chain and feature subsets come from one splitmix64 array
            # derivation (no per-node Generator constructions; the recursion
            # consumes the identical chain, so builders still agree
            # bit-for-bit); argmin over the perm gather keeps the
            # recursion's first-strict-min tie-breaking across features
            rows_w = np.arange(W)
            seeds_w = np.array([t[2] for t in active], dtype=np.uint64)
            lseeds = _child_seeds(seeds_w, 0)
            rseeds = _child_seeds(seeds_w, 1)
            P = _feature_subsets(seeds_w, d, k)
            FS = best_sse[rows_w[:, None], P]
            R = np.argmin(FS, axis=1)
            F = P[rows_w, R]
            split_ok = np.isfinite(FS[rows_w, R])
            THR = best_thr[rows_w, F]
            mask_flat = X[cat, np.repeat(F, counts)] <= np.repeat(THR, counts)
            next_frontier: List[Tuple[int, np.ndarray, int, bool]] = []
            for s in np.flatnonzero(split_ok):
                nid, idx, seed, _ = active[s]
                a = starts[s]
                m = mask_flat[a : a + counts[s]]
                li, ri = idx[m], idx[~m]
                if len(li) < msl or len(ri) < msl:
                    continue
                node = self.nodes[nid]
                node.feature = int(F[s])
                node.threshold = float(THR[s])
                yl, yr = y[li], y[ri]
                node.left = self._new_node(yl)
                node.right = self._new_node(yr)
                next_frontier.append((
                    node.left, li, int(lseeds[s]),
                    len(li) >= mss and np.maximum.reduce(yl) != np.minimum.reduce(yl),
                ))
                next_frontier.append((
                    node.right, ri, int(rseeds[s]),
                    len(ri) >= mss and np.maximum.reduce(yr) != np.minimum.reduce(yr),
                ))
            frontier = next_frontier
            level += 1

    def _freeze(self) -> None:
        """Pack nodes into arrays for vectorized descent."""
        n = len(self.nodes)
        self._feat = np.array([nd.feature for nd in self.nodes], dtype=np.int64)
        self._thr = np.array([nd.threshold for nd in self.nodes], dtype=float)
        self._left = np.array([nd.left for nd in self.nodes], dtype=np.int64)
        self._right = np.array([nd.right for nd in self.nodes], dtype=np.int64)
        self._mean = np.array([nd.mean for nd in self.nodes], dtype=float)
        self._var = np.array([nd.var for nd in self.nodes], dtype=float)
        # actual depth (children are appended after their parent, so one
        # forward pass assigns levels top-down)
        level = np.zeros(n, dtype=np.int64)
        depth = 0
        for i in range(n):
            if self._feat[i] >= 0:
                child_level = level[i] + 1
                level[self._left[i]] = child_level
                level[self._right[i]] = child_level
                depth = max(depth, int(child_level))
        self._depth = depth

    def predict(self, X: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized descent: O(depth * n) per call."""
        X = np.atleast_2d(np.asarray(X, dtype=float))
        if not hasattr(self, "_feat"):
            self._freeze()
        nid = np.zeros(len(X), dtype=np.int64)
        for _ in range(self.max_depth + 1):
            feat = self._feat[nid]
            active = feat >= 0
            if not active.any():
                break
            ai = np.where(active)[0]
            f = feat[ai]
            go_left = X[ai, f] <= self._thr[nid[ai]]
            nid[ai] = np.where(go_left, self._left[nid[ai]], self._right[nid[ai]])
        return self._mean[nid], self._var[nid]


# ---------------------------------------------------------------------------
# Packed forest plane (struct-of-arrays ensemble inference)
# ---------------------------------------------------------------------------


def packed_descend(
    feat: np.ndarray,
    thr: np.ndarray,
    child: np.ndarray,
    roots: np.ndarray,
    X: np.ndarray,
    depth: int,
) -> np.ndarray:
    """Level-synchronous descent over a packed node arena (numpy backend).

    Node encoding: leaves carry ``thr = +inf`` and self-loop children, so
    every lane takes the "left" branch into itself once it lands on a leaf
    and the loop needs no active-lane bookkeeping. ``child`` interleaves the
    two children of node ``i`` at ``[2i, 2i+1]`` so the post-comparison
    branch is a single gather. Returns leaf node ids, shape (T, N).
    """
    X = np.ascontiguousarray(X, dtype=float)
    N, D = X.shape
    T = len(roots)
    xflat = X.reshape(-1)
    col = np.broadcast_to((np.arange(N, dtype=np.intp) * D)[None, :], (T, N))
    nid = np.repeat(roots[:, None], N, axis=1)
    buf_i = np.empty((T, N), dtype=np.intp)
    buf_x = np.empty((T, N))
    buf_t = np.empty((T, N))
    for _ in range(depth):
        np.take(feat, nid, out=buf_i)
        buf_i += col
        np.take(xflat, buf_i, out=buf_x)
        np.take(thr, nid, out=buf_t)
        go_right = buf_x > buf_t
        nid += nid
        nid += go_right
        np.take(child, nid, out=nid)
    return nid


@dataclass
class PackedForest:
    """All trees of one forest stacked into a struct-of-arrays node arena.

    ``feat``/``thr``/``mean``/``var`` are per-node (leaves: feat clamped to
    0, thr = +inf); ``child`` holds the interleaved (left, right) pointers
    rebased to arena indices, with leaves pointing at themselves; ``roots``
    holds each tree's root index. ``y_mean``/``y_std`` carry the fit-time
    target normalization so predictions are self-contained.
    """

    feat: np.ndarray          # (n_nodes,) intp
    thr: np.ndarray           # (n_nodes,) float64
    child: np.ndarray         # (2 * n_nodes,) intp
    mean: np.ndarray          # (n_nodes,) float64
    var: np.ndarray           # (n_nodes,) float64
    roots: np.ndarray         # (n_trees,) intp
    depth: int                # max tree depth in the arena
    y_mean: float = 0.0
    y_std: float = 1.0

    @property
    def n_trees(self) -> int:
        return len(self.roots)

    @property
    def n_nodes(self) -> int:
        return len(self.feat)

    @staticmethod
    def from_trees(
        trees: Sequence[RegressionTree], y_mean: float = 0.0, y_std: float = 1.0
    ) -> "PackedForest":
        feat, thr, child, mean, var, roots = [], [], [], [], [], []
        off = 0
        depth = 0
        for tree in trees:
            if not hasattr(tree, "_feat"):
                tree._freeze()
            n = len(tree._feat)
            leaf = tree._feat < 0
            feat.append(np.where(leaf, 0, tree._feat))
            thr.append(np.where(leaf, np.inf, tree._thr))
            self_idx = np.arange(n)
            left = np.where(leaf, self_idx, tree._left) + off
            right = np.where(leaf, self_idx, tree._right) + off
            child.append(np.stack([left, right], axis=1).reshape(-1))
            mean.append(tree._mean)
            var.append(tree._var)
            roots.append(off)
            depth = max(depth, tree._depth)
            off += n
        return PackedForest(
            feat=np.concatenate(feat).astype(np.intp),
            thr=np.concatenate(thr),
            child=np.concatenate(child).astype(np.intp),
            mean=np.concatenate(mean),
            var=np.concatenate(var),
            roots=np.asarray(roots, dtype=np.intp),
            depth=depth,
            y_mean=y_mean,
            y_std=y_std,
        )

    # ------------------------------------------------------------- inference
    def predict_trees(
        self, X: np.ndarray, backend: str = "numpy", chunk_n: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Per-tree leaf stats, each shape (n_trees, n_points). ``chunk_n``
        bounds rows per descent dispatch (see ``forest_eval``) for oversized
        pools such as the batched Shapley composite tensor."""
        X = np.atleast_2d(np.asarray(X, dtype=float))
        if backend == "numpy" and chunk_n is None:
            nid = packed_descend(self.feat, self.thr, self.child, self.roots, X, self.depth)
            return np.take(self.mean, nid), np.take(self.var, nid)
        from ..kernels.forest_eval.ops import forest_eval

        return forest_eval(
            self.feat, self.thr, self.child, self.mean, self.var, self.roots,
            X, self.depth, backend=backend, chunk_n=chunk_n,
        )

    def combine(self, m_t: np.ndarray, v_t: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Ensemble (mean, var) from per-tree stats — the exact op sequence
        of the legacy per-tree loop, so results are bit-identical."""
        mean = m_t.mean(axis=0)
        var = v_t.mean(axis=0) + m_t.var(axis=0)
        var = np.maximum(var, 1e-10)
        return mean * self.y_std + self.y_mean, var * self.y_std**2

    def predict(
        self, X: np.ndarray, backend: str = "numpy", chunk_n: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        return self.combine(*self.predict_trees(X, backend=backend, chunk_n=chunk_n))


class ForestPlane:
    """Several packed forests fused into one arena for multi-source predict.

    The combined surrogate (one PRF per source task plus one per fidelity
    level, §6.2) evaluates every source on the same candidate pool; fusing
    the arenas means one gather descent over all sources' trees instead of a
    Python loop over forests. Per-source combination still runs on each
    forest's own tree slice, so the output matches per-forest ``predict``
    bit-for-bit.
    """

    def __init__(self, forests: Sequence[PackedForest]):
        if not forests:
            raise ValueError("ForestPlane needs at least one forest")
        self.forests = list(forests)
        offs = np.cumsum([0] + [f.n_nodes for f in forests])
        self.feat = np.concatenate([f.feat for f in forests])
        self.thr = np.concatenate([f.thr for f in forests])
        self.child = np.concatenate([f.child + off for f, off in zip(forests, offs)])
        self.mean = np.concatenate([f.mean for f in forests])
        self.var = np.concatenate([f.var for f in forests])
        self.roots = np.concatenate([f.roots + off for f, off in zip(forests, offs)])
        self.depth = max(f.depth for f in forests)
        tree_counts = np.cumsum([0] + [f.n_trees for f in forests])
        self.tree_slices = [
            (int(a), int(b)) for a, b in zip(tree_counts[:-1], tree_counts[1:])
        ]
        self.y_means = np.array([f.y_mean for f in forests])
        self.y_stds = np.array([f.y_std for f in forests])

    @staticmethod
    def from_forests(forests: Sequence[PackedForest]) -> "ForestPlane":
        return ForestPlane(forests)

    @property
    def uniform_tree_count(self) -> Optional[int]:
        """Trees per source when all sources agree, else None — the shape
        contract for the fused device paths (forest_plane_eval and the
        propose step), which slice the leaf-stat matrix per source."""
        counts = {f.n_trees for f in self.forests}
        return next(iter(counts)) if len(counts) == 1 else None

    def predict(
        self, X: np.ndarray, backend: str = "numpy", delta=None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Fused multi-source predict: (means, vars), each (S, N).

        ``delta`` opts the host path into bitvector pool scoring with
        per-base reuse: a ``(bases, base_of)`` pair (see
        ``chain.PoolPlan.leaf_stats``) from a mutation-heavy candidate
        pool. Leaf routing via the QuickScorer words is bit-identical to
        the gather descent, so the output is unchanged — only the
        per-candidate cost drops to the mutated coordinates plus
        O(log d) segment lookups. Ignored on accelerated backends (the
        fused device descent already carries those).
        """
        X = np.atleast_2d(np.asarray(X, dtype=float))
        if backend == "numpy":
            m_t = None
            if delta is not None and X.shape[0]:
                from ..kernels.forest_eval.chain import build_pool_plan_ex

                plan, _reason = build_pool_plan_ex(self, X.shape[1])
                if plan is not None:
                    _obs.count("forest_plane/chain_delta")
                    m_t, v_t = plan.leaf_stats(X, *delta)
            if m_t is None:
                _obs.count("forest_plane/numpy")
                nid = packed_descend(
                    self.feat, self.thr, self.child, self.roots, X, self.depth
                )
                m_t, v_t = np.take(self.mean, nid), np.take(self.var, nid)
        else:
            from ..kernels.forest_eval.ops import (
                available_backends, forest_eval, forest_plane_eval,
            )

            tree_counts = {f.n_trees for f in self.forests}
            if (backend in ("jax", "auto") and len(tree_counts) == 1
                    and "jax" in available_backends()):
                # uniform tree counts: descent + combine fuse on device
                out = forest_plane_eval(
                    self.feat, self.thr, self.child, self.mean, self.var,
                    self.roots, X, self.depth, self.y_means, self.y_stds,
                    trees_per_source=next(iter(tree_counts)),
                )
                _obs.count("forest_plane/fused_device")
                return out

            _obs.count("forest_plane/host_combine")
            m_t, v_t = forest_eval(
                self.feat, self.thr, self.child, self.mean, self.var, self.roots,
                X, self.depth, backend=backend,
            )
        means = np.empty((len(self.forests), X.shape[0]))
        vars_ = np.empty_like(means)
        for s, ((a, b), f) in enumerate(zip(self.tree_slices, self.forests)):
            means[s], vars_[s] = f.combine(m_t[a:b], v_t[a:b])
        return means, vars_


class ProbabilisticRandomForest(Surrogate):
    def __init__(
        self,
        n_trees: int = 10,
        max_depth: int = 12,
        min_samples_split: int = 4,
        min_samples_leaf: int = 1,
        bootstrap: bool = True,
        seed: int = 0,
        backend: Optional[str] = None,
    ):
        self.n_trees = n_trees
        self.max_depth = max_depth
        self.min_samples_split = min_samples_split
        self.min_samples_leaf = min_samples_leaf
        self.bootstrap = bootstrap
        self.seed = seed
        # "loop" = legacy per-tree reference; "numpy"/"jax"/"pallas"/"auto"
        # select the packed-descent backend (None = module default)
        self.backend = backend or get_forest_backend()
        self.trees: List[RegressionTree] = []
        self._packed: Optional[PackedForest] = None
        self._y_mean = 0.0
        self._y_std = 1.0
        self.X_: Optional[np.ndarray] = None
        self.y_: Optional[np.ndarray] = None

    def fit(self, X: np.ndarray, y: np.ndarray) -> "ProbabilisticRandomForest":
        X = np.atleast_2d(np.asarray(X, dtype=float))
        y = np.asarray(y, dtype=float)
        with _obs.span("forest_fit", n_obs=len(y), dim=X.shape[1],
                       trees=self.n_trees):
            self._fit_trees(X, y)
        return self

    def _fit_trees(self, X: np.ndarray, y: np.ndarray) -> None:
        self.X_, self.y_ = X, y
        self._y_mean = float(y.mean()) if len(y) else 0.0
        self._y_std = float(y.std()) or 1.0
        yn = (y - self._y_mean) / self._y_std
        rng = np.random.default_rng(self.seed)
        self.trees = []
        self._packed = None
        n = len(y)
        # "loop" pins the legacy recursive builder along with the per-tree
        # predict loop; every packed backend fits via the level-synchronous
        # frontier builder (bit-identical trees either way).
        builder = "recursive" if self.backend == "loop" else "frontier"
        # one splitmix64 array derivation replaces the per-tree default_rng
        # constructions: a single PCG64 array draw seeds a counter stream
        # per tree, which yields every tree's bootstrap rows and the root of
        # its per-node seed chain without touching a Generator again
        tree_seeds = rng.integers(2**63, size=self.n_trees, dtype=np.uint64)
        root_seeds = _splitmix64(tree_seeds ^ np.uint64(0xD1B54A32D192ED03)) & np.uint64(
            (1 << 63) - 1
        )
        if self.bootstrap and n > 1:
            ctr = tree_seeds[:, None] + np.uint64(_GOLDEN) * np.arange(
                1, n + 1, dtype=np.uint64
            )
            boot = (_splitmix64(ctr) % np.uint64(n)).astype(np.intp)
        else:
            boot = np.broadcast_to(np.arange(n), (self.n_trees, n))
        for t in range(self.n_trees):
            tree = RegressionTree(
                max_depth=self.max_depth,
                min_samples_split=self.min_samples_split,
                min_samples_leaf=self.min_samples_leaf,
                root_seed=int(root_seeds[t]),
                builder=builder,
            )
            tree.fit(X[boot[t]], yn[boot[t]])
            self.trees.append(tree)

    def pack(self) -> PackedForest:
        """Stack all trees into one struct-of-arrays arena (cached per fit)."""
        if not self.trees:
            raise ValueError("pack() before fit()")
        if self._packed is None:
            self._packed = PackedForest.from_trees(self.trees, self._y_mean, self._y_std)
        return self._packed

    def predict(self, X: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        X = np.atleast_2d(np.asarray(X, dtype=float))
        if not self.trees:
            return np.zeros(len(X)), np.ones(len(X))
        if self.backend == "loop":
            return self.predict_loop(X)
        return self.pack().predict(X, backend=self.backend)

    def predict_loop(self, X: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Legacy per-tree loop — kept as the reference the packed plane is
        equivalence-tested against."""
        X = np.atleast_2d(np.asarray(X, dtype=float))
        if not self.trees:
            return np.zeros(len(X)), np.ones(len(X))
        ms = np.empty((self.n_trees, len(X)))
        vs = np.empty((self.n_trees, len(X)))
        for i, tree in enumerate(self.trees):
            ms[i], vs[i] = tree.predict(X)
        mean = ms.mean(axis=0)
        # law of total variance across trees
        var = vs.mean(axis=0) + ms.var(axis=0)
        var = np.maximum(var, 1e-10)
        return mean * self._y_std + self._y_mean, var * self._y_std**2


# ---------------------------------------------------------------------------
# Forest factory — the one PRF construction point the whole repo shares
# ---------------------------------------------------------------------------

_DEFAULT_BACKEND = "numpy"


def get_forest_backend() -> str:
    return _DEFAULT_BACKEND


def set_forest_backend(backend: str) -> None:
    """Set the module-default packed-descent backend ("loop" forces the
    legacy per-tree reference everywhere — used by equivalence tests)."""
    global _DEFAULT_BACKEND
    _DEFAULT_BACKEND = backend


@contextlib.contextmanager
def forest_backend(backend: str):
    prev = get_forest_backend()
    set_forest_backend(backend)
    try:
        yield
    finally:
        set_forest_backend(prev)


def make_forest(seed: int = 0, backend: Optional[str] = None, **kwargs) -> ProbabilisticRandomForest:
    """Packed factory: every surrogate stack in the repo builds PRFs here."""
    return ProbabilisticRandomForest(seed=seed, backend=backend, **kwargs)


# ---------------------------------------------------------------------------
# Gaussian process (for the Tuneful MTGP baseline)
# ---------------------------------------------------------------------------


class GaussianProcess(Surrogate):
    """Exact GP with Matérn-5/2 kernel, constant mean, jitter + noise MLE-lite.

    Hyperparameters are set by a small grid search over (lengthscale, noise)
    maximizing the log marginal likelihood — adequate at these data sizes.
    """

    def __init__(self, lengthscales=(0.1, 0.2, 0.5, 1.0, 2.0), noises=(1e-6, 1e-4, 1e-2)):
        self.lengthscales = lengthscales
        self.noises = noises
        self.X_: Optional[np.ndarray] = None
        self.alpha_: Optional[np.ndarray] = None
        self.L_: Optional[np.ndarray] = None
        self.ls_: float = 0.5
        self.noise_: float = 1e-4
        self._y_mean = 0.0
        self._y_std = 1.0

    @staticmethod
    def _matern52(A: np.ndarray, B: np.ndarray, ls: float) -> np.ndarray:
        d2 = np.maximum(
            (A**2).sum(1)[:, None] + (B**2).sum(1)[None, :] - 2 * A @ B.T, 0.0
        )
        r = np.sqrt(d2) / ls
        s5r = np.sqrt(5.0) * r
        return (1 + s5r + 5 * d2 / (3 * ls**2)) * np.exp(-s5r)

    def fit(self, X: np.ndarray, y: np.ndarray) -> "GaussianProcess":
        X = np.atleast_2d(np.asarray(X, dtype=float))
        y = np.asarray(y, dtype=float)
        self._y_mean = float(y.mean()) if len(y) else 0.0
        self._y_std = float(y.std()) or 1.0
        yn = (y - self._y_mean) / self._y_std
        best = (np.inf, None)
        n = len(X)
        for ls in self.lengthscales:
            K0 = self._matern52(X, X, ls)
            for noise in self.noises:
                K = K0 + (noise + 1e-8) * np.eye(n)
                try:
                    L = np.linalg.cholesky(K)
                except np.linalg.LinAlgError:
                    continue
                alpha = np.linalg.solve(L.T, np.linalg.solve(L, yn))
                nll = 0.5 * yn @ alpha + np.log(np.diag(L)).sum()
                if nll < best[0]:
                    best = (nll, (ls, noise, L, alpha))
        if best[1] is None:
            raise RuntimeError("GP fit failed")
        self.ls_, self.noise_, self.L_, self.alpha_ = best[1]
        self.X_ = X
        return self

    def predict(self, X: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        X = np.atleast_2d(np.asarray(X, dtype=float))
        Ks = self._matern52(X, self.X_, self.ls_)
        mean = Ks @ self.alpha_
        v = np.linalg.solve(self.L_, Ks.T)
        var = np.maximum(1.0 - (v**2).sum(axis=0), 1e-10)
        return mean * self._y_std + self._y_mean, var * self._y_std**2
