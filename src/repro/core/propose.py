"""Host-side driver for the fused on-device propose step.

:class:`ProposeEngine` owns everything the jitted program in
``repro.kernels.forest_eval.propose`` needs resident on device: the fused
``ForestPlane`` arena (via the acquisition plane LRU, so cache stats stay
in one place), per-source denorm stats, and the sample-space transform
tables — uploaded once per (plane / space) identity and reused across
propose calls. It also threads the JAX PRNG key between steps and tracks
every static jit signature it has launched, which is the jit-cache-growth
guard surface for the pool-scaling bench (compile count must stay bounded
by the number of shape buckets).

Two pool modes (see ``acquisition.set_acquisition_pool``):

* ``device`` — the pool is drawn on device from the threaded key
  (uniform + LHS halves over the sample space's restriction CDFs); only
  the top-k rows come back to the host. Fastest path; changes fixed-seed
  pool draws (SEED NOTE in CHANGES.md).
* ``host`` — the generator's numpy pool is uploaded and only scoring +
  selection run on device, so the chosen indices are bit-identical to the
  staged numpy path (this is what the MFTune trajectory-identity test
  pins). The pool goes up as its binary64 bit patterns and the split
  thresholds as monotone uint64 order keys (``rank.monotone_keys``); the
  program keys the pool with integer ops, so leaf routing compares the
  host's exact binary64 values on any device: XLA:TPU keeps float64 as a
  pair of float32, which rounds away the last bits that tell a pool value
  from a threshold one ulp away.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..kernels.forest_eval.ops import depth_bucket
from .surrogate import ForestPlane, ProbabilisticRandomForest

__all__ = ["ProposeEngine"]

_CONST_SIG = (4, False, False, False, False, 1)  # dropped knob: unit default

# descent="auto" picks the merged QuickScorer tables at pool buckets >= this
# (measured crossover on XLA:CPU — below it the per-feature table gathers
# cost more than the pointer-chasing they replace), gather descent below
QS_AUTO_MIN = 32768


def _shapes(*trees) -> tuple:
    """Array shapes of device inputs: with the static arguments, what the
    jitted program is compiled for."""
    import jax

    return tuple(a.shape for a in jax.tree.leaves(trees))


def _default_rank_impl() -> str:
    from ..kernels.forest_eval import rank as _rank
    return _rank.default_rank_impl()


class ProposeEngine:
    def __init__(self, space, seed: int = 0, pool_size: int = 256,
                 margin: int = 64, arena_cache: int = 8):
        self.space = space
        self.seed = seed
        self.pool_size = pool_size
        self.margin = margin
        self._key = None
        self._zi = None
        self._arena_cache: "OrderedDict[int, tuple]" = OrderedDict()
        self._arena_cache_max = arena_cache
        self._tables_cache: "OrderedDict[int, tuple]" = OrderedDict()
        # every static jit signature launched; the bench asserts this stays
        # <= the number of shape buckets it sweeps (jit-cache-growth guard)
        self.compiled: set = set()

    # ----------------------------------------------------------- availability
    @staticmethod
    def available() -> bool:
        try:
            import jax  # noqa: F401
            return True
        except ImportError:
            return False

    @staticmethod
    def decline_reason(models: Sequence) -> str:
        """Why the fused program does not apply to ``models`` ("" when it
        does): it needs fitted PRFs on a packed backend with a uniform tree
        count (the per-source slice contract)."""
        if not models:
            return "no_models"
        if not all(isinstance(m, ProbabilisticRandomForest) and m.trees
                   for m in models):
            return "not_fitted_prf"
        if any(m.backend == "loop" for m in models):
            return "loop_backend"
        if len({len(m.trees) for m in models}) != 1:
            return "mixed_tree_counts"
        return ""

    @staticmethod
    def fusable(models: Sequence) -> bool:
        """True when the fused program applies (see :meth:`decline_reason`)."""
        return not ProposeEngine.decline_reason(models)

    # --------------------------------------------------------------- uploads
    def _x64(self):
        import jax
        return jax.enable_x64(True)

    def _plane(self, models: Sequence) -> ForestPlane:
        from .acquisition import _plane_for
        return _plane_for([m.pack() for m in models])

    def _arena_for(self, plane: ForestPlane, keyed: bool = False
                   ) -> Tuple[tuple, tuple, Optional[tuple], str]:
        """Device-resident (arena, ystats, qs_plan, qs_reason) for a fused
        plane, LRU-cached by plane identity. With ``keyed`` the arena's and
        the plan's thresholds are the host-pool order keys instead of
        float64 (same device arrays otherwise). Unlike ``ops._device_arena``
        this keeps the exact tree set (no power-of-two root padding):
        padded trees would pollute the per-source combine and double the
        descent work (the node arrays do pad, with unreachable leaves).
        ``qs_plan`` is the uploaded merged QuickScorer table
        set (None when a tree exceeds 128 leaves — gather descent then,
        with the decline cause in ``qs_reason``)."""
        key = id(plane)
        hit = self._arena_cache.get(key)
        if hit is None or hit[0] is not plane:
            import jax.numpy as jnp

            from ..kernels.forest_eval.propose import build_qs_plan_ex

            # the upload dtype follows the ambient x64 flag; entering the
            # scope here keeps a direct caller outside propose()/score_topk()
            # from silently caching a float32 arena
            with self._x64():
                hit = self._arena_upload(plane, jnp, build_qs_plan_ex, key)
        self._arena_cache.move_to_end(key)
        _, arena, ystats, qs, qs_reason, arena_k, qs_k = hit
        if keyed:
            return arena_k, ystats, qs_k, qs_reason
        return arena, ystats, qs, qs_reason

    def _arena_upload(self, plane, jnp, build_qs_plan_ex, key):
        from ..kernels.forest_eval.ops import pad_nodes
        from ..kernels.forest_eval.rank import monotone_keys

        # nodes pad to a power-of-two bucket so that refits reuse the
        # compiled program; int32 indices (64-bit ints are emulated on TPU)
        feat, thr, child, mean, var = pad_nodes(
            plane.feat, plane.thr, plane.child, plane.mean, plane.var)
        arena = (
            jnp.asarray(feat, dtype=jnp.int32), jnp.asarray(thr),
            jnp.asarray(child, dtype=jnp.int32), jnp.asarray(mean),
            jnp.asarray(var), jnp.asarray(plane.roots, dtype=jnp.int32),
        )
        # y_std**2 on host with the same python-float pow PackedForest.combine
        # uses, so the device denorm replays it exactly
        ystats = (
            jnp.asarray(plane.y_means),
            jnp.asarray(plane.y_stds),
            jnp.asarray(np.array([f.y_std ** 2 for f in plane.forests])),
        )
        qs_host, qs_reason = build_qs_plan_ex(
            plane.feat, plane.thr, plane.child, plane.mean, plane.var,
            plane.roots, self.space.dim,
        )
        arena_k = arena[:1] + (jnp.asarray(monotone_keys(thr, descending=False)),) + arena[2:]
        qs = qs_k = None
        if qs_host is not None:
            qs = tuple(jnp.asarray(a) for a in qs_host)
            qs_k = (jnp.asarray(monotone_keys(qs_host[0], descending=False)),) + qs[1:]
        entry = (plane, arena, ystats, qs, qs_reason, arena_k, qs_k)
        self._arena_cache[key] = entry
        while len(self._arena_cache) > self._arena_cache_max:
            self._arena_cache.popitem(last=False)
        return entry

    def _tables_for(self, sample_space) -> dict:
        """Device transform tables for pool draws over ``sample_space``
        (``pack_draw_tables``), mapped onto the *full* space's column order
        (dropped knobs become constant unit-default columns). Restrictions
        don't change a knob's lo/hi/log, so the sample space's unit
        transform is the full space's.
        """
        key = id(sample_space)
        hit = self._tables_cache.get(key)
        if hit is not None and hit[0] is sample_space:
            self._tables_cache.move_to_end(key)
            return hit[1]
        import jax.numpy as jnp

        from ..kernels.forest_eval.propose import pack_draw_tables

        ss_plane = sample_space.plane()
        sig_ss, cols_ss = ss_plane.device_tables()
        pos = {name: i for i, name in enumerate(sample_space.names)}
        fplane = self.space.plane()
        unit_default = fplane.encode_values(
            np.atleast_2d(fplane.default_row.copy())
        )[0]
        sig: List[tuple] = []
        cols: List[tuple] = []
        for j, name in enumerate(self.space.names):
            i = pos.get(name)
            if i is None:
                sig.append(_CONST_SIG)
                cols.append((np.array([unit_default[j]]),))
            else:
                sig.append(sig_ss[i])
                cols.append(cols_ss[i])
        with self._x64():
            tabs = {name: jnp.asarray(a)
                    for name, a in pack_draw_tables(sig, cols).items()}
        self._tables_cache[key] = (sample_space, tabs)
        while len(self._tables_cache) > self._arena_cache_max:
            self._tables_cache.popitem(last=False)
        return tabs

    def _next_key(self):
        import jax
        if self._key is None:
            self._key = jax.random.PRNGKey(self.seed)
        self._key, sub = jax.random.split(self._key)
        return sub

    def _zero(self):
        import jax.numpy as jnp
        if self._zi is None:
            self._zi = jnp.zeros((), dtype=jnp.uint64)
        return self._zi

    @staticmethod
    def _pow2(n: int) -> int:
        return 1 << (max(int(n), 1) - 1).bit_length()

    # ---------------------------------------------------------------- propose
    def propose(
        self,
        models: Sequence,
        incumbents: Sequence[float],
        weights: Sequence[float],
        n: int,
        sample_space=None,
        descent: str = "auto",
        rank_impl: Optional[str] = None,
        pool_size: Optional[int] = None,
        steps: Optional[int] = None,
    ):
        """Device-pool mode: draw a fresh on-device pool from the threaded
        key and return the fused top-k as ``(idx, unit_rows, agg)`` numpy
        arrays (k = n + margin rows for host-side exclusion dedup). With
        ``steps`` set, runs that many iterations under one ``lax.scan`` and
        returns stacked outputs with a leading steps axis."""
        from ..kernels.forest_eval import propose as P

        with self._x64():
            with obs.span("propose_prepare", mode="device_pool"):
                plane = self._plane(models)
                tps = plane.uniform_tree_count
                if tps is None:
                    raise ValueError("propose requires a uniform tree count per source")
                arena, ystats, qs, qs_reason = self._arena_for(plane)
                tabs = self._tables_for(sample_space or self.space)
                import jax.numpy as jnp

                n_pool = P.pool_bucket(pool_size or self.pool_size)
                if descent == "auto":
                    descent = "qs" if qs is not None and n_pool >= QS_AUTO_MIN else "jax"
                elif descent == "qs" and qs is None:
                    raise ValueError(f"no QuickScorer plan: {qs_reason}")
                if rank_impl is None:
                    rank_impl = _default_rank_impl()
                k = min(self._pow2(n + self.margin), n_pool)
                S = len(plane.forests)
                inc = jnp.asarray(np.asarray(incumbents, dtype=float))
                w = jnp.asarray(np.asarray(weights, dtype=float))
                qs = qs if descent == "qs" else None
                depth = depth_bucket(plane.depth)
                self.compiled.add(("propose", n_pool, depth, S, tps, k,
                                   _shapes(arena, qs, tabs), rank_impl,
                                   descent, steps))
            jitted = P._propose_jit if steps is None else P._propose_scan_jit
            cached = jitted._cache_size()
            with obs.span("propose_step", mode="device_pool", bucket=n_pool,
                          descent=descent, rank=rank_impl, sources=S,
                          k=k) as sp:
                with obs.span("propose_dispatch"):
                    if steps is None:
                        idx, Xu, agg = P.propose_step(
                            self._next_key(), tabs, arena, ystats, inc, w,
                            self._zero(), n_pool=n_pool, depth=depth,
                            n_sources=S, tps=tps, k=k, descent=descent,
                            rank_impl=rank_impl, qs=qs,
                        )
                    else:
                        if self._key is None:
                            import jax
                            self._key = jax.random.PRNGKey(self.seed)
                        self._key, (idx, Xu, agg) = P.propose_scan(
                            self._key, tabs, arena, ystats, inc, w,
                            self._zero(), n_pool=n_pool, depth=depth,
                            n_sources=S, tps=tps, k=k, descent=descent,
                            rank_impl=rank_impl, steps=steps, qs=qs,
                        )
                with obs.span("propose_fetch"):
                    out = np.asarray(idx), np.asarray(Xu), np.asarray(agg)
                # the call compiled, or loaded from the persistent cache, a
                # program this process had not run yet
                sp.set(compile=jitted._cache_size() > cached)
            return out

    def score_topk(
        self,
        models: Sequence,
        X_unit: np.ndarray,
        incumbents: Sequence[float],
        weights: Sequence[float],
        n: int,
        descent: str = "auto",
        rank_impl: Optional[str] = None,
    ) -> np.ndarray:
        """Host-pool mode: score an uploaded unit pool and return the top-n
        candidate indices, bit-identical to the staged numpy path
        (``score_sources`` → ``aggregate_ranks`` → stable argsort) on
        XLA:CPU. The pool travels as its bit patterns and is routed on order
        keys, so the leaf routing is the host's on every device."""
        from ..kernels.forest_eval import propose as P

        with self._x64():
            with obs.span("propose_prepare", mode="host_pool"):
                X_unit = np.atleast_2d(np.asarray(X_unit, dtype=float))
                plane = self._plane(models)
                tps = plane.uniform_tree_count
                if tps is None:
                    raise ValueError("score_topk requires a uniform tree count per source")
                arena, ystats, qs, qs_reason = self._arena_for(plane, keyed=True)
                import jax.numpy as jnp

                N, D = X_unit.shape
                bucket = P.pool_bucket(N)
                if descent == "auto":
                    descent = "qs" if qs is not None and bucket >= QS_AUTO_MIN else "jax"
                elif descent == "qs" and qs is None:
                    raise ValueError(f"no QuickScorer plan: {qs_reason}")
                if rank_impl is None:
                    rank_impl = _default_rank_impl()
                Xp = np.zeros((bucket, D))
                Xp[:N] = X_unit
                k = min(self._pow2(n), bucket)
                S = len(plane.forests)
                inc = jnp.asarray(np.asarray(incumbents, dtype=float))
                w = jnp.asarray(np.asarray(weights, dtype=float))
                qs = qs if descent == "qs" else None
                depth = depth_bucket(plane.depth)
                self.compiled.add(("score", bucket, depth, S, tps, k,
                                   _shapes(arena, qs), rank_impl, descent))
                zi = self._zero()
            cached = P._propose_jit._cache_size()
            with obs.span("propose_step", mode="host_pool", bucket=bucket,
                          descent=descent, rank=rank_impl, sources=S, k=k,
                          occupancy=N / bucket) as sp:
                with obs.span("propose_upload"):
                    X = jnp.asarray(Xp.view(np.uint64))
                with obs.span("propose_dispatch"):
                    idx, _, _ = P.propose_step(
                        None, None, arena, ystats, inc, w, zi,
                        n_pool=bucket, depth=depth, n_sources=S, tps=tps,
                        k=k, descent=descent, rank_impl=rank_impl,
                        X=X, n_valid=N, qs=qs,
                    )
                with obs.span("propose_fetch"):
                    idx = np.asarray(idx)
                sp.set(compile=P._propose_jit._cache_size() > cached)
            return idx[: min(n, N)]
