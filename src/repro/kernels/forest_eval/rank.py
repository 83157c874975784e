"""Radix rank kernels for weighted rank aggregation (numpy + jax + pallas).

``aggregate_ranks`` needs, per score row, the float rank each candidate
would get under ``np.argsort(-scores, kind="stable")`` — rank 0 = highest
score, ties broken by index. The comparison sorts spent ~0.6 s at MFTune's
12 x 131072 propose scale (lax.sort u64+i32 on XLA:CPU) and ~0.18 s
(numpy f64 stable argsort); both are the measured rank-aggregation floor
of the fused propose step (see ROADMAP / PR 7).

This module replaces them with an LSD radix over a *monotone uint64
remap* of the negated scores, in the package's usual triple pattern:

* numpy (:func:`rank_rows_radix`) — four 16-bit digit passes. numpy's
  stable argsort on a ``uint16`` column IS an O(n) counting/radix sort in
  C, so composing ``perm = perm[argsort(digit[perm])]`` low-to-high digit
  replays a textbook LSD radix at memory speed: ~6.5x over the lax.sort
  path and ~2.4x over the f64 argsort at 12 x 131072 on this host. The
  permutation equals ``np.argsort(keys, kind="stable")`` *exactly* (each
  pass is stable, u64 order = descending float order by construction), so
  ranks are bit-identical to the reference — including all-tied rows, ±0
  and subnormal scores (pinned in tests/test_rank_kernel.py).
* jax (:func:`rank_rows_traced`) — three trace-time implementations:
  ``"callback"`` hands the key halves to the numpy radix through a raw
  ``emit_python_callback`` primitive (on the CPU backend the "device"
  *is* the host, so the callback is a plain function call on the operand
  buffers — the honest fast path inside the fused propose program; it
  exists only there); ``"sort"`` is the portable pure-XLA stable sort
  (:func:`stable_argsort`, the only impl that compiles for a TPU);
  ``"pallas"`` uses the histogram kernel below.
* pallas (:func:`radix_rank_pallas`) — 8-bit histogram radix passes, one
  program per score row: digit histogram → exclusive prefix (digit base)
  → stable within-digit offsets from a blocked lower-triangular equality
  count plus a running per-digit occupancy. Interpreted on the CPU
  backend; Mosaic refuses it on a TPU v5e.

Scores must be NaN-free (numpy sorts any NaN last; the monotone remap
would order -NaN first). EI scores — the only caller — are >= 0 or the
padding sentinels (-1 / -inf), all NaN-free.
"""

from __future__ import annotations

import functools

import numpy as np

try:
    import jax
    import jax.numpy as jnp
    from jax import lax
except ImportError:  # pragma: no cover - jax ships with the image
    jax = None


__all__ = [
    "RADIX_MIN_N",
    "RANK_IMPLS",
    "monotone_keys",
    "keys_from_bits",
    "radix_argsort",
    "rank_rows_radix",
    "rank_rows_reference",
    "rank_rows",
    "default_rank_impl",
    "monotone_keys_traced",
    "split_keys_argsort",
    "stable_argsort",
    "rank_rows_traced",
    "radix_rank_pallas",
]

# numpy dispatch crossover: below this row length the single f64 stable
# argsort beats four digit passes (measured ~1024 on this host; radix is
# 1.7x at 4096 and ~2.4x from 16384 up)
RADIX_MIN_N = 1024

# trace-time implementations of the rank matrix inside a jitted program
RANK_IMPLS = ("callback", "sort", "pallas")

_U16 = np.uint64(0xFFFF)
_MSB = np.uint64(1) << np.uint64(63)


# ---------------------------------------------------------------------------
# numpy: monotone key remap + 16-bit digit-pass radix
# ---------------------------------------------------------------------------


def monotone_keys(scores: np.ndarray, descending: bool = True) -> np.ndarray:
    """uint64 keys whose ascending order is the descending float order
    (the ascending one with ``descending=False``).

    Everything happens in the integer domain: IEEE negation is a sign-bit
    XOR, ±0 detection is a bit-pattern test, and the classic monotone
    remap (negatives complement, positives set the MSB) is pure bit
    arithmetic. No float op ever touches the values — deliberately, since
    XLA:CPU runs its compute threads with FTZ/DAZ set, and a float
    ``-scores`` / ``== 0.0`` there silently flushes subnormal scores into
    the zero tie group (observed: all ±subnormals collapsing onto ±0 when
    the same remap ran inside a ``pure_callback``). The integer path is
    bit-exact under any FPU mode. ±0 compare equal as floats but differ
    bitwise, so they canonicalize to one key — ties then fall back to
    index order exactly like the stable numpy argsort.
    """
    x = np.ascontiguousarray(np.asarray(scores, dtype=np.float64))
    bits = x.view(np.uint64)
    if descending:
        bits = bits ^ _MSB  # negate: flip the sign bit
    bits = np.where((bits & ~_MSB) == 0, np.uint64(0), bits)  # ±0 -> +0
    sign = (bits >> np.uint64(63)).astype(bool)
    return np.where(sign, ~bits, bits | _MSB)


def _radix_perm_row(keys: np.ndarray) -> np.ndarray:
    """Stable ascending argsort of a u64 key row via 4 LSD 16-bit passes.

    numpy's stable argsort on uint16 is an O(n) counting sort; composing
    the per-digit permutations low-to-high digit is the classic LSD radix
    and yields the exact stable u64 argsort.
    """
    perm = np.argsort((keys & _U16).astype(np.uint16), kind="stable")
    for shift in (np.uint64(16), np.uint64(32), np.uint64(48)):
        digit = ((keys >> shift) & _U16).astype(np.uint16)
        perm = perm[np.argsort(digit[perm], kind="stable")]
    return perm


def radix_argsort(scores: np.ndarray) -> np.ndarray:
    """Row-wise ``np.argsort(-scores, axis=1, kind="stable")``, via radix."""
    K = monotone_keys(np.atleast_2d(scores))
    out = np.empty(K.shape, dtype=np.intp)
    for s in range(K.shape[0]):
        out[s] = _radix_perm_row(K[s])
    return out


def rank_rows_radix(scores: np.ndarray) -> np.ndarray:
    """Float ranks per row (rank 0 = best) via the radix permutation."""
    K = monotone_keys(np.atleast_2d(scores))
    out = np.empty(K.shape, dtype=np.float64)
    r = np.arange(K.shape[1], dtype=np.float64)
    for s in range(K.shape[0]):
        out[s, _radix_perm_row(K[s])] = r
    return out


def rank_rows_reference(scores: np.ndarray) -> np.ndarray:
    """The pinned reference: stable f64 argsort + put_along_axis."""
    scores = np.atleast_2d(np.asarray(scores, dtype=float))
    s, n = scores.shape
    order = np.argsort(-scores, axis=1, kind="stable")
    ranks = np.empty((s, n), dtype=float)
    np.put_along_axis(
        ranks, order, np.broadcast_to(np.arange(n, dtype=float), (s, n)), axis=1
    )
    return ranks


def rank_rows(scores: np.ndarray) -> np.ndarray:
    """Rank matrix with the numpy dispatch: radix above RADIX_MIN_N,
    reference argsort below (both produce bit-identical ranks)."""
    scores = np.atleast_2d(np.asarray(scores, dtype=float))
    if scores.shape[1] >= RADIX_MIN_N:
        return rank_rows_radix(scores)
    return rank_rows_reference(scores)


# ---------------------------------------------------------------------------
# jax: traced rank matrix (callback / sort / pallas)
# ---------------------------------------------------------------------------


def default_rank_impl() -> str:
    """Trace-time default: the host radix callback on CPU (where device
    memory is host memory), the pure-XLA sort elsewhere."""
    if jax is None:
        return "sort"
    return "callback" if jax.default_backend() == "cpu" else "sort"


def monotone_keys_traced(scores, descending: bool = True):
    """Traced :func:`monotone_keys` — same all-integer remap, so the key
    order survives XLA:CPU's FTZ/DAZ compute threads bit-exactly.
    ``descending=False`` skips the negation (ascending float order)."""
    return keys_from_bits(lax.bitcast_convert_type(scores, jnp.uint64),
                          descending)


def keys_from_bits(bits, descending: bool = True):
    """Traced :func:`monotone_keys` of float64 values given as their raw
    uint64 bit patterns (``x.view(np.uint64)`` on the host). Integer ops
    only, so it also runs on XLA:TPU, which refuses the f64 -> integer
    bitcast and rounds uploaded float64 values to a float32 pair."""
    msb = jnp.uint64(1) << jnp.uint64(63)
    if descending:
        bits = bits ^ msb
    bits = jnp.where((bits & ~msb) == 0, jnp.uint64(0), bits)
    sign = (bits >> jnp.uint64(63)).astype(bool)
    return jnp.where(sign, ~bits, bits | msb)


def _argsort_keys(keys):
    """int32 stable argsort along the last axis by a list of same-shape keys,
    most significant first: LSD passes of a one-key stable sort that carries
    the permutation as payload."""
    perm = lax.broadcasted_iota(jnp.int32, keys[0].shape, keys[0].ndim - 1)
    for i, key in enumerate(reversed(keys)):
        if i:
            key = jnp.take_along_axis(key, perm, axis=-1)
        _, perm = lax.sort((key, perm), dimension=key.ndim - 1,
                           is_stable=True, num_keys=1)
    return perm


def _monotone_u32(f):
    """uint32 keys whose unsigned order is the float32 order of ``f``."""
    bits = lax.bitcast_convert_type(f, jnp.uint32)
    msb = jnp.uint32(1 << 31)
    return jnp.where((bits & msb) != 0, ~bits, bits | msb)


def split_keys_argsort(x, descending: bool = False):
    """:func:`stable_argsort` without an f64 -> integer bitcast.

    The float64 key splits into three float32 pieces, ``p0 = f32(x)``,
    ``p1 = f32(x - p0)``, ``p2 = f32(x - p0 - p1)``: each subtraction is
    exact and each rounding monotone, so the lexicographic order of the
    pieces is the order of ``x`` for every finite value in float32's
    normal range (±0 fold to one key first; values beyond it tie with
    their float32 overflow). Each piece maps to a monotone uint32 key and
    the sort runs as three stable uint32 passes: XLA:TPU compiles those in
    seconds, where one f64-keyed sort of a 4096-wide row takes minutes.
    """
    k = -x if descending else x
    r = jnp.where(k == 0, jnp.zeros_like(k), k)
    keys = []
    for _ in range(3):
        p = r.astype(jnp.float32)
        keys.append(_monotone_u32(p))
        r = jnp.where(jnp.isfinite(p), r - p.astype(r.dtype), jnp.zeros_like(r))
    return _argsort_keys(keys)


def stable_argsort(x, descending: bool = False):
    """int32 stable argsort of a float array along its last axis, ties
    (±0 included) in index order — ``np.argsort(-x if descending else x,
    kind="stable")``.

    The key is chosen by the platform the program is lowered for. On
    XLA:CPU it is the all-integer :func:`monotone_keys_traced` remap:
    compute threads there run with FTZ/DAZ, and a float ``-x`` or
    ``== 0.0`` would fold subnormal scores into the zero tie group. XLA:TPU
    refuses f64 -> integer bitcasts, so every other platform sorts by
    :func:`split_keys_argsort`.
    """
    def int_keys(v):
        return _argsort_keys([monotone_keys_traced(v, descending)])

    return lax.platform_dependent(
        x, cpu=int_keys,
        default=functools.partial(split_keys_argsort, descending=descending),
    )


def _rank_callback(lo_hi) -> np.ndarray:
    """int32 ranks from a raw (S, N, 2) uint32 key-half buffer.

    Invoked by the XLA runtime directly on views of the custom-call
    operand buffers — NOT through :func:`jax.pure_callback`. The stock
    callback primitives ``device_put`` their args back onto the device
    before calling the Python function; on XLA:CPU any copy over the
    small-transfer threshold is enqueued on the same single-thread
    executor that is blocked running the enclosing program, so the
    callback deadlocks waiting for its own arguments (and whether the
    zero-copy path saves you depends on the operand's arena alignment —
    it reproduced flakily from 65536-candidate pools up). Lowering
    through ``mlir.emit_python_callback`` hands this function plain numpy
    views with no device round-trip, which removes the mechanism.

    The boundary also sticks to 32-bit dtypes on purpose: the repo
    enables x64 in *scopes* while the global config stays x32, and the
    runtime thread canonicalizes return dtypes under the *global* mode —
    uint32 in / int32 out are canonical under both. The u64 keys are
    remapped in-graph (integer ops, FTZ-immune) and reassembled here;
    ranks convert to float64 exactly in-graph.
    """
    a = np.asarray(lo_hi)
    K = a[..., 0].astype(np.uint64) | (a[..., 1].astype(np.uint64) << np.uint64(32))
    out = np.empty(K.shape, dtype=np.int32)
    r = np.arange(K.shape[1], dtype=np.int32)
    for s in range(K.shape[0]):
        out[s, _radix_perm_row(K[s])] = r
    return out


if jax is not None:
    from jax.core import ShapedArray as _ShapedArray
    from jax.extend.core import Primitive as _Primitive
    from jax.interpreters import mlir as _jmlir

    _rank_rows_p = _Primitive("repro_rank_rows")
    _rank_rows_p.def_abstract_eval(
        lambda aval: _ShapedArray(aval.shape[:-1], np.dtype(np.int32))
    )
    _rank_rows_p.def_impl(lambda lo_hi: _rank_callback(np.asarray(lo_hi)))

    def _rank_rows_lowering(ctx, lo_hi):
        res, _, _ = _jmlir.emit_python_callback(
            ctx,
            lambda a: (_rank_callback(np.asarray(a)),),
            None,
            [lo_hi],
            ctx.avals_in,
            ctx.avals_out,
            has_side_effect=False,
        )
        return res

    _jmlir.register_lowering(_rank_rows_p, _rank_rows_lowering)


def rank_rows_traced(scores, impl: str):
    """(S, N) float ranks inside a jitted program.

    ``impl`` is trace-time static: "callback" (host radix via the raw
    callback primitive — the CPU fast path, ~5x the sort path at
    12 x 131072), "sort" (monotone-key ``lax.sort`` + per-row scatter,
    pure XLA), or "pallas" (the histogram radix kernel, interpreted on
    CPU). All three return the exact reference ranks.
    """
    if impl == "callback":
        if jax.default_backend() != "cpu":
            raise ValueError("rank impl 'callback' runs the host radix and "
                             "exists only on the CPU backend")
        keys = monotone_keys_traced(scores)
        lo_hi = lax.bitcast_convert_type(keys, jnp.uint32)  # (..., 2) LE halves
        return _rank_rows_p.bind(lo_hi).astype(jnp.float64)
    if impl == "pallas":
        keys = monotone_keys_traced(scores)
        return radix_rank_pallas(keys)
    if impl != "sort":
        raise ValueError(f"unknown rank impl {impl!r}; expected one of {RANK_IMPLS}")
    perm = stable_argsort(scores, descending=True)
    iota_f = jnp.broadcast_to(
        jnp.arange(scores.shape[1], dtype=jnp.float64)[None, :], scores.shape
    )
    rows = jnp.arange(scores.shape[0], dtype=jnp.int32)[:, None]
    return jnp.zeros(scores.shape, dtype=jnp.float64).at[rows, perm].set(
        iota_f, unique_indices=True
    )


# ---------------------------------------------------------------------------
# pallas: histogram radix rank, one program per score row
# ---------------------------------------------------------------------------


def _radix_rank_kernel(keys_ref, rank_ref, *, n, occ_block):
    """8 x 8-bit LSD histogram passes over one row of u64 keys.

    Per pass: gather the digits in current permutation order, histogram
    them (256 bins), exclusive-prefix the histogram into per-digit base
    offsets, then walk the row in ``occ_block`` slabs computing each
    element's stable within-digit offset as (strictly-lower-triangular
    equality count inside the slab) + (running per-digit occupancy from
    the slabs before it) and scattering the permutation entries to
    ``base[digit] + offset``. Every pass is a stable counting sort, so
    the composed permutation is the exact stable u64 argsort.
    """
    keys = keys_ref[...].reshape(-1)
    perm = jnp.arange(n, dtype=jnp.int32)
    tri = jnp.tril(jnp.ones((occ_block, occ_block), dtype=jnp.int32), -1)
    n_blocks = n // occ_block
    for p in range(8):  # static unroll: one pass per byte, LSD first
        d = ((keys[perm] >> np.uint64(8 * p)) & jnp.uint64(0xFF)).astype(jnp.int32)
        hist = jnp.zeros(256, dtype=jnp.int32).at[d].add(1)
        base = jnp.cumsum(hist) - hist

        def body(b, carry, d=d, perm=perm, base=base):
            new_perm, run = carry
            db = lax.dynamic_slice(d, (b * occ_block,), (occ_block,))
            pb = lax.dynamic_slice(perm, (b * occ_block,), (occ_block,))
            eq = (db[None, :] == db[:, None]).astype(jnp.int32)
            occ = (eq * tri).sum(axis=1) + run[db]
            new_perm = new_perm.at[base[db] + occ].set(pb, unique_indices=True)
            return new_perm, run.at[db].add(1)

        perm, _ = lax.fori_loop(
            0, n_blocks, body,
            (jnp.zeros(n, dtype=jnp.int32), jnp.zeros(256, dtype=jnp.int32)),
        )
    rank_ref[...] = (
        jnp.zeros((1, n), dtype=jnp.float64)
        .at[0, perm].set(jnp.arange(n, dtype=jnp.float64), unique_indices=True)
    )


def radix_rank_pallas(keys):
    """Float rank matrix from (S, N) monotone u64 keys via the pallas
    histogram radix; N must be a multiple of the occupancy block (any
    power-of-two pool bucket is). Interpreted on the CPU backend only;
    Mosaic refuses its ``(1, N)`` block on a TPU v5e (the (8, 128) tiling
    rule), so there it raises its compile error."""
    from jax.experimental import pallas as pl

    from .kernel import interpret_mode

    S, N = keys.shape
    occ_block = min(256, N)
    while N % occ_block:
        occ_block //= 2
    return pl.pallas_call(
        functools.partial(_radix_rank_kernel, n=N, occ_block=occ_block),
        grid=(S,),
        in_specs=[pl.BlockSpec((1, N), lambda s: (s, 0))],
        out_specs=pl.BlockSpec((1, N), lambda s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((S, N), jnp.float64),
        interpret=interpret_mode(),
    )(keys)
