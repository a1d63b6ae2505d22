"""Chunked distance-engine for the k-center kernels.

This module is the real execution layer behind ``repro.kernels.ops`` (which
is kept as a thin façade for API stability). It owns:

  * ``impl`` resolution — Pallas on TPU, pure-jnp reference elsewhere
    (interpret-mode Pallas only on request, and only on CPU);
  * shape padding — kernels need block-divisible sizes, callers don't;
  * **row-chunk streaming** — the paper-motivated memory model below.

Memory model (paper §3.3 capacity argument / Ceccarello et al. 1802.09205):
the un-chunked formulation of ``assign_nearest`` / ``pairwise_dist2``
materializes an ``(n, m)`` distance block, i.e. O(n·m) working memory — fine
when the shard fits, fatal when n exceeds device memory. With a ``chunk``
parameter every op streams row-blocks of at most ``chunk`` points:

  * reference path — a ``lax.scan`` over ``(chunk, d)`` tiles, so peak
    working memory is O(chunk·(m + d) + m·d) regardless of n;
  * Pallas path — the grid walks row tiles of a fixed ``ROW_TILE``
    multiple (TPU grids already execute tiles sequentially, so the grid
    *is* the stream); ``chunk`` can only lower the tile to one
    ``ROW_TILE``, the least row block the TPU lowering accepts.

``chunk=None`` (default) preserves the legacy un-chunked behavior exactly.
``memory_budget`` (bytes) derives a chunk from the working-set model
``4·chunk·(m + d) + 4·m·d <= budget``. Results are independent of ``chunk``
(parity-tested in tests/test_engine.py): elementwise minima are bitwise
identical, and cross-chunk arg-reductions resolve ties to the first
occurrence exactly like ``jnp.argmax``/``argmin``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import fused_stream
from . import ref
from .assign import DEFAULT_BM as _A_BM
from .assign import DEFAULT_BN as _A_BN
from .assign import assign_nearest_blocks
from .fused_argfar import DEFAULT_BN as _F_BN
from .fused_argfar import fused_min_argmax_blocks
from .fused_stream import ROW_TILE
from .pairwise import DEFAULT_BM as _P_BM
from .pairwise import DEFAULT_BN as _P_BN
from .pairwise import pairwise_dist2 as _pairwise_pallas

# np scalars, not jnp: module import must not commit the jax backend
# (jax.distributed.initialize refuses to run after any computation).
_BIG = np.float32(3.4e38)
_NEG = np.float32(-3.4e38)


# ---------------------------------------------------------------------------
# impl / chunk resolution
# ---------------------------------------------------------------------------

def _pallas_native() -> bool:
    """Do Pallas kernels compile natively (Mosaic) on the default backend?
    Only on TPU; the backend is fixed at jax init."""
    return jax.default_backend() == "tpu"


def _resolve(impl: str):
    """-> (use_pallas, interpret)

    ``auto`` uses the Pallas kernels on TPU and the jnp reference
    elsewhere. ``pallas`` forces the kernels: native on TPU, interpret
    mode on CPU (the form the CPU parity tests exercise) — interpret mode
    is a CPU correctness tool and never runs on an accelerator. ``ref``
    forces the oracle.
    """
    if impl == "auto":
        return (True, False) if _pallas_native() else (False, False)
    if impl == "pallas":
        if _pallas_native():
            return True, False
        if jax.default_backend() != "cpu":
            raise ValueError(
                f"impl='pallas' has no native lowering on "
                f"{jax.default_backend()!r}, and interpret mode runs on "
                "CPU only")
        return True, True
    if impl == "ref":
        return False, False
    raise ValueError(f"unknown impl {impl!r}")


def resolve_chunk(n: int, m: int, d: int, *, chunk: int | None = None,
                  memory_budget: int | None = None,
                  sublane: int | None = None) -> int | None:
    """Row-chunk size for an ``(n, d) × (m, d)`` distance op.

    Explicit ``chunk`` wins (clipped to ``[1, n]``; ``chunk >= n`` means one
    chunk, i.e. the un-chunked compute with chunked code path). Otherwise a
    ``memory_budget`` in bytes is solved against the f32 working-set model
    ``4·chunk·(m + d) + 4·m·d`` — the streamed tile plus resident centers.
    Returns None when neither is given (legacy un-chunked path).

    ``sublane`` (Pallas callers pass 8, the f32 sublane minimum) keeps a
    *budget-derived* chunk honest against the kernels' block rounding: the
    solved rows are floored to a sublane multiple — never rounded up past
    what the budget covers — and a budget that cannot hold even one
    ``sublane``-row block raises instead of silently overshooting.
    Explicit ``chunk`` is a shape request, not a budget, and is returned
    unrounded (``_pallas_bn`` may round it up).
    """
    if chunk is not None:
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        return min(int(chunk), max(n, 1))
    if memory_budget is not None:
        avail = memory_budget - 4 * m * d
        rows = avail // (4 * (m + d)) if avail > 0 else 0
        if sublane is not None and sublane > 1:
            # Floor to the sublane multiple the kernel will actually run:
            # rounding *up* here could exceed the stated budget (rows is
            # the largest count the model covers).
            rows = (rows // sublane) * sublane
            if rows < 1:
                raise ValueError(
                    f"memory_budget={memory_budget} cannot hold one "
                    f"{sublane}-row sublane block "
                    f"({4 * m * d} bytes of centers + "
                    f"{4 * sublane * (m + d)} bytes/block)")
        if rows < 1:
            raise ValueError(
                f"memory_budget={memory_budget} cannot hold even one row "
                f"(centers alone need {4 * m * d} bytes + {4 * (m + d)}/row)")
        return min(int(rows), max(n, 1))
    return None


def _pad_rows(a: jnp.ndarray, mult: int, fill: float):
    n = a.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return a, n
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1), constant_values=fill), n


def _blocks(a: jnp.ndarray, chunk: int, fill: float):
    """Pad rows to a chunk multiple and reshape to (nb, chunk, ...)."""
    ap, n = _pad_rows(a, chunk, fill)
    nb = ap.shape[0] // chunk
    return ap.reshape((nb, chunk) + ap.shape[1:]), n


def _pallas_bn(bn: int, n: int, chunk: int | None) -> int:
    """Row block for the 2-D-output pairwise grid: ≤ bn, ≤ chunk (rounded
    up to the 8-row sublane minimum), never below 8.

    The round-*up* is only safe because budget-derived chunks arrive
    pre-floored to a sublane multiple (``resolve_chunk(..., sublane=8)``),
    so it can engage only for explicit user chunks — a shape request, not
    a byte budget (tests/test_engine.py pins the budget-honesty side).
    """
    bn_ = min(bn, max(8, n))
    if chunk is not None:
        bn_ = min(bn_, max(8, -(-chunk // 8) * 8))
    return bn_


def _row_bn(bn: int, n: int, chunk: int | None, interpret: bool) -> int:
    """Row block for the kernels with per-row vector operands (argfar,
    assign, the fused streamed tiles).

    Native (TPU): the largest ``ROW_TILE`` multiple ≤ bn and ≤ chunk, never
    below one ``ROW_TILE`` — the vectors' lane-dense ``(bn/128, 128)``
    blocks are (8, 128)-aligned, as the TPU lowering requires, only then.
    Interpret mode (CPU) has no layouts, so it keeps the 8-row sublane
    granularity of ``_pallas_bn``: the CPU parity tests then walk many
    small tiles, and a chunked ref scan and the tile grid split rows
    alike. Callers pad rows up to a multiple of the block.
    """
    if interpret:
        return _pallas_bn(bn, n, chunk)
    cap = bn if chunk is None else min(bn, chunk)
    return max(ROW_TILE, cap // ROW_TILE * ROW_TILE)


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------

def dist2_to_center(x, c, *, impl: str = "auto", chunk: int | None = None,
                    memory_budget: int | None = None):
    """Squared distance of each row of x (n,d) to center c (d,)."""
    # Single-center distance has an O(n·d) working set already — no (n,m)
    # block exists to chunk away; the reference pass is optimal everywhere.
    del impl, chunk, memory_budget
    return ref.dist2_to_center(x, c)


def pairwise_dist2(x, c, *, impl: str = "auto", chunk: int | None = None,
                   memory_budget: int | None = None,
                   bn: int = _P_BN, bm: int = _P_BM):
    """(n,d),(m,d) -> (n,m) squared Euclidean distances.

    Note the *output* is inherently O(n·m); chunking bounds the transient
    working set (useful when the caller immediately reduces each row-block,
    and on backends where the fused matmul intermediate is the peak).
    """
    n, m = x.shape[0], c.shape[0]
    d = x.shape[1]
    use_pallas, interpret = _resolve(impl)
    chunk = resolve_chunk(n, m, d, chunk=chunk, memory_budget=memory_budget,
                          sublane=8 if use_pallas else None)
    if use_pallas:
        bn_ = _pallas_bn(bn, n, chunk)
        bm_ = min(bm, max(8, m))
        xp, n0 = _pad_rows(x, bn_, 0.0)
        cp, m0 = _pad_rows(c, bm_, 0.0)
        out = _pairwise_pallas(xp, cp, bn=bn_, bm=bm_, interpret=interpret)
        return out[:n0, :m0]
    if chunk is None or chunk >= n:
        return ref.pairwise_dist2(x, c)
    xb, n0 = _blocks(x, chunk, 0.0)

    def step(_, xrow):
        return None, ref.pairwise_dist2(xrow, c)

    _, d2 = jax.lax.scan(step, None, xb)
    return d2.reshape(-1, m)[:n0]


def fused_min_argmax(x, c, min_d2, *, impl: str = "auto",
                     chunk: int | None = None,
                     memory_budget: int | None = None, bn: int = _F_BN):
    """Fused Gonzalez step: (new_min_d2 (n,), far_val (), far_idx () i32)."""
    n, d = x.shape
    use_pallas, interpret = _resolve(impl)
    chunk = resolve_chunk(n, 1, d, chunk=chunk, memory_budget=memory_budget,
                          sublane=8 if use_pallas else None)
    if use_pallas:
        bn_ = _row_bn(bn, n, chunk, interpret)
        xp, _ = _pad_rows(x, bn_, 0.0)
        # Padded rows get -inf min-dist so they never become the farthest
        # point and their updated min stays -inf.
        mdp, _ = _pad_rows(min_d2, bn_, -_BIG)
        new_md, bmax, barg = fused_min_argmax_blocks(xp, c, mdp, bn=bn_,
                                                     interpret=interpret)
        blk = jnp.argmax(bmax[:, 0, 0])
        return new_md[:n], bmax[blk, 0, 0], barg[blk, 0, 0]
    if chunk is None or chunk >= n:
        return ref.fused_min_argmax(x, c, min_d2)
    xb, n0 = _blocks(x, chunk, 0.0)
    mdb, _ = _blocks(min_d2, chunk, -_BIG)
    offs = jnp.arange(xb.shape[0], dtype=jnp.int32) * chunk

    def step(carry, inp):
        best_v, best_i = carry
        xrow, mdrow, off = inp
        new_md, v, i = ref.fused_min_argmax(xrow, c, mdrow)
        # Strict > keeps the earliest block on ties — matches the global
        # first-occurrence semantics of jnp.argmax.
        take = v > best_v
        carry = (jnp.where(take, v, best_v),
                 jnp.where(take, i + off, best_i))
        return carry, new_md

    (far_v, far_i), new_md = jax.lax.scan(
        step, (-_BIG, jnp.int32(0)), (xb, mdb, offs))
    return new_md.reshape(-1)[:n0], far_v, far_i


def assign_nearest(x, c, *, impl: str = "auto", chunk: int | None = None,
                   memory_budget: int | None = None,
                   bn: int = _A_BN, bm: int = _A_BM):
    """Nearest-center assignment: (idx (n,) i32, d2 (n,)).

    With ``chunk``/``memory_budget`` the (n, m) distance block never
    materializes — each scan step reduces its (chunk, m) tile to a
    (chunk,) min/argmin pair, so n is bounded by HBM for the *points*
    only, not the distance matrix.
    """
    n, m = x.shape[0], c.shape[0]
    d = x.shape[1]
    use_pallas, interpret = _resolve(impl)
    chunk = resolve_chunk(n, m, d, chunk=chunk, memory_budget=memory_budget,
                          sublane=8 if use_pallas else None)
    if use_pallas:
        bn_ = _row_bn(bn, n, chunk, interpret)
        bm_ = min(bm, -(-m // 8) * 8)
        xp, _ = _pad_rows(x, bn_, 0.0)
        # Pad centers at +inf-ish distance: fill with a huge coordinate so
        # padded centers are never nearest.
        cp, _ = _pad_rows(c, bm_, 1e18)
        idx, d2 = assign_nearest_blocks(xp, cp, bn=bn_, bm=bm_,
                                        interpret=interpret)
        return idx[:n], d2[:n]
    if chunk is None or chunk >= n:
        return ref.assign_nearest(x, c)
    xb, n0 = _blocks(x, chunk, 0.0)

    def step(_, xrow):
        return None, ref.assign_nearest(xrow, c)

    _, (idx, d2) = jax.lax.scan(step, None, xb)
    return idx.reshape(-1)[:n0], d2.reshape(-1)[:n0]


# Coordinate-space far sentinel for padded/invalid center rows: distance to
# a 1e18-coordinate row is ~1e36·d (or +inf past f32 range) — it loses every
# nearest reduction, so sentinel rows never win an assignment.
_FAR_CENTER = np.float32(1e18)


def assign_bucketed(q, c, cmask, *, impl: str = "auto",
                    chunk: int | None = None):
    """Nearest-center assignment against a *bucketed* cached center set —
    the online-serving query program (``repro/serve/kcenter.py``).

    ``c (m_cap, d)`` is a fixed power-of-two bucket holding ``m <= m_cap``
    live centers and ``cmask (m_cap,)`` marks the live rows (0/1 operand,
    f32 or bool). Invalid rows are pushed to the far coordinate sentinel —
    the same 1e18 fill ``assign_nearest`` pads centers with — so they can
    never win a nearest reduction: for every valid query row the result is
    **bitwise** equal to ``assign_nearest(q[:b], c[:m])``. Callers pad the
    query block to a fixed row bucket and slice the tail off themselves
    (tests/test_serve_kcenter.py pins both contracts).

    Deliberately NOT module-jitted: the repo-wide assignment contract is
    the *eager* ``assign_nearest`` bits, and jitting fuses the
    ``|x|² − 2x·c + |c|²`` matmul differently on CPU (1-ulp d2 drift — the
    same reason ``Executor.radius2`` stays an eager fold). Recompile
    avoidance comes from the fixed bucket shapes instead: every operand
    signature is one of O(log max_batch · log m_cap) buckets, so the op
    cache serves the steady state with zero new compilations. Epoch bumps
    of the serving cache re-upload the *same* shapes — never a new
    signature. reprolint R004 lists this entry point in ``JITTED_CALLEES``
    so a ragged block stream must do the pad dance before reaching it.

    Eager-only for a second reason: the mask is read *concretely* to
    special-case a single live center (the m=1 dot lowers as a matvec with
    different accumulation than the m>=2 gemm — masking it inside the
    bucket would cost 1 ulp of parity), so ``cmask`` must not be a tracer.
    """
    cmask_h = np.asarray(cmask) > 0
    if cmask_h.shape[0] != c.shape[0]:
        raise ValueError(
            f"cmask rows {cmask_h.shape[0]} != center bucket rows {c.shape[0]}")
    nvalid = int(cmask_h.sum())
    if nvalid == 1:
        # XLA lowers the m=1 distance dot as a matvec whose accumulation
        # differs from the m>=2 gemm by 1 ulp, so a single live center
        # masked inside the bucket would break bitwise parity with the
        # unbucketed reference. Route through the true 1-row set — still a
        # fixed operand signature per query bucket — and restore the
        # bucket-row index.
        j = int(np.argmax(cmask_h))
        idx, d2 = assign_nearest(q, jnp.asarray(c)[j:j + 1],
                                 impl=impl, chunk=chunk)
        return idx + jnp.int32(j), d2
    c = jnp.where(jnp.asarray(cmask)[:, None] > 0, c, _FAR_CENTER)
    return assign_nearest(q, c, impl=impl, chunk=chunk)


def argmin_dist2_over_rows(x, c, *, impl: str = "auto",
                           chunk: int | None = None,
                           memory_budget: int | None = None):
    """For each center row of ``c (m,d)``: index of the nearest row of
    ``x (n,d)`` — ``argmin_i |x_i - c_j|^2 -> (m,) i32``.

    Semantically ``assign_nearest(c, x)[0]``, but chunked over the *x*
    rows: the scan keeps an (m,)-sized running (min, argmin) carry, so the
    working set is O(chunk·m) instead of the (m, n) block that formulation
    materializes on the ref path. (The Pallas grid already tiles the n
    axis, so that path delegates to the kernel unchanged.)
    """
    n, d = x.shape
    m = c.shape[0]
    use_pallas, _ = _resolve(impl)
    chunk = resolve_chunk(n, m, d, chunk=chunk, memory_budget=memory_budget,
                          sublane=8 if use_pallas else None)
    if use_pallas or chunk is None or chunk >= n:
        idx, _ = assign_nearest(c, x, impl=impl)
        return idx
    # Pad rows at a far-away coordinate so padding can never be nearest
    # (its distance is ~1e36·d, or +inf past f32 range — both lose).
    xb, _ = _blocks(x, chunk, 1e18)
    offs = jnp.arange(xb.shape[0], dtype=jnp.int32) * chunk

    def step(carry, inp):
        best_d, best_i = carry
        xrow, off = inp
        d2 = ref.pairwise_dist2(xrow, c)                     # (chunk, m)
        loc_d = jnp.min(d2, axis=0)                          # (m,)
        loc_i = jnp.argmin(d2, axis=0).astype(jnp.int32) + off
        # Strict < keeps the earliest row on ties — matches the global
        # first-occurrence semantics of jnp.argmin.
        take = loc_d < best_d
        return (jnp.where(take, loc_d, best_d),
                jnp.where(take, loc_i, best_i)), None

    init = (jnp.full((m,), _BIG), jnp.zeros((m,), jnp.int32))
    (_, idx), _ = jax.lax.scan(step, init, (xb, offs))
    return idx


# ---------------------------------------------------------------------------
# counter-based per-row sampling — Philox-4x32-10 keyed by absolute row index
#
# EIM's Round-1 Bernoulli draws must be *blocking-invariant*: the streamed
# out-of-core path sees the input in super-shards, and the decision for
# global row i may not depend on which shard i landed in (the same trick
# ``SyntheticSource("unif")`` uses with numpy's Philox counter advance).
# ``jax.random.bernoulli`` can't give that — its counters are positions in
# one fixed-shape draw — so this is a counter-based generator whose only
# inputs are (key, absolute row index). Pure uint32 jnp (16-bit limb
# multiplies, no uint64), so it runs identically with JAX_ENABLE_X64 off,
# on any backend, traced or eager — the device fast path and the host-
# driven stream produce bitwise-identical samples.
# ---------------------------------------------------------------------------

_PHILOX_M0 = np.uint32(0xD2511F53)
_PHILOX_M1 = np.uint32(0xCD9E8D57)
_PHILOX_W0 = np.uint32(0x9E3779B9)
_PHILOX_W1 = np.uint32(0xBB67AE85)


def _mulhilo32(a, b):
    """Full 32x32 -> 64 multiply as (hi, lo) uint32 words, via 16-bit limbs
    (jnp uint64 needs x64 mode; uint32 arithmetic wraps mod 2^32)."""
    a_lo, a_hi = a & 0xFFFF, a >> 16
    b_lo, b_hi = b & 0xFFFF, b >> 16
    lo = a * b
    t = a_hi * b_lo + ((a_lo * b_lo) >> 16)        # < 2^32, no wrap
    u = (t & 0xFFFF) + a_lo * b_hi                 # < 2^32, no wrap
    hi = a_hi * b_hi + (t >> 16) + (u >> 16)
    return hi, lo


def _philox_rows(k0, k1, c0, c1):
    """One Philox-4x32-10 output word per counter (c0 = row lo, c1 = row hi)."""
    x0, x1 = c0, c1
    x2 = jnp.zeros_like(c0)
    x3 = jnp.zeros_like(c0)
    for _ in range(10):
        hi0, lo0 = _mulhilo32(_PHILOX_M0, x0)
        hi1, lo1 = _mulhilo32(_PHILOX_M1, x2)
        x0, x1, x2, x3 = hi1 ^ x1 ^ k0, lo1, hi0 ^ x3 ^ k1, lo0
        k0 = k0 + _PHILOX_W0
        k1 = k1 + _PHILOX_W1
    return x0


def _key_words(key):
    """Two uint32 key words from a jax PRNG key (legacy or typed) or a raw
    (2,) uint32 array."""
    key = jnp.asarray(key) if not isinstance(key, jnp.ndarray) else key
    if key.dtype != jnp.uint32:
        key = jax.random.key_data(key)
    key = key.reshape(-1)
    return key[0], key[1]


def _uniform_rows_words(k0, k1, lo, hi, rows: int) -> jnp.ndarray:
    """``uniform_rows`` with the 64-bit start pre-split into uint32 words
    (``lo``/``hi`` may be traced — jit callers pass them as operands so one
    compilation serves every block offset)."""
    c0 = lo + jnp.arange(rows, dtype=jnp.uint32)
    carry = (c0 < lo).astype(jnp.uint32)
    c1 = hi + carry
    bits = _philox_rows(k0, k1, c0, c1)
    # 24 high-entropy bits -> f32 in [0, 1): exact scale, matches the
    # resolution jax.random.uniform uses for f32.
    return (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)


@functools.partial(jax.jit, static_argnames=("rows",))
def _uniform_rows_block(key, start_lo, start_hi, rows: int):
    k0, k1 = _key_words(key)
    return _uniform_rows_words(k0, k1, start_lo, start_hi, rows)


def uniform_rows(key, start: int, rows: int) -> jnp.ndarray:
    """Counter-based U[0,1) for absolute rows ``[start, start + rows)``.

    Row i's value depends only on ``(key, i)`` — never on ``start``'s
    blocking — so concatenating per-block calls over any partition of
    ``[0, n)`` is bitwise identical to one full-range call. ``start`` is a
    host int, split into uint32 counter words (with an explicit carry, so
    blocks may cross the 2^32 row boundary) that reach one jitted program
    per ``rows`` as operands — every block offset shares its compilation.
    """
    if rows < 0:
        raise ValueError(f"rows must be >= 0, got {rows}")
    return _uniform_rows_block(key, np.uint32(start & 0xFFFFFFFF),
                               np.uint32((start >> 32) & 0xFFFFFFFF),
                               rows=rows)


def bernoulli_rows(key, start: int, rows: int, p) -> jnp.ndarray:
    """Per-global-row Bernoulli(p) draws for rows ``[start, start + rows)``
    — ``uniform_rows(key, start, rows) < p`` in f32, so callers on the
    device fast path and the streamed path agree bitwise as long as they
    feed the same f32 ``p``."""
    return uniform_rows(key, start, rows) < jnp.asarray(p, jnp.float32)


def split_index_words(indices) -> tuple[np.ndarray, np.ndarray]:
    """Host-side split of 64-bit absolute row indices into uint32 counter
    words (jnp cannot hold int64 with JAX_ENABLE_X64 off — the split
    happens in numpy before anything touches the device). The words are
    the operand form ``bernoulli_rows_at_block`` consumes."""
    idx = np.asarray(indices, np.uint64).reshape(-1)
    return ((idx & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            (idx >> np.uint64(32)).astype(np.uint32))


def _uniform_at_words(k0, k1, c_lo, c_hi) -> jnp.ndarray:
    bits = _philox_rows(k0, k1, c_lo, c_hi)
    return (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def uniform_rows_at(key, indices) -> jnp.ndarray:
    """Gather-form ``uniform_rows``: counter-based U[0,1) at *arbitrary*
    absolute row indices.

    Row i's draw is the same pure function of ``(key, i)`` as
    ``uniform_rows`` evaluates, so for any index array ``idx``::

        uniform_rows_at(key, idx) == uniform_rows(key, 0, n)[idx]

    bitwise. This is what keeps the compacted-R streamed EIM's Round-1
    sampling identical to the full-view path: a survivor's Bernoulli
    decision is keyed by its *original* global row index, never by its
    position inside the compacted view. ``indices`` is host numpy
    (64-bit indices are split into uint32 counter words on the host, so
    the call is x64-off safe).
    """
    k0, k1 = _key_words(key)
    lo, hi = split_index_words(indices)
    return _uniform_at_words(k0, k1, jnp.asarray(lo), jnp.asarray(hi))


def bernoulli_rows_at(key, indices, p) -> jnp.ndarray:
    """Per-row Bernoulli(p) draws at arbitrary absolute row indices —
    ``uniform_rows_at(key, indices) < p`` in f32, bitwise identical to
    ``bernoulli_rows(key, 0, n, p)[indices]`` for the same f32 ``p``."""
    return uniform_rows_at(key, indices) < jnp.asarray(p, jnp.float32)


@jax.jit
def bernoulli_rows_at_block(key, idx_lo, idx_hi, p):
    """Jitted gather-form Bernoulli block: the index words arrive as
    *operands* (uint32 arrays of one fixed block shape — callers pad the
    tail), so one compilation per block shape serves every iteration and
    every compacted view."""
    k0, k1 = _key_words(key)
    return _uniform_at_words(k0, k1, idx_lo, idx_hi) < jnp.asarray(
        p, jnp.float32)


# ---------------------------------------------------------------------------
# streamed top-k merge — EIM's Round-2 Select pivot as a cross-block fold
# ---------------------------------------------------------------------------

def top_k_init(k: int) -> jnp.ndarray:
    """Identity carry for ``merge_top_k``: k slots at the -inf sentinel."""
    return jnp.full((k,), _NEG)


def merge_top_k(carry: jnp.ndarray, vals: jnp.ndarray, k: int) -> jnp.ndarray:
    """Fold step: merge a block's values into a running descending top-k.

    Top-k *values* of a multiset are blocking-invariant (unlike arg-
    reductions, no tie-break subtlety), so folding per-block top-k's equals
    the monolithic ``lax.top_k`` over the concatenation bitwise.
    """
    return jax.lax.top_k(jnp.concatenate([carry, vals.reshape(-1)]), k)[0]


def fold_top_k(value_blocks, k: int) -> jnp.ndarray:
    """Top-k values over an iterable of value blocks (descending, padded
    with the -inf sentinel when fewer than k values exist)."""
    top = top_k_init(k)
    for v in value_blocks:
        top = merge_top_k(top, jnp.asarray(v), k)
    return top


# ---------------------------------------------------------------------------
# source folds — streamed ops over a PointSource
#
# A "source" here is duck-typed: anything with ``n``, ``d`` and
# ``blocks(block_rows)`` yielding (<= block_rows, d) float32 device arrays
# covering the rows in order (see repro/data/source.py). These folds are the
# shared entry points the executors (repro/core/executor.py) and the
# source-aware algorithm layer build on: at most ``1 + prefetch``
# super-shards of the input (the consumed block plus the device-side
# prefetch ring) are ever device-resident, so n is bounded by host RAM /
# disk, not HBM.
#
# Two nested capacity knobs exist by design: ``block_rows``/``memory_budget``
# bounds the resident *input block* (this layer), while ``chunk`` bounds the
# per-pass *distance working set* within a block (the layer above). They
# mirror the paper's machine capacity c and its per-round working memory.
# ---------------------------------------------------------------------------

DEFAULT_BLOCK_ROWS = 1 << 16
# Default lookahead depth of the sources' device-side prefetch ring (the
# single home of the constant — repro/data/source.py imports it); at the
# peak 1 + DEFAULT_PREFETCH blocks are device-resident.
DEFAULT_PREFETCH = 2


def resolve_block_rows(n: int, d: int, *, block_rows: int | None = None,
                       memory_budget: int | None = None,
                       default: int = DEFAULT_BLOCK_ROWS,
                       prefetch: int = DEFAULT_PREFETCH) -> int:
    """Super-shard size for streaming an ``(n, d)`` source.

    Explicit ``block_rows`` wins (clipped to ``[1, n]``). Otherwise a
    ``memory_budget`` in bytes is solved against the f32 residency model
    ``(1 + prefetch) · 4·rows·(d + 1)`` — the consumed block plus up to
    ``prefetch`` in-flight blocks coexist under the sources' device-side
    prefetch ring, each with one per-row reduction carry. Falls back to
    ``DEFAULT_BLOCK_ROWS``.
    """
    if block_rows is not None:
        if block_rows < 1:
            raise ValueError(f"block_rows must be >= 1, got {block_rows}")
        return min(int(block_rows), max(n, 1))
    if prefetch < 1:
        raise ValueError(f"prefetch must be >= 1, got {prefetch}")
    if memory_budget is not None:
        rows = memory_budget // (4 * (1 + prefetch) * (d + 1))
        if rows < 1:
            raise ValueError(
                f"memory_budget={memory_budget} cannot hold even one "
                f"{d}-dim row per buffer ({4 * (1 + prefetch) * (d + 1)} "
                f"bytes/row across {1 + prefetch} ring slots)")
        return min(int(rows), max(n, 1))
    return min(default, max(n, 1))


def host_blocks_of(source, rows: int):
    """Numpy host blocks of any source: ``host_blocks`` when the source
    offers it (every built-in host-backed source does), else the device
    stream pulled back block-by-block — so per-shard consumers (the
    sharded executors) can stage each block themselves without assuming a
    source kind."""
    blocks = (source.host_blocks(rows) if hasattr(source, "host_blocks")
              else source.blocks(rows))
    for blk in blocks:
        yield np.asarray(blk, np.float32)


def zip_shard_blocks(shards, rows: int, *, with_weights: bool = False,
                     local_ids=None):
    """Per-shard fold entry point: align the shards' host streams into
    lockstep steps.

    Yields ``(pts (S, rows, d) f32, counts (S,) int64)`` per step — each
    shard's next block, zero-padded to the common ``rows`` shape (the
    executor turns ``counts`` into validity masks), until *every* shard is
    exhausted. A shard that runs out early (unequal shard sizes)
    contributes all-padding steps with ``counts == 0``. The host working
    set is one step — ``S · rows · d`` floats — never a full shard, and
    never n.

    ``with_weights=True`` inserts each shard's per-row f32 weights between
    the points and the counts — ``(pts, w (S, rows), counts)``, padded
    rows at weight 0 — fetched per shard through ``weights_of`` (default
    ones), tracked by per-shard row cursors so the slices stay aligned
    with the blocks.

    ``local_ids`` (a collection of shard indices, or ``None`` for "all")
    is the multi-process form: shards *not* in it are never read — their
    data lives on other controller processes — and their slot in ``pts``
    is ``None``. Their ``counts`` are still exact, computed arithmetically
    from the shard size and a row cursor (every process knows the global
    partition), so masks and step counts agree across processes. The
    yielded ``pts`` is then a list of per-shard ``(rows, d)`` arrays /
    ``None``, which ``compat.global_array_from_shards`` accepts directly.
    """
    if rows < 1:
        raise ValueError(f"rows must be >= 1, got {rows}")
    shards = list(shards)
    if not shards:
        raise ValueError("zip_shard_blocks needs at least one shard")
    local = (set(range(len(shards))) if local_ids is None
             else set(int(i) for i in local_ids))
    sparse = local_ids is not None
    if sparse and with_weights:
        raise NotImplementedError(
            "weighted lockstep steps are not supported with non-local "
            "shards (no weighted multi-process caller exists)")
    d = shards[0].d
    its = [host_blocks_of(s, rows) if s_i in local else None
           for s_i, s in enumerate(shards)]
    pos = [0] * len(shards)
    while True:
        if sparse:
            pts = [None] * len(shards)
        else:
            pts = np.zeros((len(shards), rows, d), np.float32)
        w = np.zeros((len(shards), rows), np.float32) if with_weights else None
        counts = np.zeros((len(shards),), np.int64)
        any_rows = False
        for s, it in enumerate(its):
            if it is None:
                # Non-local shard: exact block accounting without a read.
                nb = min(rows, shards[s].n - pos[s])
                if nb <= 0:
                    continue
                pos[s] += nb
                counts[s] = nb
                any_rows = True
                continue
            blk = next(it, None)
            if blk is None:
                continue
            nb = blk.shape[0]
            if nb > rows:
                raise ValueError(
                    f"shard {s} yielded a {nb}-row block for "
                    f"block_rows={rows}")
            if sparse:
                piece = np.zeros((rows, d), np.float32)
                piece[:nb] = blk
                pts[s] = piece
            else:
                pts[s, :nb] = blk
            if with_weights:
                w[s, :nb] = _source_weights(shards[s], pos[s], nb)
            pos[s] += nb
            counts[s] = nb
            if nb:
                any_rows = True
        if not any_rows:
            return
        if with_weights:
            yield pts, w, counts
        else:
            yield pts, counts


def _source_blocks(source, rows: int, prefetch: int | None):
    """``source.blocks(rows)``, forwarding ``prefetch`` when the source
    supports the keyword (the protocol only requires ``blocks(rows)``)."""
    if prefetch is not None:
        try:
            return source.blocks(rows, prefetch=prefetch)
        except TypeError:
            pass
    return source.blocks(rows)


def _source_weights(source, start: int, rows: int) -> np.ndarray:
    """Per-row f32 weights of rows ``[start, start + rows)``, duck-typed
    (this module imports nothing from ``repro.data`` — cycle direction);
    sources without a ``weights_of`` method get the default-ones path.
    ``repro.data.source.weights_of`` is the public form of the same
    contract."""
    fn = getattr(source, "weights_of", None)
    if fn is None:
        return np.ones((int(rows),), np.float32)
    w = np.asarray(fn(start, rows), np.float32).reshape(-1)
    if w.shape[0] != rows:
        raise ValueError(
            f"weights_of({start}, {rows}) returned {w.shape[0]} weights")
    return w


# -- fused Pallas tiles for the streamed folds (kernels/fused_stream.py) ----
#
# The fold loops below each have a Pallas branch: every block is padded to
# ONE fixed ``ceil(rows/bn)·bn`` shape with validity carried as a kernel
# *operand* (f32 0/1 mask), so a single compilation of the fused tile
# serves the whole stream, ragged tail included — no recompile per tail
# shape (tests/test_engine.py spies on the operand shapes as the
# compile-count proxy). The ref branches are the bitwise oracle; the tile
# kernels reproduce their bits exactly (rows-only tiling — see the
# fused_stream module docstring for why that makes bitwise possible).
#
# The fused tiles keep every center resident in VMEM. A center set too
# large for that (``fused_stream.fits_vmem``) takes the unfused branch,
# whose ``assign_nearest`` is the center-tiled kernel on TPU: the choice
# is made from the shapes, and both branches run on the device.

def _stream_tiles(impl: str, chunk: int | None, rows: int, m: int, d: int):
    """-> (fused, interpret, bn) for a streamed fold of ``rows``-row blocks
    against ``m`` centers: ``fused`` when the Pallas path is selected and
    the centers fit VMEM."""
    use_pallas, interpret = _resolve(impl)
    bn = _row_bn(fused_stream.DEFAULT_BN, -(-rows // 8) * 8, chunk,
                 interpret)
    fused = use_pallas and fused_stream.fits_vmem(bn, m, d)
    return fused, interpret, bn


def _padded_rows(rows: int, bn: int) -> int:
    return -(-rows // bn) * bn


def _filter_update_tiles(blk, c, d_blk, h_blk, rank: int, bn: int,
                         interpret: bool, w_blk=None):
    """Traced helper: pad one block to the tile grid and run the fused
    filter kernel. Returns ``(d_new (rows,), tops (tiles, rank))`` — the
    d(x,S) min-update for every input row plus each tile's descending
    top-``rank`` of the H-masked candidates. ``w_blk`` (optional per-row
    weights) routes to the weighted sibling kernel, whose extra VMEM
    operand gates ``w <= 0`` rows out of candidacy."""
    rows = blk.shape[0]
    rows_p = _padded_rows(rows, bn)
    pad = rows_p - rows
    blk_p = jnp.pad(blk, ((0, pad), (0, 0)))
    # Padded lanes: d_s at +BIG (their update is sliced off), H=0 so they
    # never enter the top-k.
    d_p = jnp.pad(d_blk, (0, pad), constant_values=_BIG)
    h_p = jnp.pad(h_blk, (0, pad)).astype(jnp.float32)
    if w_blk is None:
        d_new, tops = fused_stream.fused_filter_blocks(
            blk_p, c, d_p, h_p, rank=rank, bn=bn, interpret=interpret)
    else:
        w_p = jnp.pad(w_blk, (0, pad)).astype(jnp.float32)
        d_new, tops = fused_stream.fused_filter_blocks_w(
            blk_p, c, d_p, h_p, w_p, rank=rank, bn=bn, interpret=interpret)
    return d_new[:rows], tops


def filter_tile_update(blk, c, d_blk, h_blk, *, rank: int,
                       impl: str = "auto", chunk: int | None = None,
                       w_blk=None):
    """One machine-block's share of EIM Rounds 2–3 (traceable, unjitted —
    the executors' shard_map/vmap programs and ``eim_filter_block`` wrap
    it): ``d_new = min(d_blk, d(blk, c)²)`` plus the block's descending
    top-``min(rank, rows)`` of ``where(h_blk, d_new, -inf)``.

    The ref branch is the oracle; the Pallas branch fuses the whole update
    into the streamed tile kernel and reduces the per-tile tops (top-k
    *values* are blocking-invariant, so the results are bitwise equal).
    ``w_blk`` (optional per-row f32 weights) additionally gates ``w <= 0``
    rows out of top-k candidacy; ``w_blk=None`` runs the exact pre-weights
    program.
    """
    fused, interpret, bn = _stream_tiles(impl, chunk, blk.shape[0],
                                         c.shape[0], blk.shape[1])
    r = min(rank, d_blk.shape[0])
    if fused:
        d_new, tops = _filter_update_tiles(blk, c, d_blk, h_blk, rank,
                                           bn, interpret, w_blk=w_blk)
        return d_new, jax.lax.top_k(tops.reshape(-1), r)[0]
    _, dn = assign_nearest(blk, c, impl=impl, chunk=chunk)
    d_new = jnp.minimum(d_blk, dn)
    if w_blk is None:
        cand = jnp.where(h_blk, d_new, _NEG)
    else:
        cand = jnp.where(h_blk & (w_blk > 0), d_new, _NEG)
    return d_new, jax.lax.top_k(cand, r)[0]


@functools.partial(jax.jit, static_argnames=("rank", "impl", "chunk"))
def eim_filter_block(blk, c, d_blk, h_blk, top, w_blk=None, *, rank: int,
                     impl: str, chunk: int | None = None):
    """One super-shard's share of EIM Rounds 2–3, fused and jitted:
    incremental-min d(x, S_new) update + this block's contribution to
    Select's top-k merged into the running ``top`` carry. ``c`` is the
    fixed-capacity S_new buffer (far-sentinel padded) and callers pad
    ``blk``/``d_blk``/``h_blk`` (and ``w_blk`` when weighted) to one fixed
    ``rows`` shape, so one compilation serves every iteration and every
    block — ragged tail included. The executors' streamed filter rounds
    call this; ``impl`` picks the fused Pallas tile vs the jnp oracle
    (bitwise-identical). ``w_blk=None`` (an empty jit pytree leaf, not an
    operand) keeps the unweighted compiled program byte-identical."""
    d_blk, tops = filter_tile_update(blk, c, d_blk, h_blk, rank=rank,
                                     impl=impl, chunk=chunk, w_blk=w_blk)
    return d_blk, merge_top_k(top, tops, rank)


def fold_min_d2(source, c, *, impl: str = "auto", chunk: int | None = None,
                block_rows: int | None = None,
                memory_budget: int | None = None,
                prefetch: int | None = None,
                weighted: bool = False) -> jnp.ndarray:
    """Max over all source points of the min squared distance to ``c`` —
    the squared covering radius, as a streamed fold.

    Per-block maxima combine exactly (max is associative and order-safe),
    so the result is bitwise-identical to the in-memory
    ``max(assign_nearest(x, c)[1])`` for any blocking.

    ``weighted=True`` restricts the max to rows with source weight > 0
    (the weighted instance's support), via the rank-1 case of
    ``fold_top_k_min_d2``; for a source whose weights are all positive —
    unit weights in particular — the value is the same max over the same
    per-block d² multisets, hence bitwise the unweighted fold.
    """
    if weighted:
        top = fold_top_k_min_d2(source, c, 1, impl=impl, chunk=chunk,
                                block_rows=block_rows,
                                memory_budget=memory_budget,
                                prefetch=prefetch, weighted=True)
        # An empty support leaves the -inf sentinel; report radius 0 like
        # the empty-source fold below (real d² are >= 0, so the clamp is
        # the identity on any nonempty support).
        return jnp.maximum(top[0], jnp.float32(0.0))
    rows = resolve_block_rows(source.n, source.d, block_rows=block_rows,
                              memory_budget=memory_budget,
                              prefetch=prefetch or DEFAULT_PREFETCH)
    fused, interpret, bn = _stream_tiles(impl, chunk, rows, c.shape[0],
                                         source.d)
    if fused:
        # Fused tile path: the filter kernel with rank=1 and a +BIG d_s
        # carry IS the per-tile max of min-distances; the validity mask
        # gates padded lanes, so one compilation serves the ragged tail.
        rows_p = _padded_rows(rows, bn)
        d_big = jnp.full((rows_p,), _BIG)
        best = None
        for blk in _source_blocks(source, rows, prefetch):
            nb = blk.shape[0]
            blk_p = jnp.pad(blk, ((0, rows_p - nb), (0, 0)))
            vm = (jnp.arange(rows_p) < nb).astype(jnp.float32)
            _, tops = fused_stream.fused_filter_blocks(
                blk_p, c, d_big, vm, rank=1, bn=bn, interpret=interpret)
            bmax = jnp.max(tops)
            best = bmax if best is None else jnp.maximum(best, bmax)
        if best is None:
            return jnp.float32(0.0)
        return best
    best = None
    for blk in _source_blocks(source, rows, prefetch):
        _, d2 = assign_nearest(blk, c, impl=impl, chunk=chunk)
        bmax = jnp.max(d2)
        best = bmax if best is None else jnp.maximum(best, bmax)
    if best is None:
        return jnp.float32(0.0)
    return best


def fold_top_k_min_d2(source, c, rank: int, *, impl: str = "auto",
                      chunk: int | None = None,
                      block_rows: int | None = None,
                      memory_budget: int | None = None,
                      prefetch: int | None = None,
                      weighted: bool = False) -> jnp.ndarray:
    """Descending top-``rank`` of the min squared distances to ``c`` over
    all source points — the streamed evaluation fold of the outlier
    objective: with ``rank = z + 1``, slot ``z`` is the squared covering
    radius after excluding the ``z`` farthest points
    (``core.outliers.covering_radius_excluding``).

    Top-k *values* are blocking-invariant (``merge_top_k``), so the result
    is bitwise the in-memory ``lax.top_k(assign_nearest(x, c)[1], rank)``
    for any blocking; slots beyond the support size carry the -inf
    sentinel. ``weighted=True`` gates rows with source weight <= 0 out of
    candidacy (they are absent from the weighted instance) — on the Pallas
    branch via the weighted tile's extra VMEM operand, on the ref branch
    via an eager mask; all-positive (e.g. unit) weights leave the
    candidate multiset untouched, hence the bits.
    """
    if rank < 1:
        raise ValueError(f"rank must be >= 1, got {rank}")
    rows = resolve_block_rows(source.n, source.d, block_rows=block_rows,
                              memory_budget=memory_budget,
                              prefetch=prefetch or DEFAULT_PREFETCH)
    fused, interpret, bn = _stream_tiles(impl, chunk, rows, c.shape[0],
                                         source.d)
    top = top_k_init(rank)
    off = 0
    if fused:
        rows_p = _padded_rows(rows, bn)
        d_big = jnp.full((rows_p,), _BIG)
        for blk in _source_blocks(source, rows, prefetch):
            nb = blk.shape[0]
            blk_p = jnp.pad(blk, ((0, rows_p - nb), (0, 0)))
            vm = (jnp.arange(rows_p) < nb).astype(jnp.float32)
            if weighted:
                w_p = np.zeros((rows_p,), np.float32)
                w_p[:nb] = _source_weights(source, off, nb)
                _, tops = fused_stream.fused_filter_blocks_w(
                    blk_p, c, d_big, vm, jnp.asarray(w_p), rank=rank,
                    bn=bn, interpret=interpret)
            else:
                _, tops = fused_stream.fused_filter_blocks(
                    blk_p, c, d_big, vm, rank=rank, bn=bn,
                    interpret=interpret)
            top = merge_top_k(top, tops, rank)
            off += nb
        return top
    for blk in _source_blocks(source, rows, prefetch):
        nb = blk.shape[0]
        _, d2 = assign_nearest(blk, c, impl=impl, chunk=chunk)
        if weighted:
            w = jnp.asarray(_source_weights(source, off, nb))
            d2 = jnp.where(w > 0, d2, _NEG)
        top = merge_top_k(top, d2, rank)
        off += nb
    return top


def assign_nearest_source(source, c, *, impl: str = "auto",
                          chunk: int | None = None,
                          block_rows: int | None = None,
                          memory_budget: int | None = None,
                          prefetch: int | None = None,
                          with_weights: bool = False):
    """Streaming nearest-center assignment over a source.

    Yields ``(idx (rows,) i32, d2 (rows,))`` per block, in row order —
    callers fold (counts, sums, maxima) instead of holding an (n,) result
    on device. Concatenating the yields equals the in-memory
    ``assign_nearest`` output bitwise.

    ``with_weights=True`` appends each block's per-row f32 weights to the
    yield — ``(idx, d2, w (rows,))`` — fetched through the source's
    ``weights_of`` (default ones for unweighted sources), so weighted
    accumulations (``engine`` leaves those to the caller: e.g.
    ``counts.at[idx].add(w)``) ride the same stream with zero extra
    passes. The idx/d2 arithmetic is untouched by the flag.
    """
    rows = resolve_block_rows(source.n, source.d, block_rows=block_rows,
                              memory_budget=memory_budget,
                              prefetch=prefetch or DEFAULT_PREFETCH)
    fused, interpret, bn = _stream_tiles(impl, chunk, rows, c.shape[0],
                                         source.d)
    off = 0
    if fused:
        rows_p = _padded_rows(rows, bn)
        for blk in _source_blocks(source, rows, prefetch):
            nb = blk.shape[0]
            blk_p = jnp.pad(blk, ((0, rows_p - nb), (0, 0)))
            # No mask: padded rows' outputs are sliced off, and the
            # fixed rows_p shape keeps the stream at one compilation.
            idx, d2 = fused_stream.fused_assign_blocks(
                blk_p, c, bn=bn, interpret=interpret)
            if with_weights:
                yield (idx[:nb], d2[:nb],
                       jnp.asarray(_source_weights(source, off, nb)))
            else:
                yield idx[:nb], d2[:nb]
            off += nb
        return
    for blk in _source_blocks(source, rows, prefetch):
        nb = blk.shape[0]
        if with_weights:
            idx, d2 = assign_nearest(blk, c, impl=impl, chunk=chunk)
            yield idx, d2, jnp.asarray(_source_weights(source, off, nb))
        else:
            yield assign_nearest(blk, c, impl=impl, chunk=chunk)
        off += nb


def argmin_dist2_over_source(source, c, *, impl: str = "auto",
                             chunk: int | None = None,
                             block_rows: int | None = None,
                             memory_budget: int | None = None,
                             prefetch: int | None = None) -> jnp.ndarray:
    """``argmin_dist2_over_rows`` over a source: for each center row of
    ``c (m, d)``, the global row index of the nearest source point.

    The fold carries an (m,)-sized running (min, argmin); strict ``<``
    keeps the earliest block on ties, and within a block ``assign_nearest``
    resolves ties to the first row — together matching the global
    first-occurrence semantics of ``jnp.argmin``.
    """
    m = c.shape[0]
    rows = resolve_block_rows(source.n, source.d, block_rows=block_rows,
                              memory_budget=memory_budget,
                              prefetch=prefetch or DEFAULT_PREFETCH)
    fused, interpret, bn = _stream_tiles(impl, chunk, rows, m, source.d)
    best_d = jnp.full((m,), _BIG)
    best_i = jnp.zeros((m,), jnp.int32)
    off = 0
    if fused:
        rows_p = _padded_rows(rows, bn)
        for blk in _source_blocks(source, rows, prefetch):
            nb = blk.shape[0]
            blk_p = jnp.pad(blk, ((0, rows_p - nb), (0, 0)))
            bd, bi = fused_stream.fused_argmin_blocks(
                blk_p, c, np.int32(nb), bn=bn, interpret=interpret)
            take = bd < best_d
            best_d = jnp.where(take, bd, best_d)
            best_i = jnp.where(take, bi + off, best_i)
            off += nb
        return best_i
    for blk in _source_blocks(source, rows, prefetch):
        bi, bd = assign_nearest(c, blk, impl=impl, chunk=chunk)
        take = bd < best_d
        best_d = jnp.where(take, bd, best_d)
        best_i = jnp.where(take, bi + off, best_i)
        off += blk.shape[0]
    return best_i
