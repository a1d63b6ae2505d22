"""Pallas TPU kernels: fused tiles for the streamed source × executor folds.

The streamed folds (``engine.fold_min_d2`` / ``assign_nearest_source`` /
``argmin_dist2_over_source`` and the executors' EIM filter round) are the
hot per-pass loops of the paper's MapReduce rounds: every super-shard is
read once, distance-reduced against a small resident center set, and folded
into O(m)- or O(rank)-sized state. On the reference path each block costs
several XLA dispatches (distances, min, where, top-k) with the ``(rows, m)``
distance block materialized between them. The kernels here fuse one block's
whole share of the round into a single ``pl.pallas_call``: each ``(bn, d)``
row tile is read from HBM exactly once, the MXU computes the
``|x|²+|c|²−2·x·cᵀ`` tile, and the min-reduce / carried d(x,S) update /
per-tile top-k all happen while the tile is VMEM-resident — the
bandwidth-bound one-pass-per-round claim of §3/§5.1, on the out-of-core
path and not just the legacy in-memory one.

Design contract (shared by all the kernels here; tests/test_engine.py pins
it bitwise against the ref oracle in interpret mode):

* **Rows-only tiling.** The grid walks row tiles; the ``(m, d)`` center set
  stays whole in VMEM, walked ``CENTER_CHUNK`` rows at a time inside the
  tile (a center set of one chunk is used whole). Per-row arithmetic is
  therefore the un-tiled reference expression — row-blocking a matmul's
  major operand does not change per-element accumulation order — which is
  what makes the Pallas path match ref, in interpret mode bit for bit.
  ``vmem_bytes`` models a step's scoped VMEM; ``fits_vmem`` tells the
  engine when a center set is too large for these tiles.
* **Masked ragged tails.** Callers pad every block to one fixed
  ``rows_p = ceil(rows/bn)·bn`` shape and pass validity as an *operand*
  (f32 0/1 — bool has no native TPU tile layout), so one compilation
  serves every block of a stream, tail included; padded lanes carry the
  ``-3.4e38`` sentinel through the reductions and can never win.
* **First-occurrence arg-semantics.** In-tile arg-reductions use
  ``jnp.argmin``/``argmax`` (first occurrence); cross-tile merges use
  strict ``<``/``>`` so the earliest tile keeps ties — composing to exactly
  ``jnp.argmin``/``argmax`` over the whole stream.
* **Unrolled top-k.** ``lax.top_k``/``sort`` are not relied on inside the
  tile; the per-tile top-``rank`` is ``rank`` unrolled max extractions
  (rank is a static, O(log n)-sized Select parameter), each removing the
  first lane that holds the max. The extracted multiset equals
  ``lax.top_k``'s, so the caller's ``merge_top_k`` fold is bitwise the
  monolithic top-k.
* **TPU block shapes.** Per-row vectors (``d_s``, masks, weights, the
  assignment outputs) travel as lane-dense ``(n/L, L)`` arrays in
  ``(bn/L, L)`` blocks, ``L = row_lanes(bn)`` (128 on TPU), and are
  reshaped to and from ``(bn,)`` inside the kernel. A 1-D block would have
  to match XLA's 1-D TPU tiling, and under ``vmap`` (the simulated
  machines) it gains a squeezed leading dim the TPU lowering refuses; the
  2-D form is (8, 128)-aligned either way when ``bn`` is a multiple of
  ``ROW_TILE`` (``engine._row_bn`` picks it). Per-tile results (top-k
  rows) are ``(nb, 1, rank)`` arrays with ``(1, rank)`` blocks: the last
  two block dims then equal the array's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import DOT_PRECISION

# Lanes of a TPU vector register, and the row tile whose (ROW_TILE/LANES,
# LANES) lane-dense block is (8, 128)-aligned: on TPU, row blocks are
# multiples of ROW_TILE (see the module docstring).
LANES = 128
ROW_TILE = 1024
# Default row tile: one ROW_TILE.
DEFAULT_BN = ROW_TILE
# Centers are walked in chunks of this many rows inside a tile, so the live
# distance tile is (bn, CENTER_CHUNK) whatever m is.
CENTER_CHUNK = 256
# v5e's scoped-VMEM default, and the most of its 128 MiB a kernel may ask for.
VMEM_DEFAULT = 16 * 1024 * 1024
VMEM_CAP = 96 * 1024 * 1024


def vmem_bytes(bn: int, m: int, d: int) -> int:
    """Scoped-VMEM model of one tile step, in bytes: the double-buffered
    ``(bn, d)`` rows plus the split operands of an f32 HIGHEST matmul, the
    double-buffered ``(m, d)`` centers, and the ``(bn, bm)`` distance-tile
    temporaries. Calibrated against the TPU compiler's allocations at
    d ∈ {4, 768}, m ∈ {16, 1024, 4096}, bn = 1024, which it bounds from
    above (d=768, m=1024: 31 MiB modelled, 26 MiB allocated)."""
    bm = _center_chunk(m)
    return 4 * (3 * bn * d + 2 * m * d + 16 * bn * bm)


def row_lanes(bn: int) -> int:
    """Lane width of the ``(bn/L, L)`` row blocks: ``LANES`` when ``bn`` is
    a multiple of it (always on TPU), else the largest divisor of ``bn``
    that divides ``LANES`` (the small interpret-mode tiles)."""
    return int(np.gcd(bn, LANES))


def to_lanes(v, bn: int):
    """``(n,)`` -> the ``(n/L, L)`` lane-dense form the kernels take."""
    return v.reshape(-1, row_lanes(bn))


def fits_vmem(bn: int, m: int, d: int) -> bool:
    """Whether a tile step with ``m`` resident centers fits the VMEM a
    kernel may ask for; callers take a center-tiled path when it does not."""
    return vmem_bytes(bn, m, d) <= VMEM_CAP


def compiler_params(bn: int, m: int, d: int):
    """Raise the scoped-VMEM limit to the modelled working set where it
    exceeds the default (``None`` keeps the compiler's default)."""
    need = vmem_bytes(bn, m, d)
    if need <= VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(need, VMEM_CAP))


# numpy scalars, NOT jnp: a jnp scalar is a device array, which a Pallas
# kernel body would capture as a constant instead of inlining as a literal.
_BIG = np.float32(3.4e38)
_NEG = np.float32(-3.4e38)
# Far coordinate for padded center rows: their distance overflows to +inf
# (or ~1e36·d), so they never win a min.
_FAR = np.float32(1e18)


def _dist2_tile(x, xn, c):
    """(bn, d) × (bm, d) -> (bn, bm) squared distances, the exact expression
    ``ref.pairwise_dist2`` evaluates (clamped MXU decomposition) — the
    bitwise contract of the whole module hangs on this being the same
    per-element arithmetic as the oracle. ``xn (bn, 1)`` is the rows'
    squared norms, computed once per tile."""
    cn = jnp.sum(c * c, axis=-1, keepdims=True)           # (bm, 1)
    prod = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), precision=DOT_PRECISION,
        preferred_element_type=jnp.float32
    )                                                     # (bn, bm)  MXU
    return jnp.maximum(xn + cn.T - 2.0 * prod, 0.0)


def _nearest(x_ref, c_ref, bm: int):
    """Per-row (min d², first-occurrence argmin) of the ``(bn, d)`` row tile
    over all resident centers, walking them ``bm`` rows at a time so the
    live distance tile is ``(bn, bm)``. Strict ``<`` keeps the earlier
    center chunk on ties, which composes to ``jnp.argmin``'s answer."""
    x = x_ref[...].astype(jnp.float32)                    # (bn, d)
    xn = jnp.sum(x * x, axis=-1, keepdims=True)           # (bn, 1)

    def chunk(j):
        d2 = _dist2_tile(x, xn, _center_rows(c_ref, j, bm))
        return (jnp.min(d2, axis=-1),
                jnp.argmin(d2, axis=-1).astype(jnp.int32) + j * bm)

    def body(j, carry):
        best_d, best_i = carry
        loc_d, loc_i = chunk(j)
        take = loc_d < best_d
        return jnp.where(take, loc_d, best_d), jnp.where(take, loc_i, best_i)

    return jax.lax.fori_loop(1, c_ref.shape[0] // bm, body, chunk(0))


def _center_rows(c_ref, j, bm: int):
    """Center chunk ``j`` (``bm`` rows) of the resident centers, in f32; the
    whole array when it is one chunk (no dynamic slice to align)."""
    if bm == c_ref.shape[0]:
        return c_ref[...].astype(jnp.float32)
    return c_ref[pl.ds(pl.multiple_of(j * bm, bm), bm), :].astype(
        jnp.float32)


def _row_spec(bn: int):
    """Block of one tile's per-row vector in its lane-dense form."""
    lanes = row_lanes(bn)
    return pl.BlockSpec((bn // lanes, lanes), lambda i: (i, 0))


def _center_chunk(m: int) -> int:
    """Centers per in-kernel step: all of them when they fit one chunk (the
    block is then the whole center array, which needs no alignment)."""
    return m if m <= CENTER_CHUNK else CENTER_CHUNK


def _pad_centers(c, bm: int):
    pad = (-c.shape[0]) % bm
    return jnp.pad(c, ((0, pad), (0, 0)), constant_values=_FAR) if pad else c


def _top_rank(cand, rank: int):
    """Per-tile descending top-``rank`` of a ``(bn/L, L)`` row block by
    unrolled max extraction, as a ``(1, rank)`` row.

    Each step removes exactly one lane (the first, in row order, that
    holds the max), so duplicates keep their multiplicity and the value
    multiset equals ``lax.top_k(cand.reshape(-1), rank)`` — with
    ``rank > bn`` the surplus slots fill with the ``_NEG`` sentinel,
    exactly like ``engine.top_k_init``. Only whole-block reductions and
    selects: no dynamic lane index.
    """
    rows, lanes = cand.shape
    order = (jax.lax.broadcasted_iota(jnp.int32, cand.shape, 0) * lanes
             + jax.lax.broadcasted_iota(jnp.int32, cand.shape, 1))
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, rank), 1)
    out = jnp.full((1, rank), _NEG)
    for r in range(rank):
        v = jnp.max(cand)
        first = jnp.min(jnp.where(cand == v, order, rows * lanes))
        out = jnp.where(slots == r, v, out)
        cand = jnp.where(order == first, _NEG, cand)
    return out


def _filter_kernel(x_ref, c_ref, ds_ref, hm_ref, newds_ref, top_ref, *,
                   rank: int, bm: int):
    d_min, _ = _nearest(x_ref, c_ref, bm)
    new_ds = jnp.minimum(ds_ref[...], d_min.reshape(ds_ref.shape))
    newds_ref[...] = new_ds
    # hm gates top-k candidacy only (EIM's H set ∧ tail validity); the
    # d(x,S) update above runs on every lane — callers slice padding off.
    cand = jnp.where(hm_ref[...] > 0, new_ds, _NEG)
    top_ref[...] = _top_rank(cand, rank)


@functools.partial(jax.jit, static_argnames=("rank", "bn", "interpret"))
def fused_filter_blocks(
    x: jnp.ndarray,
    c: jnp.ndarray,
    d_s: jnp.ndarray,
    hm: jnp.ndarray,
    *,
    rank: int,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
):
    """EIM Rounds 2–3 block tile: one fused pass computing
    ``new_d_s = min(d_s, d(x, c)²)`` and each tile's descending
    top-``rank`` of ``where(hm > 0, new_d_s, -inf)``.

    ``x (n, d)`` with ``n % bn == 0`` (callers pad), ``d_s (n,)`` f32,
    ``hm (n,)`` f32 0/1. Returns ``(new_d_s (n,), tops (n/bn, 1, rank))``;
    the caller merges tile tops with ``engine.merge_top_k`` (top-k values
    are blocking-invariant). With ``rank=1`` and ``d_s = +BIG`` this is
    the covering-radius fold's block max of min-distances.
    """
    n, d = x.shape
    assert n % bn == 0, (n, bn)
    nb = n // bn
    bm = _center_chunk(c.shape[0])
    c = _pad_centers(c, bm)
    m = c.shape[0]
    rows = _row_spec(bn)
    new_ds, tops = pl.pallas_call(
        functools.partial(_filter_kernel, rank=rank, bm=bm),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((m, d), lambda i: (0, 0)),
            rows,
            rows,
        ],
        out_specs=[
            rows,
            pl.BlockSpec((None, 1, rank), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n // row_lanes(bn), row_lanes(bn)),
                                 jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, rank), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=compiler_params(bn, m, d),
    )(x, c, to_lanes(d_s, bn), to_lanes(hm, bn))
    return new_ds.reshape(-1), tops


def _filter_kernel_w(x_ref, c_ref, ds_ref, hm_ref, w_ref, newds_ref,
                     top_ref, *, rank: int, bm: int):
    d_min, _ = _nearest(x_ref, c_ref, bm)
    new_ds = jnp.minimum(ds_ref[...], d_min.reshape(ds_ref.shape))
    newds_ref[...] = new_ds
    # Weights join hm in gating candidacy only: a w <= 0 row is absent
    # from the weighted instance, so it cannot contribute to the fold's
    # top-k, but its carried d(x,S) still updates like any padded lane.
    cand = jnp.where((hm_ref[...] > 0) & (w_ref[...] > 0), new_ds, _NEG)
    top_ref[...] = _top_rank(cand, rank)


@functools.partial(jax.jit, static_argnames=("rank", "bn", "interpret"))
def fused_filter_blocks_w(
    x: jnp.ndarray,
    c: jnp.ndarray,
    d_s: jnp.ndarray,
    hm: jnp.ndarray,
    w: jnp.ndarray,
    *,
    rank: int,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
):
    """Weighted sibling of ``fused_filter_blocks``: per-row f32 weights
    ``w (n,)`` ride as one extra VMEM operand (``4·bn`` bytes per step on
    top of the plain tile's working set) and gate top-k candidacy — rows
    with ``w <= 0`` are absent from the weighted instance. The arithmetic
    of the d(x,S) update and the top-k extraction is untouched, so with
    ``w > 0`` everywhere (unit weights) the program computes bitwise the
    plain kernel's outputs (pinned in tests/test_engine.py). A separate
    entry point — not a flag on ``fused_filter_blocks`` — so the plain
    kernel's compiled program is byte-identical to before this refactor.
    """
    n, d = x.shape
    assert n % bn == 0, (n, bn)
    nb = n // bn
    bm = _center_chunk(c.shape[0])
    c = _pad_centers(c, bm)
    m = c.shape[0]
    rows = _row_spec(bn)
    new_ds, tops = pl.pallas_call(
        functools.partial(_filter_kernel_w, rank=rank, bm=bm),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((m, d), lambda i: (0, 0)),
            rows,
            rows,
            rows,
        ],
        out_specs=[
            rows,
            pl.BlockSpec((None, 1, rank), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n // row_lanes(bn), row_lanes(bn)),
                                 jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, rank), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=compiler_params(bn, m, d),
    )(x, c, to_lanes(d_s, bn), to_lanes(hm, bn), to_lanes(w, bn))
    return new_ds.reshape(-1), tops


def _assign_kernel(x_ref, c_ref, idx_ref, d2_ref, *, bm: int):
    d_min, i_min = _nearest(x_ref, c_ref, bm)
    d2_ref[...] = d_min.reshape(d2_ref.shape)
    idx_ref[...] = i_min.reshape(idx_ref.shape)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def fused_assign_blocks(
    x: jnp.ndarray,
    c: jnp.ndarray,
    *,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
):
    """Nearest-center tile for the streamed assignment fold: returns
    ``(idx (n,) int32, d2 (n,) f32)``. ``n % bn == 0`` (callers pad and
    slice the tail back off — no mask is needed because padded rows'
    outputs are simply discarded). Unlike ``assign.py`` this keeps the
    center set un-tiled, so in-tile ``argmin`` is the whole first-
    occurrence answer and values are bitwise the ref oracle's.
    """
    n, d = x.shape
    assert n % bn == 0, (n, bn)
    bm = _center_chunk(c.shape[0])
    c = _pad_centers(c, bm)
    m = c.shape[0]
    rows = _row_spec(bn)
    shape = (n // row_lanes(bn), row_lanes(bn))
    idx, d2 = pl.pallas_call(
        functools.partial(_assign_kernel, bm=bm),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((m, d), lambda i: (0, 0)),
        ],
        out_specs=[rows, rows],
        out_shape=[
            jax.ShapeDtypeStruct(shape, jnp.int32),
            jax.ShapeDtypeStruct(shape, jnp.float32),
        ],
        interpret=interpret,
        compiler_params=compiler_params(bn, m, d),
    )(x, c)
    return idx.reshape(-1), d2.reshape(-1)


def _argmin_rows_kernel(nv_ref, x_ref, c_ref, bestd_ref, besti_ref, *,
                        bm: int):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)                    # (bn, d)
    bn = x.shape[0]
    xn = jnp.sum(x * x, axis=-1, keepdims=True)
    row = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    valid = row < nv_ref[0]                               # (bn, 1)

    # Center chunk j owns row j of the (m/bm, bm) outputs: the per-center
    # reductions run over rows (axis 0), so each lands lane-major.
    def body(j, carry):
        d2 = _dist2_tile(x, xn, _center_rows(c_ref, j, bm))  # (bn, bm)
        # Invalid (padded) rows go to the +BIG sentinel so they can never
        # be any center's nearest row (real distances are finite, smaller).
        d2 = jnp.where(valid, d2, _BIG)
        loc_d = jnp.min(d2, axis=0, keepdims=True)        # (1, bm)
        loc_i = (jnp.argmin(d2, axis=0, keepdims=True).astype(jnp.int32)
                 + i * bn)
        row = pl.ds(j, 1)

        @pl.when(i == 0)
        def _init():
            bestd_ref[row, :] = loc_d
            besti_ref[row, :] = loc_i

        @pl.when(i > 0)
        def _update():
            prev_d = bestd_ref[row, :]
            # Strict < keeps the earliest tile on ties — composing with the
            # in-tile first-occurrence argmin to global jnp.argmin semantics.
            take = loc_d < prev_d
            bestd_ref[row, :] = jnp.where(take, loc_d, prev_d)
            besti_ref[row, :] = jnp.where(take, loc_i, besti_ref[row, :])
        return carry

    nc = c_ref.shape[0] // bm
    if nc == 1:
        body(0, 0)
    else:
        jax.lax.fori_loop(0, nc, body, 0)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def fused_argmin_blocks(
    x: jnp.ndarray,
    c: jnp.ndarray,
    nvalid,
    *,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
):
    """Per-center argmin over a block's rows: for each center row of
    ``c (m, d)``, the (min d², first-occurrence argmin row) over the valid
    rows of ``x (n, d)`` — its first ``nvalid`` rows (an int32 operand in
    SMEM, so one compilation serves a ragged tail); ``n % bn == 0``. Returns ``(best_d (m,), best_i (m,) int32)`` — the
    running (m,)-carry accumulates across tiles in the revisited output
    block (sequential TPU grid), so the block never materializes (n, m).
    """
    n, d = x.shape
    m0 = c.shape[0]
    assert n % bn == 0, (n, bn)
    bm = _center_chunk(m0)
    c = _pad_centers(c, bm)
    m = c.shape[0]
    best_d, best_i = pl.pallas_call(
        functools.partial(_argmin_rows_kernel, bm=bm),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((m, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((m // bm, bm), lambda i: (0, 0)),
            pl.BlockSpec((m // bm, bm), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m // bm, bm), jnp.float32),
            jax.ShapeDtypeStruct((m // bm, bm), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=compiler_params(bn, m, d),
    )(jnp.asarray(nvalid, jnp.int32).reshape(1), x, c)
    return best_d.reshape(-1)[:m0], best_i.reshape(-1)[:m0]
