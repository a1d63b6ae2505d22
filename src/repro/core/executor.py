"""Executors — the paper's "machines", unified behind one interface.

The MapReduce model of the paper (§3) is: the input lives partitioned on
``m`` machines of capacity ``c``; a round runs GON on every partition and a
reducer combines the per-machine center sets (Lemma 2 for 2 rounds, Lemma 3
for the multi-round generalization). Historically this repo hard-coded
three different machine notions — vmapped blocks in ``mrg_sim``, mesh
shards in ``mrg_distributed``, and device-resident arrays everywhere. An
``Executor`` owns that choice, so ``repro.core.mrg.mrg`` is one algorithm
over any substrate:

=================== ======================= ===================== ===========
executor            machines                capacity knob         input
=================== ======================= ===================== ===========
SimExecutor         m vmapped blocks        ``capacity`` (rows)   device
MeshExecutor        mesh shards             shard size / axes     device
(fused device path)                         (``hierarchical``)
MeshExecutor        mesh shards, each       ``memory_budget`` /   per-shard
(sharded streamed)  streaming its own       ``block_rows``        sources —
                    per-shard source        (per shard) +         no host
                                            ``capacity`` (rows)   holds n
HostStreamExecutor  sequential super-shards ``memory_budget`` /   host RAM /
                    DMA'd from the source   ``block_rows``        disk
=================== ======================= ===================== ===========

Interface (paper correspondence in brackets):

  * ``run_blocks(fn, source)`` — round 1 [map]: apply the per-machine
    reducer ``fn(points (rows, d), mask (rows,) bool) -> (k, d)`` to every
    machine-block of the source; returns the center union ``(M·k, d)``
    plus a validity mask.
  * ``combine(centers, valid, k, capacity)`` — rounds 2+ [reduce /
    "send all points in S to a single reducer"]: while the union exceeds
    ``capacity``, re-block and reduce again (Lemma 3, +2 to the
    approximation factor per extra level), then run the final
    single-machine GON. Runs device-side — the union is k·M rows, tiny
    next to n.
  * ``radius2(source, centers)`` — the covering-radius fold over the
    *original* source (streamed; only one block device-resident).
  * ``mrg(source, k)`` — the orchestration of the three. ``MeshExecutor``
    overrides it wholesale: its rounds are one fused ``shard_map`` program
    (all_gather instead of a host-side reduce; every device recomputes the
    tiny final instance instead of idling).

``HostStreamExecutor`` is the out-of-core form: round 1 is a sequential
fold over super-shards DMA'd from a ``HostSource``/``MemmapSource``
(prefetch-ring buffered, see data/source.py), so ``mrg`` completes at n
bounded by host RAM or disk — the ROADMAP's "out-of-core input" step. Its
``memory_budget`` is the paper's machine capacity ``c`` in bytes.

``MeshExecutor`` additionally owns the *sharded streamed* form — the
paper's model verbatim: the input arrives as a ``ShardedSource`` (one
``PointSource`` per mesh shard; ``data/source.py``), each shard streams
its own blocks into its own mesh address space, and no host ever holds
all n rows. ``memory_budget`` is then the per-*shard* capacity ``c``.

Beyond MRG, executors own one more per-iteration primitive:
``run_filter_round`` — EIM's MapReduce Rounds 2–3 (paper §4, Algorithm 2):
update the host-resident ``d(x, S)`` state against the newly sampled
centers and reduce the φ·log n-th-farthest pivot (Algorithm 3's Select) in
the same pass. ``HostStreamExecutor`` executes it as a streamed fold under
``memory_budget`` (the per-block top-k's merge exactly — see
``engine.merge_top_k``); ``SimExecutor`` keeps the vmapped-machines
simulation (per-machine update + per-machine top-k, merged like the
MapReduce shuffle would). Both produce bitwise-identical ``d_s`` and pivot
for the same inputs on the ref backend — value reductions (min, top-k
values) are blocking-invariant.
"""
from __future__ import annotations

import functools
import warnings
import weakref
from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.data.source import (ArraySource, ShardedSource, as_source,
                               shard_source, stream_device, weights_of)
from repro.kernels import engine, ops

from .gonzalez import gonzalez

BlockFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
# Weighted round-1 reducer: (points (rows,d), mask (rows,), w (rows,)) ->
# (centers (k,d), cluster weights (k,)).
WeightedBlockFn = Callable[
    [jnp.ndarray, jnp.ndarray, jnp.ndarray],
    tuple[jnp.ndarray, jnp.ndarray]]


@dataclass(frozen=True)
class Objective:
    """Pluggable fold objective over the source × executor substrate.

    The executors' round surface (``run_blocks`` / ``combine_weighted`` /
    ``radius2`` / ``mrg``) dispatches on this descriptor instead of
    hard-coding unit-weight plain k-center:

    * ``weighted`` — round 1 reduces *weighted* instances: the per-machine
      reducer also emits per-cluster weight sums (the coreset outputs of
      Ceccarello et al. 1802.09205 — each center stands in for its
      cluster's total weight), carried through the Lemma-3 combine so the
      final k centers arrive with their cluster weights.
    * ``outliers`` — z: ``radius2`` becomes the top-(z+1) evaluation fold
      (squared covering radius after excluding the z farthest points),
      i.e. the (k,z)-center objective value.

    The default descriptor (``Objective()``, equivalently passing
    ``objective=None``) is plain k-center and keeps every executor code
    path *literally* unchanged — the bitwise contract the parity tests
    pin. Center *selection* is weight-oblivious throughout (k-center's
    max-min objective over the support doesn't scale with multiplicity),
    which is also what makes unit-weight weighted runs bitwise the plain
    runs.
    """

    name: str = "kcenter"
    weighted: bool = False
    outliers: int = 0

    def __post_init__(self):
        if self.outliers < 0:
            raise ValueError(f"outliers must be >= 0, got {self.outliers}")


def _is_plain(objective: Objective | None) -> bool:
    return objective is None or (not objective.weighted
                                 and objective.outliers == 0)

# np scalars so importing this module never commits the jax backend
_NEG = np.float32(-3.4e38)   # Select's invalid-slot sentinel (matches eim)
_BIG = np.float32(3.4e38)


# One super-shard's share of EIM Rounds 2–3, fused and jitted: the engine
# owns the implementation (it dispatches between the jnp oracle and the
# fused Pallas streamed tile — bitwise-identical); the historical name
# stays for callers and tests.
_eim_filter_block = engine.eim_filter_block


@functools.partial(jax.jit, static_argnames=("rank",))
def _eim_pivot_block(d_blk, h_blk, top, w_blk=None, *, rank):
    """Pivot-only block step for a zero-sample iteration (the distance
    state must stay bit-for-bit untouched, like the device path's
    ``any(s_valid)`` gate). ``w_blk=None`` is an empty jit pytree leaf —
    the unweighted compiled program is byte-identical to the pre-weights
    one; when present, ``w <= 0`` rows are gated out like ``H=False``."""
    sel = h_blk if w_blk is None else h_blk & (w_blk > 0)
    cand = jnp.where(sel, d_blk, _NEG)
    return engine.merge_top_k(top, cand, rank)


def _pivot_from_top(top: jnp.ndarray, rank: int) -> np.float32:
    """Algorithm 3's pivot from a merged descending top-``rank``: the
    rank-th largest d(·,S)^2, or -1.0 when fewer than ``rank`` valid points
    existed (sentinel slots survive the merge) — no distance-based removals
    that iteration, exactly the device path's ``where(pivot <= _NEG/2)``."""
    pivot = np.float32(np.asarray(top)[rank - 1])
    if pivot <= np.float32(_NEG) / 2:
        return np.float32(-1.0)
    return pivot


@functools.lru_cache(maxsize=None)
def gon_block_fn(k: int, impl: str = "auto",
                 chunk: int | None = None) -> BlockFn:
    """The per-machine reducer: GON restricted to a (masked) block.

    Cached on ``(k, impl, chunk)`` so repeated ``mrg`` calls reuse one
    function object — and therefore one jit cache entry per block shape.
    """
    def fn(points: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        return gonzalez(points, k, mask=mask, impl=impl, chunk=chunk).centers
    return fn


@functools.lru_cache(maxsize=None)
def weighted_gon_block_fn(k: int, impl: str = "auto",
                          chunk: int | None = None, *,
                          mask_zero: bool = True) -> WeightedBlockFn:
    """The weighted per-machine reducer: masked GON + per-cluster weight
    sums — one machine's share of a weighted coreset (Ceccarello et al.
    1802.09205: the per-reducer weighted instance).

    Selection runs the *same* masked GON as ``gon_block_fn`` (k-center's
    objective is weight-oblivious over the support), then each valid row's
    weight is summed onto its nearest selected center. f32 sums of
    integer-valued weights (cluster counts) are exact below 2^24.
    ``mask_zero`` additionally drops ``w <= 0`` rows from selection (they
    are absent from the instance) — round 1 wants that; the combine levels
    pass ``mask_zero=False`` so their selection mask is *exactly* the
    plain ``combine``'s (zero-weight duplicate rows from short blocks stay
    selectable there, keeping unit-weight runs bitwise plain).
    """
    def fn(points: jnp.ndarray, mask: jnp.ndarray, w: jnp.ndarray):
        sel = mask & (w > 0) if mask_zero else mask
        centers = gonzalez(points, k, mask=sel, impl=impl,
                           chunk=chunk).centers
        idx, _ = ops.assign_nearest(points, centers, impl=impl, chunk=chunk)
        cw = jnp.zeros((k,), jnp.float32).at[idx].add(
            jnp.where(sel, w, 0.0))
        return centers, cw
    return fn


@functools.lru_cache(maxsize=None)
def _vmapped(fn: BlockFn):
    return jax.jit(jax.vmap(fn))


@functools.partial(jax.jit, static_argnums=1)
def _block(points: jnp.ndarray, m: int):
    """Pad & reshape (n,d) -> (m, ceil(n/m), d) plus validity mask.

    Jitted: eagerly, a pad (even a zero-width one) and a reshape each copy
    the points, and three copies of a 6.4 GB input do not fit one chip."""
    n, d = points.shape
    per = -(-n // m)
    pad = per * m - n
    pts = jnp.pad(points, ((0, pad), (0, 0)))
    mask = jnp.arange(per * m) < n
    return pts.reshape(m, per, d), mask.reshape(m, per)


def _run_round(points_blocked: jnp.ndarray, mask_blocked: jnp.ndarray,
               fn: BlockFn):
    """vmapped ``fn`` over m blocks -> (m*k, d) center union + validity."""
    centers = _vmapped(fn)(points_blocked, mask_blocked)   # (m, k, d)
    m, k = centers.shape[0], centers.shape[1]
    centers = centers.reshape(m * k, -1)
    # a block with zero valid points still emits k (zero) rows; mark validity
    any_valid = jnp.any(mask_blocked, axis=1)              # (m,)
    valid = jnp.repeat(any_valid, k)                       # (m*k,)
    return centers, valid


def _run_round_w(points_blocked: jnp.ndarray, mask_blocked: jnp.ndarray,
                 w_blocked: jnp.ndarray, fn: WeightedBlockFn):
    """Weighted ``_run_round``: also flattens the per-block cluster-weight
    sums -> ``(centers (m*k, d), valid (m*k,), weights (m*k,))``."""
    centers, cw = _vmapped(fn)(points_blocked, mask_blocked, w_blocked)
    m, k = centers.shape[0], centers.shape[1]
    any_valid = jnp.any(mask_blocked, axis=1)
    valid = jnp.repeat(any_valid, k)
    return centers.reshape(m * k, -1), valid, cw.reshape(-1)


def _mrg_round(points_blocked: jnp.ndarray, mask_blocked: jnp.ndarray,
               k: int, m: int, impl: str, chunk: int | None = None):
    """PR-1-compatible round entry (benchmarks/runtime_scaling.py times it)."""
    del m  # implied by the blocking
    return _run_round(points_blocked, mask_blocked, gon_block_fn(k, impl, chunk))


_DIVERGED_MSG = ("combine diverged (k too close to capacity; "
                 "paper §3.3 requires 2k < c)")


def check_combine_capacity(k: int, capacity: int, *,
                           warn: bool = True) -> None:
    """Feasibility of the Lemma-3 reduction under machine capacity ``c``.

    Mirrors ``plan_rounds``' checks so ``mrg()``/``combine`` fail up front
    instead of looping forever: a level re-blocks M rows into
    ``m2 = ceil(M / capacity)`` machines and emits ``m2·k`` rows, so with
    ``capacity <= k`` the union never shrinks (e.g. ``mrg(x, 8,
    capacity=4)``: 400 rows → m2=100 → 800 rows, growing every level) —
    hard error. With ``k < capacity < 2k`` the recurrence may still stall
    on the ceil (§3.3 requires ``2k < c`` for convergence) — warn (unless
    ``warn=False``; ``mrg`` pre-checks with it off so the warning fires
    once, from ``combine``), and let the divergence guard in ``combine``
    raise if it does.
    """
    if capacity <= k:
        raise ValueError(
            f"infeasible: k={k} needs single-machine capacity > k, got "
            f"{capacity} — every combine level re-blocks M rows into "
            "ceil(M/capacity) machines of k centers each, so the center "
            "union never shrinks")
    if warn and capacity < 2 * k:
        warnings.warn(
            f"capacity={capacity} < 2k={2 * k}: paper §3.3 requires "
            "2k < c for the round recurrence to converge; combine may "
            "stall and raise", RuntimeWarning, stacklevel=3)


class Executor:
    """Base: block-mapped round 1 + shared Lemma-3 reduction."""

    def run_blocks(self, fn, source, *, objective: Objective | None = None):
        """Round 1: map ``fn`` over the source's machine-blocks.

        Plain (default) objective: ``fn`` is a ``BlockFn`` and the return
        is ``(centers (M·k, d), valid (M·k,) bool)`` — exactly the
        pre-objective surface. With ``objective.weighted``, ``fn`` is a
        ``WeightedBlockFn`` (e.g. ``weighted_gon_block_fn``) and the
        return gains the per-cluster weight sums:
        ``(centers, valid, weights (M·k,) f32)``.
        """
        raise NotImplementedError

    def default_capacity(self, source, k: int) -> int:
        """The paper's machine capacity ``c`` implied by this executor's
        blocking (rows per machine, floored at 2k — §3.3 requires 2k < c
        for the round recurrence to converge)."""
        return 2 * k

    def combine(self, centers: jnp.ndarray, valid: jnp.ndarray, k: int,
                capacity: int, *, impl: str = "auto",
                chunk: int | None = None):
        """Rounds 2+: reduce the center union to k centers.

        While the union exceeds ``capacity``, re-block and run another
        vmapped GON level (paper §3.3 — each extra level adds +2 to the
        approximation factor), then the final single-machine GON.
        Returns ``(centers (k, d), extra_rounds)``.

        ``capacity`` is validated up front (``check_combine_capacity``):
        ``capacity <= k`` makes every level *grow* the union, so it raises
        instead of looping forever; ``capacity < 2k`` warns (§3.3) and a
        divergence guard raises if a level fails to shrink the union (or
        more than 64 levels accumulate — the same bound ``plan_rounds``
        enforces).
        """
        check_combine_capacity(k, capacity)
        extra = 0
        while centers.shape[0] > capacity and centers.shape[0] > k:
            m2 = -(-centers.shape[0] // capacity)  # >= 2 since rows > capacity
            if m2 * k >= centers.shape[0] or extra >= 64:
                # With capacity >= 2k a level always shrinks the union
                # (m2*k <= M/2 + k < M); reaching here means the warned
                # k < capacity < 2k regime stalled on the ceil.
                raise ValueError(_DIVERGED_MSG)
            blocked, bmask = _block(centers, m2)
            vpad = jnp.pad(valid, (0, bmask.size - valid.shape[0]),
                           constant_values=False)
            bmask = bmask & vpad.reshape(bmask.shape)
            centers, valid = _mrg_round(blocked, bmask, k, m2, impl, chunk)
            extra += 1
        final = gonzalez(centers, k, mask=valid, impl=impl, chunk=chunk)
        return final.centers, extra

    def combine_weighted(self, centers: jnp.ndarray, valid: jnp.ndarray,
                         weights: jnp.ndarray, k: int, capacity: int, *,
                         impl: str = "auto", chunk: int | None = None,
                         final_gon: bool = True):
        """Lemma-3 reduction carrying cluster weights — coreset outputs
        stay weighted instances through every level.

        Each level re-blocks the weighted union and picks per-block GON
        centers with *exactly* ``combine``'s selection mask (validity
        only — weights never steer selection, so on unit-weight inputs the
        per-level center unions are bitwise the plain ``combine``'s), then
        re-aggregates every row's weight onto its nearest new center
        (Ceccarello et al.'s coreset re-weighting; f32 sums of integer
        weights are exact below 2^24). With ``final_gon=False`` the
        reduction stops as soon as the union fits ``capacity`` — the
        weighted-coreset form ``core.outliers.kz_center`` hands to its
        host-side solve — otherwise the final single-machine GON runs and
        the weights are re-aggregated onto the k winners.

        Returns ``(centers, weights, valid, extra_rounds)``; after a
        final GON, ``centers`` is (k, d) and ``valid`` all-True.
        """
        check_combine_capacity(k, capacity)
        w = jnp.asarray(weights, jnp.float32)
        fn = weighted_gon_block_fn(k, impl, chunk, mask_zero=False)
        extra = 0
        while centers.shape[0] > capacity and centers.shape[0] > k:
            m2 = -(-centers.shape[0] // capacity)
            if m2 * k >= centers.shape[0] or extra >= 64:
                raise ValueError(_DIVERGED_MSG)
            blocked, bmask = _block(centers, m2)
            vpad = jnp.pad(valid, (0, bmask.size - valid.shape[0]),
                           constant_values=False)
            bmask = bmask & vpad.reshape(bmask.shape)
            wpad = jnp.pad(w, (0, bmask.size - w.shape[0]))
            centers, valid, w = _run_round_w(blocked, bmask,
                                             wpad.reshape(bmask.shape), fn)
            extra += 1
        if not final_gon:
            return centers, w, valid, extra
        final = gonzalez(centers, k, mask=valid, impl=impl, chunk=chunk)
        idx, _ = ops.assign_nearest(centers, final.centers, impl=impl,
                                    chunk=chunk)
        w_out = jnp.zeros((k,), jnp.float32).at[idx].add(
            jnp.where(valid, w, 0.0))
        return (final.centers, w_out,
                jnp.ones((k,), bool), extra)

    def radius2(self, source, centers: jnp.ndarray, *, impl: str = "auto",
                chunk: int | None = None,
                objective: Objective | None = None) -> jnp.ndarray:
        """Squared covering radius over ALL source points (streamed).

        Returns the squared fold ``max(min_d2)`` *directly* — no
        ``sqrt(d2)`` → ``r*r`` round-trip, which is lossy in f32 (the fold
        is already squared). All executor paths return the same exact
        value, which is what the cross-path bitwise parity tests compare.

        A non-plain ``objective`` generalizes the fold: ``outliers=z``
        evaluates the (k,z) objective — the top-(z+1) streamed fold's last
        slot, i.e. the covering radius after excluding the z farthest
        points — and ``weighted`` restricts candidacy to the source's
        positive-weight support. The default objective takes the exact
        pre-objective code path.
        """
        if not _is_plain(objective):
            top = engine.fold_top_k_min_d2(
                source, centers, objective.outliers + 1, impl=impl,
                chunk=chunk, weighted=objective.weighted)
            return jnp.maximum(top[objective.outliers], jnp.float32(0.0))
        return engine.fold_min_d2(source, centers, impl=impl, chunk=chunk)

    def run_filter_round(self, source, s_new, d_s: np.ndarray,
                         h_mask: np.ndarray, rank: int, *,
                         impl: str = "auto", chunk: int | None = None,
                         weights: np.ndarray | None = None):
        """One EIM iteration's Rounds 2–3 over this executor's machines.

        ``s_new`` is the iteration's newly sampled centers ``(m_new, d)``
        (host numpy, possibly padded with far-away ``1e18`` sentinel rows
        to a fixed capacity — padding can never win the distance min;
        ``None``/empty for a zero-sample iteration — the distance state is
        then left untouched, like the device path's ``any(s_valid)``
        gate). ``d_s (n,) f32`` and ``h_mask (n,) bool`` are host-resident
        per-point state. Updates ``d_s`` in place to
        ``min(d_s, d(x, S_new)^2)`` (paper §4 Round 3's incremental
        update) and reduces Select's pivot — the ``rank``-th largest
        updated ``d_s`` over H (Round 2) — in the same pass.

        ``weights`` (optional host ``(n,) f32``, aligned with ``d_s``)
        threads the weighted objective through the fused update+top-k:
        ``w <= 0`` rows are gated out of pivot candidacy exactly like
        ``h_mask=False`` rows (their d(x,S) still updates). ``None`` — the
        only form existing callers pass — runs the exact pre-weights
        program.

        Returns ``(d_s, pivot)`` with ``pivot`` an np.float32 (−1.0 when H
        held fewer than ``rank`` points).
        """
        raise NotImplementedError(
            f"{type(self).__name__} does not implement EIM's "
            "run_filter_round; use HostStreamExecutor (streamed), "
            "SimExecutor (vmapped machines) or MeshExecutor (sharded)")

    def end_filter_rounds(self, source) -> None:
        """Called once when an EIM run's iteration loop finishes — the
        hook for executors to release any per-source state they cached
        across ``run_filter_round`` calls. Default: nothing to release."""

    def mrg(self, source, k: int, *, capacity: int | None = None,
            impl: str = "auto", chunk: int | None = None,
            objective: Objective | None = None):
        """Full MRG on this executor. Returns ``(centers, radius2,
        rounds)`` — or, with a weighted ``objective``, ``(centers,
        radius2, rounds, weights (k,))``: the same rounds run weighted
        (``weighted_gon_block_fn`` + ``combine_weighted``), so the k
        centers arrive with their cluster weights (a weighted coreset).
        An ``outliers=z`` objective scores ``radius2`` as the top-(z+1)
        evaluation fold; the default objective is byte-for-byte the
        pre-objective orchestration."""
        source = as_source(source)
        if capacity is None:
            capacity = self.default_capacity(source, k)
        # Fail on an infeasible capacity *before* the round-1 pass over
        # all of n, not inside combine's reduction loop (warn=False:
        # combine's own check owns the §3.3 warning).
        check_combine_capacity(k, capacity, warn=False)
        if objective is not None and objective.weighted:
            wfn = weighted_gon_block_fn(k, impl, chunk)
            centers, valid, cw = self.run_blocks(wfn, source,
                                                 objective=objective)
            centers, w, _, extra = self.combine_weighted(
                centers, valid, cw, k, capacity, impl=impl, chunk=chunk)
            r2 = self.radius2(source, centers, impl=impl, chunk=chunk,
                              objective=objective)
            return centers, r2, 2 + extra, w
        fn = gon_block_fn(k, impl, chunk)
        centers, valid = self.run_blocks(fn, source)
        centers, extra = self.combine(centers, valid, k, capacity,
                                      impl=impl, chunk=chunk)
        r2 = self.radius2(source, centers, impl=impl, chunk=chunk,
                          objective=objective)
        return centers, r2, 2 + extra


class SimExecutor(Executor):
    """The paper's experimental setup (§7.1): ``m`` simulated machines on
    one device — the source is materialized and blocked into m shards, and
    GON runs on every shard via ``vmap``."""

    def __init__(self, m: int = 50):
        if m < 1:
            raise ValueError(f"need at least one machine, got m={m}")
        self.m = m

    def run_blocks(self, fn, source, *, objective: Objective | None = None):
        src = as_source(source)
        x = src.materialize()
        blocked, mask = _block(x, self.m)
        if objective is not None and objective.weighted:
            w = jnp.asarray(weights_of(src, 0, src.n))
            wb = jnp.pad(w, (0, mask.size - w.shape[0]))
            return _run_round_w(blocked, mask, wb.reshape(mask.shape), fn)
        return _run_round(blocked, mask, fn)

    def default_capacity(self, source, k: int) -> int:
        return max(-(-source.n // self.m), 2 * k)

    def radius2(self, source, centers, *, impl="auto", chunk=None,
                objective: Objective | None = None):
        # Device-resident input: one single-pass fold (avoids re-blocking
        # an array that is already in HBM). Returns the squared max
        # directly — the sqrt→square round-trip of ``covering_radius`` is
        # lossy in f32 and would break cross-path bitwise parity.
        src = as_source(source)
        # reprolint: disable=R002 -- SimExecutor simulates m machines on one device; inputs are device-resident by contract
        _, d2 = ops.assign_nearest(src.materialize(), centers, impl=impl,
                                   chunk=chunk)
        if _is_plain(objective):
            return jnp.max(d2)
        # Same eager d2; the objective only changes the reduction (top-1
        # of a multiset == its max, so weighted unit runs keep the bits).
        if objective.weighted:
            w = jnp.asarray(weights_of(src, 0, src.n))
            d2 = jnp.where(w > 0, d2, _NEG)
        r = objective.outliers + 1
        top = engine.merge_top_k(engine.top_k_init(r), d2, r)
        return jnp.maximum(top[r - 1], jnp.float32(0.0))

    def _blocked_for(self, source):
        """Materialize + block once per source object (EIM calls the
        filter round every iteration with the same source; the points
        never change across iterations). Weakref-keyed so a different
        source object can never hit a stale cache, and released by
        ``end_filter_rounds`` so the blocked copy does not outlive the
        run. Un-weakref-able inputs are simply not cached."""
        cache = getattr(self, "_eim_blocked_cache", None)
        if cache is not None and cache[0]() is source:
            return cache[1]
        x = as_source(source).materialize()
        blocked, _ = _block(x, self.m)
        try:
            self._eim_blocked_cache = (weakref.ref(source),
                                       (x.shape[0], blocked))
        except TypeError:
            pass
        return x.shape[0], blocked

    def end_filter_rounds(self, source) -> None:
        self._eim_blocked_cache = None

    def run_filter_round(self, source, s_new, d_s, h_mask, rank, *,
                         impl="auto", chunk=None, weights=None):
        """Vmapped-machines EIM round: each of the m blocks updates its
        slice of d(x,S) against S_new and emits a per-machine top-k; the
        host merge of those tops is the simulated shuffle. ``weights``
        (optional, aligned with ``d_s``) gates ``w <= 0`` rows out of
        pivot candidacy — ``None`` runs the exact pre-weights program."""
        n, blocked = self._blocked_for(source)              # (m, per, d)
        m, per = blocked.shape[0], blocked.shape[1]
        pad = m * per - n
        # Padded rows: _BIG distance but H=False, so they can't enter the
        # pivot top-k and their d_s is dropped on the un-pad below.
        d_b = jnp.pad(jnp.asarray(d_s), (0, pad),
                      constant_values=_BIG).reshape(m, per)
        h_b = jnp.pad(jnp.asarray(h_mask), (0, pad),
                      constant_values=False).reshape(m, per)
        w_b = None
        if weights is not None:
            # Padded lanes at weight 0 — gated out of candidacy like H=0.
            w_b = jnp.pad(jnp.asarray(np.asarray(weights, np.float32)),
                          (0, pad)).reshape(m, per)
        have_s = s_new is not None and len(s_new) > 0
        use_pallas, _ = engine._resolve(impl)
        if have_s:
            c = jnp.asarray(np.asarray(s_new, np.float32))
            if use_pallas:
                # Fused tile path: vmap over a pallas_call is not a
                # supported lowering everywhere, and the machine axis is a
                # simulation artifact — flatten it. The per-row d-update
                # is machine-oblivious and the global top-k values equal
                # the merge of per-machine top-k's (value folds are
                # blocking-invariant), so this is bitwise the vmapped ref.
                d_flat, top = engine.filter_tile_update(
                    blocked.reshape(m * per, -1), c, d_b.reshape(-1),
                    h_b.reshape(-1), rank=rank, impl=impl, chunk=chunk,
                    w_blk=None if w_b is None else w_b.reshape(-1))
                d_s[:] = np.asarray(d_flat[:n])
                top = engine.merge_top_k(engine.top_k_init(rank), top, rank)
                return d_s, _pivot_from_top(top, rank)

            def update(pts, dvec):
                _, dn = ops.assign_nearest(pts, c, impl=impl, chunk=chunk)
                return jnp.minimum(dvec, dn)

            d_b = jax.vmap(update)(blocked, d_b)
            d_s[:] = np.asarray(d_b.reshape(-1)[:n])
        cand_mask = h_b if w_b is None else h_b & (w_b > 0)
        cand = jnp.where(cand_mask, d_b, _NEG)
        r = min(rank, per)
        tops = jax.vmap(lambda v: jax.lax.top_k(v, r)[0])(cand)  # (m, r)
        top = jax.lax.top_k(tops.reshape(-1), rank)[0]
        return d_s, _pivot_from_top(top, rank)


class HostStreamExecutor(Executor):
    """Out-of-core machines: sequential super-shards DMA'd from the source.

    Round 1 is a host-driven fold — each super-shard is uploaded (through
    the source's prefetch ring), reduced to k centers by GON, and
    discarded; at most ``1 + prefetch`` shards (the consumed one plus the
    in-flight ring) and the accumulated union are device-resident.
    ``memory_budget`` (bytes) bounds all of them via the engine's
    ``(1+prefetch)·4·rows·(d+1)`` model — the paper's machine capacity
    ``c``; ``block_rows`` sets the shard size directly.
    """

    def __init__(self, block_rows: int | None = None,
                 memory_budget: int | None = None,
                 prefetch: int = engine.DEFAULT_PREFETCH):
        self.block_rows = block_rows
        self.memory_budget = memory_budget
        if prefetch < 1:
            raise ValueError(f"prefetch must be >= 1, got {prefetch}")
        self.prefetch = prefetch

    def rows_for(self, source) -> int:
        return engine.resolve_block_rows(source.n, source.d,
                                         block_rows=self.block_rows,
                                         memory_budget=self.memory_budget,
                                         prefetch=self.prefetch)

    def _blocks(self, source, rows: int):
        return engine._source_blocks(source, rows, self.prefetch)

    def run_blocks(self, fn, source, *, objective: Objective | None = None):
        rows = self.rows_for(source)
        weighted = objective is not None and objective.weighted
        outs, wouts = [], []
        off = 0
        for blk in self._blocks(source, rows):
            nb = blk.shape[0]
            w_blk = None
            if weighted:
                # Padded lanes carry weight 0 — like mask=False, they can
                # never contribute to the per-cluster weight sums.
                w_np = np.zeros((rows,), np.float32)
                w_np[:nb] = engine._source_weights(source, off, nb)
                w_blk = jnp.asarray(w_np)
            if nb < rows:
                # Pad the ragged final block to the common shape and mask
                # the padding off: one compilation of the per-machine GON
                # serves every block (the mask is a traced operand, and a
                # masked GON picks bitwise-identical centers — padded rows
                # sit at the _NEG sentinel and can never be selected).
                blk = jnp.pad(blk, ((0, rows - nb), (0, 0)))
            mask = jnp.arange(rows) < nb
            if weighted:
                c, cw = fn(blk, mask, w_blk)               # (k, d), (k,)
                outs.append(c)
                wouts.append(cw)
            else:
                outs.append(fn(blk, mask))                 # (k, d) each
            off += nb
        centers = jnp.concatenate(outs, axis=0)            # (M*k, d)
        valid = jnp.ones((centers.shape[0],), bool)
        if weighted:
            return centers, valid, jnp.concatenate(wouts, axis=0)
        return centers, valid

    def default_capacity(self, source, k: int) -> int:
        return max(self.rows_for(source), 2 * k)

    def radius2(self, source, centers, *, impl="auto", chunk=None,
                objective: Objective | None = None):
        if not _is_plain(objective):
            top = engine.fold_top_k_min_d2(
                source, centers, objective.outliers + 1, impl=impl,
                chunk=chunk, block_rows=self.rows_for(source),
                prefetch=self.prefetch, weighted=objective.weighted)
            return jnp.maximum(top[objective.outliers], jnp.float32(0.0))
        return engine.fold_min_d2(source, centers, impl=impl, chunk=chunk,
                                  block_rows=self.rows_for(source),
                                  prefetch=self.prefetch)

    def run_filter_round(self, source, s_new, d_s, h_mask, rank, *,
                         impl="auto", chunk=None, weights=None):
        """EIM Rounds 2–3 as one out-of-core fold: each super-shard's
        d(x, S_new) update and its contribution to Select's top-k happen
        while the shard is device-resident; only the shard, S_new, and the
        (rank,)-sized running top-k occupy the device. The per-point state
        (d_s, h_mask) stays host-resident — O(n) bytes next to the (n, d)
        points that never materialize.

        ``source`` may be a compacted ``IndexedSource`` view (``d_s`` /
        ``h_mask`` then hold the per-view slices). Every block is padded
        to the resolved ``rows`` shape — padded lanes carry ``H=False``
        (never enter the pivot top-k) and their distance update is
        discarded — so one compilation of the fused block kernel serves
        all iterations over a given view, ragged tail included.

        ``weights`` (optional, aligned with ``d_s``) gates ``w <= 0``
        rows out of pivot candidacy; ``None`` (every plain caller) keeps
        the block programs byte-identical to the pre-weights ones."""
        rows = self.rows_for(source)
        have_s = s_new is not None and len(s_new) > 0
        if have_s:
            c = jnp.asarray(np.asarray(s_new, np.float32))
        top = engine.top_k_init(rank)
        off = 0
        for blk in self._blocks(source, rows):
            nb = blk.shape[0]
            d_np = d_s[off:off + nb]
            h_np = h_mask[off:off + nb]
            w_blk = None
            if weights is not None:
                w_np = np.zeros((rows,), np.float32)
                w_np[:nb] = np.asarray(weights[off:off + nb], np.float32)
                w_blk = jnp.asarray(w_np)
            if nb < rows:
                pad = rows - nb
                blk = jnp.pad(blk, ((0, pad), (0, 0)))
                d_np = np.concatenate(
                    [d_np, np.full(pad, np.float32(3.4e38), np.float32)])
                h_np = np.concatenate([h_np, np.zeros(pad, bool)])
            d_blk = jnp.asarray(d_np)
            h_blk = jnp.asarray(h_np)
            if have_s:
                d_blk, top = _eim_filter_block(blk, c, d_blk, h_blk, top,
                                               w_blk, rank=rank, impl=impl,
                                               chunk=chunk)
                d_s[off:off + nb] = np.asarray(d_blk)[:nb]
            else:
                top = _eim_pivot_block(d_blk, h_blk, top, w_blk, rank=rank)
            off += nb
        return d_s, _pivot_from_top(top, rank)


class MeshExecutor(Executor):
    """The production TPU form: machines are mesh shards.

    Two input regimes share the executor:

    * **Device-resident** (raw arrays / ``ArraySource``): ``mrg`` is one
      fused ``shard_map`` program — round 1 (per-shard GON), round 2+
      (all_gather of center sets + replicated GON; with ``hierarchical``,
      axis-by-axis gathers with an intermediate GON per level, exactly
      Lemma 3 with ICI-domain capacities) and the radius reduction, with
      no host round-trips. The input is materialized then resharded, so
      n is bounded by single-host RAM — the historical behavior.
    * **Sharded / streamed** (a ``ShardedSource``, or any host/disk/
      generator source — auto-split by ``shard_source`` into the paper's
      contiguous machine ranges): round 1 streams each shard's blocks
      host→device *into that shard's mesh address space* through the
      sources' prefetch ring (``compat.global_array_from_shards`` — per-
      shard DMA, no global host staging buffer), one ``shard_map`` program
      of per-shard GONs per step. **No host buffer ever holds all n
      rows**: per-shard residency is bounded by ``memory_budget`` via the
      same ``(1+prefetch)·4·rows·(d+1)`` model as ``HostStreamExecutor``,
      applied per shard. Rounds 2+ reuse the shared Lemma-3 ``combine``
      (``capacity`` is honored on this path), the covering radius is a
      per-step sharded fold, and EIM's ``run_filter_round`` streams the
      same way — so ``mrg``/``eim`` over a ``ShardedSource`` are
      *bitwise identical* to the Sim/HostStream paths on ref for matching
      machine blockings (tests/test_distributed.py pins the grid).
    """

    def __init__(self, mesh: Mesh, shard_axes: Sequence[str] = ("data",),
                 hierarchical: bool = False, *,
                 block_rows: int | None = None,
                 memory_budget: int | None = None,
                 prefetch: int = engine.DEFAULT_PREFETCH):
        self.mesh = mesh
        self.shard_axes = tuple(shard_axes)
        self.hierarchical = hierarchical
        self.block_rows = block_rows
        self.memory_budget = memory_budget
        if prefetch < 1:
            raise ValueError(f"prefetch must be >= 1, got {prefetch}")
        self.prefetch = prefetch
        self._step_cache: dict = {}

    # -- the machine blocking the mesh implies ------------------------------

    @property
    def num_shards(self) -> int:
        """Machines = product of the ``shard_axes`` mesh-axis sizes."""
        count = 1
        for ax in self.shard_axes:
            count *= int(self.mesh.shape[ax])
        return count

    def _pspec(self) -> P:
        axes = self.shard_axes
        return P(axes if len(axes) > 1 else axes[0])

    def _sharded(self, source) -> ShardedSource:
        """The per-shard view of ``source``: a ``ShardedSource`` passes
        through (its shard count must match the mesh blocking — a
        mismatch is a real partitioning bug, not something to silently
        re-split); anything else is split into the paper's contiguous
        machine ranges (zero-copy ``SliceSource`` views)."""
        src = as_source(source)
        if isinstance(src, ShardedSource):
            if src.num_shards != self.num_shards:
                raise ValueError(
                    f"ShardedSource has {src.num_shards} shards but the "
                    f"mesh blocking over {self.shard_axes} has "
                    f"{self.num_shards} — re-shard the input or change "
                    "shard_axes")
            return src
        return shard_source(src, self.num_shards)

    def rows_for(self, source) -> int:
        """Per-shard super-shard rows: ``memory_budget`` (bytes, *per
        shard*) solved against the ring residency model, like
        ``HostStreamExecutor`` but per machine."""
        sh = self._sharded(source)
        return engine.resolve_block_rows(max(sh.max_shard_rows, 1), sh.d,
                                         block_rows=self.block_rows,
                                         memory_budget=self.memory_budget,
                                         prefetch=self.prefetch)

    # -- multi-process topology ---------------------------------------------

    def _local_ids(self, sh: ShardedSource):
        """Shard indices this process feeds, or ``None`` for "all"
        (single-process — the historical behavior, kept byte-identical).

        Under ``jax.distributed`` each process feeds exactly the shards
        whose mesh address space it owns (``compat.local_shard_indices``);
        a ``ProcessShardedSource`` must hold real data for all of them —
        a mismatch between the data partition and the mesh partition is a
        launch bug, reported here rather than as a RemoteShard read deep
        inside a fold."""
        src_local = getattr(sh, "local_shard_ids", None)
        if compat.process_count() == 1:
            if src_local is not None and len(src_local) < sh.num_shards:
                raise ValueError(
                    "source has remote shards but the runtime is "
                    "single-process — no other process exists to feed "
                    "them (launch via repro.launch.cluster)")
            return None
        lids = compat.local_shard_indices(self.mesh, self._pspec(),
                                          sh.num_shards)
        if src_local is not None:
            missing = sorted(set(lids) - set(src_local))
            if missing:
                raise ValueError(
                    f"process {compat.process_index()} owns mesh shards "
                    f"{lids} but the source holds no data for shards "
                    f"{missing} — align the data partition with the mesh "
                    "(ProcessShardedSource.for_process with the launch "
                    "process id)")
        return lids

    def _fetch(self, arr) -> np.ndarray:
        """Host value of a per-step output: plain ``np.asarray`` single-
        process; the ``process_allgather`` collective when shards span
        processes (every process then holds every shard's slice — the
        O(k·S) per-step shuffle, never the points)."""
        return compat.fetch_global(arr)

    # -- per-step sharded streaming -----------------------------------------

    def _stream_steps(self, sh: ShardedSource, rows: int):
        """Per-step global device arrays for the sharded fold: yields
        ``(pts (S·rows, d), mask (S·rows,) bool, counts (S,) np)`` with
        every shard's piece device-put into its own mesh address space.
        The transfer rides the sources' prefetch ring (``stream_device``
        with a sharded ``put``), so up to ``prefetch`` steps' DMAs are in
        flight ahead of the consumed one — the same overlap model as the
        single-device stream, per shard.

        Multi-process, each process reads (and ``device_put``s) only its
        own shards — the other entries in the piece list are ``None`` and
        the global array is assembled from local shards alone; masks and
        step counts are computed arithmetically for every shard, so all
        processes run the same step sequence in lockstep."""
        mesh, pspec = self.mesh, self._pspec()
        local = self._local_ids(sh)

        def put(step):
            pts, counts = step            # (S, rows, d) or [piece|None], (S,)
            mask = np.arange(rows)[None, :] < counts[:, None]
            g_p = compat.global_array_from_shards(mesh, pspec, list(pts))
            g_m = compat.global_array_from_shards(mesh, pspec, list(mask))
            return g_p, g_m, counts

        return stream_device(
            engine.zip_shard_blocks(sh.shards, rows, local_ids=local),
            self.prefetch, put=put)

    def _stream_steps_w(self, sh: ShardedSource, rows: int):
        """Weighted sibling of ``_stream_steps``: each step additionally
        ships the shards' per-row weight slices (padded lanes at weight
        0), yielding ``(pts, mask, w, counts)`` global arrays. No
        weighted multi-process caller exists, so non-local shards are
        rejected by ``zip_shard_blocks`` rather than half-supported."""
        mesh, pspec = self.mesh, self._pspec()
        local = self._local_ids(sh)

        def put(step):
            pts, wts, counts = step          # (S, rows, d), (S, rows), (S,)
            mask = np.arange(rows)[None, :] < counts[:, None]
            g_p = compat.global_array_from_shards(mesh, pspec, list(pts))
            g_m = compat.global_array_from_shards(mesh, pspec, list(mask))
            g_w = compat.global_array_from_shards(mesh, pspec, list(wts))
            return g_p, g_m, g_w, counts

        return stream_device(
            engine.zip_shard_blocks(sh.shards, rows, with_weights=True,
                                    local_ids=local),
            self.prefetch, put=put)

    def _replicated(self, arr) -> jnp.ndarray:
        if compat.process_count() > 1:
            # Assemble the replica set from per-local-device copies (the
            # host value is identical on every process by SPMD
            # construction).
            return compat.replicated_array(self.mesh,
                                           np.asarray(arr, np.float32))
        return jax.device_put(jnp.asarray(arr, jnp.float32),
                              NamedSharding(self.mesh, P()))

    # -- jitted per-step shard_map programs (cached per program kind) -------

    def _round1_step(self, fn: BlockFn):
        key = ("round1", fn)
        if key not in self._step_cache:
            pspec = self._pspec()

            @functools.partial(compat.shard_map, mesh=self.mesh,
                               in_specs=(pspec, pspec),
                               out_specs=(pspec, pspec),
                               check_replication=False)
            def step(pts, mask):                    # local (rows, d), (rows,)
                c = fn(pts, mask)                   # (k, d)
                return c[None], jnp.any(mask)[None]

            self._step_cache[key] = jax.jit(step)
        return self._step_cache[key]

    def _round1w_step(self, fn: WeightedBlockFn):
        key = ("round1w", fn)
        if key not in self._step_cache:
            pspec = self._pspec()

            @functools.partial(compat.shard_map, mesh=self.mesh,
                               in_specs=(pspec, pspec, pspec),
                               out_specs=(pspec, pspec, pspec),
                               check_replication=False)
            def step(pts, mask, w):        # local (rows, d), (rows,), (rows,)
                c, cw = fn(pts, mask, w)   # (k, d), (k,)
                return c[None], cw[None], jnp.any(mask)[None]

            self._step_cache[key] = jax.jit(step)
        return self._step_cache[key]

    def _filter_step(self, rank: int, impl: str, chunk: int | None):
        key = ("filter", rank, impl, chunk)
        if key not in self._step_cache:
            pspec = self._pspec()

            @functools.partial(compat.shard_map, mesh=self.mesh,
                               in_specs=(pspec, pspec, pspec, P()),
                               out_specs=(pspec, pspec),
                               check_replication=False)
            def step(pts, d_blk, h_blk, c):
                # Per-shard fused filter tile (engine dispatches the
                # Pallas streamed kernel vs the jnp oracle — bitwise).
                d_blk, tops = engine.filter_tile_update(
                    pts, c, d_blk, h_blk, rank=rank, impl=impl, chunk=chunk)
                return d_blk, tops[None]

            self._step_cache[key] = jax.jit(step)
        return self._step_cache[key]

    def _pivot_step(self, rank: int):
        key = ("pivot", rank)
        if key not in self._step_cache:
            pspec = self._pspec()

            @functools.partial(compat.shard_map, mesh=self.mesh,
                               in_specs=(pspec, pspec),
                               out_specs=pspec,
                               check_replication=False)
            def step(d_blk, h_blk):
                cand = jnp.where(h_blk, d_blk, _NEG)
                r = min(rank, cand.shape[0])
                return jax.lax.top_k(cand, r)[0][None]

            self._step_cache[key] = jax.jit(step)
        return self._step_cache[key]

    # -- the Executor interface, sharded ------------------------------------

    def run_blocks(self, fn, source, *, objective: Objective | None = None):
        """Round 1 over the mesh machines: every step feeds each shard's
        next (padded, masked) block into its own address space and runs
        one shard_map of per-shard GONs. The center union is ordered
        shard-major (shard 0's blocks first) — global row order, exactly
        the sequential ``HostStreamExecutor`` union for the same blocking.
        Weighted objectives run the 3-operand sibling step, shipping the
        shards' weight slices through the same ring."""
        sh = self._sharded(source)
        rows = self.rows_for(sh)
        weighted = objective is not None and objective.weighted
        cs, vs, ws = [], [], []
        if weighted:
            step = self._round1w_step(fn)
            for pts, mask, w, _ in self._stream_steps_w(sh, rows):
                c, cw, v = step(pts, mask, w)       # (S,k,d), (S,k), (S,)
                cs.append(self._fetch(c))
                ws.append(self._fetch(cw))
                vs.append(self._fetch(v))
        else:
            step = self._round1_step(fn)
            for pts, mask, _ in self._stream_steps(sh, rows):
                c, v = step(pts, mask)              # (S, k, d), (S,)
                cs.append(self._fetch(c))
                vs.append(self._fetch(v))
        if not cs:
            raise ValueError("cannot run round 1 over an empty source")
        cent = np.stack(cs, axis=1)                 # (S, B, k, d) after swap
        val = np.stack(vs, axis=1)                  # (S, B)
        k = cent.shape[2]
        centers = jnp.asarray(cent.reshape(-1, cent.shape[-1]))   # (S·B·k, d)
        valid = jnp.asarray(np.repeat(val.reshape(-1), k))
        if weighted:
            wgt = np.stack(ws, axis=1)              # (S, B, k)
            return centers, valid, jnp.asarray(wgt.reshape(-1))
        return centers, valid

    def default_capacity(self, source, k: int) -> int:
        return max(self.rows_for(source), 2 * k)

    def radius2(self, source, centers, *, impl="auto", chunk=None,
                objective: Objective | None = None):
        """Squared covering radius over the sharded stream.

        Runs the *eager* per-block ``engine.fold_min_d2`` over the
        ``ShardedSource``'s global block stream (per-shard ``rows``, the
        prefetch ring) rather than a jitted shard_map fold: the repo-wide
        radius2 contract is the eager ``assign_nearest`` bits (Sim / the
        device EIM path / HostStream all reduce those), and XLA's fused
        jit form of the ``x²+c²−2x·c`` chain is *not* bit-identical to
        the op-by-op eager dispatch on every backend — a jitted mesh fold
        here would break the cross-executor bitwise-parity guarantee.
        Residency is unchanged: one block (plus the ring) at a time,
        bounded by the per-shard budget. Device-resident inputs keep the
        one-pass fused max."""
        src = as_source(source)
        if isinstance(src, ArraySource):
            # reprolint: disable=R002 -- ArraySource is already in HBM; materialize() is a zero-copy unwrap
            _, d2 = ops.assign_nearest(src.materialize(), centers,
                                       impl=impl, chunk=chunk)
            if _is_plain(objective):
                return jnp.max(d2)
            # Same eager d2 bits; the objective only changes the reduction
            # (top-1 of a multiset == its max, preserving unit-weight bits).
            if objective.weighted:
                w = jnp.asarray(weights_of(src, 0, src.n))
                d2 = jnp.where(w > 0, d2, _NEG)
            r = objective.outliers + 1
            top = engine.merge_top_k(engine.top_k_init(r), d2, r)
            return jnp.maximum(top[r - 1], jnp.float32(0.0))
        sh = self._sharded(src)
        local = self._local_ids(sh)
        if local is not None:
            if not _is_plain(objective):
                raise NotImplementedError(
                    "multi-process radius2 supports only the plain "
                    "objective (a top-(z+1) cross-process merge is a "
                    "value fold too, but no caller exists yet)")
            # Per-process partial max over the *local* shards (same
            # blocks, same eager fold_min_d2 bits as the global stream —
            # blocks never cross shard boundaries), then an exact
            # cross-process max merge: max is invariant to merge order,
            # so the result is bitwise the single-process fold.
            rows = self.rows_for(sh)
            best = np.float32(0.0)       # d² ≥ 0; empty shards fold to 0
            for s in local:
                part = engine.fold_min_d2(sh.shards[s], centers, impl=impl,
                                          chunk=chunk, block_rows=rows,
                                          prefetch=self.prefetch)
                best = np.maximum(best, np.asarray(part, np.float32))
            parts = compat.exchange_host(np.asarray(best, np.float32))
            return jnp.asarray(np.max(parts), jnp.float32)
        if not _is_plain(objective):
            top = engine.fold_top_k_min_d2(
                sh, centers, objective.outliers + 1, impl=impl, chunk=chunk,
                block_rows=self.rows_for(sh), prefetch=self.prefetch,
                weighted=objective.weighted)
            return jnp.maximum(top[objective.outliers], jnp.float32(0.0))
        return engine.fold_min_d2(sh, centers, impl=impl, chunk=chunk,
                                  block_rows=self.rows_for(sh),
                                  prefetch=self.prefetch)

    def run_filter_round(self, source, s_new, d_s, h_mask, rank, *,
                         impl="auto", chunk=None, weights=None):
        """EIM Rounds 2–3 over the mesh machines: each step updates every
        shard's slice of d(x, S_new) in its own address space and emits a
        per-shard top-k; the host merge of the per-shard tops is the
        MapReduce shuffle (top-k *values* are blocking-invariant, so the
        pivot is bitwise the Sim/HostStream one). ``source`` may be a
        compacted ``IndexedSource`` view — it is split into contiguous
        machine ranges on the fly; ``d_s``/``h_mask`` hold the per-view
        slices, updated in place exactly like the other executors."""
        if weights is not None:
            # Weighted EIM needs per-shard weight slices riding the state
            # ring; no weighted caller exists yet (kz_center solves on the
            # host-resident coreset), so fail loudly rather than silently
            # ignoring the weights.
            raise NotImplementedError(
                "MeshExecutor.run_filter_round does not support weights "
                "yet — use SimExecutor or HostStreamExecutor for weighted "
                "filter rounds")
        sh = self._sharded(source)
        rows = self.rows_for(sh)
        S = sh.num_shards
        have_s = s_new is not None and len(s_new) > 0
        mesh, pspec = self.mesh, self._pspec()
        local = self._local_ids(sh)
        pos = sh.offsets[:-1].astype(np.int64)      # per-shard view cursor

        def put(step_data):
            """Ring transfer: ship the step's points *and* the matching
            d/h state slices (rows are touched exactly once per call, so
            prefetching state ahead of the fold is safe)."""
            pts, counts = step_data
            starts = pos.copy()
            p_d, p_h = [], []
            for s in range(S):
                nb = int(counts[s])
                a = int(pos[s])
                dd = np.full(rows, np.float32(3.4e38), np.float32)
                dd[:nb] = d_s[a:a + nb]
                hh = np.zeros(rows, bool)
                hh[:nb] = h_mask[a:a + nb]
                p_d.append(dd)
                p_h.append(hh)
                pos[s] += nb
            return (compat.global_array_from_shards(mesh, pspec, list(pts)),
                    compat.global_array_from_shards(mesh, pspec, p_d),
                    compat.global_array_from_shards(mesh, pspec, p_h),
                    counts, starts)

        steps = stream_device(
            engine.zip_shard_blocks(sh.shards, rows, local_ids=local),
            self.prefetch, put=put)
        if have_s:
            c = self._replicated(np.asarray(s_new, np.float32))
            fstep = self._filter_step(rank, impl, chunk)
        else:
            pstep = self._pivot_step(rank)
        top = engine.top_k_init(rank)
        for g_pts, g_d, g_h, counts, starts in steps:
            if have_s:
                d_upd, tops = fstep(g_pts, g_d, g_h, c)
                # Multi-process, the fetch is an allgather: every process
                # writes back *every* shard's slice, keeping the host
                # d(x, S) relation replicated — the next iteration's state
                # pieces are then constructible everywhere.
                d_np = self._fetch(d_upd).reshape(S, rows)
                for s in range(S):
                    nb = int(counts[s])
                    a = int(starts[s])
                    d_s[a:a + nb] = d_np[s, :nb]
            else:
                tops = pstep(g_d, g_h)
            top = engine.merge_top_k(top, jnp.asarray(self._fetch(tops)),
                                     rank)
        return d_s, _pivot_from_top(top, rank)

    # -- MRG: fused device program, or the streamed sharded orchestration ---

    def mrg(self, source, k: int, *, capacity: int | None = None,
            impl: str = "auto", chunk: int | None = None,
            objective: Objective | None = None):
        """MRG on the mesh. Device-resident inputs (raw arrays /
        ``ArraySource``) run the fused shard_map program (capacity is
        fixed by the mesh blocking there — ``capacity=`` raises);
        sharded / host-backed sources run the streamed per-shard rounds
        with the shared Lemma-3 ``combine`` (``capacity`` honored).
        Non-plain objectives always take the streamed orchestration —
        the fused program has no weight operand, and grafting one in
        would recompile (and risk perturbing) the plain device path."""
        src = as_source(source)
        if isinstance(src, ArraySource) and _is_plain(objective):
            if capacity is not None:
                raise ValueError(
                    "MeshExecutor's machine capacity on the device path is "
                    "fixed by the mesh blocking (shard size / gather "
                    "tree); capacity= is not supported — use shard_axes/"
                    "hierarchical, or pass a ShardedSource / host-backed "
                    "source for the streamed path")
            return self._mrg_fused(src, k, impl=impl, chunk=chunk)
        return super().mrg(src, k, capacity=capacity, impl=impl, chunk=chunk,
                           objective=objective)

    def _mrg_fused(self, source, k: int, *, impl: str = "auto",
                   chunk: int | None = None):
        axes = self.shard_axes
        hierarchical = self.hierarchical
        pspec = self._pspec()

        @functools.partial(
            compat.shard_map,
            mesh=self.mesh,
            in_specs=(pspec,),
            out_specs=(P(), P()),
            check_replication=False,
        )
        def run(local):
            res = gonzalez(local, k, impl=impl, chunk=chunk)
            centers = res.centers
            if hierarchical and len(axes) > 1:
                for ax in axes:
                    centers = jax.lax.all_gather(centers, ax, tiled=True)
                    centers = gonzalez(centers, k, impl=impl,
                                       chunk=chunk).centers
            else:
                for ax in axes:
                    centers = jax.lax.all_gather(centers, ax, tiled=True)
                centers = gonzalez(centers, k, impl=impl, chunk=chunk).centers
            # local covering radius -> global max
            _, d2 = ops.assign_nearest(local, centers, impl=impl, chunk=chunk)
            r2 = jnp.max(d2)
            for ax in axes:
                r2 = jax.lax.pmax(r2, ax)
            return centers, r2

        x = as_source(source).materialize()
        sharding = NamedSharding(self.mesh, pspec)
        x = jax.device_put(x, sharding)
        centers, r2 = run(x)
        rounds = 1 + (len(axes) if hierarchical and len(axes) > 1 else 1)
        return centers, r2, rounds
