"""MRG — "MapReduce Gonzalez" (paper §3, Algorithm 1), one algorithm.

``mrg(points_or_source, k, executor=...)`` runs the paper's algorithm on
any machine substrate: round 1 maps GON over the executor's machine-blocks
of the input, rounds 2+ reduce the center union under the capacity ``c``
(Lemma 2 for 2 rounds ⇒ 4-approximation; Lemma 3's multi-round
generalization adds +2 per extra level), and the covering radius is a
streamed fold over the original source. The machine notions — vmapped
blocks, mesh shards, or sequential out-of-core super-shards — live in
``repro.core.executor``; the input notions — device array, host numpy,
on-disk shards, generator program — live in ``repro.data.source``.

Thin wrappers keep the historical API:

* ``mrg_sim`` — the paper's experimental setup: ``m`` simulated machines on
  one device (``SimExecutor``: points blocked into m shards, GON on every
  shard via ``vmap``).
* ``mrg_distributed`` — the production TPU form (``MeshExecutor``: points
  sharded over mesh axes, round 1 a ``shard_map`` block, round 2 an
  ``all_gather`` + replicated GON; hierarchical gathers go axis-group by
  axis-group, mirroring Lemma 3 with ICI-domain capacities).

Out-of-core: ``mrg(HostSource(x), k)`` (or ``MemmapSource`` /
``SyntheticSource``) defaults to ``HostStreamExecutor`` — round 1 becomes a
sequential fold over DMA'd super-shards under a ``memory_budget``, so n is
bounded by host RAM or disk instead of HBM.

Paper correspondence: machines m = number of blocks; capacity c = per-
machine working-set budget (``capacity`` rows / ``memory_budget`` bytes);
"send all points in S to a single reducer" = ``Executor.combine`` (an
``all_gather`` on the mesh — the gathered set is k·m points, tiny next
to n).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Sequence

import jax.numpy as jnp
from jax.sharding import Mesh

from repro.data.source import ArraySource, as_source, is_source

from .executor import (  # noqa: F401  (_block/_mrg_round re-exported for
    Executor,            # benchmarks/runtime_scaling.py's round-timing)
    HostStreamExecutor,
    MeshExecutor,
    SimExecutor,
    _block,
    _mrg_round,
)


class MRGResult(NamedTuple):
    centers: jnp.ndarray   # (k, d)
    radius2: jnp.ndarray   # () squared covering radius over ALL points
    rounds: int            # number of GON levels used (2 = classic MRG)
    # (k,) per-cluster weight sums when run with a weighted Objective (the
    # centers then form a weighted coreset); None on plain k-center runs.
    weights: jnp.ndarray | None = None


# ---------------------------------------------------------------------------
# Round planning (paper §3.3, inequality (1))
# ---------------------------------------------------------------------------

def plan_rounds(n: int, m: int, k: int, capacity: int) -> int:
    """Number of GON levels needed so the final instance fits ``capacity``.

    Implements the machine-count recurrence m^(i) <= m (k/c)^i + (1-(k/c)^i)
    / (1-k/c): run first-round style reductions until fewer than 2 machines
    are needed. Returns total levels (>= 2). Raises if k > capacity (the
    paper's hard feasibility requirement: a k-point instance must fit on one
    machine).
    """
    if k > capacity:
        raise ValueError(f"infeasible: k={k} exceeds single-machine capacity {capacity}")
    levels = 1
    machines = m
    while machines * k > capacity:
        machines = math.ceil(machines * k / capacity)
        levels += 1
        if levels > 64:
            raise ValueError("round planning diverged (k too close to capacity; paper §3.3 requires 2k < c)")
    return levels + 1  # +1 for the final single-machine GON


# ---------------------------------------------------------------------------
# The unified algorithm
# ---------------------------------------------------------------------------

def mrg(points, k: int, *, executor: Executor | None = None, m: int = 50,
        capacity: int | None = None, impl: str = "auto",
        chunk: int | None = None, objective=None) -> MRGResult:
    """Paper Algorithm 1 over any point source and machine substrate.

    ``points`` is anything ``repro.data.source.as_source`` accepts: an
    array (device or numpy) or an explicit ``PointSource``. Without an
    ``executor``, raw arrays and ``ArraySource`` run on ``SimExecutor(m)``
    (the historical ``mrg_sim``); an explicit host/disk/generator source
    runs on ``HostStreamExecutor()`` (the out-of-core fold) — only passing
    a ``PointSource`` opts into streaming.

    ``capacity`` (rows; default: the executor's machine size) triggers the
    multi-round path when the k·m center union would not fit on one
    machine (``MeshExecutor``'s fused device path rejects it — that
    blocking is fixed by the mesh; its streamed sharded path honors it).
    ``chunk`` streams every distance pass in row-blocks within a machine
    (see kernels/engine.py).

    Distributed out-of-core: ``mrg(sharded, k,
    executor=MeshExecutor(mesh, memory_budget=...))`` with a
    ``ShardedSource`` (or any host-backed source — auto-split into the
    paper's contiguous machine ranges) streams each shard's blocks into
    that shard's mesh address space, so no host ever holds all n rows —
    and returns bitwise-identical results to the Sim/HostStream paths on
    ref for matching blockings.

    >>> import numpy as np
    >>> x = np.random.default_rng(0).normal(size=(256, 2)).astype(np.float32)
    >>> res = mrg(x, 4, m=8)          # 8 simulated machines, 2 rounds
    >>> res.centers.shape, res.rounds
    ((4, 2), 2)

    ``objective`` (a ``core.executor.Objective``; default ``None`` = plain
    k-center, byte-for-byte the historical orchestration) generalizes the
    run: ``weighted=True`` threads the source's per-row weights through
    every round and fills ``MRGResult.weights`` with the per-cluster
    sums; ``outliers=z`` scores ``radius2`` with the top-(z+1) fold.
    """
    streamed = is_source(points) and not isinstance(points, ArraySource)
    if streamed:
        source = as_source(points)
    else:
        # Raw arrays (numpy included) keep the legacy device path on every
        # executor — only an explicit PointSource opts into streaming.
        source = points if isinstance(points, ArraySource) \
            else ArraySource(points)
    if executor is None:
        executor = (HostStreamExecutor() if streamed else SimExecutor(m=m))
    if objective is not None and objective.weighted:
        centers, r2, rounds, w = executor.mrg(
            source, k, capacity=capacity, impl=impl, chunk=chunk,
            objective=objective)
        return MRGResult(centers, r2, rounds, w)
    if objective is None:
        # Plain runs call without the kwarg so custom Executor subclasses
        # written against the pre-objective signature keep working.
        centers, r2, rounds = executor.mrg(source, k, capacity=capacity,
                                           impl=impl, chunk=chunk)
    else:
        centers, r2, rounds = executor.mrg(source, k, capacity=capacity,
                                           impl=impl, chunk=chunk,
                                           objective=objective)
    return MRGResult(centers, r2, rounds)


# ---------------------------------------------------------------------------
# Historical wrappers (API stability)
# ---------------------------------------------------------------------------

def mrg_sim(points, k: int, m: int = 50, *,
            capacity: int | None = None, impl: str = "auto",
            chunk: int | None = None) -> MRGResult:
    """Paper Algorithm 1 with m simulated machines (single device).

    ``capacity`` (default: block size n/m) triggers the multi-round path
    when the k*m center union would not fit on one machine. ``chunk``
    streams every distance pass in row-blocks (see kernels/engine.py).
    """
    return mrg(points, k, executor=SimExecutor(m=m), capacity=capacity,
               impl=impl, chunk=chunk)


def mrg_distributed(
    points,
    k: int,
    mesh: Mesh,
    *,
    shard_axes: Sequence[str] = ("data",),
    hierarchical: bool = False,
    impl: str = "auto",
    chunk: int | None = None,
):
    """Distributed MRG on a device mesh.

    ``points (n,d)`` is (re)sharded along ``shard_axes`` (n must divide the
    product of those axis sizes). Round 1: per-device GON on the local
    shard. Round 2(+): all_gather of center sets; with ``hierarchical``,
    gathers proceed one axis at a time with an intermediate GON per level
    (Lemma 3 multi-round; +2 approx per level) — used when k·m exceeds the
    working-set budget of a single gather.

    ``chunk`` bounds each device's per-pass working set to O(chunk·k) —
    the paper's capacity c decoupled from the shard size n/m, so a shard
    may exceed what an un-chunked (n/m, k) block would allow.

    Returns ``(centers (k,d) replicated, radius2 ())``.
    """
    ex = MeshExecutor(mesh, shard_axes=shard_axes, hierarchical=hierarchical)
    centers, r2, _ = ex.mrg(as_source(points), k, impl=impl, chunk=chunk)
    return centers, r2
