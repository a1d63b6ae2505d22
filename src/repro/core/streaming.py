"""Streaming k-center — beyond-paper extension (DESIGN.md §3).

The paper's MRG assumes the point set fits across the cluster's memory
(n/m ≤ c). For *unbounded streams* (the framework's embedding-curation
use-case: every training batch produces new embeddings), we add the
classic doubling algorithm (Charikar, Chekuri, Feder & Motwani 1997):
an 8-approximation that sees each point once and stores only k+1 points.

    state = stream_init(k, d)
    state = stream_update(state, batch)     # any number of times
    centers, radius_lb = stream_result(state)

Invariants (property-tested):
  * at most k centers are kept, pairwise separation > lower bound `r`;
  * every streamed point is within 8·OPT of some kept center (the
    algorithm guarantee; we test ≤ 8·GON-radius as an upper proxy).

The update is a host-side fold over jitted per-point kernels — streaming
is inherently sequential in the worst case, but each *batch* first drops
points already covered by the current centers (one vectorized
assign_nearest pass, the common case at steady state), so per-batch cost
is O(b·k) vectorized + rare sequential insertions.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.data.source import is_source
from repro.kernels import ops


class StreamState(NamedTuple):
    centers: np.ndarray    # (k, d) — rows beyond `count` are undefined
    count: int             # live centers
    r: float               # current lower-bound radius (doubling)
    k: int


def stream_init(k: int, d: int) -> StreamState:
    return StreamState(np.zeros((k + 1, d), np.float32), 0, 0.0, k)


def _min_d2(x: np.ndarray, centers: np.ndarray,
            chunk: int | None = None) -> np.ndarray:
    _, d2 = ops.assign_nearest(jnp.asarray(x), jnp.asarray(centers),
                               chunk=chunk)
    return np.asarray(d2)


def stream_update(state: StreamState, batch, *,
                  chunk: int | None = None,
                  block_rows: int | None = None,
                  memory_budget: int | None = None,
                  tail: str = "host") -> StreamState:
    """Fold one batch of points (b,d) into the sketch.

    ``batch`` may also be any ``PointSource`` (host numpy, on-disk shards,
    or a generator program): its blocks are folded in order, so an entire
    out-of-core dataset can be sketched without ever materializing it —
    the natural pairing of the doubling algorithm's O(k) state with the
    source layer's O(block) residency. ``block_rows`` / ``memory_budget``
    set that blocking (kernels/engine.py residency model).

    ``chunk`` streams the per-batch coverage pass in row-blocks
    (kernels/engine.py) so arbitrarily large batches never materialize a
    (b, k) distance block.

    ``tail`` picks the sequential-insertion tail: ``"host"`` (default)
    checks insertion candidates against only the centers added since the
    batch's vectorized coverage pass — O(b·new) host flops, one device
    pass per doubling instead of one per insertion; ``"device"`` is the
    legacy per-insertion re-pass (one ``assign_nearest`` round-trip per
    inserted center), kept as the before/after micro-bench baseline
    (``benchmarks/serve_bench.py``, insert-heavy regime)."""
    if tail not in ("host", "device"):
        raise ValueError(f"tail must be 'host' or 'device', got {tail!r}")
    if is_source(batch):
        rows = ops.resolve_block_rows(batch.n, batch.d,
                                      block_rows=block_rows,
                                      memory_budget=memory_budget)
        # The sketch's fold runs host-side, so prefer the source's numpy
        # blocks (no device round-trip); device-resident sources fall back
        # to pulling their blocks down.
        if hasattr(batch, "host_blocks"):
            blocks = batch.host_blocks(rows)
        else:
            blocks = (np.asarray(b) for b in batch.blocks(rows))
        for blk in blocks:
            state = stream_update(state, blk, chunk=chunk, tail=tail)
        return state
    centers, count, r, k = (np.array(state.centers), state.count,
                            state.r, state.k)
    batch = np.asarray(batch, np.float32)

    # bootstrap (only before the first doubling): the first k+1 distinct
    # points define the initial r; afterwards insertion always requires
    # > 4r. A row equal to a held center is covered and dropped, so r is
    # half a positive distance; while at most k distinct points have
    # arrived, r stays 0 and the sketch is exact.
    while r == 0.0 and batch.size:
        x, batch = batch[0], batch[1:]
        if (centers[:count] == x).all(axis=1).any():
            continue
        centers[count] = x
        count += 1
        if count == k + 1:
            d2 = _pair_d2(centers)
            np.fill_diagonal(d2, np.inf)
            r = float(np.sqrt(d2.min())) / 2.0
            centers, count = _merge(centers, count, r, k)
    if not batch.size:
        return StreamState(centers, count, r, k)

    while batch.size:
        # vectorized drop of covered points (≤ 4r of a center: the
        # doubling invariant allows absorbing them) — ONE device pass
        d2 = _min_d2(batch, centers[:count], chunk)
        dist = np.sqrt(d2)
        keep = dist > 4.0 * r
        batch, dist = batch[keep], dist[keep]
        if batch.size == 0:
            break
        batch, centers, count, r = _insert_tail(
            batch, dist, centers, count, r, k,
            one_per_pass=(tail == "device"))
    return StreamState(centers, count, r, k)


def _insert_tail(batch: np.ndarray, dist: np.ndarray, centers: np.ndarray,
                 count: int, r: float, k: int, *, one_per_pass: bool):
    """Sequential-insertion tail of one ``stream_update`` coverage pass.

    Every row of ``batch`` already failed the ≤4r coverage test against the
    pass-time center set; ``dist`` caches those pass-time min-distances.
    Insertion candidates are re-checked host-side against only the centers
    *added since the pass* — O(b·new) flops, no per-point host↔device
    round-trip. A doubling+merge shrinks the center set to a subset of the
    pass-time centers, so the cached distances survive only as lower
    bounds; the tail hands the unconsumed rows back for a fresh vectorized
    pass instead of consuming stale bounds (that keeps ``_merge``'s
    coverage rebuild on the vectorized device path). A center inserted
    this tail is at true distance > 4r from every live center (cached
    distance ≤ true distance), so the doubling separation invariant holds
    exactly as in the legacy tail.

    ``one_per_pass=True`` reproduces the legacy device tail bit-for-bit:
    return after the first insertion so every candidate is re-screened by
    a fresh ``assign_nearest`` pass.
    """
    added: list = []                    # centers inserted since the pass
    for i in range(batch.shape[0]):
        x = batch[i]
        cd = float(dist[i])
        for c in added:
            diff = x - c
            cd = min(cd, float(np.sqrt(np.dot(diff, diff))))
        if cd <= 4.0 * r:
            continue                    # covered by a center added mid-tail
        if count < k + 1:
            centers[count] = x
            count += 1
            if count == k + 1:
                # classic doubling: never rest with more than k centers
                centers, count, r = _double(centers, count, r, k)
                return batch[i + 1:], centers, count, r
            added.append(x.copy())
            if one_per_pass:
                return batch[i + 1:], centers, count, r
        else:
            centers, count, r = _double(centers, count, r, k)
            return batch[i:], centers, count, r
    return batch[:0], centers, count, r


def _double(centers: np.ndarray, count: int, r: float, k: int):
    """Double r and re-cluster at the new scale. The bootstrap leaves r
    positive before k+1 distinct centers can be held, and a doubling of 0
    would never shrink the center set, so r <= 0 here is a broken
    invariant, raised rather than spun on."""
    if not r > 0.0:
        raise RuntimeError(f"stream sketch doubling from r = {r}: the "
                           f"bootstrap must leave r > 0")
    r *= 2.0
    centers, count = _merge(centers, count, r, k)
    return centers, count, r


def _pair_d2(live: np.ndarray) -> np.ndarray:
    """(count, count) squared distances of a small host block, by exact
    differences summed in f64: distinct rows are always at a positive
    distance (the expanded |x|²+|y|²−2x·y form can round a close pair of
    large-norm rows to 0)."""
    diff = live[:, None, :] - live[None, :, :]
    return np.einsum("ijk,ijk->ij", diff, diff, dtype=np.float64)


def _merge(centers: np.ndarray, count: int, r: float, k: int):
    """Greedy re-cluster of the kept centers at scale 4r: keep a maximal
    subset with pairwise distance > 4r. The rebuild is vectorized — one
    (count, count) distance block (count ≤ k+1) plus a masked greedy scan,
    no per-pair python distance loop."""
    live = centers[:count]
    d2 = _pair_d2(live)
    thr = (4.0 * r) ** 2
    ok = np.ones(count, bool)
    kept = []
    for i in range(count):
        if ok[i]:
            kept.append(i)
            ok &= d2[i] > thr
    new = np.zeros_like(centers)
    new[: len(kept)] = live[kept]
    return new, len(kept)


def stream_result(state: StreamState):
    """-> (centers (count,d), radius lower bound r)."""
    return state.centers[: state.count], state.r
