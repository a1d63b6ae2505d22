"""EIM — parameterized iterative-sampling k-center (paper §4, Algorithms 2–3).

Re-implementation of Ene/Im/Moseley's MapReduce sampling scheme with the
paper's two modifications:

  * **Termination fix** (paper §4.1): points sampled into S are *always*
    removed from R, and the removal test is ``d(x,S) <= d(v,S)`` (ties
    removed), so |R| strictly decreases and the loop cannot stall.
  * **φ parameter** (paper §4.2 / Algorithm 3): the pivot v is the
    ``φ·log n``-th farthest point of H from S (original scheme: φ = 8).
    φ > 5.15 keeps the 10-approximation w.s.p. (paper §6); smaller φ
    trades the guarantee for fewer/cheaper iterations.

Two execution forms share one algorithm (and are **bitwise identical** on
the ref backend for the same key):

  * **Device fast path** (raw arrays / ``ArraySource``): MapReduce's
    shrinking relations R, S, H become masks over a fixed (n, d) array —
    XLA needs static shapes, so "remove from R" clears a mask bit. The
    loop is a ``lax.while_loop``; per-iteration sampled sets land in
    fixed-capacity index buffers (expected |S_new| = 9k·n^ε·log n with 3σ
    Poisson headroom; overflow beyond capacity is dropped and counted —
    a <1e-6-probability event that only slows convergence).
  * **Streamed source path** (host / disk / generator sources, or any
    explicit ``executor=``): the MapReduce-native formulation — R, S, H
    are host-resident per-point state (O(n) bools/floats, tiny next to the
    (n, d) points), and every per-iteration pass is a fold over a
    ``PointSource`` via ``Executor.run_filter_round``, mirroring how
    ``gonzalez`` streams. The iteration maps onto the paper's rounds:
    Round 1 (independent sampling) needs *no data pass at all* — the
    Bernoulli draws are counter-based per global row
    (``engine.bernoulli_rows``, Philox keyed by absolute row index, so
    the sampled sets are invariant to blocking, the same trick
    ``SyntheticSource("unif")`` uses) and the sampled coordinates are
    fetched by ``source.take``; Rounds 2–3 (Select + filter) are one
    streamed fold (masked incremental-min ``d(x, S_new)`` through
    ``assign_nearest`` + a cross-block top-k merge for the φ·log n
    pivot). The final "send C to one machine" GON round compacts the
    sample through ``source.take`` — all of n is never device-resident.

The loop runs while ``|R| > (4/ε)·k·n^ε·log n`` (+ an iteration cap as a
safety net; the paper proves O(1/ε) iterations w.h.p. and observes ≤ 2 in
practice). Both paths evaluate the condition, the sampling probabilities
and every distance comparison in f32 with identical expressions, which is
what makes the parity bitwise rather than approximate.

``impl`` reaches every distance pass of both forms: the executors' filter
rounds dispatch through ``engine.filter_tile_update`` /
``engine.eim_filter_block``, so on TPU (``impl="auto"``) Rounds 2–3 run
as the fused one-VMEM-pass streamed tile (``kernels/fused_stream.py``),
or the center-tiled kernel when the sampled centers overflow VMEM — the
ref oracle's arithmetic, as the parity suite pins in interpret mode on
CPU.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.data.source import (ArraySource, IndexedSource, as_device_array,
                               as_source, is_source)
from repro.kernels import engine, ops

from .executor import Executor, HostStreamExecutor
from .gonzalez import gonzalez

_NEG = np.float32(-3.4e38)
_BIG = np.float32(3.4e38)


class EIMSample(NamedTuple):
    sample_mask: jnp.ndarray   # (n,) bool — C = S ∪ R_final (numpy on the
                               #     streamed path: host-resident relations)
    s_mask: jnp.ndarray        # (n,) bool — sampled centers S
    iters: jnp.ndarray         # ()   int32 — loop iterations used
    overflow: jnp.ndarray      # ()   int32 — samples dropped by buffer caps
    sampled: jnp.ndarray       # ()   bool  — False => loop never ran (EIM≡GON)


class EIMResult(NamedTuple):
    centers: jnp.ndarray       # (k, d)
    radius2: jnp.ndarray       # ()
    sample: EIMSample


def _expected_caps(n: int, k: int, eps: float, slack: float = 3.0):
    """Fixed buffer capacities with Poisson 3σ-ish headroom."""
    ln_n = math.log(max(n, 2))
    es = 9.0 * k * (n ** eps) * ln_n
    eh = 4.0 * (n ** eps) * ln_n
    s_cap = int(min(n, math.ceil(es + slack * math.sqrt(es) + 16)))
    h_cap = int(min(n, math.ceil(eh + slack * math.sqrt(eh) + 16)))
    return s_cap, h_cap


def _params(n: int, k: int, eps: float, phi: float):
    """Shared schedule: (ln n, |R| threshold, s_cap, h_cap, pivot rank,
    S-sample numerator, H-sample numerator)."""
    ln_n = math.log(max(n, 2))
    threshold = (4.0 / eps) * k * (n ** eps) * ln_n
    s_cap, h_cap = _expected_caps(n, k, eps)
    # Select(): pivot rank φ·log n (>=1), clipped to the H buffer.
    rank = max(1, min(h_cap, int(round(phi * ln_n))))
    num_s = 9.0 * k * (n ** eps) * ln_n
    num_h = 4.0 * (n ** eps) * ln_n
    return ln_n, threshold, s_cap, h_cap, rank, num_s, num_h


def _sample_cap(n: int, k: int, eps: float, s_count: int) -> int:
    """The §4 bound on the compacted sample: |C| = |R_final| + |S| with
    |R_final| <= (4/ε)k·n^ε·log n at loop exit."""
    ln_n = math.log(max(n, 2))
    return int(min(n, math.ceil((4.0 / eps) * k * (n ** eps) * ln_n)
                   + s_count))


def _check_sample_cap(pop: int, s_count: int, n: int, k: int, eps: float,
                      max_iters: int) -> None:
    cap = _sample_cap(n, k, eps, s_count)
    if pop > cap:
        raise RuntimeError(
            f"EIM sample overflow: |C| = {pop} exceeds the paper-§4 bound "
            f"(4/ε)k·n^ε·log n + |S| = {cap} — the sampling loop hit "
            f"max_iters={max_iters} before |R| fell under the threshold. "
            f"Raise max_iters (or φ) instead of truncating the sample.")


def _compact_cap(pop: int, n: int) -> int:
    """Shape-stable gather capacity for the final GON: |C| rounded up to
    the next power of two (capped at n), so repeated ``eim`` calls re-jit
    the compact GON only per size bucket, never per exact |C|."""
    cap = 1
    while cap < pop:
        cap <<= 1
    return min(cap, n)


def _compact_gonzalez(pts_np: np.ndarray, pop: int, cap: int, k: int, *,
                      impl: str, chunk: int | None):
    """GON over the compacted sample, padded to ``cap`` rows with a
    validity mask (padding can never be selected or affect the radius —
    and both EIM paths pick identical centers for identical valid rows)."""
    d = pts_np.shape[1]
    if cap > pop:
        pts_np = np.concatenate(
            [pts_np, np.zeros((cap - pop, d), np.float32)])
    valid = np.zeros(cap, bool)
    valid[:pop] = True
    return gonzalez(jnp.asarray(pts_np), k, mask=jnp.asarray(valid),
                    impl=impl, chunk=chunk)


# ---------------------------------------------------------------------------
# public API — dispatch between the device fast path and the streamed loop
# (raw arrays / ArraySource keep the legacy device path, mirroring ``mrg``'s
# rule: only an explicit non-device PointSource — or an explicit executor —
# opts into streaming)
# ---------------------------------------------------------------------------

def _check_compact_threshold(compact_threshold: float) -> float:
    if not 0.0 <= compact_threshold <= 1.0:
        raise ValueError(
            f"compact_threshold must be in [0, 1], got {compact_threshold} "
            "(0 = never compact, 1 = compact whenever R shrank)")
    return float(compact_threshold)


def eim_sample(
    points,
    k: int,
    key: jax.Array,
    *,
    eps: float = 0.1,
    phi: float = 8.0,
    max_iters: int = 64,
    impl: str = "auto",
    chunk: int | None = None,
    executor: Executor | None = None,
    compact_threshold: float = 0.5,
) -> EIMSample:
    """Algorithm 2 (EIM-MapReduce-Sample) with the φ-parameterized Select.

    ``points`` is anything ``as_source`` accepts. Raw arrays and
    ``ArraySource`` run the jitted device fast path; host / disk /
    generator sources (or any call with an explicit ``executor=``) run the
    streamed out-of-core loop — per-point state on the host, every pass a
    fold over the source (``HostStreamExecutor`` by default; its
    ``memory_budget`` bounds device residency). Both paths draw from the
    same counter-based per-row sampler, so for the same ``key`` the
    returned sample is bitwise identical on the ref backend regardless of
    path or blocking.

    ``compact_threshold`` (streamed path only) controls the shrinking-|R|
    iteration cost (paper §4: Round 3 is charged O(|R_l|·|S_new|/m), not
    O(n·|S_new|)): when the surviving |R| falls under this fraction of the
    current view, the fold is re-pointed at an ``IndexedSource`` of the
    survivors, so later passes touch |R| rows instead of n. ``0`` never
    compacts, ``1`` compacts after every shrinking iteration; the sampled
    sets are bitwise invariant to the choice (Round-1 draws are keyed by
    *original* absolute row index — ``engine.bernoulli_rows_at`` — and
    the d(x,S)/pivot folds are per-row/value reductions). The device fast
    path has no views (masks over a fixed array) and ignores the knob.

    ``chunk`` streams the per-iteration distance update in row-blocks
    (kernels/engine.py memory model) — the sample is unchanged: the PRNG
    stream is identical and, for inputs whose coordinates are far below
    the 1e18 invalid-slot sentinel, so is every distance the loop compares.
    """
    compact_threshold = _check_compact_threshold(compact_threshold)
    streamed = is_source(points) and not isinstance(points, ArraySource)
    if not streamed and executor is None:
        return _eim_sample_device(as_device_array(points), k, key, eps=eps,
                                  phi=phi, max_iters=max_iters, impl=impl,
                                  chunk=chunk)
    source = as_source(points)
    if executor is None:
        executor = HostStreamExecutor()
    return _eim_sample_stream(source, k, key, eps=eps, phi=phi,
                              max_iters=max_iters, executor=executor,
                              impl=impl, chunk=chunk,
                              compact_threshold=compact_threshold)


def eim(
    points,
    k: int,
    key: jax.Array,
    *,
    eps: float = 0.1,
    phi: float = 8.0,
    max_iters: int = 64,
    impl: str = "auto",
    chunk: int | None = None,
    compact: bool = True,
    executor: Executor | None = None,
    compact_threshold: float = 0.5,
) -> EIMResult:
    """Full EIM: sample, then run GON on the sample (final MapReduce round).

    With ``compact=True`` the sample is gathered into a dense ``|C|``-row
    buffer before the final GON — the "send S ∪ R to one machine" round;
    the final GON then costs O(k·|C|) instead of O(k·n). |C| is checked
    against the paper's §4 bound ``(4/ε)k·n^ε·log n + |S|`` (with the
    realized |S|) and a ``RuntimeError`` is raised if the loop failed to
    converge within ``max_iters`` — never a silent truncation.

    Streamed sources compact through ``source.take`` (random-access
    gather), so the full (n, d) array is never device-resident; the
    covering radius is the executor's streamed fold. ``compact=False``
    (GON over the masked full array) is device-path only.
    ``compact_threshold`` is the streamed loop's shrinking-|R| knob (see
    ``eim_sample``) — unrelated to ``compact``, which is about the *final*
    GON round.

    Returns an ``EIMResult`` ``(centers (k, d), radius2 (), sample)``;
    ``sample.sampled`` is False when n is too small for the sampling
    regime to engage (the loop guard ``|R| > (4/ε)k·n^ε·log n`` — then
    EIM degenerates to GON, as the paper observes for large k):

    >>> import numpy as np, jax
    >>> x = np.random.default_rng(0).normal(size=(512, 3)).astype(np.float32)
    >>> res = eim(x, 8, jax.random.PRNGKey(1))
    >>> res.centers.shape
    (8, 3)
    >>> bool(res.sample.sampled)   # n = 512 is below the sampling regime
    False
    """
    compact_threshold = _check_compact_threshold(compact_threshold)
    streamed = is_source(points) and not isinstance(points, ArraySource)
    if not streamed and executor is None:
        return _eim_device(points, k, key, eps=eps, phi=phi,
                           max_iters=max_iters, impl=impl, chunk=chunk,
                           compact=compact)
    if not compact:
        raise ValueError(
            "compact=False runs GON over the masked full array and needs "
            "it device-resident; streamed EIM always compacts via "
            "source.take")
    source = as_source(points)
    if executor is None:
        executor = HostStreamExecutor()
    sample = _eim_sample_stream(source, k, key, eps=eps, phi=phi,
                                max_iters=max_iters, executor=executor,
                                impl=impl, chunk=chunk,
                                compact_threshold=compact_threshold)
    idx = np.nonzero(np.asarray(sample.sample_mask))[0]
    pop = len(idx)
    _check_sample_cap(pop, int(np.asarray(sample.s_mask).sum()),
                      source.n, k, eps, max_iters)
    if pop == source.n:
        # EIM ≡ GON (the loop never engaged): stream GON over the source
        # instead of gathering all of n — the out-of-core contract holds
        # even in the degenerate regime.
        res = gonzalez(source, k, impl=impl, chunk=chunk,
                       block_rows=(executor.rows_for(source)
                                   if hasattr(executor, "rows_for")
                                   else None))
    else:
        # Final round: C is compacted to one machine by random-access
        # gather — O(|C|) rows move, never the full source.
        res = _compact_gonzalez(source.take(idx), pop,
                                _compact_cap(pop, source.n), k,
                                impl=impl, chunk=chunk)
    r2 = executor.radius2(source, res.centers, impl=impl, chunk=chunk)
    return EIMResult(res.centers, r2, sample)


# ---------------------------------------------------------------------------
# device fast path — masks over a fixed (n, d) array, one lax.while_loop
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("k", "eps", "phi", "max_iters", "impl", "chunk")
)
def _eim_sample_device(
    points: jnp.ndarray,
    k: int,
    key: jax.Array,
    *,
    eps: float = 0.1,
    phi: float = 8.0,
    max_iters: int = 64,
    impl: str = "auto",
    chunk: int | None = None,
) -> EIMSample:
    n, d = points.shape
    points = points.astype(jnp.float32)
    _, threshold, s_cap, _, rank, num_s, num_h = _params(n, k, eps, phi)

    def cond(state):
        r_mask, s_mask, d_s, key, it, ovf = state
        # f32 compare, mirrored exactly by the streamed loop's host check.
        return ((jnp.sum(r_mask).astype(jnp.float32)
                 > jnp.float32(threshold)) & (it < max_iters))

    def body(state):
        r_mask, s_mask, d_s, key, it, ovf = state
        keys = jax.random.split(key, 3)
        key, k_s, k_h = keys[0], keys[1], keys[2]
        r_size = jnp.sum(r_mask).astype(jnp.float32)

        # --- Round 1: independent sampling within R (Alg. 2, lines 3-4) ---
        # Counter-based draws (Philox over the absolute row index): the
        # same f32 probabilities and per-row stream as the out-of-core
        # path, so the two paths sample identical sets.
        p_s = jnp.minimum(jnp.float32(num_s) / r_size, jnp.float32(1.0))
        p_h = jnp.minimum(jnp.float32(num_h) / r_size, jnp.float32(1.0))
        new_s = engine.bernoulli_rows(k_s, 0, n, p_s) & r_mask
        h_mask = engine.bernoulli_rows(k_h, 0, n, p_h) & r_mask

        # Materialize new S members into a fixed buffer (gather indices).
        s_idx = jnp.nonzero(new_s, size=s_cap, fill_value=n)[0]
        s_valid = s_idx < n
        ovf = ovf + (jnp.sum(new_s) - jnp.sum(s_valid)).astype(jnp.int32)
        s_pts = points[jnp.minimum(s_idx, n - 1)]           # (s_cap, d)

        # Incremental d(x, S) update: distances to the *new* members only
        # (the paper's Round-3 O(|R|·|S|/m) term). Invalid buffer slots are
        # moved to a far-away coordinate so they never win the min; routing
        # through assign_nearest (a fused min-reduction) instead of
        # pairwise keeps the chunked peak at O(chunk·s_cap) — a chunked
        # pairwise would still stack the full (n, s_cap) block. The update
        # is gated on having any valid sample so a zero-sample iteration
        # leaves the uncovered-point sentinel (_BIG) exactly untouched.
        far_pts = jnp.where(s_valid[:, None], s_pts, 1e18)
        _, d_new = ops.assign_nearest(points, far_pts, impl=impl,
                                      chunk=chunk)            # (n,)
        d_s = jnp.where(jnp.any(s_valid), jnp.minimum(d_s, d_new), d_s)

        s_mask = s_mask | new_s
        # Termination fix (paper §4.1): sampled points always leave R.
        r_mask = r_mask & ~new_s

        # --- Round 2: Select(H, S) (Alg. 3) ----------------------------
        d_h = jnp.where(h_mask, d_s, _NEG)
        top = jax.lax.top_k(d_h, rank)[0]
        pivot = top[rank - 1]                                # d(v, S)^2
        # If H had fewer than `rank` valid points, pivot is _NEG: no
        # distance-based removals this iteration (sampling still shrinks R).
        pivot = jnp.where(pivot <= _NEG / 2, -1.0, pivot)

        # --- Round 3: filter R (Alg. 2, lines 7-8) ----------------------
        r_mask = r_mask & ~(d_s <= pivot)
        return r_mask, s_mask, d_s, key, it + 1, ovf

    r0 = jnp.ones((n,), bool)
    s0 = jnp.zeros((n,), bool)
    d0 = jnp.full((n,), _BIG)
    sampled = jnp.asarray(n > threshold)
    r_mask, s_mask, _, _, iters, ovf = jax.lax.while_loop(
        cond, body, (r0, s0, d0, key, jnp.int32(0), jnp.int32(0))
    )
    return EIMSample(r_mask | s_mask, s_mask, iters, ovf, sampled)


def _eim_device(points, k, key, *, eps, phi, max_iters, impl, chunk,
                compact):
    """Device-path eim(): jitted sample + host-side compaction."""
    points = as_device_array(points)
    n, d = points.shape
    sample = _eim_sample_device(points, k, key, eps=eps, phi=phi,
                                max_iters=max_iters, impl=impl, chunk=chunk)
    if compact:
        idx = np.nonzero(np.asarray(sample.sample_mask))[0]
        pop = len(idx)
        _check_sample_cap(pop, int(np.asarray(sample.s_mask).sum()),
                          n, k, eps, max_iters)
        pts = np.asarray(points[jnp.asarray(idx, jnp.int32)])
        res = _compact_gonzalez(pts, pop, _compact_cap(pop, n), k,
                                impl=impl, chunk=chunk)
    else:
        res = gonzalez(points, k, mask=sample.sample_mask, impl=impl,
                       chunk=chunk)
    # Squared fold directly — the sqrt→square round-trip of
    # ``covering_radius`` is lossy in f32 and must match the executors'
    # ``radius2`` bitwise (cross-path parity tests compare these).
    _, d2 = ops.assign_nearest(points, res.centers, impl=impl, chunk=chunk)
    return EIMResult(res.centers, jnp.max(d2), sample)


# ---------------------------------------------------------------------------
# streamed source path — host-driven iterations over Executor.run_filter_round
# ---------------------------------------------------------------------------

def _eim_sample_stream(source, k: int, key, *, eps: float, phi: float,
                       max_iters: int, executor: Executor,
                       impl: str = "auto",
                       chunk: int | None = None,
                       compact_threshold: float = 0.5) -> EIMSample:
    """Out-of-core Algorithm 2: the MapReduce-native form.

    Per-point relations live on the host (``r_mask``, ``s_mask`` bools and
    ``d_s`` f32 — O(n) bytes); the (n, d) points stay wherever the source
    keeps them. Each iteration is:

      * Round 1 — sampling needs **no pass over the data**: the Bernoulli
        decision for global row i is a pure function of (iteration key, i)
        (``engine.bernoulli_rows`` / the gather-form
        ``engine.bernoulli_rows_at`` once the relation is compacted),
        evaluated here in index blocks; only the |S_new| sampled
        coordinates are fetched, by ``source.take``.
      * Rounds 2–3 — one streamed fold (``executor.run_filter_round``)
        over the *current view* of the relation: the masked
        incremental-min d(x, S_new) update and the cross-block top-k merge
        for the φ·log n pivot share the pass; the Round-3 filter is then a
        host mask update.

    The paper charges Round 3 only O(|R_l|·|S_new|/m) because R shrinks
    every iteration — so the loop tracks the live row set and, when the
    survivors fall under ``compact_threshold`` of the current view,
    re-points the fold at an ``IndexedSource`` of the survivors (their
    *original* row indices): later passes touch |R∪H| rows, not n.

    Every comparison is evaluated in f32 exactly as the device path's jit
    traces it, so the two paths return bitwise-identical samples for the
    same key (any blocking, compacted or not — the sampler is counter-
    based on original row ids, the d(x,S) update is per-row, and min/top-k
    value folds are blocking-invariant).
    """
    if type(executor).run_filter_round is Executor.run_filter_round:
        # Fail before the loop does any work (a bare Executor subclass
        # without the per-iteration hook cannot run the filter rounds).
        raise NotImplementedError(
            f"{type(executor).__name__} does not implement EIM's "
            "run_filter_round; use HostStreamExecutor (streamed), "
            "SimExecutor (vmapped machines) or MeshExecutor (sharded)")
    n = source.n
    _, threshold, s_cap, _, rank, num_s, num_h = _params(n, k, eps, phi)
    rows = (executor.rows_for(source) if hasattr(executor, "rows_for")
            else engine.resolve_block_rows(n, source.d))

    r_mask = np.ones(n, bool)
    s_mask = np.zeros(n, bool)
    d_s = np.full(n, np.float32(_BIG), np.float32)
    sampled = bool(n > threshold)
    try:
        iters, overflow = _stream_loop(
            source, executor, jnp.asarray(key), r_mask, s_mask, d_s,
            threshold, s_cap, rank, num_s, num_h, rows, max_iters,
            impl, chunk, compact_threshold)
    finally:
        # Release any per-source state the executor cached across the
        # filter rounds (e.g. SimExecutor's materialized blocking).
        executor.end_filter_rounds(source)
    return EIMSample(r_mask | s_mask, s_mask, np.int32(iters),
                     np.int32(overflow), sampled)


def _stream_loop(source, executor, key, r_mask, s_mask, d_s, threshold,
                 s_cap, rank, num_s, num_h, rows, max_iters, impl, chunk,
                 compact_threshold):
    """The iteration loop of ``_eim_sample_stream`` (mutates the host
    relations in place; returns ``(iterations, overflow)``).

    ``view_idx`` tracks the fold substrate: ``None`` means the identity
    view (every pass touches all n source rows, the pre-compaction
    behavior); otherwise it holds the sorted *original* row indices of the
    current ``IndexedSource`` view and ``d_view`` the matching slice of
    ``d_s``. Invariant: the live relation R (``r_mask``) is always a
    subset of the view — views are created from R and R only shrinks — so
    sampling, the pivot's H, and the Round-3 filter see exactly the same
    rows the full pass would.
    """
    n = source.n
    overflow = 0
    it = 0
    view = source          # current fold substrate (IndexedSource once compacted)
    view_idx = None        # None => identity view over all n rows
    d_view = d_s           # per-view slice of d_s (aliases d_s when identity)
    while (np.float32(int(r_mask.sum())) > np.float32(threshold)
           and it < max_iters):
        keys = jax.random.split(key, 3)
        key, k_s, k_h = keys[0], keys[1], keys[2]
        r_size = np.float32(int(r_mask.sum()))
        p_s = np.minimum(np.float32(num_s) / r_size, np.float32(1.0))
        p_h = np.minimum(np.float32(num_h) / r_size, np.float32(1.0))

        # --- Round 1: counter-based sampling, no data pass --------------
        # Draws are keyed by the *original* absolute row index (the view's
        # ``indices``), so the sampled sets are bitwise invariant to
        # whether/when compaction happened.
        if view_idx is None:
            new_s = _bernoulli_mask(k_s, n, p_s, rows) & r_mask
            h_view = _bernoulli_mask(k_h, n, p_h, rows) & r_mask
            s_idx = np.nonzero(new_s)[0]
        else:
            sub_r = r_mask[view_idx]
            new_s = _bernoulli_mask_at(k_s, view_idx, p_s, rows) & sub_r
            h_view = _bernoulli_mask_at(k_h, view_idx, p_h, rows) & sub_r
            s_idx = view_idx[new_s]
        # The device path's fixed S-buffer drops samples past s_cap (first-
        # index-first, a <1e-6 event at the default headroom); replicate
        # for parity and count the drops. Padding the gathered buffer up to
        # s_cap with the same far-away sentinel the device path uses keeps
        # the executor's block kernel one fixed shape across iterations
        # (padded rows can never win the distance min).
        overflow += max(0, len(s_idx) - s_cap)
        if len(s_idx):
            taken = source.take(s_idx[:s_cap])
            pad = s_cap - taken.shape[0]
            s_new = (taken if pad == 0 else np.concatenate(
                [taken, np.full((pad, taken.shape[1]), 1e18, np.float32)]))
        else:
            s_new = None
        # Termination fix (paper §4.1): sampled points always leave R.
        if view_idx is None:
            s_mask |= new_s
            r_mask &= ~new_s
        else:
            s_mask[s_idx] = True
            r_mask[s_idx] = False

        # --- Rounds 2-3: streamed d(x,S) update + pivot Select ----------
        # One fold over the *view* — |view| rows move, not n.
        d_view, pivot = executor.run_filter_round(view, s_new, d_view,
                                                  h_view, rank, impl=impl,
                                                  chunk=chunk)
        if view_idx is None:
            r_mask &= ~(d_s <= pivot)      # d_view aliases d_s here
        else:
            r_mask[view_idx[d_view <= pivot]] = False
        it += 1

        # --- compact the relation between iterations (paper §4's
        # shrinking |R|) --------------------------------------------------
        live = int(r_mask.sum())
        if np.float32(live) <= np.float32(threshold):
            break                          # loop is over; skip the re-view
        cur = n if view_idx is None else len(view_idx)
        # Multi-process, compaction is skipped: an IndexedSource re-view
        # would route per-shard block reads through the cross-process
        # ``take`` collective with *different* indices per process — a
        # protocol mismatch. The sample is bitwise invariant to the knob
        # (PR 4's contract), so skipping only costs the shrinking-|R|
        # speedup, never parity.
        if (live < compact_threshold * cur and live < cur
                and compat.process_count() == 1):
            if view is not source:
                # Release per-view executor caches (e.g. SimExecutor's
                # blocked copy) before the old view is dropped.
                executor.end_filter_rounds(view)
            if view_idx is not None:
                d_s[view_idx] = d_view     # scatter state back first
            view_idx = np.nonzero(r_mask)[0]
            view = IndexedSource(source, view_idx)
            d_view = d_s[view_idx]
    if view is not source:
        executor.end_filter_rounds(view)
    return it, overflow


def _bernoulli_mask(key, n: int, p: np.float32, rows: int) -> np.ndarray:
    """(n,) host bool mask of per-global-row Bernoulli(p) draws, generated
    in ``rows``-sized index blocks (the mask is O(n) bits on the host; the
    device working set is O(rows))."""
    parts = []
    for start in range(0, n, rows):
        parts.append(np.asarray(engine.bernoulli_rows(
            key, start, min(rows, n - start), np.float32(p))))
    return (np.concatenate(parts) if parts
            else np.zeros((0,), bool))


def _bernoulli_mask_at(key, idx: np.ndarray, p: np.float32,
                       rows: int) -> np.ndarray:
    """Gather-form ``_bernoulli_mask``: per-row Bernoulli(p) draws at the
    given *original* absolute row indices (a compacted view's survivors),
    in ``rows``-sized blocks padded to one fixed shape — so one
    compilation of the jitted gather sampler serves every view size, and
    draw i is bitwise the full-range draw at row ``idx[i]``."""
    out = np.empty(idx.size, bool)
    for start in range(0, idx.size, rows):
        sub = idx[start:start + rows]
        nb = sub.size
        lo, hi = engine.split_index_words(sub)
        if nb < rows:
            lo = np.pad(lo, (0, rows - nb))
            hi = np.pad(hi, (0, rows - nb))
        blk = np.asarray(engine.bernoulli_rows_at_block(
            key, lo, hi, np.float32(p)))
        out[start:start + nb] = blk[:nb]
    return out
