"""Public wrappers over the k-center distance engine (API façade).

The execution logic — ``impl`` resolution, shape padding, and row-chunk
streaming under a memory budget — lives in ``repro.kernels.engine``; this
module re-exposes it under the stable historical names so existing callers
(core algorithms, tests, benchmarks) are untouched. The names are direct
aliases, so signatures, defaults, and docstrings have a single home in
engine.py.

``impl`` resolution:
  * ``"auto"``   — Pallas on TPU, reference jnp elsewhere.
  * ``"pallas"`` — force the Pallas kernel (interpret mode on CPU; this is
                   the path tests use to validate kernels there).
  * ``"ref"``    — force the pure-jnp oracle.

New in the chunked engine (all optional, default = legacy behavior):
  * ``chunk``          — max rows of ``x`` processed per streamed step;
  * ``memory_budget``  — bytes; the engine derives ``chunk`` from the
                         working-set model ``4·chunk·(m+d) + 4·m·d``.

The budget bounds *working* memory — the streamed tile plus resident
centers. ``pairwise_dist2`` is the exception: its (n, m) *output* is
inherently O(n·m) and is not covered by the model (chunking there bounds
only the per-step transients); use ``assign_nearest`` /
``fused_min_argmax`` / ``argmin_dist2_over_rows`` when the caller only
needs a reduction of the distance block.

See ``repro/kernels/engine.py`` for the memory model and the jax-version
support notes.
"""
from __future__ import annotations

from . import engine, ref  # noqa: F401  (ops.ref is public API)

resolve_chunk = engine.resolve_chunk
dist2_to_center = engine.dist2_to_center
pairwise_dist2 = engine.pairwise_dist2
fused_min_argmax = engine.fused_min_argmax
assign_nearest = engine.assign_nearest
assign_bucketed = engine.assign_bucketed
argmin_dist2_over_rows = engine.argmin_dist2_over_rows

# Source folds (engine.py): block-streamed ops over a PointSource, so the
# input itself — not just the distance block — stays out of device memory.
resolve_block_rows = engine.resolve_block_rows
fold_min_d2 = engine.fold_min_d2
fold_top_k_min_d2 = engine.fold_top_k_min_d2
assign_nearest_source = engine.assign_nearest_source
argmin_dist2_over_source = engine.argmin_dist2_over_source

# Fused streamed filter primitives (engine.py over kernels/fused_stream.py):
# the executors' EIM Rounds 2–3 block step — d(x,S) min-update + per-block
# top-k in one pass, Pallas tile or jnp oracle per ``impl`` (bitwise-equal).
filter_tile_update = engine.filter_tile_update
eim_filter_block = engine.eim_filter_block

# Counter-based per-row sampling + streamed top-k (engine.py): the
# blocking-invariant Bernoulli draws and the cross-block pivot Select that
# the out-of-core EIM sampler is built on.
uniform_rows = engine.uniform_rows
bernoulli_rows = engine.bernoulli_rows
split_index_words = engine.split_index_words
uniform_rows_at = engine.uniform_rows_at
bernoulli_rows_at = engine.bernoulli_rows_at
bernoulli_rows_at_block = engine.bernoulli_rows_at_block
top_k_init = engine.top_k_init
merge_top_k = engine.merge_top_k
fold_top_k = engine.fold_top_k
