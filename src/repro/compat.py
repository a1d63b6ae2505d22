"""The jax API surface shared across the codebase, in one place.

The repository targets the installed jax line (0.9; see
requirements-dev.txt). This module keeps the few wrappers that add
behavior over jax's own API, so callers never repeat it: ``shard_map``
under a ``check_replication`` name, meshes with explicit *auto* axis
types, an ambient-mesh context that yields the mesh, global arrays
assembled from per-shard host pieces, and multi-process helpers that
degrade to cheap single-process paths when no cluster was initialized.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Sequence

import jax
import jax.sharding
import numpy as np


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------

def shard_map(f=None, *, mesh, in_specs, out_specs, check_replication=True):
    """``jax.shard_map`` with its replication check (``check_vma``) named
    ``check_replication``. Usable directly or as a decorator factory::

        @functools.partial(compat.shard_map, mesh=mesh,
                           in_specs=..., out_specs=...,
                           check_replication=False)
        def run(local): ...
    """
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_replication)
    if f is None:
        return functools.partial(jax.shard_map, **kwargs)
    return jax.shard_map(f, **kwargs)


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------

def make_mesh(devices, axis_names: Sequence[str]) -> jax.sharding.Mesh:
    """``Mesh`` over an ndarray of devices with every axis *auto*."""
    axes = tuple(axis_names)
    return jax.sharding.Mesh(
        devices, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# ---------------------------------------------------------------------------
# Ambient mesh context
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def set_mesh(mesh: jax.sharding.Mesh):
    """Install ``mesh`` as the ambient mesh (``jax.set_mesh``) for the
    duration; yields the mesh."""
    with jax.set_mesh(mesh):
        yield mesh


# ---------------------------------------------------------------------------
# Sharded data feeding
# ---------------------------------------------------------------------------

def global_array_from_shards(mesh: jax.sharding.Mesh, pspec,
                             pieces: Sequence[np.ndarray]) -> jax.Array:
    """Assemble one global device array from per-shard *host* pieces.

    ``pieces`` is one host array per dim-0 shard of the sharding
    ``NamedSharding(mesh, pspec)``, all of equal shape, in shard order
    (= global row order). Each piece is ``device_put`` onto its own
    shard's device(s) and stitched with
    ``jax.make_array_from_single_device_arrays`` — per-shard DMA with no
    host-side staging buffer for the global array, which is the multi-host
    data-feeding pattern in single-process form (the per-shard transfers
    are asynchronous, so the sources' prefetch ring overlaps them with
    compute). Axes of ``mesh`` not named in ``pspec`` replicate each piece
    across their devices.

    **Multi-process:** under ``jax.distributed`` each process addresses
    only its own devices, so a piece whose shard lives on *another*
    process may be ``None`` — only the locally-addressable shards'
    pieces are ``device_put``, and ``make_array_from_single_device_arrays``
    assembles the global array from local shards alone (every process
    contributes its own). A ``None`` piece for a *locally addressable*
    shard is an error, as is any ``None`` on the concatenate fallback
    (which needs every row on this host).
    """
    arrs = [None if p is None else np.asarray(p) for p in pieces]
    ref = next((a for a in arrs if a is not None), None)
    if ref is None:
        raise ValueError(
            "all pieces are None — at least this process's own shards "
            "must be provided")
    rows = ref.shape[0]
    for i, a in enumerate(arrs):
        if a is not None and a.shape != ref.shape:
            raise ValueError(
                f"piece {i} has shape {a.shape}, expected {ref.shape} "
                "(pad every shard's piece to one common block shape)")
    shape = (rows * len(arrs),) + ref.shape[1:]
    sharding = jax.sharding.NamedSharding(mesh, pspec)
    shards = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        start = idx[0].start or 0
        stop = idx[0].stop if idx[0].stop is not None else shape[0]
        if stop - start != rows or start % rows:
            raise ValueError(
                f"sharding splits dim 0 into [{start}, {stop}) slices; "
                f"expected one {rows}-row piece per shard — pass one piece "
                "per dim-0 shard of the pspec")
        piece = arrs[start // rows]
        if piece is None:
            raise ValueError(
                f"piece {start // rows} is None but its shard is "
                f"addressable from this process ({dev}) — only shards "
                "owned by other processes may omit their data")
        shards.append(jax.device_put(piece, dev))
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)


# ---------------------------------------------------------------------------
# Multi-process (multi-controller) runtime
#
# Everything below is the compat surface for genuine ``jax.distributed``
# runs (repro/launch/cluster.py): initialization with CPU collectives
# selected, process topology queries, cross-process value exchange, and
# the sharding helpers the streamed MeshExecutor needs to know which
# shards this process feeds. All of it degrades to cheap single-process
# behavior when no cluster was initialized, so callers never branch on
# the runtime themselves.
# ---------------------------------------------------------------------------


def enable_cpu_collectives(impl: str = "gloo") -> bool:
    """Select the CPU cross-process collectives backend (default gloo).

    Must run *before* the CPU backend is first initialized — without it,
    multi-process programs on CPU fail with "Multiprocess computations
    aren't implemented on the CPU backend". Returns True once selected.
    """
    jax.config.update("jax_cpu_collectives_implementation", impl)
    return True


def distributed_initialize(coordinator_address: str, num_processes: int,
                           process_id: int, *,
                           initialization_timeout: float | None = None,
                           cpu_collectives: str | None = "gloo") -> None:
    """``jax.distributed.initialize`` with the coordinator named explicitly
    (so nothing is looked up from a cluster environment).

    Selects the CPU collectives backend first (set ``cpu_collectives=None``
    on accelerator clusters where XLA's native collectives apply), then
    initializes the distributed runtime. ``initialization_timeout=None``
    keeps jax's default.
    """
    if cpu_collectives is not None:
        enable_cpu_collectives(cpu_collectives)
    kwargs = dict(coordinator_address=coordinator_address,
                  num_processes=int(num_processes),
                  process_id=int(process_id))
    if initialization_timeout is not None:
        kwargs["initialization_timeout"] = int(initialization_timeout)
    jax.distributed.initialize(**kwargs)


def distributed_shutdown() -> None:
    """Tear down the distributed runtime; a no-op when none is active."""
    try:
        jax.distributed.shutdown()
    except RuntimeError:  # pragma: no cover - already down
        pass


def process_index() -> int:
    """This controller's process id (0 on single-process runtimes)."""
    return int(jax.process_index())


def process_count() -> int:
    """Number of controller processes (1 on single-process runtimes)."""
    return int(jax.process_count())


def fetch_global(arr) -> np.ndarray:
    """The full host value of a (possibly cross-process sharded) array.

    Single-process: a plain ``np.asarray`` — byte-identical to the
    pre-multi-process executors, so compiled programs and parity tests
    are untouched. Multi-process: ``multihost_utils.process_allgather``,
    which every process must call (it is a collective); the result is
    the same full value on every process.
    """
    if process_count() == 1:
        return np.asarray(arr)
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(arr))


def exchange_host(x) -> np.ndarray:
    """All-gather a per-process *host* value: returns ``(P, ...)`` stacked
    in process order (row p is process p's contribution). Single-process:
    ``x[None]``. Every process must pass the same shape/dtype and every
    process must call (collective). This is the O(k) candidate exchange
    of the paper's MapReduce rounds — centers move, points never do.
    """
    x = np.asarray(x)
    if process_count() == 1:
        return x[None]
    from jax.experimental import multihost_utils
    out = np.asarray(multihost_utils.process_allgather(x, tiled=False))
    return out.reshape((process_count(),) + x.shape)


def replicated_array(mesh: jax.sharding.Mesh, x) -> jax.Array:
    """``x`` replicated across every device of ``mesh``.

    Single-process this is just ``device_put`` with a replicated
    ``NamedSharding``. Multi-process, the replica set is assembled from
    per-local-device copies via ``make_array_from_single_device_arrays``
    — every process holds the same host value (replicated-by-construction
    SPMD drivers), so no data crosses processes.
    """
    x = np.asarray(x)
    sharding = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    if process_count() == 1:
        return jax.device_put(x, sharding)
    local = [d for d in mesh.devices.flat
             if d.process_index == process_index()]
    arrs = [jax.device_put(x, d) for d in local]
    return jax.make_array_from_single_device_arrays(x.shape, sharding, arrs)


def local_shard_indices(mesh: jax.sharding.Mesh, pspec,
                        num_shards: int) -> list:
    """Which dim-0 shards of ``NamedSharding(mesh, pspec)`` this process
    addresses, as sorted shard indices in ``range(num_shards)``.

    This is how the streamed ``MeshExecutor`` decides which source shards
    to actually read in a multi-process run (the others are fed by their
    owning processes). Computed from the sharding's addressable-device
    index map over a one-row-per-shard probe shape, so it tracks whatever
    device order the mesh was built with.
    """
    sharding = jax.sharding.NamedSharding(mesh, pspec)
    shape = (int(num_shards), 1)
    out = set()
    for _, idx in sharding.addressable_devices_indices_map(shape).items():
        start = idx[0].start or 0
        stop = idx[0].stop if idx[0].stop is not None else num_shards
        out.update(range(start, stop))
    return sorted(out)


# ---------------------------------------------------------------------------
# Compiled-executable introspection
# ---------------------------------------------------------------------------

def cost_analysis(compiled) -> dict:
    """Cost dict of a compiled executable (empty when unavailable)."""
    return compiled.cost_analysis() or {}
