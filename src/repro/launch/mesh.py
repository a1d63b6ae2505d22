"""Production mesh construction.

IMPORTANT: functions only — importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialization).

Mesh construction goes through ``repro.compat.make_mesh`` (every axis
explicitly *auto*).

Multi-process: under ``jax.distributed`` the full device set is
``jax.devices()`` (global, ordered process-major) while this process can
address only ``jax.local_devices()``. Meshes for SPMD programs must be
built over the *global* set — a mesh over local devices describes a
different (per-process) program on every controller, which is exactly the
bug class ``make_cluster_mesh`` exists to prevent. ``make_mesh`` therefore
takes an explicit ``devices=`` (defaulting to the global set) so callers
on one process can describe the whole cluster's mesh.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import Mesh

from repro import compat


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 v5e pod mesh, or 2×16×16 across two pods.

    Uses the first prod(shape) devices, so a 256-chip mesh builds fine on
    a 512-placeholder-device dry-run platform.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              *, devices: Optional[Sequence] = None) -> Mesh:
    """Mesh of the first prod(shape) devices of ``devices`` (default: the
    *global* ``jax.devices()`` — every process of a multi-process run
    builds the same mesh; pass ``jax.local_devices()`` explicitly only
    for deliberately per-process programs)."""
    import numpy as np
    n = int(np.prod(shape))
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    arr = np.asarray(devs[:n]).reshape(tuple(shape))
    return compat.make_mesh(arr, axes)


def make_cluster_mesh(axes: Sequence[str] = ("data",)) -> Mesh:
    """The canonical multi-process data mesh: one axis spanning every
    device of every process, ordered process-major.

    Validates the global view so partition bugs fail at construction
    rather than as silent per-process divergence:

      * the mesh covers *all* ``jax.devices()`` — never a local subset
        (``len == process_count · local_device_count``);
      * each process's devices form one contiguous run in process order,
        so dim-0 shard *s* of a ``P(axes)``-sharded array is owned by
        process ``s // local_device_count`` — the contract
        ``ProcessShardedSource.for_process`` and the streamed
        ``MeshExecutor`` rely on.

    Single-process this degenerates to a mesh over all local devices —
    the same object ``make_mesh((len(devices),), axes)`` builds — so
    scenario code is identical on 1 and N processes.
    """
    import numpy as np
    devs = jax.devices()
    pc = compat.process_count()
    per = len(devs) // pc
    if per * pc != len(devs):
        raise ValueError(
            f"{len(devs)} global devices do not divide evenly over "
            f"{pc} processes")
    for i, d in enumerate(devs):
        if d.process_index != i // per:
            raise ValueError(
                f"global device order is not process-major: device {i} "
                f"belongs to process {d.process_index}, expected "
                f"{i // per} — build the mesh from an explicitly "
                "reordered device list instead")
    arr = np.asarray(devs)
    axes = tuple(axes)
    if len(axes) != 1:
        raise ValueError(
            f"make_cluster_mesh builds a single sharding axis, got {axes}")
    return compat.make_mesh(arr, axes)


def make_elastic_mesh(model_parallel: int = 16,
                      devices: Optional[list] = None) -> Mesh:
    """Largest (data, model) grid over the *live* device set — the elastic
    restart path: a degraded pod (e.g. 448 of 512 chips) still yields a
    valid mesh; data-parallel size shrinks to fit (DESIGN.md §6)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    mp = min(model_parallel, n)
    while n % mp:
        mp -= 1
    dp = n // mp
    import numpy as np
    arr = np.asarray(devices[: dp * mp]).reshape(dp, mp)
    return compat.make_mesh(arr, ("data", "model"))
