"""Pin repro.compat's wrappers over the installed jax line: shard_map's
``check_replication`` spelling, auto-axis meshes, global-array assembly
from per-shard host pieces, and the single-process forms of the
multi-process helpers.

A fresh module instance is loaded via spec_from_file_location where a
test needs one: ``importlib.reload`` would mutate the singleton other
modules hold references to.
"""
import importlib.util
from pathlib import Path

import jax
import jax.sharding
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat as canonical

COMPAT_PATH = (Path(__file__).resolve().parents[1]
               / "src" / "repro" / "compat.py")

_counter = [0]


def load_fresh_compat():
    """Import a brand-new compat module instance under current jax attrs."""
    _counter[0] += 1
    spec = importlib.util.spec_from_file_location(
        f"_compat_fresh_{_counter[0]}", COMPAT_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def one_device_mesh(mod):
    return mod.make_mesh(np.array(jax.devices()[:1]), ("data",))


def test_fresh_load_matches_canonical_flags():
    # A fresh instance builds the same mesh and ambient context as the
    # canonical module (nothing in compat depends on import order).
    mod = load_fresh_compat()
    mesh = one_device_mesh(mod)
    ref = one_device_mesh(canonical)
    assert mesh.axis_names == ref.axis_names == ("data",)
    assert mesh.axis_types == ref.axis_types == (
        jax.sharding.AxisType.Auto,)
    with mod.set_mesh(mesh) as m:
        assert m is mesh


def test_shard_map_replication_kwarg_resolved():
    # check_replication reaches jax.shard_map as check_vma, both as a
    # direct call and as a decorator factory.
    mesh = one_device_mesh(canonical)
    g = canonical.shard_map(mesh=mesh, in_specs=P(), out_specs=P(),
                            check_replication=False)(lambda x: x * 2.0)
    np.testing.assert_array_equal(np.asarray(g(np.ones((3,), np.float32))),
                                  np.full((3,), 2.0, np.float32))
    f = canonical.shard_map(lambda x: x + 1.0, mesh=mesh, in_specs=P(),
                            out_specs=P(), check_replication=False)
    x = np.ones((3,), np.float32)
    np.testing.assert_array_equal(np.asarray(f(x)), x + 1.0)


def test_global_assembly_rejects_none_local_piece():
    # Multi-process callers may pass None for *remote* shards only; on a
    # single process every shard is addressable, so any None must raise.
    # (the addressable-but-None branch needs >= 2 devices and is pinned
    # by the multi-process assembly scenario in tests/distributed/)
    mesh = one_device_mesh(canonical)
    good = np.zeros((4, 3), np.float32)
    with pytest.raises(ValueError, match="all pieces are None"):
        canonical.global_array_from_shards(mesh, P("data"), [None])
    with pytest.raises(ValueError, match="all pieces are None"):
        canonical.global_array_from_shards(mesh, P("data"), [None] * 4)
    with pytest.raises(ValueError, match="expected"):
        canonical.global_array_from_shards(
            mesh, P("data"), [good, np.zeros((2, 3), np.float32)])
    # a present piece still assembles when *it* is the only shard
    out = canonical.global_array_from_shards(mesh, P("data"), [good])
    np.testing.assert_array_equal(np.asarray(out), good)


def test_single_process_distributed_helpers():
    # On a one-process runtime the cross-process primitives degenerate to
    # identities — these are the exact code paths the single-process
    # executors keep using after the multi-process refactor.
    assert canonical.process_count() >= 1
    assert canonical.process_index() == 0
    x = np.arange(12, dtype=np.float32).reshape(4, 3)
    np.testing.assert_array_equal(canonical.fetch_global(x), x)
    ex = canonical.exchange_host(x)
    assert ex.shape == (1, 4, 3)
    np.testing.assert_array_equal(ex[0], x)
    mesh = one_device_mesh(canonical)
    rep = canonical.replicated_array(mesh, x)
    np.testing.assert_array_equal(np.asarray(rep), x)
    assert canonical.local_shard_indices(mesh, P("data"), 1) == [0]
    # enable_cpu_collectives is idempotent and reports availability
    assert canonical.enable_cpu_collectives() in (True, False)
