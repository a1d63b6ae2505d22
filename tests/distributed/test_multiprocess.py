"""Real multi-process ``jax.distributed`` runs, checked bitwise against
single-process references.

Every test here spawns an actual cluster of worker processes (own
Python interpreters, ``jax.distributed.initialize`` against a localhost
coordinator, gloo CPU collectives) through the harness, then compares
the per-process verdicts against references computed *in this pytest
process* with the single-process executors over the identical logical
input.  The contract is bit equality, not tolerance: multi-process
``mrg`` and streamed ``eim`` must produce the same float32 bits as
``SimExecutor`` / ``HostStreamExecutor`` for matching blockings.
"""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import harness  # noqa: E402
import scenarios  # noqa: E402

from repro.core.eim import eim, eim_sample  # noqa: E402
from repro.core.executor import HostStreamExecutor, SimExecutor  # noqa: E402
from repro.core.mrg import mrg  # noqa: E402
from repro.data import shard_source, synthetic_source  # noqa: E402

# One parameter set per parity cell. eps/k are chosen so the EIM
# sampling loop engages without covering everything (pop ≈ 0.6·n after
# ~6 iterations at these values) — both the degenerate all-sampled path
# and the never-engaged path would skip the cross-process machinery.
PARITY = dict(n=6144, d=3, k=4, eim_k=2, eps=0.1, phi=8.0, key=0)
GRID = dict(n=6001, d=3, k=2, eps=0.1, phi=8.0, key=3, block_rows=512)


def _ref_source(n: int, d: int, shards: int):
    base = synthetic_source("unif", n, seed=scenarios.SEED, d=d)
    return shard_source(base, shards)


def _assert_spy(verdict: dict, block_rows: int) -> None:
    """No process materialized more than its own shard: streaming stayed
    within block_rows, materialize() never ran, and random access (the
    O(k) candidate exchange) touched far fewer rows than the shard."""
    spy = verdict["spy"]
    assert spy["materialize_calls"] == 0
    assert spy["blocks_read"] > 0
    assert 0 < spy["max_block_rows"] <= block_rows
    # random access is per-call bounded by the candidate-set size, never
    # a whole-shard gather (cumulative rows across iterations may exceed
    # the shard; resident-at-once rows must not)
    assert spy["max_take_rows"] < spy["local_n"]


_PER_PROCESS_KEYS = ("spy", "process_id", "ok")


def _assert_replicated(verdicts: list) -> None:
    """SPMD: every process must report identical bits."""
    def shared(v):
        return {k: w for k, w in v.items() if k not in _PER_PROCESS_KEYS}
    for v in verdicts[1:]:
        assert shared(v) == shared(verdicts[0])


def test_two_process_parity_vs_host_stream():
    """2-process mrg + streamed eim == HostStreamExecutor over the same
    ShardedSource with the same block_rows, bit for bit."""
    p = dict(PARITY, block_rows=512)
    verdicts = harness.run("parity", 2, args=p, tag="parity-hs")
    _assert_replicated(verdicts)
    for v in verdicts:
        _assert_spy(v, p["block_rows"])

    src = _ref_source(p["n"], p["d"], 2)
    hs = HostStreamExecutor(block_rows=p["block_rows"])
    m = mrg(src, p["k"], executor=hs)
    e = eim(src, p["eim_k"], jax.random.PRNGKey(p["key"]),
            eps=p["eps"], phi=p["phi"], executor=hs)

    v = verdicts[0]
    np.testing.assert_array_equal(
        np.asarray(v["mrg_centers"], np.float32),
        np.asarray(m.centers, np.float32))
    assert np.float32(v["mrg_radius2"]) == np.float32(m.radius2)
    assert v["mrg_rounds"] == m.rounds
    np.testing.assert_array_equal(
        np.asarray(v["eim_centers"], np.float32),
        np.asarray(e.centers, np.float32))
    assert np.float32(v["eim_radius2"]) == np.float32(e.radius2)
    assert v["eim_iters"] == e.sample.iters
    assert v["sample_idx"] == np.nonzero(np.asarray(e.sample.sample_mask))[0].tolist()
    assert v["s_idx"] == np.nonzero(np.asarray(e.sample.s_mask))[0].tolist()


def test_two_process_parity_vs_sim_executor():
    """With one block per equal shard the mesh blocking *is* SimExecutor's
    machine blocking — the 2-process run must reproduce the simulated
    2-machine reference exactly (mrg and eim)."""
    per = PARITY["n"] // 2
    assert per * 2 == PARITY["n"]
    p = dict(PARITY, block_rows=per)
    verdicts = harness.run("parity", 2, args=p, tag="parity-sim")
    _assert_replicated(verdicts)
    for v in verdicts:
        _assert_spy(v, per)

    x = np.asarray(
        synthetic_source("unif", p["n"], seed=scenarios.SEED,
                         d=p["d"]).materialize())
    sim = SimExecutor(m=2)
    m = mrg(x, p["k"], executor=sim)
    e = eim(x, p["eim_k"], jax.random.PRNGKey(p["key"]),
            eps=p["eps"], phi=p["phi"], executor=sim)

    v = verdicts[0]
    np.testing.assert_array_equal(
        np.asarray(v["mrg_centers"], np.float32),
        np.asarray(m.centers, np.float32))
    assert np.float32(v["mrg_radius2"]) == np.float32(m.radius2)
    np.testing.assert_array_equal(
        np.asarray(v["eim_centers"], np.float32),
        np.asarray(e.centers, np.float32))
    assert np.float32(v["eim_radius2"]) == np.float32(e.radius2)
    assert v["sample_idx"] == np.nonzero(np.asarray(e.sample.sample_mask))[0].tolist()


def test_eim_draws_deterministic_across_process_counts():
    """The determinism grid: EIM Round-1 draws are keyed on absolute
    global row ids, so the sampled index sets are bitwise identical for
    1, 2 and 4 processes — n is chosen so the final shard is ragged for
    both multi-process cells, and the 2-process cell additionally pins
    x64 off explicitly."""
    ref_src = _ref_source(GRID["n"], GRID["d"], 2)
    ref = eim_sample(ref_src, GRID["k"], jax.random.PRNGKey(GRID["key"]),
                     eps=GRID["eps"], phi=GRID["phi"],
                     executor=HostStreamExecutor(
                         block_rows=GRID["block_rows"]))
    ref_sample = np.nonzero(np.asarray(ref.sample_mask))[0].tolist()
    ref_s = np.nonzero(np.asarray(ref.s_mask))[0].tolist()
    assert 0 < len(ref_sample) < GRID["n"], "sampling loop must engage"

    cells = [(1, None), (2, {"JAX_ENABLE_X64": "0"}), (4, None)]
    for procs, env in cells:
        verdicts = harness.run("eim_draws", procs, args=GRID, env=env,
                               tag=f"draws-p{procs}")
        _assert_replicated(verdicts)
        for v in verdicts:
            assert v["sample_idx"] == ref_sample, f"P={procs}"
            assert v["s_idx"] == ref_s, f"P={procs}"
            assert v["iters"] == ref.iters
            assert v["overflow"] == bool(ref.overflow)
            assert v["sampled"] == int(ref.sampled)
            assert v["x64"] is False
            _assert_spy(v, GRID["block_rows"])


def test_global_array_assembly_multiprocess():
    """compat.global_array_from_shards across real process boundaries:
    local pieces only, None for remote shards, allgather returns the full
    bits, and a None local piece raises."""
    for procs in (1, 2):
        verdicts = harness.run("assembly", procs, tag=f"assembly-p{procs}")
        for pid, v in enumerate(verdicts):
            assert v["fetched_sum"] == v["full_sum"]
            assert v["local_ids"] == [pid] if procs > 1 else [0]
            assert v["none_local_raised"] == (procs > 1)


def test_cluster_mesh_topology():
    """make_cluster_mesh spans the *global* device set process-major and
    local_shard_indices maps each process to exactly its own shard."""
    verdicts = harness.run("cluster_env", 2, tag="cluster-env")
    for pid, v in enumerate(verdicts):
        assert v["process_index"] == pid
        assert v["process_count"] == 2
        assert v["global_devices"] == 2
        assert v["local_devices"] == 1
        assert v["mesh_owners"] == [0, 1]
        assert v["make_mesh_matches"] is True
        assert v["local_shard_ids"] == [pid]
