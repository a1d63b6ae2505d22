"""Fault paths of the cluster harness: every failure mode must surface
the child's traceback in the pytest error *within the timeout* — a dead
or wedged worker must never hang CI for the full hard deadline."""
from __future__ import annotations

import socket
import time

import pytest

jax = pytest.importorskip("jax")

import harness  # noqa: E402



def test_worker_dies_pre_initialize():
    """An import-time failure in the scenario module kills every worker
    before it reaches the coordination barrier; the parent reports the
    traceback immediately instead of waiting out the timeout."""
    t0 = time.monotonic()
    with pytest.raises(harness.ClusterError) as ei:
        harness.run_scenario(harness.FAULTY_IMPORT + ":never", 2,
                             timeout=120, log_dir=None)
    elapsed = time.monotonic() - t0
    msg = str(ei.value)
    assert "boom at import" in msg
    assert "RuntimeError" in msg
    assert elapsed < 60, f"pre-init fault took {elapsed:.0f}s to surface"
    assert all(not r.ok for r in ei.value.results)
    assert not any(r.timed_out for r in ei.value.results)


def test_worker_raises_mid_round():
    """One worker raises between collectives; the survivor is blocked in
    a dead collective and must be reaped by the early-exit rule, with the
    crashed worker's traceback in the report."""
    t0 = time.monotonic()
    with pytest.raises(harness.ClusterError) as ei:
        harness.run("crash_mid_round", 2, args={"crash_on": 1},
                    timeout=180, tag="fault-mid-round")
    elapsed = time.monotonic() - t0
    msg = str(ei.value)
    assert "boom mid-round" in msg
    assert elapsed < 120, f"mid-round fault took {elapsed:.0f}s to surface"
    crashed = ei.value.results[1]
    assert crashed.returncode not in (0, None)
    assert not crashed.timed_out
    # the survivor either got reaped (killed) or failed its collective —
    # both are acceptable; hanging to the hard deadline is not.
    assert not any(r.timed_out for r in ei.value.results)


def test_coordinator_port_collision():
    """A coordinator that cannot bind its port must fail the run quickly
    (bounded by init_timeout + grace), with the child error surfaced."""
    blocker = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    port = blocker.getsockname()[1]
    try:
        t0 = time.monotonic()
        with pytest.raises(harness.ClusterError) as ei:
            harness.run("trivial", 2, coordinator_port=port,
                        init_timeout=10, timeout=120,
                        tag="fault-port-collision")
        elapsed = time.monotonic() - t0
        assert elapsed < 100, f"port collision took {elapsed:.0f}s"
        assert any(not r.ok for r in ei.value.results)
        # at least one child's own error text made it into the report
        msg = str(ei.value)
        assert "worker" in msg and ("Error" in msg or "error" in msg)
    finally:
        blocker.close()
