"""pytest-facing wrapper around ``repro.launch.cluster``.

Resolves scenario names to ``tests/distributed/scenarios.py:<fn>``
targets, threads ``CLUSTER_LOG_DIR`` (set by CI) through as per-worker
log capture, and re-exports the pieces the tests assert on.  All the
process management — spawn, pipe drain, verdict parse, early-exit
reaping, hard-kill on timeout — lives in ``repro.launch.cluster``; this
module only names scenarios.
"""
from __future__ import annotations

import os

from repro.launch.cluster import (  # noqa: F401  (re-exported for tests)
    ClusterError,
    WorkerResult,
    free_port,
    launch_cluster,
    run_scenario,
)

_HERE = os.path.dirname(os.path.abspath(__file__))
SCENARIOS = os.path.join(_HERE, "scenarios.py")
FAULTY_IMPORT = os.path.join(_HERE, "_faulty_import.py")

# Locally these all finish in seconds to ~a minute. The bound stays under
# the per-test time limit (conftest.py, 300 s), so a stuck cluster fails
# its own test with every worker's output.
DEFAULT_TIMEOUT = 240.0


def scenario_target(name: str) -> str:
    return f"{SCENARIOS}:{name}"


def _log_dir(tag: str):
    base = os.environ.get("CLUSTER_LOG_DIR")
    if not base:
        return None
    path = os.path.join(base, tag)
    os.makedirs(path, exist_ok=True)
    return path


def run(name: str, num_processes: int, *, args=None,
        timeout: float = DEFAULT_TIMEOUT, tag: str | None = None,
        **kwargs) -> list:
    """Run scenario ``name`` in an ``num_processes``-worker cluster and
    return the per-process verdict dicts (process order).  Raises
    ``ClusterError`` — with every worker's traceback/output tail — on any
    failure, timeout included."""
    kwargs.setdefault("log_dir", _log_dir(tag or f"{name}-p{num_processes}"))
    return run_scenario(scenario_target(name), num_processes,
                        args=args, timeout=timeout, **kwargs)
