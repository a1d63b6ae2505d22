"""What every cell shares: finding its files by name, the device check,
the compile clock, host spans, the peak memory reading and the result
line. The cell's own work is in ``kinds/<kind>.py``; each per-layer metric
is read by ``metrics/<metric>.py``."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import threading
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def load_module(kind_dir: str, name: str):
    """``bench/<kind_dir>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind_dir, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind_dir}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _applies(metric: dict, cell: str, reported: set | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its files."""
    bm = benchmark()
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    cfg = load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     f"{w['traffic']}.json"))
    e2e = [m for m in bm["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _applies(m, name, names)]
    return Cell(name, int(w["chips"]), cfg, traffic, e2e, per_layer)


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; ``NoChip`` where there are fewer."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU chip: JAX sees only {devs[0].platform} "
                     f"devices ({len(devs)}); this benchmark runs on a TPU "
                     "and nowhere else")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program in it (however fast it compiled)."""
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Seconds and count of XLA backend compiles (Mosaic kernels and
    persistent-cache reads included), from JAX's monitoring events. A copy
    of ``chip_smoke.CompileClock`` with a count added."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        self.count = 0
        self._mu = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == self.EVENT:
            with self._mu:
                self.total += duration
                self.count += 1

    def read(self) -> tuple[float, int]:
        with self._mu:
            return self.total, self.count


def span(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


@dataclass
class Outcome:
    """What a kind's ``run`` hands back to the harness."""
    e2e: dict                       # end-to-end metric name -> value
    checks: dict                    # check name -> (value, limit)
    attempted: int
    failed: int
    layer: dict = field(default_factory=dict)   # values metric readers use


@contextlib.contextmanager
def tracing(trace_dir: str | None):
    """The JAX profiler on around the block when ``trace_dir`` is given."""
    if trace_dir is None:
        yield
        return
    import jax
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
