"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. The cell, its configuration and its
traffic are found by name (``BENCHMARK.json``, ``bench/configs``,
``bench/traffic``); the traffic's kind (``bench/kinds/<kind>.py``) makes the
data from ``--seed`` on the device, warms up, measures for ``--seconds`` and
checks every answer against the plain reference (``bench/reference.py``).
With ``--trace 1`` the window runs under the profiler and the line carries
the cell's per-layer metrics (``bench/metrics/<name>.py``) instead of its
end-to-end ones. The last lines of standard error, and the key ``checks``
that ends the result line, give each number compared beside its limit.

Exits 2, printing no result, where JAX sees no TPU or fewer chips than the
cell asks for, and where the chip is missing from ``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def add_paths() -> None:
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             devices=None, overrides: dict | None = None) -> dict:
    """Run cell ``name`` and return its result line as a dict.

    ``devices`` skips the look for a chip (tests pass CPU devices);
    ``overrides`` replaces configuration values (tests shrink the
    sizes)."""
    add_paths()
    from bench import harness, work
    from bench import trace as trace_mod

    cell = harness.load_cell(name)
    cell.config.update(overrides or {})
    if devices is None:
        devices = harness.tpu_devices(cell.chips)
        harness.enable_compile_cache()
    kind_name = devices[0].device_kind
    if devices[0].platform == "tpu":
        work.peaks(kind_name)          # an unknown chip is an error
    clock = harness.CompileClock()
    trace_dir = None
    if trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    kind = harness.load_module("kinds", cell.kind)
    out = kind.run(cell, seed=seed, seconds=seconds, devices=devices,
                   clock=clock, t_start=T_START, trace_dir=trace_dir)

    device = {"platform": devices[0].platform, "kind": kind_name,
              "count": len(devices),
              "memory_peak_bytes": int(out.layer["peak_bytes"])}
    line = {"correct": all(v <= lim for v, lim in out.checks.values()),
            "attempted": out.attempted, "failed": out.failed}
    if trace:
        summary = trace_mod.reduce_dir(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        out.layer.update(trace=summary, device_kind=kind_name)
        metrics = {}
        for m in cell.per_layer:
            value = harness.load_module("metrics", m["name"]).read(out.layer)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        line.update(metrics=metrics, device=device, breakdown={
            "device_ops": summary.top_ops, "idle_gaps": summary.idle_gaps})
    else:
        line.update(metrics={m["name"]: {"value": out.e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end
                             if m["name"] in out.e2e}, device=device)
    line["diagnostics"] = {k: v for k, v in out.layer.items()
                           if isinstance(v, (int, float))}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes only
    # inside its checkout and the directories it is given.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    add_paths()
    from bench import harness
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except (harness.NoChip, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    diag = line.pop("diagnostics")
    print("bench: " + " ".join(f"{k}={v!r}" for k, v in diag.items()),
          file=sys.stderr)
    for k, c in line["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {k} = {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
