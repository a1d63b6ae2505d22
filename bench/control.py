"""Runs of a cell with the control in the program's place, and sound runs
on the same seeds, in one process, each judged by the harness's own
comparison (``run.run_cell``). Not part of a benchmark run: its readings
set the limits in the configuration files (see ``PERF.md``).

    python3 bench/control.py --workload <name> --seeds 1,2,3 \
        [--seconds 1] [--sound 1]

The control is the plain reference put in the program's place:
``repro.core.mrg`` and ``MeshExecutor.mrg`` answer with the reference's
own MRG on the job's blocking (``bench/reference.py``), its radius folded
with dots one step below the configuration's f32 at HIGHEST: the chip's
HIGH mode, three bf16 passes. A CPU computes HIGH in f32, so the CPU test
passes ``"default"`` (one bf16 pass). One JSON line a run: the seed, the
side (``program`` or ``control``) and the harness's verdict and checks.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import types

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

from bench import run  # noqa: E402

CONTROL = "high"                    # three bf16 passes


@contextlib.contextmanager
def reference_in_place(prec: str = CONTROL):
    """While open, the program's MRG entries answer with the reference's
    MRG at dot precision ``prec``."""
    import repro.core as core

    from bench import reference
    saved = core.mrg, core.MeshExecutor

    def mrg(x, k, *, m, **_):
        c, r2 = reference.mrg(x, k=k, machines=m, prec=prec,
                              rows=min(8192, x.shape[0]))
        return types.SimpleNamespace(centers=c, radius2=r2)

    class MeshExecutor:
        def __init__(self, mesh):
            self.mesh = mesh

        def mrg(self, src, k, **_):
            x = src.materialize()
            c, r2 = reference.mrg(
                x, k=k, machines=1, prec=prec, mesh=self.mesh,
                rows=min(8192, x.shape[0] // self.mesh.size))
            return c, r2, None

    core.mrg, core.MeshExecutor = mrg, MeshExecutor
    try:
        yield
    finally:
        core.mrg, core.MeshExecutor = saved


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--sound", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for seed in (int(s) for s in args.seeds.split(",")):
        sides = (["program"] if args.sound else []) + ["control"]
        for side in sides:
            t = time.perf_counter()
            ctx = (reference_in_place() if side == "control"
                   else contextlib.nullcontext())
            with ctx:
                line = run.run_cell(args.workload, seed, args.seconds,
                                    False)
            print(json.dumps({"seed": seed, "side": side,
                              "correct": line["correct"],
                              "checks": line["checks"],
                              "attempted": line["attempted"],
                              "seconds": time.perf_counter() - t}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
