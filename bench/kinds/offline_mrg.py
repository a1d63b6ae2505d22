"""Offline MRG jobs back to back on a device-resident corpus.

Traffic parameters (``bench/traffic/<name>.json``):

- ``executor``: ``"sim"`` (``SimExecutor``: ``machines`` simulated machines
  on one chip, through ``repro.core.mrg.mrg``) or ``"mesh"`` (the fused
  ``MeshExecutor`` program, one machine per chip);
- ``machines``: simulated machines per chip (``"sim"`` only).

Set-up makes the corpus on the device from the seed and runs one job,
which compiles every program the window runs. The window then runs jobs
back to back, each ending in ``block_until_ready``, and closes at the end
of the first job that ends after ``seconds``; ``solve_s`` is the window
over the jobs. Every job's answer is checked afterwards against the plain
reference over all rows:

- ``r2_gap_eps``: the job's squared radius against the reference's
  recomputation for the job's centers, in f32 roundings of the largest
  squared norm;
- ``radius_gap``: how far the job's radius lies from the radius of the
  reference's own MRG on the same blocking, as a share of the latter
  (|r / r_ref - 1|). With ``r2_gap_eps`` holding the job's radius to its
  own centers, this is the check on which centers the job picks: sound
  runs pick other rows of the same clusters now and then and differ by a
  few 1e-5 (limit and readings in ``PERF.md``);
- ``bad_centers``: centers that are not a row of the corpus, or repeat
  another (exact: 0).
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import data, harness, reference, work

F32_EPS = float(np.finfo(np.float32).eps)


def _corpus(cfg, seed, devices):
    import jax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    n = int(cfg["n"])
    if len(devices) == 1:
        return data.device_rows(cfg, seed, 0, n), None
    mesh = Mesh(np.asarray(devices), ("data",))
    x = data.device_rows(cfg, seed, 0, n,
                         sharding=NamedSharding(mesh, P("data")))
    return jax.block_until_ready(x), mesh


def _job_fn(traffic, cfg, x, mesh):
    from repro.core import MeshExecutor, mrg
    from repro.data import ArraySource
    k = int(cfg["k"])
    if traffic["executor"] == "mesh":
        ex = MeshExecutor(mesh)
        src = ArraySource(x)

        def job():
            centers, r2, _ = ex.mrg(src, k, impl="auto")
            return centers, r2
        return job
    machines = int(traffic["machines"])

    def job():
        res = mrg(x, k, m=machines, impl="auto")
        return res.centers, res.radius2
    return job


def run(cell, *, seed, seconds, devices, clock, t_start, trace_dir):
    import jax
    cfg, traffic = cell.config, cell.traffic
    t_init = time.perf_counter()
    with harness.span("setup.data"):
        x, mesh = _corpus(cfg, seed, devices)
    job = _job_fn(traffic, cfg, x, mesh)
    t_data = time.perf_counter()
    with harness.span("setup.warmup"):
        jax.block_until_ready(job())
    compile_setup_s, compiles_setup = clock.read()

    results = []
    with harness.tracing(trace_dir):
        t0 = time.perf_counter()
        with harness.span("window"):
            while True:
                with harness.span("job"):
                    results.append(jax.block_until_ready(job()))
                t1 = time.perf_counter()
                if t1 - t0 >= seconds:
                    break
    compile_window_s, compiles = clock.read()
    peak = harness.peak_bytes(devices)
    host = [(np.asarray(c), float(r2)) for c, r2 in results]
    del results, job

    # -- the check, against the plain reference (not counted in set-up) --
    gap, rgap, bad, radius = check(x, mesh, cfg, traffic, host)
    lim = cfg["limits"]
    checks = {"r2_gap_eps": (gap, float(lim["r2_gap_eps"])),
              "radius_gap": (rgap, float(lim["radius_gap"])),
              "bad_centers": (bad, 0)}
    window_s = t1 - t0
    shape = _shape(cfg, devices, traffic)
    return harness.Outcome(
        e2e={"solve_s": window_s / len(host), "radius": radius,
             "peak_hbm_gb": peak / 1e9, "setup_s": t0 - t_start},
        checks=checks, attempted=len(host), failed=0,
        layer={"peak_bytes": peak, "compile_s": compile_setup_s,
               "setup_init_s": t_init - t_start,
               "setup_data_s": t_data - t_init,
               "setup_warmup_s": t0 - t_data,
               "compiles_setup": compiles_setup,
               "compiles_window": compiles - compiles_setup,
               "compile_window_s": compile_window_s - compile_setup_s,
               "jobs": len(host), "window_s": window_s,
               "job_shape": shape, "job_work": work.mrg_job(*shape)})


def reference_radius(x, mesh, cfg, traffic, prec="highest"):
    """The reference's own MRG on the job's blocking: (centers, r2)."""
    n_local = int(cfg["n"]) // (1 if mesh is None else mesh.size)
    machines = 1 if mesh is not None else int(traffic["machines"])
    return reference.mrg(x, k=int(cfg["k"]), machines=machines,
                         rows=min(8192, n_local), mesh=mesh, prec=prec)


def check(x, mesh, cfg, traffic, answers, ref_r2=None):
    """``answers`` [(centers, r2)] against the reference over all rows.
    -> (r2 gap in f32 roundings of the largest squared norm, worst
    |radius / the reference MRG's - 1|, worst count of bad centers, the
    radius of the last answer's centers as the reference measures it)."""
    n_local = int(cfg["n"]) // (1 if mesh is None else mesh.size)
    rows = min(8192, n_local)
    if ref_r2 is None:
        ref_r2 = reference_radius(x, mesh, cfg, traffic)[1]
    ref_r = math.sqrt(float(ref_r2))
    gap = rgap = 0.0
    bad = 0
    radius = None
    for centers, r2 in answers:
        a_r2, near, scale = reference.audit_centers(x, centers, rows=rows,
                                                    mesh=mesh)
        a_r2, scale = float(a_r2), float(scale)
        gap = max(gap, abs(r2 - a_r2) / (F32_EPS * scale))
        rgap = max(rgap, abs(math.sqrt(max(r2, 0.0)) / ref_r - 1.0))
        bad = max(bad, reference.bad_centers(x, centers, near))
        radius = math.sqrt(a_r2)
    return gap, rgap, bad, radius


def _shape(cfg, devices, traffic):
    """(rows per chip, d, k, candidates round 2 reduces)."""
    k, d = int(cfg["k"]), int(cfg["d"])
    machines = (len(devices) if traffic["executor"] == "mesh"
                else int(traffic["machines"]))
    return int(cfg["n"]) // len(devices), d, k, machines * k
