#!/usr/bin/env python3
"""Smoke run of the k-center main path on a TPU: the quickest proof that the
system still starts on the chip, and that its answers are right there.

    python chip_smoke.py              # one chip: phases (a)-(c) below
    python chip_smoke.py --chips 4    # four chips: the sharded MeshExecutor
                                      # paths and their one-device twins

Data: planted, well-separated clusters generated on the device from
``--seed`` — ``n`` rows of width d=768 (the width of sentence embeddings)
around 1,024 centers drawn uniformly from a cube of side 100, each row its
center plus uniform noise in [-1, 1]^d. Clusters are ~16 apart inside and
~1,100 apart from each other, so every correct k-center run at k = 1,024
picks exactly one center per cluster.

One chip (n = 2,097,152 rows, 6.4 GB on the device):
  (a) ``gonzalez``, ``mrg`` (SimExecutor, 16 machines) and ``eim`` on the
      device array. ``eim`` runs at the largest k at which its sampling
      loop starts at this n (its loop needs n > (4/ε)·k·n^ε·ln n, which no
      ε meets at k = 1,024 and n = 2^21).
  (b) streamed ``mrg`` and ``eim`` through ``HostStreamExecutor`` over a
      seeded ``SyntheticSource`` (n = 1,048,576) in 8 super-shards.
  (c) ``KCenterService(k=1024, d=768)``: ingest 262,144 rows, drain, then
      2,048 queries of 1-256 rows from 4 client threads.
Before them, a precision probe measures what an f32 dot computes on the
chip at each precision, against float64.

Four chips (n = 4 x 2,097,152 rows, each chip's quarter the one-chip size):
``mrg`` through the fused ``MeshExecutor`` device program; then streamed
``mrg`` (k = 256) and ``eim`` (k = 64) over ``shard_source(src, mesh)``, src
a host copy of 4 x 1,048,576 of those rows. Each is compared with the same
algorithm and blocking run on one device.

Checks (any failure raises and the run exits non-zero): the device is a
TPU and ``impl="auto"`` resolves to the native kernels; every run returns
centers in distinct planted clusters (one per cluster at k = 1,024); every
reported squared radius matches the ref path's recomputation over all n
within ``TOL_EPS`` f32 roundings of the squared norms, and a float64
recomputation on a 65,536-row subsample does not exceed it; MRG's radius is
at most 4x and EIM's at most 10x GON's at the same k; every served answer
is bitwise ``ops.assign_nearest`` on its epoch's snapshot. The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import threading
import time
from dataclasses import dataclass

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# A radius may differ between two f32 evaluations of |x|² + |c|² − 2x·c by
# a few roundings of the squared norms it cancels; the bound used is
# TOL_EPS·ε₃₂·max(|x|², |c|²).
TOL_EPS = 32
F32_EPS = float(np.finfo(np.float32).eps)


@dataclass(frozen=True)
class Sizes:
    n: int = 2_097_152            # device-resident rows (6.4 GB at d=768)
    d: int = 768
    clusters: int = 1024
    k: int = 1024
    machines: int = 16            # SimExecutor machines for (a)
    n_stream: int = 1_048_576     # SyntheticSource rows for (b)
    stream_shards: int = 8        # super-shards the memory budget yields
    serve_rows: int = 262_144
    serve_batches: int = 16
    queries: int = 2048
    max_query: int = 256
    clients: int = 4
    subsample: int = 65_536       # float64 recomputation rows
    ref_chunk: int = 1024         # row chunk of the ref path's distance scans
    probe_rows: int = 4096        # precision probe rows
    side: float = 100.0           # planted centers ~ U[-side/2, side/2]^d
    noise: float = 1.0            # row = center + U[-noise, noise]^d
    mrg_k_mesh: int = 256         # streamed MRG k on four chips
    eim_k_mesh: int = 64          # streamed EIM k on four chips


# ---------------------------------------------------------------------------
# data: planted clusters, counter-based in the row index
# ---------------------------------------------------------------------------

def planted_centers(sz: Sizes, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.uniform(-sz.side / 2, sz.side / 2,
                       (sz.clusters, sz.d)).astype(np.float32)


def _mix32(h):
    """murmur3's 32-bit finalizer (uint32 arithmetic wraps)."""
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _device_rows(planted, start, seed, *, rows: int, noise: float):
    import jax
    import jax.numpy as jnp
    return _rows_at(planted, start + jax.lax.iota(jnp.uint32, rows), seed,
                    noise)


def _rows_at(planted, r, seed, noise: float):
    """Rows ``r`` (uint32 indices): each a pure function of its index."""
    import jax
    import jax.numpy as jnp
    rh = _mix32(r ^ seed)
    cid = _mix32(rh ^ np.uint32(0x68E31DA4)) % np.uint32(planted.shape[0])
    col = jax.lax.iota(jnp.uint32, planted.shape[1]) * np.uint32(0x9E3779B9)
    h = _mix32(rh[:, None] ^ col[None, :])
    u = (h >> 8).astype(jnp.float32) * np.float32(2.0 ** -24)
    return planted[cid] + np.float32(noise) * (np.float32(2.0) * u
                                               - np.float32(1.0))


def device_points(planted, start: int, rows: int, seed: int, noise: float,
                  sharding=None):
    """Rows ``[start, start + rows)`` generated on the device (sharded by
    ``sharding`` when given — each device then computes its own rows)."""
    import jax
    fn = jax.jit(functools.partial(_device_rows, rows=rows, noise=noise),
                 out_shardings=sharding)
    # planted stays a numpy (uncommitted) operand, so a sharded program
    # may place it on every device.
    return fn(planted, np.uint32(start), np.uint32(seed))


def host_source(planted: np.ndarray, n: int, seed: int, noise: float):
    """Seeded ``SyntheticSource`` of the planted rows ``[0, n)`` (those of
    ``device_points``): each row is a pure function of its index, so any
    blocking and any ``take`` regenerate the same rows. The rows are
    computed on the device and handed over as host blocks, in index
    buckets of powers of two (one compilation per bucket)."""
    import jax

    from repro.data.source import SyntheticSource

    gen = jax.jit(functools.partial(_rows_at, noise=noise))

    def rows_at(idx: np.ndarray) -> np.ndarray:
        b = max(1024, 1 << (len(idx) - 1).bit_length())
        r = np.zeros((b,), np.uint32)
        r[:len(idx)] = idx
        return np.asarray(gen(planted, r, np.uint32(seed)))[:len(idx)]

    class PlantedSource(SyntheticSource):
        def take(self, indices) -> np.ndarray:
            idx = np.asarray(indices, np.int64).reshape(-1)
            if idx.size and (idx.min() < 0 or idx.max() >= self.n):
                raise IndexError("take indices out of range")
            return rows_at(idx)

    return PlantedSource(
        lambda start, rows: rows_at(np.arange(start, start + rows)),
        n, planted.shape[1], name="planted")


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds spent in XLA's backend compile (Mosaic kernels and
    persistent-cache reads included), from JAX's monitoring events, so a
    phase's wall time splits into compile and run. Tracing and lowering
    count as run time: their events nest, the backend compile's do not."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        self._mu = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == self.EVENT:
            with self._mu:
                self.total += duration


def peak_bytes(devices) -> list:
    out = []
    for dev in devices:
        stats = dev.memory_stats() or {}
        out.append(int(stats.get("peak_bytes_in_use", -1)))
    return out


def run_phase(name: str, fn, clock: CompileClock, devices):
    """Run ``fn`` to completion (``block_until_ready``) and print its
    compile seconds, run seconds and the devices' peak bytes in use."""
    import jax
    c0 = clock.total
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    comp = clock.total - c0
    print(f"phase={name} compile_s={comp:.3f} run_s={wall - comp:.3f} "
          f"peak_device_bytes={peak_bytes(devices)}", flush=True)
    return out


def fail(msg: str):
    raise AssertionError(msg)


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def planted_ids(centers, planted: np.ndarray):
    """-> (nearest planted center of each center, its distance), float64."""
    c = np.asarray(centers, np.float64)
    p = planted.astype(np.float64)
    d2 = (c * c).sum(1)[:, None] + (p * p).sum(1)[None, :] - 2.0 * c @ p.T
    ids = d2.argmin(1)
    return ids, np.sqrt(np.maximum(d2[np.arange(len(c)), ids], 0.0))


def check_clusters(name: str, centers, planted: np.ndarray, k: int,
                   sz: Sizes) -> None:
    """Centers fall in k distinct planted clusters, each a row of its
    cluster (within the noise box of the planted center)."""
    c = np.asarray(centers)
    ids, dist = planted_ids(c, planted)
    distinct = len(set(ids.tolist()))
    box = sz.noise * math.sqrt(sz.d) * (1 + 1e-6)
    print(f"check={name} centers={len(c)} distinct_clusters={distinct} "
          f"max_dist_to_planted={dist.max():.4f} noise_box={box:.4f}",
          flush=True)
    if len(c) != k or distinct != k or dist.max() > box:
        fail(f"{name}: {len(c)} centers in {distinct} distinct clusters "
             f"(want {k}), farthest {dist.max():.4f} from its planted "
             f"center (box {box:.4f})")


def _f64_max_d2(xs: np.ndarray, centers) -> tuple[float, float]:
    """float64 max over rows of the min squared distance; also the largest
    squared norm seen (the tolerance scale)."""
    x = np.asarray(xs, np.float64)
    c = np.asarray(centers, np.float64)
    xn = (x * x).sum(1)
    cn = (c * c).sum(1)
    d2 = xn[:, None] + cn[None, :] - 2.0 * x @ c.T
    return float(np.maximum(d2.min(1), 0.0).max()), float(max(xn.max(),
                                                               cn.max()))


def check_radius(name: str, r2, ref_r2, sub_rows: np.ndarray, centers):
    r2, ref_r2 = float(r2), float(ref_r2)
    sub_r2, scale = _f64_max_d2(sub_rows, centers)
    tol = TOL_EPS * F32_EPS * scale
    print(f"check={name} radius2={r2!r} ref_radius2={ref_r2!r} "
          f"f64_subsample_radius2={sub_r2!r} tol={tol!r}", flush=True)
    if abs(r2 - ref_r2) > tol:
        fail(f"{name}: radius2 {r2!r} vs ref {ref_r2!r} (tol {tol!r})")
    if sub_r2 > r2 + tol:
        fail(f"{name}: float64 subsample radius2 {sub_r2!r} exceeds "
             f"{r2!r} (tol {tol!r})")


def check_ratio(name: str, r2, base_r2, bound: float):
    ratio = math.sqrt(float(r2)) / math.sqrt(float(base_r2))
    print(f"check={name} radius_ratio={ratio!r} bound={bound}", flush=True)
    if not ratio <= bound:
        fail(f"{name}: radius ratio {ratio!r} > {bound}")


def same_centers(a, b) -> bool:
    return bool(np.array_equal(np.asarray(a), np.asarray(b)))


def check_twins(name: str, centers, r2, twin_centers, twin_r2,
                planted: np.ndarray, sample_rows: np.ndarray) -> None:
    """A sharded run agrees with its one-device twin: bitwise where the
    two programs compute the same per-element arithmetic, else the same
    planted clusters and a radius within the stated tolerance."""
    bitwise = (same_centers(centers, twin_centers)
               and float(r2) == float(twin_r2))

    def clusters(c):
        return set(planted_ids(c, planted)[0].tolist())

    _, scale = _f64_max_d2(sample_rows, centers)
    tol = TOL_EPS * F32_EPS * scale
    print(f"check={name} bitwise_one_device={bitwise} "
          f"radius2={float(r2)!r} one_device_radius2={float(twin_r2)!r} "
          f"tol={tol!r}", flush=True)
    if bitwise:
        return
    if clusters(centers) != clusters(twin_centers):
        fail(f"{name}: the sharded run picked other clusters than its "
             "one-device twin")
    if abs(float(r2) - float(twin_r2)) > tol:
        fail(f"{name}: radius2 {float(r2)!r} vs one device "
             f"{float(twin_r2)!r} (tol {tol!r})")


@functools.lru_cache(maxsize=None)
def _ref_fold(rows: int):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref

    @jax.jit
    def fold(x, c):
        def body(i, best):
            blk = jax.lax.dynamic_slice_in_dim(x, i * rows, rows)
            return jnp.maximum(best, jnp.max(ref.assign_nearest(blk, c)[1]))
        return jax.lax.fori_loop(0, x.shape[0] // rows, body,
                                 jnp.float32(0.0))
    return fold


def ref_radius2(x, centers, rows: int):
    """The ref path's squared covering radius of ``centers`` over a device
    array, in ``rows``-row chunks (no (n, m) block)."""
    import jax.numpy as jnp
    return _ref_fold(rows)(x, jnp.asarray(centers))


def eim_k_for(n: int, k_max: int) -> int:
    """Largest k <= k_max at which EIM's sampling loop starts (n above the
    loop threshold at the default ε and φ)."""
    from repro.core.eim import _params
    for k in range(k_max, 0, -1):
        if n > _params(n, k, 0.1, 8.0)[1]:
            return k
    fail(f"EIM's sampling loop starts at no k for n={n}")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_precision(sz: Sizes, planted: np.ndarray, x, clock, devs):
    """What an f32 distance dot computes on this device at each precision:
    max |error| of x·c over the rows' squared-norm scale, against float64,
    for XLA's dot and a Mosaic (Pallas) kernel's dot."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from repro.kernels import ref

    xs = x[:sz.probe_rows]
    c = jnp.asarray(planted)
    dn = (((1,), (1,)), ((), ()))

    def xla(prec):
        return jax.jit(lambda a, b: jax.lax.dot_general(
            a, b, dn, precision=prec, preferred_element_type=jnp.float32))

    def mosaic(prec):
        def kernel(x_ref, c_ref, o_ref):
            o_ref[...] = jax.lax.dot_general(
                x_ref[...], c_ref[...], dn, precision=prec,
                preferred_element_type=jnp.float32)
        tn, tm = min(256, xs.shape[0]), min(256, c.shape[0])
        return jax.jit(lambda a, b: pl.pallas_call(
            kernel, grid=(a.shape[0] // tn, b.shape[0] // tm),
            in_specs=[pl.BlockSpec((tn, a.shape[1]), lambda i, j: (i, 0)),
                      pl.BlockSpec((tm, b.shape[1]), lambda i, j: (j, 0))],
            out_specs=pl.BlockSpec((tn, tm), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((a.shape[0], b.shape[0]),
                                           jnp.float32))(a, b))

    x64 = np.asarray(xs, np.float64)
    c64 = planted.astype(np.float64)
    exact = x64 @ c64.T
    scale = float(max((x64 * x64).sum(1).max(), (c64 * c64).sum(1).max()))
    hi = jax.lax.Precision.HIGHEST
    variants = {"xla_default": xla(None), "xla_highest": xla(hi),
                "mosaic_default": mosaic(None), "mosaic_highest": mosaic(hi)}

    def run():
        return {name: f(xs, c) for name, f in variants.items()}

    outs = run_phase("precision", run, clock, devs)
    for name, out in outs.items():
        err = np.abs(np.asarray(out, np.float64) - exact).max() / scale
        print(f"precision dot={name} max_abs_err_over_norm2={float(err)!r} "
              f"(f32 eps {F32_EPS!r}, bf16 eps {2.0 ** -8!r})", flush=True)
    # The oracle's distances at its stated precision, against float64.
    d2 = np.asarray(ref.pairwise_dist2(xs, c), np.float64)
    d2_64 = ((x64 * x64).sum(1)[:, None] + (c64 * c64).sum(1)[None, :]
             - 2.0 * exact)
    near = d2_64.argmin(1)
    rows = np.arange(len(near))
    rel = np.abs(d2[rows, near] - d2_64[rows, near]) / d2_64[rows, near]
    print(f"precision oracle=ref.pairwise_dist2 "
          f"dot_precision={ref.DOT_PRECISION} "
          f"max_rel_err_nearest_d2={float(rel.max())!r}", flush=True)


def phase_device(sz: Sizes, planted, x, seed, clock, devs, impl="auto"):
    """(a): gonzalez, mrg (Sim, 16 machines) and eim on the device array."""
    import jax

    from repro.core import eim, gonzalez, mrg

    n = x.shape[0]
    rng = np.random.default_rng(seed + 1)
    sub = np.sort(rng.choice(n, min(sz.subsample, n), replace=False))
    sub_rows = np.asarray(x[sub])
    fold_rows = min(sz.ref_chunk * 8, n)

    def verify(name, centers, r2, k):
        check_clusters(name, centers, planted, k, sz)
        check_radius(name, r2, ref_radius2(x, centers, fold_rows),
                     sub_rows, centers)

    out = {}
    for im, chunk in ((impl, None), ("ref", sz.ref_chunk)):
        g = run_phase(f"a.gonzalez.{im}",
                      lambda: gonzalez(x, sz.k, impl=im), clock, devs)
        verify(f"a.gonzalez.{im}", g.centers, g.radius2, sz.k)
        m = run_phase(f"a.mrg.{im}",
                      lambda: mrg(x, sz.k, m=sz.machines, impl=im,
                                  chunk=chunk), clock, devs)
        verify(f"a.mrg.{im}", m.centers, m.radius2, sz.k)
        check_ratio(f"a.mrg_vs_gonzalez.{im}", m.radius2, g.radius2, 4.0)
        out[im] = (g, m)
    print(f"a.gonzalez same_centers_auto_ref="
          f"{same_centers(out[impl][0].centers, out['ref'][0].centers)}")
    print(f"a.mrg same_centers_auto_ref="
          f"{same_centers(out[impl][1].centers, out['ref'][1].centers)}")
    del out

    k_e = eim_k_for(n, sz.k)
    print(f"a.eim k={k_e} (largest k <= {sz.k} at which the sampling loop "
          f"starts at n={n})", flush=True)
    ge = run_phase(f"a.gonzalez_k{k_e}.{impl}",
                   lambda: gonzalez(x, k_e, impl=impl), clock, devs)
    verify(f"a.gonzalez_k{k_e}.{impl}", ge.centers, ge.radius2, k_e)
    res = {}
    for im, chunk in ((impl, None), ("ref", sz.ref_chunk)):
        e = run_phase(f"a.eim.{im}",
                      lambda: eim(x, k_e, jax.random.PRNGKey(seed),
                                  impl=im, chunk=chunk), clock, devs)
        iters = int(e.sample.iters)
        print(f"a.eim.{im} iterations={iters} "
              f"sample_rows={int(np.asarray(e.sample.sample_mask).sum())} "
              f"overflow={int(e.sample.overflow)}", flush=True)
        if iters < 1:
            fail(f"a.eim.{im}: the sampling loop did not run")
        verify(f"a.eim.{im}", e.centers, e.radius2, k_e)
        check_ratio(f"a.eim_vs_gonzalez.{im}", e.radius2, ge.radius2, 10.0)
        res[im] = e
    print(f"a.eim same_centers_auto_ref="
          f"{same_centers(res[impl].centers, res['ref'].centers)}")


def phase_stream(sz: Sizes, planted, seed, clock, devs, impl="auto"):
    """(b): streamed mrg and eim through HostStreamExecutor over a seeded
    SyntheticSource, in ``stream_shards`` super-shards."""
    import jax

    from repro.core import HostStreamExecutor, eim, gonzalez, mrg
    from repro.kernels import engine

    n = sz.n_stream
    src = host_source(planted, n, seed + 2, sz.noise)
    rows = -(-n // sz.stream_shards)
    budget = engine.DEFAULT_PREFETCH + 1
    budget = budget * 4 * rows * (sz.d + 1)    # the ring residency model
    ex = HostStreamExecutor(memory_budget=budget)
    if -(-n // ex.rows_for(src)) < 8:
        fail(f"memory_budget={budget} yields fewer than 8 super-shards")
    print(f"b.source n={n} memory_budget={budget} "
          f"super_shard_rows={ex.rows_for(src)} "
          f"super_shards={-(-n // ex.rows_for(src))}", flush=True)
    # The same rows on the device (3.2 GB): the ref path's radius
    # recomputation and the GON radii of the ratio checks run on them.
    xb = run_phase("b.upload", lambda: src.materialize(), clock, devs)
    rng = np.random.default_rng(seed + 3)
    sub = np.sort(rng.choice(n, min(sz.subsample, n), replace=False))
    sub_rows = np.asarray(xb[sub])
    fold_rows = min(sz.ref_chunk * 8, n)

    def verify(name, centers, r2, k):
        check_clusters(name, centers, planted, k, sz)
        check_radius(name, r2, ref_radius2(xb, centers, fold_rows),
                     sub_rows, centers)

    g = gonzalez(xb, sz.k, impl=impl)
    k_e = eim_k_for(n, sz.k)
    print(f"b.eim k={k_e} (largest k <= {sz.k} at which the sampling loop "
          f"starts at n={n})", flush=True)
    ge = gonzalez(xb, k_e, impl=impl)
    jax.block_until_ready((g.radius2, ge.radius2))

    res = {}
    for im, chunk in ((impl, None), ("ref", sz.ref_chunk)):
        m = run_phase(f"b.mrg.{im}",
                      lambda: mrg(src, sz.k, executor=ex, impl=im,
                                  chunk=chunk), clock, devs)
        verify(f"b.mrg.{im}", m.centers, m.radius2, sz.k)
        check_ratio(f"b.mrg_vs_gonzalez.{im}", m.radius2, g.radius2, 4.0)
        e = run_phase(f"b.eim.{im}",
                      lambda: eim(src, k_e, jax.random.PRNGKey(seed + 4),
                                  executor=ex, impl=im, chunk=chunk),
                      clock, devs)
        iters = int(e.sample.iters)
        print(f"b.eim.{im} iterations={iters} "
              f"sample_rows={int(np.asarray(e.sample.sample_mask).sum())}",
              flush=True)
        if iters < 1:
            fail(f"b.eim.{im}: the sampling loop did not run")
        verify(f"b.eim.{im}", e.centers, e.radius2, k_e)
        check_ratio(f"b.eim_vs_gonzalez.{im}", e.radius2, ge.radius2, 10.0)
        res[im] = (m, e)
    print(f"b.mrg same_centers_auto_ref="
          f"{same_centers(res[impl][0].centers, res['ref'][0].centers)}")
    print(f"b.eim same_centers_auto_ref="
          f"{same_centers(res[impl][1].centers, res['ref'][1].centers)}")


def phase_serve(sz: Sizes, planted, seed, clock, devs, impl="auto"):
    """(c): KCenterService — ingest, drain, then mixed-size queries from
    several client threads, each answer checked bitwise against
    ``ops.assign_nearest`` of its own query rows on its epoch's snapshot
    (the service's contract)."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.serve import KCenterService

    rng = np.random.default_rng(seed + 5)
    sizes = np.minimum(
        np.exp(rng.uniform(0, math.log(sz.max_query + 1), sz.queries)
               ).astype(int), sz.max_query)
    sizes = np.maximum(sizes, 1)
    base = sz.n + sz.serve_rows
    ingest = np.asarray(device_points(planted, sz.n, sz.serve_rows,
                                      seed, sz.noise))
    qrows = np.asarray(device_points(planted, base, int(sizes.sum()),
                                     seed, sz.noise))
    offs = np.concatenate([[0], np.cumsum(sizes)])
    answers = [None] * sz.queries

    svc = KCenterService(k=sz.k, d=sz.d, impl=impl, snapshot_history=True)
    try:
        def ingest_all():
            per = sz.serve_rows // sz.serve_batches
            for b in range(sz.serve_batches):
                svc.submit_points(ingest[b * per:(b + 1) * per])
            svc.drain()
            return jnp.zeros(())

        run_phase("c.ingest", ingest_all, clock, devs)
        epoch, centers, r = svc.snapshot()
        print(f"c.sketch epoch={epoch} centers={len(centers)} r={r!r}",
              flush=True)
        check_clusters("c.sketch", centers, planted, len(centers), sz)

        def client(t):
            for i in range(t, sz.queries, sz.clients):
                answers[i] = svc.assign(qrows[offs[i]:offs[i + 1]])

        def serve_all():
            threads = [threading.Thread(target=client, args=(t,))
                       for t in range(sz.clients)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            return jnp.zeros(())

        run_phase("c.queries", serve_all, clock, devs)
        stats = svc.stats
    finally:
        svc.close()
    print(f"c.serve queries={sz.queries} rows={int(sizes.sum())} "
          f"batches={stats['batches']} epochs={stats['epochs']}", flush=True)
    if any(a is None for a in answers):
        fail("c: a query went unanswered")
    mismatched = 0
    for i, a in enumerate(answers):
        ri, rd = ops.assign_nearest(jnp.asarray(qrows[offs[i]:offs[i + 1]]),
                                    jnp.asarray(svc.snapshot_at(a.epoch)),
                                    impl=impl)
        if not (np.array_equal(a.idx, np.asarray(ri))
                and np.array_equal(a.d2, np.asarray(rd))):
            mismatched += 1
    print(f"check=c.answers queries={sz.queries} "
          f"epochs={sorted({a.epoch for a in answers})} "
          f"not_bitwise={mismatched}", flush=True)
    if mismatched:
        fail(f"c: {mismatched} served answers differ from "
             "ops.assign_nearest on their epoch's snapshot")


def run_one_chip(sz: Sizes, seed: int, clock, devs):
    import jax

    planted = planted_centers(sz, seed)
    x = run_phase("data", lambda: device_points(planted, 0, sz.n, seed,
                                                sz.noise), clock, devs)
    phase_precision(sz, planted, x, clock, devs)
    phase_device(sz, planted, x, seed, clock, devs)
    x.delete()
    del x
    phase_stream(sz, planted, seed, clock, devs)
    phase_serve(sz, planted, seed, clock, devs)
    jax.block_until_ready(jax.numpy.zeros(()))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def bytes_in_use(device) -> int:
    return int((device.memory_stats() or {}).get("bytes_in_use", -1))


def check_quarters(x, devs, rows_per: int):
    """Each device holds its own quarter of the rows, and its memory
    shows it."""
    shards = sorted(x.addressable_shards, key=lambda s: s.index[0].start or 0)
    seen = set()
    for i, s in enumerate(shards):
        start = s.index[0].start or 0
        stop = s.index[0].stop
        used = bytes_in_use(s.device)
        print(f"check=mesh.shard device={s.device.id} rows=[{start},{stop}) "
              f"shape={tuple(s.data.shape)} bytes_in_use={used}", flush=True)
        if (start, stop) != (i * rows_per, (i + 1) * rows_per):
            fail(f"shard {i} holds rows [{start}, {stop})")
        if used < rows_per * x.shape[1] * 4:
            fail(f"device {s.device.id} holds {used} bytes, less than its "
                 "quarter")
        seen.add(s.device.id)
    if seen != {d.id for d in devs}:
        fail(f"the quarters sit on devices {sorted(seen)}, not on "
             f"{sorted(d.id for d in devs)}")


def run_four_chips(sz: Sizes, seed: int, clock, devs):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import HostStreamExecutor, MeshExecutor, eim, gonzalez
    from repro.core import mrg
    from repro.data import ArraySource, HostSource, shard_source
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("data",), devices=devs)
    per = sz.n
    n = 4 * per
    planted = planted_centers(sz, seed)
    x = run_phase("mesh.data", lambda: device_points(
        planted, 0, n, seed, sz.noise,
        sharding=NamedSharding(mesh, P("data"))), clock, devs)
    check_quarters(x, devs, per)

    # The fused device program: per-shard GON, all_gather, GON, radius.
    ex = MeshExecutor(mesh)
    cm, r2m, _ = run_phase("mesh.mrg_fused",
                           lambda: ex.mrg(ArraySource(x), sz.k), clock, devs)
    check_quarters(x, devs, per)
    check_clusters("mesh.mrg_fused", cm, planted, sz.k, sz)

    # ...and its one-device twin: each quarter's GON on device 0 in turn,
    # the union's GON, and the radius folded quarter by quarter.
    dev0 = devs[0]

    def one_device():
        parts = []
        # One quarter at a time on device 0 (its own plus one more fits):
        # wait for each before the next is copied in.
        for s in sorted(x.addressable_shards,
                        key=lambda s: s.index[0].start or 0):
            q = jax.device_put(s.data, dev0)
            parts.append(jax.block_until_ready(gonzalez(q, sz.k).centers))
            del q
        final = gonzalez(jnp.concatenate(parts), sz.k).centers
        best = jnp.float32(0.0)
        for s in x.addressable_shards:
            q = jax.device_put(s.data, dev0)
            best = jax.block_until_ready(jnp.maximum(
                best, jnp.max(ops.assign_nearest(q, final)[1])))
            del q
        return final, best

    c1, r21 = run_phase("one.mrg_fused_twin", one_device, clock, devs)
    rng = np.random.default_rng(seed + 1)
    sub_rows = np.asarray(x[np.sort(rng.choice(n, sz.subsample,
                                               replace=False))])
    check_twins("mesh.mrg_fused", cm, r2m, c1, r21, planted, sub_rows)

    # Streamed paths over shard_source(src, mesh): src is a host copy of
    # 4 x n_stream rows, four times the one-chip streamed size.
    # Copy shard by shard: slicing the global array would all-gather its
    # 25.8 GB onto every device.
    def to_host():
        need, parts = 4 * sz.n_stream, []
        for s in sorted(x.addressable_shards,
                        key=lambda s: s.index[0].start or 0):
            if need <= 0:
                break
            parts.append(np.asarray(s.data)[:need])
            need -= parts[-1].shape[0]
        return np.concatenate(parts)

    host = run_phase("mesh.to_host", to_host, clock, devs)
    x.delete()
    del x
    src = HostSource(host)
    rows = sz.n_stream // 8
    mex = MeshExecutor(mesh, block_rows=rows)
    hex_ = HostStreamExecutor(block_rows=rows)
    sharded = shard_source(src, mesh)
    k_m = sz.mrg_k_mesh
    mm = run_phase("mesh.mrg_stream", lambda: mrg(sharded, k_m,
                                                  executor=mex), clock, devs)
    check_clusters("mesh.mrg_stream", mm.centers, planted, k_m, sz)
    m1 = run_phase("one.mrg_stream", lambda: mrg(src, k_m, executor=hex_),
                   clock, devs)
    check_twins("mesh.mrg_stream", mm.centers, mm.radius2, m1.centers,
                m1.radius2, planted, sub_rows)

    k_e = sz.eim_k_mesh
    key = jax.random.PRNGKey(seed + 6)
    em = run_phase("mesh.eim_stream", lambda: eim(sharded, k_e, key,
                                                  executor=mex), clock, devs)
    e1 = run_phase("one.eim_stream", lambda: eim(src, k_e, key,
                                                 executor=hex_), clock, devs)
    print(f"mesh.eim k={k_e} iterations={int(em.sample.iters)} "
          f"one_device_iterations={int(e1.sample.iters)}", flush=True)
    if int(em.sample.iters) < 1:
        fail("mesh.eim: the sampling loop did not run")
    check_clusters("mesh.eim_stream", em.centers, planted, k_e, sz)
    same_sample = np.array_equal(np.asarray(em.sample.sample_mask),
                                 np.asarray(e1.sample.sample_mask))
    print(f"check=mesh.eim_stream sample_bitwise_one_device={same_sample}",
          flush=True)
    check_twins("mesh.eim_stream", em.centers, em.radius2, e1.centers,
                e1.radius2, planted, sub_rows)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(c) on one chip; 4: the sharded "
                         "MeshExecutor paths and their one-device twins")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax

    from repro.kernels import engine

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU chip: JAX sees only "
              f"{devs[0].platform} devices ({len(devs)}). This smoke runs "
              "on a TPU and nowhere else.", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX sees {len(devs)}", file=sys.stderr)
        return 2
    resolved = engine._resolve("auto")
    print(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)} auto_resolves_to={resolved} "
          f"compile_cache={cache}", flush=True)
    if resolved != (True, False):
        fail(f'impl="auto" resolves to {resolved}, not the native kernels')

    clock = CompileClock()
    sz = Sizes()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(sz, args.seed, clock, devs[:4])
    else:
        run_one_chip(sz, args.seed, clock, devs[:1])
    print(f"total_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.total:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
