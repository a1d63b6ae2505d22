"""Chunked-engine scaling: radius vs runtime vs memory budget as n grows.

Demonstrates the kernels/engine.py capacity model end-to-end:

  * un-chunked ``assign_nearest`` materializes an (n, m) f32 block —
    4·n·m bytes of working memory; at n = 10⁷, m = 256 that is ~10 GiB,
    far beyond a stated per-pass budget (and beyond small-device HBM);
  * the chunked path streams row-blocks under ``memory_budget`` bytes and
    completes at any n that fits *points* in memory, with the same result.

Each row reports the streamed working set (from the engine's model
``4·chunk·(m+d) + 4·m·d``) next to what the un-chunked block would have
needed, plus GON radius invariance at a smaller n as a correctness anchor.

The **out-of-core section** goes one level further (data/source.py +
core/executor.py): full MRG over a ``HostSource``/``MemmapSource`` at an n
whose entire (n, d) f32 array exceeds a stated device budget — enforced
with an assert — so the *points* are bounded by host RAM / disk, not HBM;
only ring-buffered super-shards under ``memory_budget`` plus the k·M
center union are ever device-resident. A
smaller-n row parity-checks centers/radius bitwise against the in-memory
``mrg_sim`` on the same blocking.

The **EIM section** (``eim_out_of_core_rows``) repeats the exercise for
the paper's §4 sampling algorithm: streamed EIM over a ``MemmapSource``
at an n past the same kind of asserted budget (its per-point relations
live on the host; the counter-based Round-1 sampler needs no data pass),
plus a bitwise device-vs-streamed sample parity anchor.

The **compacted-R section** (``eim_compaction_rows``) asserts the
shrinking-|R| iteration cost of the production path: per-iteration pass
row-counts (metered at ``run_filter_round``) must shrink monotonically
below n once ``compact_threshold`` engages, the view's gathers must stay
within the budget-derived super-shard, and the compacted sample must be
bitwise the fixed-shape streamed sample.

The **sharded section** (``sharded_out_of_core_rows``) closes the loop on
the paper's machine model: per-host shard sources feed a 4-shard
``MeshExecutor`` (subprocess with forced host devices) under an asserted
per-shard ``memory_budget`` — a source-read spy proves no host-side
full-n (or even full-shard) materialization on the path, and a
smaller-n anchor pins the sharded result bitwise against ``mrg_sim``.

Run: ``PYTHONPATH=src python -m benchmarks.chunked_scaling [--full]``
(``--full`` pushes n to 10⁷; default tops out at 10⁶ to stay friendly to
one CPU core). Also callable as ``run()`` yielding benchmarks/run.py-style
``(name, us_per_call, derived)`` rows.
"""
from __future__ import annotations

import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import HostStreamExecutor, eim, eim_sample, gonzalez, mrg, \
    mrg_sim
from repro.data import HostSource, MemmapSource
from repro.kernels import engine, ops

from .kernel_bench import _t

M = 256           # centers
D = 8             # embedding dim kept small so points fit at n=1e7
BUDGET = 64 * 2 ** 20   # 64 MiB per-pass working-set budget


def run(full: bool = False):
    """Yields (name, us_per_call, derived) CSV rows."""
    rng = np.random.default_rng(0)
    c = jnp.asarray(rng.normal(size=(M, D)).astype(np.float32))

    n_grid = [10_000, 100_000, 1_000_000]
    if full:
        n_grid.append(10_000_000)

    for n in n_grid:
        x = jnp.asarray(rng.normal(size=(n, D)).astype(np.float32))
        unchunked_bytes = 4 * n * M
        chunk = engine.resolve_chunk(n, M, D, memory_budget=BUDGET)
        streamed_bytes = 4 * chunk * (M + D) + 4 * M * D
        over = unchunked_bytes > BUDGET

        t_c = _t(lambda a: ops.assign_nearest(a, c, impl="ref",
                                              memory_budget=BUDGET), x)
        yield (f"assign_chunked_n{n}", t_c * 1e6,
               f"ws={streamed_bytes / 2**20:.1f}MiB"
               f"(unchunked={unchunked_bytes / 2**20:.0f}MiB"
               f"{'>' if over else '<='}budget={BUDGET / 2**20:.0f}MiB)")

        # Un-chunked comparison only where its block respects the budget —
        # past that point the chunked engine is the only path that honors
        # the capacity model (the paper's c < n regime).
        if not over:
            t_u = _t(lambda a: ops.assign_nearest(a, c, impl="ref"), x)
            yield (f"assign_unchunked_n{n}", t_u * 1e6,
                   f"overhead={t_c / t_u:.2f}x")
        del x

    # Radius-vs-runtime anchor: GON radius is chunk-invariant while the
    # working set shrinks by orders of magnitude.
    n = 200_000 if full else 50_000
    x = jnp.asarray(rng.normal(size=(n, D)).astype(np.float32))
    k = 16
    r0 = float(jnp.sqrt(gonzalez(x, k, impl="ref").radius2))
    for chunk in (None, 65536, 4096):
        t = _t(lambda a: gonzalez(a, k, impl="ref", chunk=chunk), x)
        r = float(jnp.sqrt(gonzalez(x, k, impl="ref", chunk=chunk).radius2))
        tag = "none" if chunk is None else str(chunk)
        yield (f"gon_n{n}_k{k}_chunk{tag}", t * 1e6,
               f"radius={r:.5g}(drift={abs(r - r0):.1e})")
    del x

    yield from out_of_core_rows(full)
    yield from sharded_out_of_core_rows(full)


def out_of_core_rows(full: bool = False):
    """MRG past the device budget: the input lives on host RAM / disk.

    The stated HBM budget covers everything device-resident at once — the
    whole (n, d) array is *asserted* not to fit it, so the legacy
    device-array path is structurally impossible at this n; the
    ``HostStreamExecutor`` completes within a quarter of the budget for
    its DMA'd super-shards (two coexist under double buffering — the
    engine's residency model counts both) plus the k·M center union.
    """
    k = 16
    device_budget = (256 if full else 32) * 2 ** 20
    n = 12_000_000 if full else 1_500_000
    full_bytes = 4 * n * D
    assert full_bytes > device_budget, (
        f"out-of-core demo misconfigured: (n={n}, d={D}) f32 is "
        f"{full_bytes / 2**20:.0f}MiB, within the {device_budget / 2**20:.0f}"
        f"MiB device budget")
    ex = HostStreamExecutor(memory_budget=device_budget // 4)
    rows = engine.resolve_block_rows(n, D, memory_budget=device_budget // 4)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(n, D)).astype(np.float32)

    def timed(fn):
        t0 = time.time()
        res = fn()
        jax.block_until_ready(res.centers)
        return time.time() - t0, res

    t_host, r_host = timed(lambda: mrg(HostSource(x), k, executor=ex))
    yield (f"oocore_mrg_host_n{n}", t_host * 1e6,
           f"points={full_bytes / 2**20:.0f}MiB>budget="
           f"{device_budget / 2**20:.0f}MiB;shard={rows}rows="
           f"{4 * rows * D / 2**20:.1f}MiB;radius={float(jnp.sqrt(r_host.radius2)):.4g}")

    tmp = tempfile.mkdtemp(prefix="oocore_shards_")
    try:
        ms = MemmapSource.save_shards(x, tmp, rows_per_shard=max(rows // 2, 1))
        del x  # host array gone: the memmap run reads only from disk
        t_mm, r_mm = timed(lambda: mrg(ms, k, executor=ex))
        drift = abs(float(jnp.sqrt(r_mm.radius2)) -
                    float(jnp.sqrt(r_host.radius2)))
        yield (f"oocore_mrg_memmap_n{n}", t_mm * 1e6,
               f"shards={ms.num_shards};radius={float(jnp.sqrt(r_mm.radius2)):.4g}"
               f"(host_drift={drift:.1e})")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # Correctness anchor at a smaller n: identical blocking => centers and
    # radius must match the in-memory mrg_sim bitwise.
    n_s, rows_s = 65_536, 8_192
    xs = rng.normal(size=(n_s, D)).astype(np.float32)
    r_mem = mrg_sim(jnp.asarray(xs), k, m=n_s // rows_s, impl="ref")
    r_str = mrg(HostSource(xs), k,
                executor=HostStreamExecutor(block_rows=rows_s), impl="ref")
    exact = (np.asarray(r_mem.centers) == np.asarray(r_str.centers)).all() \
        and float(r_mem.radius2) == float(r_str.radius2)
    yield (f"oocore_parity_n{n_s}", 0,
           f"bitwise={'exact' if exact else 'DRIFT'};"
           f"radius={float(jnp.sqrt(r_str.radius2)):.5g}")

    yield from eim_out_of_core_rows(full, rng)


def eim_out_of_core_rows(full: bool, rng: np.random.Generator):
    """EIM past the device budget (paper §4 at the out-of-core regime).

    The φ-sampler's per-point relations (r/s masks, d(x,S)) are host-
    resident; every pass is a fold over the source's budget-bounded
    super-shards, so the *asserted* condition is the same as MRG's: the
    whole (n, d) f32 array exceeds the stated device budget — the
    materializing path is structurally impossible at this n — while the
    streamed EIM completes within a quarter of the budget for its ring-
    buffered shards. A smaller-n anchor checks the streamed sample is
    *bitwise identical* to the jitted device path for the same key (the
    counter-based sampler + value-fold rounds make it blocking-invariant).
    """
    k = 4
    device_budget = (64 if full else 4) * 2 ** 20
    n = 2_000_000 if full else 150_000
    full_bytes = 4 * n * D
    assert full_bytes > device_budget, (
        f"out-of-core EIM demo misconfigured: (n={n}, d={D}) f32 is "
        f"{full_bytes / 2**20:.0f}MiB, within the "
        f"{device_budget / 2**20:.0f}MiB device budget")
    ex = HostStreamExecutor(memory_budget=device_budget // 4)
    key = jax.random.PRNGKey(0)
    x = rng.normal(size=(n, D)).astype(np.float32)

    tmp = tempfile.mkdtemp(prefix="oocore_eim_shards_")
    try:
        rows = ex.rows_for(HostSource(x))
        ms = MemmapSource.save_shards(x, tmp, rows_per_shard=max(rows // 2, 1))
        del x  # the EIM run reads only from disk
        t0 = time.time()
        res = eim(ms, k, key, impl="ref", executor=ex)
        jax.block_until_ready(res.centers)
        t = time.time() - t0
        yield (f"oocore_eim_memmap_n{n}", t * 1e6,
               f"points={full_bytes / 2**20:.0f}MiB>budget="
               f"{device_budget / 2**20:.0f}MiB;shard={rows}rows;"
               f"iters={int(res.sample.iters)};"
               f"|C|={int(np.asarray(res.sample.sample_mask).sum())};"
               f"radius={float(jnp.sqrt(res.radius2)):.4g}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # Parity anchor: streamed sample == device sample bitwise, same key.
    n_s = 65_536
    xs = rng.normal(size=(n_s, D)).astype(np.float32)
    s_dev = eim_sample(jnp.asarray(xs), k, key, impl="ref")
    s_str = eim_sample(HostSource(xs), k, key, impl="ref",
                       executor=HostStreamExecutor(block_rows=8_192))
    exact = (np.array_equal(np.asarray(s_dev.sample_mask),
                            np.asarray(s_str.sample_mask))
             and np.array_equal(np.asarray(s_dev.s_mask),
                                np.asarray(s_str.s_mask))
             and int(s_dev.iters) == int(s_str.iters))
    assert exact, "streamed EIM sample drifted from the device path"
    yield (f"oocore_eim_parity_n{n_s}", 0,
           f"bitwise={'exact' if exact else 'DRIFT'};"
           f"iters={int(s_str.iters)};"
           f"sample={int(np.asarray(s_str.sample_mask).sum())}")

    yield from eim_compaction_rows(full, rng)


class _MeteredExecutor(HostStreamExecutor):
    """Records the view size each filter round streams (= the rows the
    per-iteration pass touches)."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.pass_rows = []

    def run_filter_round(self, source, *a, **kw):
        self.pass_rows.append(source.n)
        return super().run_filter_round(source, *a, **kw)


class _MeteredSource(HostSource):
    """HostSource recording the largest single block/gather it served."""

    def __init__(self, x):
        super().__init__(x)
        self.max_block = 0

    def host_blocks(self, block_rows):
        for blk in super().host_blocks(block_rows):
            self.max_block = max(self.max_block, blk.shape[0])
            yield blk

    def take(self, indices):
        out = super().take(indices)
        self.max_block = max(self.max_block, out.shape[0])
        return out


def eim_compaction_rows(full: bool, rng: np.random.Generator):
    """Compacted-R streamed EIM (paper §4's shrinking round cost).

    The fixed-shape streamed loop pays O(n·|S_new|) every iteration; with
    ``compact_threshold`` the fold re-points at an ``IndexedSource`` of
    the survivors, so iteration l touches |R_l| rows — *asserted* here by
    metering the view size of every filter round (it must shrink
    monotonically below n), while a metered source asserts the out-of-core
    budget still holds during the view's gathers (no block or take ever
    exceeds the budget-derived super-shard). Both runs are the production
    path and must return bitwise-identical samples.
    """
    k, eps, phi = 4, 0.05, 5.0
    n = 400_000 if full else 120_000
    device_budget = (32 if full else 8) * 2 ** 20
    ex_budget = device_budget // 4
    x = rng.normal(size=(n, D)).astype(np.float32)
    key = jax.random.PRNGKey(3)

    def timed_run(compact_threshold):
        src = _MeteredSource(x)
        ex = _MeteredExecutor(memory_budget=ex_budget)
        t0 = time.time()
        s = eim_sample(src, k, key, eps=eps, phi=phi, impl="ref",
                       executor=ex, compact_threshold=compact_threshold)
        return time.time() - t0, s, ex, src

    t_base, s_base, ex_base, _ = timed_run(0.0)
    t_comp, s_comp, ex_comp, src_comp = timed_run(1.0)

    rows = _MeteredExecutor(memory_budget=ex_budget).rows_for(HostSource(x))
    assert ex_base.pass_rows == [n] * int(s_base.iters), \
        "baseline pass must touch all n rows every iteration"
    passes = ex_comp.pass_rows
    assert passes[0] == n and passes[-1] < n and \
        all(a >= b for a, b in zip(passes, passes[1:])), \
        f"per-iteration pass row-count failed to shrink: {passes}"
    assert src_comp.max_block <= rows, \
        "a gathered block exceeded the memory-budget super-shard"
    assert (np.array_equal(np.asarray(s_base.sample_mask),
                           np.asarray(s_comp.sample_mask))
            and int(s_base.iters) == int(s_comp.iters)), \
        "compacted sample drifted from the fixed-shape streamed path"
    yield (f"compactR_eim_baseline_n{n}", t_base * 1e6,
           f"iters={int(s_base.iters)};pass_rows={n}x{int(s_base.iters)}")
    yield (f"compactR_eim_n{n}", t_comp * 1e6,
           f"pass_rows={'/'.join(str(p) for p in passes)};"
           f"max_block={src_comp.max_block}<=shard={rows};"
           f"speedup={t_base / t_comp:.2f}x")


_SHARDED_PROG = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
import json, time
import jax
import jax.numpy as jnp
import numpy as np
from repro import compat
from repro.core import MeshExecutor, mrg, mrg_sim
from repro.data import HostSource, ShardedSource, shard_source


class SpyShard(HostSource):
    def __init__(self, x):
        super().__init__(x)
        self.max_read = 0
        self.materialized = False

    def host_blocks(self, block_rows):
        for blk in super().host_blocks(block_rows):
            self.max_read = max(self.max_read, blk.shape[0])
            yield blk

    def take(self, indices):
        out = super().take(indices)
        self.max_read = max(self.max_read, out.shape[0])
        return out

    def materialize(self):
        self.materialized = True
        return super().materialize()


S, D, k = {devices}, {D}, 16
n, device_budget = {n}, {budget}
full_bytes = 4 * n * D
assert full_bytes > device_budget, "sharded demo misconfigured"
shard_budget = device_budget // (2 * S)
mesh = compat.make_mesh(np.array(jax.devices()[:S]), ("data",))
rng = np.random.default_rng(11)
x = rng.normal(size=(n, D)).astype(np.float32)
per = -(-n // S)
shards = [SpyShard(x[i * per:(i + 1) * per]) for i in range(S)]
sh = ShardedSource.from_per_host_shards(shards)
ex = MeshExecutor(mesh, memory_budget=shard_budget)
rows = ex.rows_for(sh)
assert rows * 4 * (D + 1) * (1 + ex.prefetch) <= shard_budget
t0 = time.time()
res = mrg(sh, k, executor=ex, impl="ref")
jax.block_until_ready(res.centers)
t = time.time() - t0
assert all(s.max_read <= rows for s in shards), "spy: oversized shard read"
assert not any(s.materialized for s in shards), "spy: full-shard materialize"

# parity anchor: one block per shard == mrg_sim's m-machine blocking
n_s = 65536
xs = rng.normal(size=(n_s, D)).astype(np.float32)
r_sim = mrg_sim(jnp.asarray(xs), k, m=S, impl="ref")
r_sh = mrg(shard_source(HostSource(xs), S), k,
           executor=MeshExecutor(mesh, block_rows=n_s // S), impl="ref")
exact = (np.asarray(r_sim.centers) == np.asarray(r_sh.centers)).all() \\
    and float(r_sim.radius2) == float(r_sh.radius2)
print(json.dumps([
    {{"name": "sharded_mrg_mesh_n%d" % n, "us": t * 1e6,
      "derived": "shards=%d;points=%.0fMiB>budget=%.0fMiB;"
                 "per_shard=%.1fMiB;rows=%d;max_read=%d;radius=%.4g"
                 % (S, full_bytes / 2**20, device_budget / 2**20,
                    shard_budget / 2**20, rows,
                    max(s.max_read for s in shards),
                    float(jnp.sqrt(res.radius2)))}},
    {{"name": "sharded_parity_n%d" % n_s, "us": 0,
      "derived": "bitwise=%s;vs=mrg_sim_m%d"
                 % ("exact" if exact else "DRIFT", S)}},
]))
assert exact, "sharded mesh MRG drifted from mrg_sim"
"""


def sharded_out_of_core_rows(full: bool = False):
    """Sharded out-of-core MRG: no host ever holds n (paper §3's model).

    Runs in a subprocess with ``--xla_force_host_platform_device_count``
    (the main process keeps its single-device view, like
    tests/test_distributed.py): per-host ``SpyShard`` sources feed a
    4-shard ``MeshExecutor`` under an *asserted* per-shard
    ``memory_budget`` — the spy proves no shard ever served a read larger
    than the budget-derived super-shard and nothing materialized a full
    shard, while the whole (n, d) array is asserted not to fit the stated
    device budget. A smaller-n anchor pins the sharded path bitwise
    against ``mrg_sim``'s m-machine blocking.
    """
    import json
    import os
    import subprocess
    import sys

    import repro

    devices = 4
    n = 12_000_000 if full else 1_200_000
    budget = (256 if full else 32) * 2 ** 20
    prog = _SHARDED_PROG.format(devices=devices, D=D, n=n, budget=budget)
    env = dict(os.environ)
    # A CPU simulation of 4 devices by design: pinning the platform keeps
    # the child off an accelerator that this process may hold.
    env["JAX_PLATFORMS"] = "cpu"
    # repro is a namespace package (no __init__.py): locate it by __path__.
    src_dir = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env["PYTHONPATH"] = src_dir + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded out-of-core cell failed:\n{out.stderr[-3000:]}")
    for row in json.loads(out.stdout.strip().splitlines()[-1]):
        yield (row["name"], row["us"], row["derived"])


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="extend n to 10^7 (the paper-scale capacity demo)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, us, derived in run(full=args.full):
        print(f"{name},{us:.0f},{derived}", flush=True)


if __name__ == "__main__":
    main()
