"""Distributed behaviors on simulated multi-device hosts.

Each test runs in a subprocess with ``--xla_force_host_platform_device_count``
so the main pytest process keeps its single-device view (the dry-run is
the only other place placeholder devices are created).
"""
import json
import subprocess
import sys
import textwrap


BASE = dict(PYTHONPATH="src")


def _run(body: str, devices: int = 8, timeout: int = 240) -> str:
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import jax, json
        import jax.numpy as jnp
        import numpy as np
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
    """)
    import os
    env = dict(os.environ)
    env.update(BASE)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_mrg_distributed_matches_quality():
    out = _run("""
        from repro.core import mrg_distributed, gonzalez
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        pts = jnp.asarray(np.random.default_rng(0)
                          .normal(size=(800, 4)).astype(np.float32))
        c, r2 = mrg_distributed(pts, 6, mesh, shard_axes=("data",))
        g = gonzalez(pts, 6)
        ratio = float(jnp.sqrt(r2)) / float(jnp.sqrt(g.radius2))
        print(json.dumps({"ratio": ratio}))
    """)
    ratio = json.loads(out.strip().splitlines()[-1])["ratio"]
    assert ratio <= 2.0 + 1e-6  # MRG<=4·OPT, GON>=OPT ⇒ ratio<=4; usually ~1


def test_mrg_hierarchical_multi_axis():
    out = _run("""
        from repro.core import mrg_distributed, gonzalez
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        pts = jnp.asarray(np.random.default_rng(1)
                          .normal(size=(960, 3)).astype(np.float32))
        c, r2 = mrg_distributed(pts, 5, mesh,
                                shard_axes=("pod", "data", "model"),
                                hierarchical=True)
        g = gonzalez(pts, 5)
        print(json.dumps({"ratio": float(jnp.sqrt(r2) /
                                         jnp.sqrt(g.radius2))}))
    """)
    ratio = json.loads(out.strip().splitlines()[-1])["ratio"]
    # hierarchical gather adds +2 per level (paper Lemma 3)
    assert ratio <= 8.0


def test_mesh_executor_hierarchical_vs_flat():
    """The MeshExecutor form of the hierarchical Lemma-3 path: per-axis
    gathers with an intermediate GON per level, vs the flat single gather,
    on the same 8-device mesh — wrapper and executor must agree exactly,
    rounds accounting must reflect the gather tree depth, and both centers
    sets must satisfy the covering bound."""
    out = _run("""
        from repro.core import MeshExecutor, gonzalez, mrg, mrg_distributed
        from repro.data import ArraySource
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        axes = ("pod", "data", "model")
        pts = np.random.default_rng(2).normal(size=(960, 3)).astype(np.float32)
        pj = jnp.asarray(pts)
        res_h = mrg(ArraySource(pts), 5,
                    executor=MeshExecutor(mesh, shard_axes=axes,
                                          hierarchical=True))
        res_f = mrg(ArraySource(pts), 5,
                    executor=MeshExecutor(mesh, shard_axes=axes))
        cw, r2w = mrg_distributed(pj, 5, mesh, shard_axes=axes,
                                  hierarchical=True)
        g = gonzalez(pj, 5)
        print(json.dumps({
            "rounds_h": res_h.rounds, "rounds_f": res_f.rounds,
            "wrapper_equal": bool((np.asarray(res_h.centers)
                                   == np.asarray(cw)).all()
                                  and float(res_h.radius2) == float(r2w)),
            "ratio_h": float(jnp.sqrt(res_h.radius2) / jnp.sqrt(g.radius2)),
            "ratio_f": float(jnp.sqrt(res_f.radius2) / jnp.sqrt(g.radius2)),
        }))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    # one GON level per gathered axis (+ round 1) vs the classic 2 rounds
    assert r["rounds_h"] == 4 and r["rounds_f"] == 2
    assert r["wrapper_equal"]  # mrg_distributed is a thin MeshExecutor shim
    # Lemma 3: +2 approx per extra level (4 levels -> <=8·OPT); flat is
    # the classic 2-round 4-approx. GON >= OPT makes these checkable.
    assert r["ratio_h"] <= 8.0 and r["ratio_f"] <= 4.0


def test_sharded_source_mesh_mrg_bitwise_parity_grid():
    """The tentpole contract: ``mrg`` over a ``ShardedSource`` on the
    streamed ``MeshExecutor`` is *bitwise identical* to the
    HostStreamExecutor run for every shard count × block_rows cell (same
    machine blocking ⇒ same centers, radius, rounds), and — with one
    block per shard — to ``mrg_sim``'s m-machine blocking too."""
    out = _run("""
        from repro import compat
        from repro.core import HostStreamExecutor, MeshExecutor, mrg, mrg_sim
        from repro.data import HostSource, shard_source
        n, d, k = 4096, 3, 5
        x = np.random.default_rng(2).normal(size=(n, d)).astype(np.float32)
        cells = []
        for S in (1, 2, 4, 8):
            mesh = compat.make_mesh(np.array(jax.devices()[:S]), ("data",))
            per = n // S
            for r in (512, per):
                me = MeshExecutor(mesh, block_rows=r)
                rm = mrg(shard_source(HostSource(x), S), k, executor=me,
                         impl="ref")
                rh = mrg(HostSource(x), k,
                         executor=HostStreamExecutor(block_rows=r),
                         impl="ref")
                cells.append({
                    "S": S, "rows": r,
                    "host_exact": bool(
                        (np.asarray(rm.centers) == np.asarray(rh.centers))
                        .all() and float(rm.radius2) == float(rh.radius2)
                        and rm.rounds == rh.rounds)})
            rs = mrg_sim(jnp.asarray(x), k, m=S, impl="ref")
            rm = mrg(shard_source(HostSource(x), S), k,
                     executor=MeshExecutor(mesh, block_rows=per), impl="ref")
            cells.append({
                "S": S, "rows": "per-vs-sim",
                "host_exact": bool(
                    (np.asarray(rm.centers) == np.asarray(rs.centers)).all()
                    and float(rm.radius2) == float(rs.radius2))})
        print(json.dumps(cells))
    """)
    cells = json.loads(out.strip().splitlines()[-1])
    assert len(cells) == 12
    bad = [c for c in cells if not c["host_exact"]]
    assert not bad, f"sharded mesh MRG drifted in cells: {bad}"


def test_sharded_source_mesh_eim_bitwise_parity_and_budget():
    """Streamed EIM over per-host shards on a 4-way mesh: bitwise the
    device-path sample and the HostStream result for the same key; and the
    no-full-n invariant — under a per-shard ``memory_budget``, a
    source-read spy sees no read larger than the budget-derived
    super-shard and no ``materialize()`` call. Also covers multi-axis
    sharding (P over ("pod", "data"))."""
    out = _run("""
        from repro import compat
        from repro.core import (HostStreamExecutor, MeshExecutor, eim,
                                eim_sample, mrg)
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

        n, d, k = 16384, 3, 4
        x = np.random.default_rng(3).normal(size=(n, d)).astype(np.float32)
        key = jax.random.PRNGKey(7)
        mesh = compat.make_mesh(np.array(jax.devices()[:4]), ("data",))
        shards = [SpyShard(x[i * 4096:(i + 1) * 4096]) for i in range(4)]
        sh = ShardedSource.from_per_host_shards(shards)
        budget = 96 * 1024
        me = MeshExecutor(mesh, memory_budget=budget)
        rows = me.rows_for(sh)
        s_dev = eim_sample(jnp.asarray(x), k, key, impl="ref")
        e_mesh = eim(sh, k, key, impl="ref", executor=me)
        e_host = eim(HostSource(x), k, key, impl="ref",
                     executor=HostStreamExecutor(memory_budget=budget))
        # multi-axis: (2, 2) mesh sharded over both axes == 4 machines
        mesh22 = compat.make_mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                                  ("pod", "data"))
        me22 = MeshExecutor(mesh22, shard_axes=("pod", "data"),
                            block_rows=512)
        rm = mrg(shard_source(HostSource(x), me22), k, executor=me22,
                 impl="ref")
        rh = mrg(HostSource(x), k,
                 executor=HostStreamExecutor(block_rows=512), impl="ref")
        print(json.dumps({
            "budget_model_ok": rows * 4 * (d + 1) * (1 + me.prefetch)
                               <= budget,
            "rows": rows,
            "max_reads": [s.max_read for s in shards],
            "materialized": any(s.materialized for s in shards),
            "sample_exact": bool(
                np.array_equal(np.asarray(s_dev.sample_mask),
                               np.asarray(e_mesh.sample.sample_mask))
                and int(s_dev.iters) == int(e_mesh.sample.iters)),
            "eim_exact": bool(
                (np.asarray(e_mesh.centers)
                 == np.asarray(e_host.centers)).all()
                and float(e_mesh.radius2) == float(e_host.radius2)),
            "multiaxis_exact": bool(
                (np.asarray(rm.centers) == np.asarray(rh.centers)).all()
                and float(rm.radius2) == float(rh.radius2)),
        }))
    """, devices=4)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["budget_model_ok"], r
    assert all(m <= r["rows"] for m in r["max_reads"]), r
    assert not r["materialized"], "a shard was materialized on the mesh path"
    assert r["sample_exact"], "mesh EIM sample drifted from the device path"
    assert r["eim_exact"], "mesh eim() drifted from the HostStream path"
    assert r["multiaxis_exact"], "multi-axis sharded MRG drifted"


def test_sharded_train_step_runs_and_matches_single_device_loss():
    out = _run("""
        from repro.configs import get_config
        from repro.data import model_batch
        from repro.launch.mesh import make_mesh
        from repro.optim import adamw, make_schedule
        from repro.sharding import (batch_pspecs, shardings, state_pspecs,
                                    use_mesh)
        from repro.train import init_train_state, make_train_step
        cfg = get_config("granite_3_2b", smoke=True)
        opt = adamw(make_schedule("constant", peak=1e-3))
        batch = {k: jnp.asarray(v) for k, v in
                 model_batch(cfg, 8, 16).items()}
        # single device
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        _, m1 = jax.jit(make_train_step(cfg, opt))(state, batch)
        # 4x2 mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        with use_mesh(mesh):
            state2 = init_train_state(jax.random.PRNGKey(0), cfg, opt)
            st_sh = shardings(state_pspecs(jax.eval_shape(lambda: state2),
                                           mesh), mesh)
            step = jax.jit(make_train_step(cfg, opt))
            _, m2 = step(state2, batch)
        print(json.dumps({"l1": float(m1["loss"]), "l2": float(m2["loss"])}))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert abs(r["l1"] - r["l2"]) < 1e-2, r


def test_elastic_checkpoint_restore_smaller_mesh(tmp_path):
    out = _run(f"""
        from repro.configs import get_config
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        from repro.launch.mesh import make_mesh
        from repro.optim import adamw, make_schedule
        from repro.sharding import use_mesh
        from repro.train import init_train_state, make_train_step
        from repro.data import model_batch
        cfg = get_config("qwen2_0_5b", smoke=True)
        opt = adamw(make_schedule("constant", peak=1e-3))
        mesh8 = make_mesh((4, 2), ("data", "model"))
        batch = {{k: jnp.asarray(v) for k, v in
                 model_batch(cfg, 8, 16).items()}}
        with use_mesh(mesh8):
            state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
            state, m = jax.jit(make_train_step(cfg, opt))(state, batch)
            save_checkpoint("{tmp_path}", 1, state)
        # restore on a smaller (2,2) mesh — degraded pod
        mesh4 = make_mesh((2, 2), ("data", "model"))
        with use_mesh(mesh4):
            template = jax.tree.map(np.asarray,
                                    init_train_state(jax.random.PRNGKey(0),
                                                     cfg, opt))
            step, host = restore_checkpoint("{tmp_path}", template)
            state2 = jax.tree.map(jnp.asarray, host)
            state2, m2 = jax.jit(make_train_step(cfg, opt))(state2, batch)
        print(json.dumps({{"step": step, "loss": float(m2["loss"])}}))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["step"] == 1 and r["loss"] > 0


def test_moe_shard_map_vs_local():
    out = _run("""
        from repro.configs import get_config
        from repro.models import init_params, forward
        from repro.launch.mesh import make_mesh
        from repro.sharding import use_mesh
        cfg = get_config("dbrx_132b", smoke=True)
        params = init_params(jax.random.PRNGKey(0), cfg)
        batch = {"tokens": jnp.arange(32).reshape(2, 16) % cfg.vocab_size}
        l1, _ = jax.jit(lambda p, b: forward(p, b, cfg))(params, batch)
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_mesh(mesh):
            l2, _ = jax.jit(lambda p, b: forward(p, b, cfg))(params, batch)
        err = float(jnp.max(jnp.abs(l1 - l2)))
        print(json.dumps({"err": err}))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["err"] < 1e-3, r
