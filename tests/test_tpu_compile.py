"""The main-path kernels compile for a TPU v5e, at real widths.

Each test compiles one kernel (through the engine entry point that
dispatches it, where one is traceable) for one chip of a described
``v5e:2x2`` topology — no chip is attached — at d=768 and m=1024, with the
row block the engine picks, and checks that the compiled program holds
the Mosaic kernel (``tpu_custom_call``). The TPU compiler refuses what
interpret mode accepts: block shapes off the (8, 128) tiling, 1-D blocks
off XLA's 1-D tiling, and kernels over their scoped VMEM.

The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU compiler. The persistent compilation cache is off
around the compiles: their entries could not be read back without a chip.
"""
import os
import signal

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import engine, fused_stream

N, D, M = 65536, 768, 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    prev_sigterm = signal.getsignal(signal.SIGTERM)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        # Loading the TPU compiler installs a crash reporter on SIGTERM
        # that writes a stack dump into this worker's stderr (the test
        # run's output) when the run is stopped; keep the prior action.
        signal.signal(signal.SIGTERM, prev_sigterm or signal.SIG_DFL)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def native(monkeypatch):
    """Steer the engine onto its TPU branch (the process backend is CPU)."""
    monkeypatch.setattr(engine, "_pallas_native", lambda: True)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _stream_bn(m: int = M) -> int:
    fused, interpret, bn = engine._stream_tiles("auto", None, N, m, D)
    assert fused and not interpret
    return bn


def test_gonzalez_step_compiles(one_chip, native):
    txt = _compiled_text(
        lambda x, c, md: engine.fused_min_argmax(x, c, md, impl="auto"),
        _spec(one_chip, (N, D)), _spec(one_chip, (D,)),
        _spec(one_chip, (N,)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("rank", [116, 1])
def test_filter_compiles(one_chip, native, rank):
    # rank=116 is EIM's Select pivot rank (φ·ln n at φ=8, n=2^21); rank=1
    # is the covering-radius fold.
    bn = _stream_bn()
    txt = _compiled_text(
        lambda x, c, ds, hm: fused_stream.fused_filter_blocks(
            x, c, ds, hm, rank=rank, bn=bn),
        _spec(one_chip, (N, D)), _spec(one_chip, (M, D)),
        _spec(one_chip, (N,)), _spec(one_chip, (N,)))
    assert "tpu_custom_call" in txt


def test_weighted_filter_compiles(one_chip, native):
    txt = _compiled_text(
        lambda x, c, ds, h, w: engine.filter_tile_update(
            x, c, ds, h, rank=8, impl="auto", w_blk=w),
        _spec(one_chip, (N, D)), _spec(one_chip, (M, D)),
        _spec(one_chip, (N,)), _spec(one_chip, (N,), jnp.bool_),
        _spec(one_chip, (N,)))
    assert "tpu_custom_call" in txt


def test_fused_assign_compiles(one_chip, native):
    bn = _stream_bn()
    txt = _compiled_text(
        lambda x, c: fused_stream.fused_assign_blocks(x, c, bn=bn),
        _spec(one_chip, (N, D)), _spec(one_chip, (M, D)))
    assert "tpu_custom_call" in txt


def test_fused_argmin_compiles(one_chip, native):
    bn = _stream_bn()
    txt = _compiled_text(
        lambda x, c, nv: fused_stream.fused_argmin_blocks(x, c, nv, bn=bn),
        _spec(one_chip, (N, D)), _spec(one_chip, (M, D)),
        _spec(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in txt


def test_center_tiled_assign_compiles(one_chip, native):
    # engine.assign_nearest's Pallas branch: the center-tiled kernel, also
    # the branch the streamed folds take when the centers overflow VMEM.
    txt = _compiled_text(
        lambda x, c: engine.assign_nearest(x, c, impl="auto"),
        _spec(one_chip, (N, D)), _spec(one_chip, (M, D)))
    assert "tpu_custom_call" in txt


def test_large_center_set_leaves_the_fused_tile(native):
    # An EIM sample of ~2.4e5 centers cannot sit whole in VMEM: the folds
    # must take the center-tiled branch (still on the device).
    fused, interpret, _ = engine._stream_tiles("auto", None, N, 240_000, D)
    assert not fused and not interpret
    assert engine._stream_tiles("auto", None, N, M, D)[0]
