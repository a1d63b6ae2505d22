"""Pallas TPU kernel: fused Gonzalez iteration (dist + min + arg-farthest).

Each Gonzalez step does three O(n) passes in the naive formulation:
  1. d2  = |x - c_new|^2          (distance to the newly chosen center)
  2. md  = min(md, d2)            (running min-distance update)
  3. far = argmax(md)             (next center = farthest point)

Fusing them keeps each ``(bn,d)`` point tile resident in VMEM for exactly
one HBM read (plus the (bn,) min-dist vector read/write), turning the step
from 3 HBM sweeps into ~1 — the memory-roofline win the paper's runtime
analysis (§5.1, "low constant in the O(kn/m)") corresponds to on TPU.

Grid: ``(n/bn,)``. Per-block outputs: updated min-dist tile, plus the
block-local (max value, global argmax index) pair; the final cross-block
argmax reduction is O(n/bn) and runs in the jit'd wrapper
(ops.fused_min_argmax).

Layout note: the min-dist vector travels in ``fused_stream``'s lane-dense
``(bn/L, L)`` row blocks (so ``bn`` is a multiple of
``fused_stream.ROW_TILE`` on TPU, and the kernel also lowers under
``vmap``). Each block's (max, argmax) pair is broadcast over one
``(1, 128)`` lane row of an ``(nb, 1, 128)`` array: a ``(1, 1)`` block of
an ``(nb, 1)`` array is neither (8, 128)-aligned nor the whole array,
which the TPU lowering refuses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fused_stream import LANES as _LANES
from .fused_stream import ROW_TILE, compiler_params, row_lanes, to_lanes

DEFAULT_BN = ROW_TILE


def _fused_kernel(x_ref, c_ref, md_ref, newmd_ref, bmax_ref, barg_ref):
    pid = pl.program_id(0)
    bn = x_ref.shape[0]
    x = x_ref[...].astype(jnp.float32)               # (bn, d)
    c = c_ref[...].astype(jnp.float32)               # (1, d)
    diff = x - c                                     # broadcast over rows
    d2 = jnp.sum(diff * diff, axis=-1)               # (bn,)  VPU
    new_md = jnp.minimum(md_ref[...], d2.reshape(md_ref.shape))  # (bn/L, L)
    newmd_ref[...] = new_md
    # First-occurrence argmax as whole-block reductions (no dynamic lane
    # index): the block max, then the lowest row holding it.
    vmax = jnp.max(new_md)
    order = (jax.lax.broadcasted_iota(jnp.int32, new_md.shape, 0)
             * new_md.shape[1]
             + jax.lax.broadcasted_iota(jnp.int32, new_md.shape, 1))
    loc = jnp.min(jnp.where(new_md == vmax, order, bn))
    bmax_ref[...] = jnp.full(bmax_ref.shape, vmax)
    barg_ref[...] = jnp.full(barg_ref.shape, loc + pid * bn)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def fused_min_argmax_blocks(
    x: jnp.ndarray,
    c: jnp.ndarray,
    min_d2: jnp.ndarray,
    *,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
):
    """Returns ``(new_min_d2 (n,), block_max (nb,1,128), block_arg
    (nb,1,128))``; every lane of a block's row holds the same value.

    ``n`` must divide ``bn`` (ops.py pads). The caller reduces the block
    maxima to the global farthest point.
    """
    n, d = x.shape
    assert n % bn == 0, (n, bn)
    nb = n // bn
    lanes = row_lanes(bn)
    new_md, bmax, barg = pl.pallas_call(
        _fused_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((bn // lanes, lanes), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn // lanes, lanes), lambda i: (i, 0)),
            pl.BlockSpec((None, 1, _LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, _LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n // lanes, lanes), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, _LANES), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=compiler_params(bn, 1, d),
    )(x, c.reshape(1, -1), to_lanes(min_d2, bn))
    return new_md.reshape(-1), bmax, barg
