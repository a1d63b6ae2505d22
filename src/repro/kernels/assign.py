"""Pallas TPU kernel: fused nearest-center assignment.

Computes ``argmin_j |x_i - c_j|^2`` without materializing the full (n,m)
distance matrix in HBM: the grid walks center tiles in the minor dimension
and keeps a running (min, argmin) pair per point tile in the revisited
output block (TPU grids execute sequentially, so cross-step accumulation
into an output block whose index_map ignores the minor grid axis is the
standard Pallas reduction pattern).

VMEM per step: x (bn,d) + c (bm,d) + two (bn,) accumulators. The
accumulators are lane-dense ``(bn/L, L)`` blocks (``fused_stream``'s row
layout, so ``bn`` is a multiple of ``fused_stream.ROW_TILE`` on TPU): an
``(n, 1)`` output would be laid out in (8, 128) tiles on TPU, 128 times
its size in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fused_stream import ROW_TILE, compiler_params, row_lanes
from .ref import DOT_PRECISION

DEFAULT_BN = ROW_TILE
DEFAULT_BM = 256


def _assign_kernel(x_ref, c_ref, d2_ref, idx_ref):
    j = pl.program_id(1)
    bm = c_ref.shape[0]
    x = x_ref[...].astype(jnp.float32)                   # (bn, d)
    c = c_ref[...].astype(jnp.float32)                   # (bm, d)
    xn = jnp.sum(x * x, axis=-1, keepdims=True)          # (bn, 1)
    cn = jnp.sum(c * c, axis=-1, keepdims=True)          # (bm, 1)
    prod = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), precision=DOT_PRECISION,
        preferred_element_type=jnp.float32
    )                                                    # (bn, bm)
    d2 = jnp.maximum(xn + cn.T - 2.0 * prod, 0.0)
    rows = d2_ref.shape
    loc_min = jnp.min(d2, axis=-1).reshape(rows)         # (bn/L, L)
    loc_arg = (jnp.argmin(d2, axis=-1).astype(jnp.int32) + j * bm
               ).reshape(rows)

    @pl.when(j == 0)
    def _init():
        d2_ref[...] = loc_min
        idx_ref[...] = loc_arg

    @pl.when(j > 0)
    def _update():
        prev = d2_ref[...]
        take = loc_min < prev
        d2_ref[...] = jnp.where(take, loc_min, prev)
        idx_ref[...] = jnp.where(take, loc_arg, idx_ref[...])


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def assign_nearest_blocks(
    x: jnp.ndarray,
    c: jnp.ndarray,
    *,
    bn: int = DEFAULT_BN,
    bm: int = DEFAULT_BM,
    interpret: bool = False,
):
    """Returns ``(idx (n,) int32, d2 (n,) f32)`` nearest-center per point."""
    n, d = x.shape
    m = c.shape[0]
    assert n % bn == 0 and m % bm == 0, (n, m, bn, bm)
    grid = (n // bn, m // bm)
    lanes = row_lanes(bn)
    d2, idx = pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn // lanes, lanes), lambda i, j: (i, 0)),
            pl.BlockSpec((bn // lanes, lanes), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n // lanes, lanes), jnp.float32),
            jax.ShapeDtypeStruct((n // lanes, lanes), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=compiler_params(bn, bm, d),
    )(x, c)
    return idx.reshape(-1), d2.reshape(-1)
