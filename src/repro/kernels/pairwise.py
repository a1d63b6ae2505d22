"""Pallas TPU kernel: tiled pairwise squared-Euclidean distances.

The k-center hot spot (paper §5: every algorithm's dominant round is
distance computation) is ``D2[i,j] = |x_i - c_j|^2``. On TPU we compute it
as ``|x|^2 + |c|^2 - 2 x c^T`` so the inner product runs on the MXU with
128-aligned tiles, and the rank-1 norm corrections run on the VPU over the
same VMEM-resident tiles (one HBM pass per operand tile instead of three).

Tiling: grid ``(n/bn, m/bm)``; each step loads ``x (bn,d)`` and ``c (bm,d)``
into VMEM and writes one ``(bn,bm)`` output tile. ``d`` is kept un-tiled —
for clustering/embedding workloads d ≤ 8192, so the per-step VMEM working
set is ``(bn+bm)·d·4B + bn·bm·4B`` ≤ ~8.5 MB at the default bn=bm=256,
d=4096 — inside the ~16 MB v5e VMEM budget. Callers with larger d should
chunk d and accumulate (see ops.pairwise_dist2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import DOT_PRECISION

DEFAULT_BN = 256
DEFAULT_BM = 256


def _pairwise_kernel(x_ref, c_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)           # (bn, d)
    c = c_ref[...].astype(jnp.float32)           # (bm, d)
    xn = jnp.sum(x * x, axis=-1, keepdims=True)  # (bn, 1)   VPU
    cn = jnp.sum(c * c, axis=-1, keepdims=True)  # (bm, 1)   VPU
    # MXU matmul; accumulate in f32 regardless of input dtype.
    prod = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), precision=DOT_PRECISION,
        preferred_element_type=jnp.float32
    )                                            # (bn, bm)
    out_ref[...] = jnp.maximum(xn + cn.T - 2.0 * prod, 0.0)


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def pairwise_dist2(
    x: jnp.ndarray,
    c: jnp.ndarray,
    *,
    bn: int = DEFAULT_BN,
    bm: int = DEFAULT_BM,
    interpret: bool = False,
) -> jnp.ndarray:
    """``(n,d) x (m,d) -> (n,m)`` squared distances. n, m must divide bn, bm
    (ops.py handles padding)."""
    n, d = x.shape
    m = c.shape[0]
    assert n % bn == 0 and m % bm == 0, (n, m, bn, bm)
    grid = (n // bn, m // bm)
    return pl.pallas_call(
        _pairwise_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        interpret=interpret,
    )(x, c)
