"""The plain reference: k-center by the definitions, in ``jax.numpy``.

It imports nothing of the program and takes nothing the program made
except the answers it checks. GON's passes use the difference form
``sum((x - c)^2)``, as the paper states them. Nearest-center searches over
many centers use the matmul form ``|x|^2 + |c|^2 - 2 x.c`` at a stated dot
precision (``"highest"`` for the configuration's f32) to find the nearest
center, then recompute the squared distance to it by the difference form,
which has no cancellation: a value it returns is exact to a few f32
roundings of the distance itself, not of the squared norms.

``prec="high"`` (the chip's three bf16 passes) is the control: the
reference put in the program's place one precision step below the
configuration's f32 at HIGHEST. On a CPU every dot is f32, so the CPU
tests plant ``prec="default"`` (one bf16 pass) instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

def dot(x, c, prec: str):
    """``x @ c.T`` with f32 accumulation. ``"highest"`` asks for full f32
    products and ``"high"`` for the three bf16 passes of the chip's HIGH
    mode (on a CPU both are f32). ``"default"`` is one bf16 pass, spelt
    out as a cast of the operands so that it computes the same on every
    backend."""
    if prec in ("highest", "high"):
        p = (jax.lax.Precision.HIGHEST if prec == "highest"
             else jax.lax.Precision.HIGH)
        return jnp.dot(x, c.T, precision=p,
                       preferred_element_type=jnp.float32)
    if prec != "default":
        raise ValueError(f"unknown dot precision {prec!r}")
    return jnp.dot(x.astype(jnp.bfloat16), c.astype(jnp.bfloat16).T,
                   preferred_element_type=jnp.float32)


def _nearest(x, c, cn, prec):
    """-> (argmin index, matmul-form min d2) of every row of ``x``."""
    d2 = (jnp.sum(x * x, axis=1, keepdims=True) + cn[None, :]
          - 2.0 * dot(x, c, prec))
    return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, axis=1)


def exact_d2(x, c):
    """Row-wise squared distance by the difference form."""
    diff = x - c
    return jnp.sum(diff * diff, axis=-1)


# -- GON and MRG -----------------------------------------------------------

def gon(x, k: int):
    """Gonzalez: first row, then k-1 times the farthest row (first index on
    ties). -> (centers (k, d), squared covering radius of ``x``)."""
    n, d = x.shape
    md = exact_d2(x, x[0][None, :])
    centers = jnp.zeros((k, d), x.dtype).at[0].set(x[0])

    def body(i, carry):
        md, centers = carry
        c = x[jnp.argmax(md)]
        return jnp.minimum(md, exact_d2(x, c[None, :])), centers.at[i].set(c)

    md, centers = jax.lax.fori_loop(1, k, body, (md, centers))
    return centers, jnp.max(md)


def _sharded(fn, mesh, n_in: int, out_specs):
    """``fn`` as one program over the rows sharded on ``mesh``'s "data"
    axis, or as a plain jitted program without a mesh."""
    if mesh is None:
        return jax.jit(functools.partial(fn, axis=None))
    from jax.sharding import PartitionSpec as P
    return jax.jit(jax.shard_map(
        functools.partial(fn, axis="data"), mesh=mesh,
        in_specs=(P("data"),) + (P(),) * (n_in - 1), out_specs=out_specs,
        check_vma=False))


def _mrg_local(x, *, k, machines, prec, rows, axis):
    n, d = x.shape
    blocks = x.reshape(machines, n // machines, d)
    union = jax.vmap(lambda b: gon(b, k)[0])(blocks).reshape(-1, d)
    if axis is not None:
        union = jax.lax.all_gather(union, axis, tiled=True)
    centers, _ = gon(union, k)
    r2 = fold_r2(x, centers, prec=prec, rows=rows)
    if axis is not None:
        r2 = jax.lax.pmax(r2, axis)
    return centers, r2


@functools.lru_cache(maxsize=None)
def _mrg_program(k, machines, prec, rows, mesh):
    from jax.sharding import PartitionSpec as P
    fn = functools.partial(_mrg_local, k=k, machines=machines, prec=prec,
                           rows=rows)
    return _sharded(fn, mesh, 1, (P(), P()))


def mrg(x, *, k: int, machines: int, prec: str = "highest",
        rows: int = 8192, mesh=None):
    """Paper Algorithm 1 with two rounds: GON on each of ``machines``
    contiguous blocks of rows (per device, where ``x`` is sharded over
    ``mesh``), GON on the union of their centers, and the squared covering
    radius of the result over all of ``x``, folded ``rows`` rows at a time
    at dot precision ``prec`` (the value the reference reports, as the
    program reports its own)."""
    return _mrg_program(k, machines, prec, rows, mesh)(x)


def fold_r2(x, centers, *, prec: str, rows: int):
    """Matmul-form squared covering radius at precision ``prec`` (what a
    program computing the radius at that precision reports)."""
    cn = jnp.sum(centers * centers, axis=1)
    xb = x.reshape(-1, rows, x.shape[1])

    def step(best, blk):
        return jnp.maximum(best, jnp.max(_nearest(blk, centers, cn,
                                                  prec)[1])), None

    best, _ = jax.lax.scan(step, jnp.float32(0.0), xb)
    return jnp.maximum(best, 0.0)


def _audit_local(x, centers, *, rows, axis):
    k, d = centers.shape
    cn = jnp.sum(centers * centers, axis=1)
    xb = x.reshape(-1, rows, d)
    base = 0 if axis is None else jax.lax.axis_index(axis) * x.shape[0]

    def step(carry, blk):
        best, cbest, crow, scale, off = carry
        d2 = (jnp.sum(blk * blk, axis=1, keepdims=True) + cn[None, :]
              - 2.0 * dot(blk, centers, "highest"))
        near = jnp.argmin(d2, axis=1)
        r2 = jnp.max(exact_d2(blk, centers[near]))
        col = jnp.min(d2, axis=0)
        arg = jnp.argmin(d2, axis=0).astype(jnp.int32) + off
        better = col < cbest
        return (jnp.maximum(best, r2), jnp.where(better, col, cbest),
                jnp.where(better, arg, crow),
                jnp.maximum(scale, jnp.max(jnp.sum(blk * blk, axis=1))),
                off + rows), None

    init = (jnp.float32(0.0), jnp.full((k,), jnp.inf, jnp.float32),
            jnp.zeros((k,), jnp.int32), jnp.max(cn), jnp.int32(base))
    (r2, cbest, crow, scale, _), _ = jax.lax.scan(step, init, xb)
    if axis is not None:
        r2 = jax.lax.pmax(r2, axis)
        scale = jax.lax.pmax(scale, axis)
        cb = jax.lax.all_gather(cbest, axis)          # (devices, k)
        cr = jax.lax.all_gather(crow, axis)
        crow = jnp.take_along_axis(cr, jnp.argmin(cb, axis=0)[None], 0)[0]
    return r2, crow, scale


@functools.lru_cache(maxsize=None)
def _audit_program(rows, mesh):
    from jax.sharding import PartitionSpec as P
    fn = functools.partial(_audit_local, rows=rows)
    return _sharded(fn, mesh, 2, (P(), P(), P()))


def audit_centers(x, centers, *, rows: int = 8192, mesh=None):
    """Everything the check needs about ``centers`` over all of ``x``, in
    one pass of ``rows``-row blocks (on each device, where ``x`` is sharded
    over ``mesh``):

    - ``r2``: the squared covering radius, each row's nearest center found
      at HIGHEST and its distance taken by the difference form;
    - ``nearest_row``: for each center, the row of ``x`` nearest to it;
    - ``scale``: the largest squared norm of a row or a center."""
    return _audit_program(rows, mesh)(x, centers)


def bad_centers(x, centers, nearest_row) -> int:
    """How many centers are not a row of ``x``, or repeat another center's
    row (GON only ever picks distinct rows of its input)."""
    rows = np.asarray(x[jnp.asarray(nearest_row)])
    c = np.asarray(centers)
    exact = np.all(rows == c, axis=1)
    distinct = len(np.unique(np.asarray(nearest_row)[exact]))
    return int(c.shape[0] - distinct)
