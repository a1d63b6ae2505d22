"""Planted-cluster corpora made on the device from a seed.

A copy of the generator of ``chip_smoke.py`` (counter-based in the row
index), kept here so that changes to the program cannot move the
benchmark's data. Two changes: the clusters have different widths, and the
set of widths is the same for every seed (only their order follows it), so
the covering radius depends on how many centers each cluster is given and
every seed poses a problem of the same difficulty; and a few rows are
singletons far from every cluster.

Row ``r`` is ``planted[c] + scale[c] * u`` with ``c`` a hash of ``r`` and
``u`` uniform in ``[-1, 1]^d``, or now and then a far singleton, a pure
function of ``(r, seed)``: any blocking of the rows, on one device or
sharded over several, yields the same rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int) -> np.uint32:
    """Fold a seed of any size (the harness passes 64-bit ones) into the
    32-bit word the counter-based generator mixes with each row index."""
    lo = np.uint32(seed & 0xFFFFFFFF)
    hi = np.uint32((seed >> 32) & 0xFFFFFFFF)
    return mix32(lo ^ mix32(hi ^ np.uint32(0x9E3779B9)))


def mix32(h):
    """murmur3's 32-bit finalizer (uint32 arithmetic wraps)."""
    with np.errstate(over="ignore"):
        h = h ^ (h >> 16)
        h = h * np.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        h = h * np.uint32(0xC2B2AE35)
        return h ^ (h >> 16)


def planted(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """-> (cluster centers (C, d) f32, cluster widths (C,) f32).

    Centers are uniform in a cube of side ``cfg["side"]``. The widths are a
    fixed geometric ladder from ``cfg["scale_min"]`` to ``cfg["scale_max"]``,
    dealt to the clusters in an order drawn from the seed."""
    c, d = int(cfg["clusters"]), int(cfg["d"])
    rng = np.random.default_rng(seed)
    side = float(cfg["side"])
    centers = rng.uniform(-side / 2, side / 2, (c, d)).astype(np.float32)
    ladder = np.geomspace(float(cfg["scale_min"]), float(cfg["scale_max"]),
                          c).astype(np.float32)
    return centers, ladder[rng.permutation(c)]


def _uniform(rh, d, salt):
    """Per-row uniform draws in [-1, 1)^d from the row hash ``rh``."""
    col = jax.lax.iota(jnp.uint32, d) * np.uint32(0x9E3779B9)
    h = mix32(rh[:, None] ^ col[None, :] ^ np.uint32(salt))
    u = (h >> 8).astype(jnp.float32) * np.float32(2.0 ** -24)
    return np.float32(2.0) * u - np.float32(1.0)


def rows_at(centers, scales, r, s32, *, every: int, side: float):
    """Rows ``r`` (uint32 indices), each a pure function of its index.

    About one row in ``every`` is a singleton: a row of its own far from
    every cluster (uniform in a cube of side ``2 * side``), as the rare
    unique items of a real corpus. GON takes each for a center, so a job
    that misses rows misses singletons and its radius shows it."""
    rh = mix32(r ^ s32)
    cid = mix32(rh ^ np.uint32(0x68E31DA4)) % np.uint32(centers.shape[0])
    d = centers.shape[1]
    row = centers[cid] + scales[cid][:, None] * _uniform(rh, d, 0)
    lone = (mix32(rh ^ np.uint32(0x2545F491)) % np.uint32(every)) == 0
    far = np.float32(side) * _uniform(rh, d, 0x5BD1E995)
    return jnp.where(lone[:, None], far, row)


@functools.lru_cache(maxsize=None)
def _gen(rows: int, every: int, side: float, sharding):
    def fn(centers, scales, start, s32):
        r = start + jax.lax.iota(jnp.uint32, rows)
        return rows_at(centers, scales, r, s32, every=every, side=side)
    return jax.jit(fn, out_shardings=sharding)


def device_rows(cfg: dict, seed: int, start: int, rows: int, *,
                sharding=None):
    """Rows ``[start, start + rows)`` of the seed's corpus, made on the
    device in one jitted call (sharded by ``sharding`` when given: each
    device then computes its own rows)."""
    centers, scales = planted(cfg, seed)
    # numpy (uncommitted) operands: a sharded program may place them on
    # every device.
    gen = _gen(int(rows), int(cfg["singleton_every"]), float(cfg["side"]),
               sharding)
    return gen(centers, scales, np.uint32(start), seed32(seed))
