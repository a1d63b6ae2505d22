"""Pure-jnp reference oracles for the k-center distance kernels.

These are the semantics contracts: every Pallas kernel in this package is
validated (shape/dtype sweeps, interpret mode) against these functions.
They are also the production path on non-TPU backends.

All distances are *squared* Euclidean (monotone in the Euclidean metric, so
center selection / assignment / argmax-farthest are identical; callers take
a sqrt only when reporting radii).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# The one matmul precision of every f32 distance dot (this oracle and the
# Pallas tiles alike). On TPU an f32 dot at DEFAULT precision is a single
# bf16 pass; HIGHEST asks for full f32 accumulation of f32 products, which
# the |x|^2 + |c|^2 - 2 x.c decomposition needs (its cancellation amplifies
# the product error by |x|^2 / d^2). On CPU every precision is f32, so
# naming it moves no bits there.
DOT_PRECISION = jax.lax.Precision.HIGHEST


def dist2_to_center(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Squared distances from every row of ``x (n,d)`` to one center ``c (d,)``."""
    diff = x - c[None, :]
    return jnp.sum(diff * diff, axis=-1)


def pairwise_dist2(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Squared distances ``(n,m)`` between rows of ``x (n,d)`` and ``c (m,d)``.

    Uses the matmul (MXU) decomposition ``|x|^2 - 2 x.c^T + |c|^2`` with a
    clamp at zero (the decomposition can go slightly negative in floating
    point).
    """
    xn = jnp.sum(x * x, axis=-1, keepdims=True)            # (n,1)
    cn = jnp.sum(c * c, axis=-1, keepdims=True).T          # (1,m)
    d2 = xn + cn - 2.0 * jnp.dot(x, c.T, precision=DOT_PRECISION,
                                 preferred_element_type=jnp.float32)
    return jnp.maximum(d2, 0.0)


def fused_min_argmax(x: jnp.ndarray, c: jnp.ndarray, min_d2: jnp.ndarray):
    """One Gonzalez iteration's hot path, fused.

    Given the new center ``c``, update the running min-squared-distance
    ``min_d2 (n,)`` and return the farthest point under the updated
    distances.

    Returns ``(new_min_d2 (n,), far_val (), far_idx () int32)``.
    """
    d2 = dist2_to_center(x, c)
    new_min = jnp.minimum(min_d2, d2)
    idx = jnp.argmax(new_min).astype(jnp.int32)
    return new_min, new_min[idx], idx


def assign_nearest(x: jnp.ndarray, c: jnp.ndarray):
    """Nearest-center assignment.

    Returns ``(idx (n,) int32, d2 (n,))`` — per-point nearest center index
    and its squared distance.
    """
    d2 = pairwise_dist2(x, c)
    idx = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    return idx, jnp.min(d2, axis=-1)
