"""SimExecutor's round 1 takes the caller's corpus, not a blocked copy.

``_round1`` blocks the points inside its own jitted program, so when the
machine count divides n the blocks are a view of the argument. These
tests pin that it is the same computation as the two-program route it
replaced (``_block``'s blocked copy, then the vmapped round), bit for bit,
and that no corpus-sized buffer passes between programs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SimExecutor, mrg
from repro.core.executor import (Objective, _block, _mrg_round, _round1,
                                 _run_round_w, _vmapped, gon_block_fn,
                                 weighted_gon_block_fn)
from repro.data import ArraySource, WeightedSource

K = 4


def _pts(n, d=16, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def _blocked_route(x, k, m, impl):
    """The parent's route: ``_block``'s copy, ``_mrg_round``, the shared
    combine and the radius fold."""
    blocked, mask = _block(jnp.asarray(x), m)
    union, valid = _mrg_round(blocked, mask, k, m, impl)
    ex, src = SimExecutor(m), ArraySource(x)
    centers, extra = ex.combine(union, valid, k, ex.default_capacity(src, k),
                                impl=impl)
    return union, valid, centers, ex.radius2(src, centers, impl=impl), extra


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("n,m", [(512, 8), (509, 7)], ids=["m|n", "m!|n"])
@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weighted"])
def test_round1_bitwise_blocked_route(n, m, impl, weighted):
    x = _pts(n, seed=n + m)
    if not weighted:
        union, valid, centers, r2, extra = _blocked_route(x, K, m, impl)
        got_u, got_v = SimExecutor(m).run_blocks(gon_block_fn(K, impl, None),
                                                 ArraySource(x))
        res = mrg(x, K, m=m, impl=impl)
        np.testing.assert_array_equal(np.asarray(res.centers),
                                      np.asarray(centers))
        assert np.asarray(res.radius2).tobytes() == np.asarray(r2).tobytes()
        assert res.rounds == 2 + extra
    else:
        w = np.random.default_rng(m).integers(0, 4, n).astype(np.float32)
        fn = weighted_gon_block_fn(K, impl, None)
        blocked, mask = _block(jnp.asarray(x), m)
        wb = jnp.pad(jnp.asarray(w), (0, mask.size - n)).reshape(mask.shape)
        union, valid, cw = _run_round_w(blocked, mask, wb, fn)
        got_u, got_v, got_w = SimExecutor(m).run_blocks(
            fn, WeightedSource(ArraySource(x), w),
            objective=Objective(weighted=True))
        np.testing.assert_array_equal(np.asarray(got_w), np.asarray(cw))
    np.testing.assert_array_equal(np.asarray(got_u), np.asarray(union))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(valid))


def test_round1_takes_the_corpus_and_hands_no_copy_on():
    n, d, m = 4096, 128, 16
    fn = gon_block_fn(K, "ref", None)
    spec = jax.ShapeDtypeStruct((n, d), jnp.float32)
    lowered = _round1(fn, m).lower(spec)
    args = jax.tree.leaves(lowered.args_info)
    assert [(a.shape, a.dtype) for a in args] == [((n, d), jnp.float32)]
    corpus = n * d * 4
    mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes == corpus
    assert mem.output_size_in_bytes < corpus // 8
    # No blocked copy inside either: the program's scratch is the vmapped
    # round's own, as when it received the blocks as its argument.
    old = _vmapped(fn).lower(
        jax.ShapeDtypeStruct((m, n // m, d), jnp.float32),
        jax.ShapeDtypeStruct((m, n // m), jnp.bool_)).compile()
    assert mem.temp_size_in_bytes <= old.memory_analysis().temp_size_in_bytes


def test_round1_program_built_once_per_shape():
    m = 8
    fn = gon_block_fn(K, "ref", None)
    x = jnp.asarray(_pts(1024, seed=3))
    program = _round1(fn, m)
    built = program._cache_size()      # other shapes other tests ran
    first = mrg(x, K, m=m, impl="ref")
    second = mrg(x, K, m=m, impl="ref")
    assert _round1(fn, m) is program
    assert program._cache_size() == built + 1
    np.testing.assert_array_equal(np.asarray(first.centers),
                                  np.asarray(second.centers))
    assert float(first.radius2) == float(second.radius2)
