"""The CNN's 2x2 max-pool (`cnn._max_pool_2x2`, a max over a reshaped view)
against `lax.reduce_window`'s VALID max-pool: the same forward values, the
same gradient where no window holds a tie, and on planted ties the same
gradient per window (the reshape form splits a tied window's cotangent
equally where reduce_window's select-and-scatter gives it to one element).
Every spatial size of the MNIST (28 -> 14 -> 7 -> 3) and CIFAR
(32 -> 16 -> 8 -> 4) presets, plain and vmapped over a client axis."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import cnn

N_CLIENTS, BATCH = 3, 2


def _reduce_window_pool(h):
    return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _window_sums(g):
    """Sum of each 2x2 window of the pool's input gradient; the trailing
    odd row or column, which no window reads, comes back separately."""
    *lead, hh, ww, c = g.shape
    inner = g[..., :hh - hh % 2, :ww - ww % 2, :]
    sums = inner.reshape(*lead, hh // 2, 2, ww // 2, 2, c).sum(axis=(-4, -2))
    dropped = np.concatenate([g[..., hh - hh % 2:, :, :].ravel(),
                              g[..., :, ww - ww % 2:, :].ravel()])
    return sums, dropped


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("c", [1, 16, 32])
@pytest.mark.parametrize("hw", [(28, 28), (14, 14), (7, 7), (32, 32), (8, 8)],
                         ids=lambda hw: f"{hw[0]}x{hw[1]}")
def test_max_pool_matches_reduce_window(hw, c, vmapped):
    rng = np.random.default_rng(hw[0] * 100 + c)
    lead = (N_CLIENTS, BATCH) if vmapped else (BATCH,)
    shape = lead + hw + (c,)
    size = int(np.prod(shape))
    pool, ref = cnn._max_pool_2x2, _reduce_window_pool
    if vmapped:
        pool, ref = jax.vmap(pool), jax.vmap(ref)

    # tie-free: distinct values, so every window has one maximum
    distinct = jnp.asarray(
        (rng.permutation(size) - size / 2).reshape(shape) / size, jnp.float32)
    out, vjp = jax.vjp(pool, distinct)
    ref_out, ref_vjp = jax.vjp(ref, distinct)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
    cot = jnp.asarray(rng.normal(size=out.shape), jnp.float32)
    np.testing.assert_array_equal(np.asarray(vjp(cot)[0]),
                                  np.asarray(ref_vjp(cot)[0]))

    # planted ties: three levels, zero (a ReLU's floor) in three draws of
    # five, so most windows hold two or more equal maxima
    tied = jnp.asarray(np.maximum(rng.integers(-2, 3, size=shape), 0),
                       jnp.float32)
    out, vjp = jax.vjp(pool, tied)
    ref_out, ref_vjp = jax.vjp(ref, tied)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
    sums, dropped = _window_sums(np.asarray(vjp(cot)[0]))
    ref_sums, ref_dropped = _window_sums(np.asarray(ref_vjp(cot)[0]))
    np.testing.assert_allclose(sums, ref_sums, rtol=1e-6, atol=0)
    np.testing.assert_array_equal(dropped, 0)
    np.testing.assert_array_equal(ref_dropped, 0)
