"""The CNN's 3x3 SAME conv (`cnn._conv2d_same`, one
`lax.conv_general_dilated`) against the contraction it replaced: nine
shifted views of the zero-padded input concatenated into a (B, H, W, 9*C)
patch tensor and one matmul. Forward values and the gradients with respect
to the input and the filters, at `highest` precision, for every conv layer
of the MNIST (28x28x1 -> 16, 14x14x16 -> 32, 7x7x32 -> 32) and CIFAR
(32x32x3 -> 32, 16x16x32 -> 64, 8x8x64 -> 64) presets, in the three ways
the simulator batches the model: plain (the post-aggregation pass), vmapped
over client weights and inputs (local SGD, eval), and vmapped over weights
with one shared input (the EM's first layer over the target's data)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import cnn

N_CLIENTS, BATCH = 3, 2

LAYERS = [(28, 1, 16), (14, 16, 32), (7, 32, 32),
          (32, 3, 32), (16, 32, 64), (8, 64, 64)]


def _nine_view_conv(x, w):
    """The contraction form: pad, nine shifted views, one matmul."""
    k = w.shape[0]
    pad = k // 2
    b, h, wd, c = x.shape
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    views = [xp[:, di:di + h, dj:dj + wd, :]
             for di in range(k) for dj in range(k)]
    patches = jnp.concatenate(views, axis=-1)
    out = patches.reshape(b * h * wd, k * k * c) @ w.reshape(k * k * c, -1)
    return out.reshape(b, h, wd, w.shape[-1])


def _batched(conv, mode):
    if mode == "plain":
        return conv
    if mode == "vmap_clients":
        return jax.vmap(conv)
    return jax.vmap(conv, in_axes=(None, 0))      # shared x, per-client w


@pytest.mark.parametrize("mode", ["plain", "vmap_clients", "vmap_weights"])
@pytest.mark.parametrize("hw,c_in,c_out", LAYERS,
                         ids=[f"{h}x{h}x{ci}-{co}" for h, ci, co in LAYERS])
def test_conv_matches_nine_view_contraction(hw, c_in, c_out, mode):
    rng = np.random.default_rng(hw * 1000 + c_in * 10 + c_out)
    x_lead = (N_CLIENTS, BATCH) if mode == "vmap_clients" else (BATCH,)
    w_lead = () if mode == "plain" else (N_CLIENTS,)
    x = jnp.asarray(rng.normal(size=x_lead + (hw, hw, c_in)), jnp.float32)
    w = jnp.asarray(rng.normal(size=w_lead + (3, 3, c_in, c_out))
                    / np.sqrt(9 * c_in), jnp.float32)
    conv = _batched(cnn._conv2d_same, mode)
    ref = _batched(_nine_view_conv, mode)

    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(conv, x, w)
        ref_out, ref_vjp = jax.vjp(ref, x, w)
        assert out.shape == ref_out.shape == w_lead + (BATCH, hw, hw, c_out)
        cot = jnp.asarray(rng.normal(size=out.shape), jnp.float32)
        grads, ref_grads = vjp(cot), ref_vjp(cot)

    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-5, atol=1e-5)
    for g, ref_g, name in zip(grads, ref_grads, ("dx", "dw")):
        scale = float(jnp.max(jnp.abs(ref_g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref_g),
                                   rtol=1e-5, atol=1e-5 * scale,
                                   err_msg=name)
