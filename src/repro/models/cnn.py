"""The client model: a CNN classifier of conv blocks (3x3 conv, bias, ReLU,
2x2 max-pool; no residual connections), a hidden dense layer and a linear
head, in pure JAX. Sec V-A uses a 3-layer CNN for MNIST and ResNet-18 for
CIFAR; the presets are in ``configs/paper_cnn``."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.paper_cnn import CNNConfig

# the named scopes the model puts on its layers' ops: a trace reads each
# layer's device time from them, forward and backward, in every phase
SCOPES = ("conv", "pool")


def _conv_init(key, k, c_in, c_out, dtype=jnp.float32):
    fan_in = k * k * c_in
    return jax.random.normal(key, (k, k, c_in, c_out), dtype) / jnp.sqrt(fan_in)


def init_params(key, cfg: CNNConfig, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, len(cfg.widths) + 2)
    params: Dict = {"blocks": []}
    c_in = cfg.channels
    for i, w in enumerate(cfg.widths):
        params["blocks"].append({
            "conv": _conv_init(ks[i], 3, c_in, w, dtype),
            "bias": jnp.zeros((w,), dtype),
        })
        c_in = w
    feat = cfg.image_size // (2 ** len(cfg.widths))
    flat = feat * feat * cfg.widths[-1]
    params["fc1"] = {
        "w": jax.random.normal(ks[-2], (flat, cfg.hidden), dtype) / jnp.sqrt(flat),
        "b": jnp.zeros((cfg.hidden,), dtype)}
    params["fc2"] = {
        "w": jax.random.normal(ks[-1], (cfg.hidden, cfg.n_classes), dtype)
        / jnp.sqrt(cfg.hidden),
        "b": jnp.zeros((cfg.n_classes,), dtype)}
    return params


def _conv2d_same(x: jax.Array, w: jax.Array) -> jax.Array:
    """Stride-1 SAME conv (odd kernel) as one ``lax.conv_general_dilated``,
    at the configuration's default matmul precision.

    The simulator vmaps the model over per-client weights, and ``vmap``
    lowers this call: with x and w both batched (local SGD, eval) to one
    grouped conv with a group per client, clients x channels on the lanes;
    with only w batched (the EM's first layer, over the target's data) to
    one dense conv with every neighbour's filters as outputs. A patch
    tensor of nine shifted views, (B, H, W, 9*C) with 9 to 288 values on
    the lanes, contracted by one matmul, took 3.1 to 5.3 times the round
    time of this form on a TPU v5e at the MNIST and CIFAR-10 presets, and
    on XLA:CPU a vmapped MNIST grad step (8 clients x 16 images) reads
    22 ms in this form against 31 ms in that one."""
    with jax.named_scope("conv"):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _max_pool_2x2(h: jax.Array) -> jax.Array:
    """2x2, stride-2 VALID max-pool as a max over a reshaped view.

    An odd trailing row or column is dropped, as VALID windows drop it.
    Under vmap over client weights, ``lax.reduce_window`` lowers on the TPU
    to a windowed reduce with W across the lanes and a select-and-scatter
    backward between layout copies; the reshape form is a plain reduction
    whose backward is elementwise. Forward values are the same; on a tie
    inside one window the cotangent is split equally among the tied
    elements where select-and-scatter gives it all to the first."""
    b, hh, ww, c = h.shape
    with jax.named_scope("pool"):
        h = h[:, :hh - hh % 2, :ww - ww % 2, :]
        return jnp.max(h.reshape(b, hh // 2, 2, ww // 2, 2, c), axis=(2, 4))


def apply(params: Dict, x: jax.Array) -> jax.Array:
    """x: (B, H, W, C) -> logits (B, n_classes)."""
    h = x
    for blk in params["blocks"]:
        h = _conv2d_same(h, blk["conv"])
        h = jax.nn.relu(h + blk["bias"][None, None, None, :])
        h = _max_pool_2x2(h)
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    return h @ params["fc2"]["w"] + params["fc2"]["b"]


def per_sample_nll(params: Dict, x: jax.Array, y: jax.Array) -> jax.Array:
    """Per-sample negative log-likelihood (the EM E-step loss, Eq 8)."""
    logits = apply(params, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]


def loss(params: Dict, x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.mean(per_sample_nll(params, x, y))


def accuracy(params: Dict, x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(apply(params, x), axis=-1) == y).astype(jnp.float32))


def masked_accuracy(params: Dict, x: jax.Array, y: jax.Array,
                    mask: jax.Array) -> jax.Array:
    """Accuracy over the rows where ``mask`` is set. Lets the simulator pad
    every client's test set to a common length and evaluate all clients in
    one vmapped call: padded rows contribute nothing, so this equals
    :func:`accuracy` on the unpadded set."""
    ok = (jnp.argmax(apply(params, x), axis=-1) == y).astype(jnp.float32)
    m = mask.astype(jnp.float32)
    return jnp.sum(ok * m) / jnp.maximum(jnp.sum(m), 1.0)
