"""Plain reference of the CNN client model, and its FLOP count.

The model kind ``cnn``: per block a 3x3 stride-1 SAME convolution, bias,
ReLU and 2x2 max-pool; then a hidden dense layer with ReLU and a linear
head. Written from that description; it imports nothing of the program.
``init_params`` draws the same weights from the same key as the program's
initializer (normal / sqrt(fan-in), zero biases), which is what lets the
reference start from the seed alone.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp


def init_params(key, mc: Dict, dtype=jnp.float32) -> Dict:
    widths = mc["widths"]
    ks = jax.random.split(key, len(widths) + 2)
    blocks, c_in = [], mc["channels"]
    for i, w in enumerate(widths):
        conv = jax.random.normal(ks[i], (3, 3, c_in, w), dtype) / jnp.sqrt(
            3 * 3 * c_in)
        blocks.append({"conv": conv, "bias": jnp.zeros((w,), dtype)})
        c_in = w
    feat = mc["image_size"] // (2 ** len(widths))
    flat = feat * feat * widths[-1]
    hidden, n_classes = mc["hidden"], mc["n_classes"]
    return {
        "blocks": blocks,
        "fc1": {"w": jax.random.normal(ks[-2], (flat, hidden), dtype)
                / jnp.sqrt(flat),
                "b": jnp.zeros((hidden,), dtype)},
        "fc2": {"w": jax.random.normal(ks[-1], (hidden, n_classes), dtype)
                / jnp.sqrt(hidden),
                "b": jnp.zeros((n_classes,), dtype)},
    }


def conv3x3(x: jax.Array, w: jax.Array) -> jax.Array:
    """Stride-1 SAME 3x3 convolution as one contraction over the 3x3
    neighbourhood of every pixel (zero outside the image). A dot: on a v5e
    the reference built on ``lax.conv`` at ``highest`` precision took over
    three minutes and over 20 GiB of host memory to compile."""
    b, h, wd, c = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    nbhd = jnp.stack([xp[:, i:i + h, j:j + wd, :]
                      for i in range(3) for j in range(3)], axis=3)
    return jnp.einsum("bhwkc,kco->bhwo", nbhd, w.reshape(9, c, -1))


def apply(params: Dict, x: jax.Array) -> jax.Array:
    """x: (B, H, W, C) -> logits (B, n_classes)."""
    h = x
    for blk in params["blocks"]:
        h = jax.nn.relu(conv3x3(h, blk["conv"]) + blk["bias"])
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    return h @ params["fc2"]["w"] + params["fc2"]["b"]


def per_sample_nll(params: Dict, x: jax.Array, y: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(apply(params, x).astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]


# ------------------------------------------------------------- FLOP count

def layer_flops(mc: Dict) -> List[Tuple[str, int]]:
    """Forward multiply-add FLOPs (2 per MAC) of each contraction, per row."""
    out, size, c_in = [], mc["image_size"], mc["channels"]
    for i, w in enumerate(mc["widths"]):
        out.append((f"conv{i + 1}", 2 * size * size * 9 * c_in * w))
        size, c_in = size // 2, w
    flat = size * size * c_in
    out.append(("fc1", 2 * flat * mc["hidden"]))
    out.append(("fc2", 2 * mc["hidden"] * mc["n_classes"]))
    return out


def forward_flops(mc: Dict) -> int:
    return sum(f for _, f in layer_flops(mc))


def train_flops(mc: Dict) -> int:
    """Forward + backward FLOPs of one trained row: the forward pass, the
    weight gradient of every layer and the input gradient of every layer but
    the first (nothing reads the gradient of the images)."""
    f = forward_flops(mc)
    return 3 * f - layer_flops(mc)[0][1]


def weight_grad_flops(mc: Dict) -> int:
    """Backward FLOPs of a pullback to the weights only (the E-step's vjp):
    weight and input gradients, less the first layer's input gradient."""
    return train_flops(mc) - forward_flops(mc)


def param_count(mc: Dict) -> int:
    n, c_in = 0, mc["channels"]
    for w in mc["widths"]:
        n += 9 * c_in * w + w
        c_in = w
    size = mc["image_size"] // (2 ** len(mc["widths"]))
    flat = size * size * c_in
    return n + flat * mc["hidden"] + mc["hidden"] + \
        mc["hidden"] * mc["n_classes"] + mc["n_classes"]
