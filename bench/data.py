"""A federation's data, made from the seed on a frozen layout.

The configuration fixes every shape: each client's class counts for train
and test (so its sizes, the padded stack lengths and the SGD steps per
round), the channel-selected participants and each link's erasure
probability. The seed draws only values: the class prototypes, the order of
each client's labels and the pixel noise. Each class is a Gaussian blob
around a prototype with a low-frequency wave, clipped to [0, 1].
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np


class LayoutError(ValueError):
    """A configuration's layout disagrees with the sizes it states."""


@dataclass
class Federation:
    train: List[tuple]          # per client (x (k, H, W, C) f32, y (k,) i32)
    test: List[tuple]
    participants: np.ndarray    # (N,) bool, the target first
    p_err: np.ndarray           # (N,) f32 erasure probability of each link


def derived_seed(seed: int) -> int:
    """A 31-bit seed for JAX keys from any whole ``--seed``: JAX keeps only
    32 bits of a seed, and the program adds small offsets to it."""
    return int(np.random.SeedSequence(abs(int(seed))).generate_state(1)[0]
               >> 2)


def layout_sizes(cfg: Dict) -> Dict[str, int]:
    """M, the longest train and test stacks and the SGD steps per round that
    the layout implies, checked against the configuration's ``expect``."""
    lay, train = cfg["layout"], cfg["train"]
    n = cfg["population"]["clients"]
    part, p_err = lay["participants"], lay["p_err"]
    tr = np.asarray(lay["train_class_counts"])
    te = np.asarray(lay["test_class_counts"])
    n_classes = cfg["model_config"]["n_classes"]
    if not (len(part) == len(p_err) == len(tr) == len(te) == n):
        raise LayoutError(f"layout lists are not all {n} clients long")
    if tr.shape[1] != n_classes or te.shape[1] != n_classes:
        raise LayoutError(f"class counts are not {n_classes} wide")
    if not part[0]:
        raise LayoutError("the target (client 0) must participate")
    if int(tr.sum() + te.sum()) != cfg["population"]["samples"]:
        raise LayoutError("class counts do not add up to the sample count")
    k_max = int(tr.sum(axis=1).max())
    got = {"M": int(sum(part[1:])), "k_max": k_max,
           "test_k_max": int(te.sum(axis=1).max()),
           "steps_per_round": -(-k_max // train["batch_size"])}
    if got != cfg["expect"]:
        raise LayoutError(f"layout gives {got}, configuration expects "
                          f"{cfg['expect']}")
    return got


def make_federation(cfg: Dict, seed: int) -> Federation:
    layout_sizes(cfg)
    mc, lay = cfg["model_config"], cfg["layout"]
    size, ch, n_classes = mc["image_size"], mc["channels"], mc["n_classes"]
    rng = np.random.default_rng(np.random.SeedSequence(abs(int(seed))))
    protos = rng.normal(0.5, 0.25, (n_classes, size, size, ch))
    xs = np.linspace(0, 2 * np.pi, size)
    wave = np.sin(xs)[None, :, None, None] * np.cos(xs)[None, None, :, None]
    protos = (protos + 0.3 * wave * (np.arange(n_classes)[:, None, None, None]
                                     / n_classes)).astype(np.float32)
    noise = np.float32(cfg["population"]["noise"])

    def split(counts) -> List[tuple]:
        out = []
        for row in counts:
            y = np.repeat(np.arange(n_classes, dtype=np.int32), row)
            rng.shuffle(y)
            x = protos[y]
            x += noise * rng.standard_normal(x.shape, dtype=np.float32)
            np.clip(x, 0.0, 1.0, out=x)
            out.append((x, y))
        return out

    return Federation(
        train=split(lay["train_class_counts"]),
        test=split(lay["test_class_counts"]),
        participants=np.asarray(lay["participants"], bool),
        p_err=np.asarray(lay["p_err"], np.float32))


def padded_stack(parts: List[tuple]):
    """(x (N, K, ...), y (N, K), mask (N, K), lengths (N,)) zero-padded to
    the longest client."""
    k = max(len(y) for _, y in parts)
    x0 = parts[0][0]
    x = np.zeros((len(parts), k) + x0.shape[1:], x0.dtype)
    y = np.zeros((len(parts), k), np.int32)
    mask = np.zeros((len(parts), k), bool)
    for i, (xi, yi) in enumerate(parts):
        x[i, :len(yi)], y[i, :len(yi)], mask[i, :len(yi)] = xi, yi, True
    return x, y, mask, np.asarray([len(yi) for _, yi in parts], np.int32)
