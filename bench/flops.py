"""FLOPs one federated round needs, counted from shapes.

Only the contractions the method needs are counted: every client's SGD
steps (forward and backward), the eval over real test rows, and what the
method adds (its file ``methods/<method>.py`` counts the whole round, from
the pieces here). Recomputation (the E-step is checkpointed) and padded
eval rows are not counted, so the share of the peak that follows is a floor
on what the chip executes, never above it.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from types import ModuleType
from typing import Dict

import numpy as np


@dataclass(frozen=True)
class Shapes:
    """What a round's FLOP count reads from a configuration."""
    model: ModuleType          # bench.models.<kind>
    mc: Dict                   # its model_config
    tr: Dict                   # its train settings
    clients: int
    steps: int                 # SGD steps per round
    batch: int
    participants: np.ndarray   # (N,) bool, the target first
    sizes: np.ndarray          # (N,) train rows
    tests: np.ndarray          # (N,) test rows
    n_params: int


def shapes(cfg: Dict) -> Shapes:
    model = importlib.import_module(f"bench.models.{cfg['model']}")
    lay = cfg["layout"]
    return Shapes(
        model=model, mc=cfg["model_config"], tr=cfg["train"],
        clients=cfg["population"]["clients"],
        steps=cfg["expect"]["steps_per_round"],
        batch=cfg["train"]["batch_size"],
        participants=np.asarray(lay["participants"], bool),
        sizes=np.asarray(lay["train_class_counts"]).sum(axis=1),
        tests=np.asarray(lay["test_class_counts"]).sum(axis=1),
        n_params=model.param_count(cfg["model_config"]))


def local_sgd(s: Shapes) -> int:
    """Every client's SGD steps, forward and backward."""
    return s.clients * s.steps * s.batch * s.model.train_flops(s.mc)


def evaluation(s: Shapes) -> int:
    """Every client's test rows, and the target's once more."""
    return (int(s.tests.sum()) + int(s.tests[0])) * s.model.forward_flops(s.mc)


def round_flops(cfg: Dict, method: str) -> float:
    mod = importlib.import_module(f"bench.methods.{method}")
    return float(mod.round_flops(shapes(cfg)))
