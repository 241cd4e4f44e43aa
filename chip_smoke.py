"""Chip smoke test: the federated round engine end to end on a TPU.

Drives ``FederatedSimulation.run`` (the path ``examples/quickstart.py`` and
the paper-table benchmarks take) for all six methods at the paper's
CIFAR-10 CNN width, ``configs/paper_cnn.cifar10_cnn()``: 32x32x3 images,
conv widths (32, 64, 64), hidden 128, 10 classes. The federation has 32
clients, the target plus 31 candidate neighbours, placed and
channel-selected by ``benchmarks.common.build_scenario`` with Table III's
thresholds. They hold CIFAR-10's train-set size of synthetic images (50,000)
split Dirichlet(0.1), then 75/25 into train and test. Training uses the
``FedSimConfig`` defaults with 4 rounds, evaluated every 2.

    python chip_smoke.py             # the fused engine on one chip
    python chip_smoke.py --chips 4   # the client-sharded engine over a
                                     # 4-chip mesh vs the fused engine,
                                     # both evaluated every round

Each method's readings (compile seconds summed over its blocks, wall
seconds of its round blocks, neighbour count M, SGD steps per round, peak
device bytes) are smoke readings, not benchmark numbers. The run checks
that every tapped train loss is finite, every EM weight vector π is on the simplex, every accuracy
is in [0, 1] and that pfedwn's last target accuracy beats chance. Any
failure exits non-zero. The last line of stdout is one JSON object naming
the device. Without a TPU it refuses to run.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from benchmarks.common import build_scenario  # noqa: E402
from repro import compat  # noqa: E402
from repro.configs.paper_cnn import CNNConfig, cifar10_cnn  # noqa: E402
from repro.core.fedsim import (METHODS, FederatedSimulation,  # noqa: E402
                               FedSimConfig)
from repro.data import (dirichlet_partition, make_client_datasets,  # noqa: E402
                        synthetic_image_dataset, train_test_split)

# the smoke configuration
SEED = 0
N_NEIGHBORS = 31              # 32 clients with the target
SAMPLES = 50_000              # CIFAR-10's train-set size
DIRICHLET_ALPHA = 0.1         # Sec V-A label skew
GAMMA_TH, EPS = 10.0, 0.1     # benchmarks/table3_accuracy.py's selection
ROUNDS, EVAL_EVERY = 4, 2
SHARDED_CHIPS = 4
# --chips 4 evaluates after every round, departing from EVAL_EVERY on
# purpose: block boundaries do not change the round stream, so the
# trajectory is the same, compared at 4 points instead of 3, and each
# method and engine compiles one block length (blocks [1, 1, 1, 1])
# instead of two ([1, 2, 1]), halving that run's compile time
SHARDED_EVAL_EVERY = 1
# the sharded-vs-fused target-accuracy tolerance, the one the CPU checks
# of the client mesh use (benchmarks.fedsim_bench.sharded_smoke,
# tests/test_fedsim_sharded.py)
PARITY_TOL = 5e-3


@dataclass
class Federation:
    """The smoke's clients: model, per-client data and channel state."""
    model_cfg: CNNConfig
    train_sets: List[Any]
    test_sets: List[Any]
    participants: np.ndarray      # (N,) bool, target first
    p_err: np.ndarray             # (N,) target-link erasure probability


def build_federation(*, model_cfg: Optional[CNNConfig] = None,
                     samples: int = SAMPLES, n_neighbors: int = N_NEIGHBORS,
                     seed: int = SEED) -> Federation:
    """Place and channel-select the neighbours, then make and split the
    synthetic data as ``benchmarks.common.build_simulation`` does."""
    model_cfg = model_cfg or cifar10_cnn()
    sc = build_scenario(seed, n_neighbors, gamma_th=GAMMA_TH, eps=EPS)
    base = synthetic_image_dataset(seed, samples,
                                   image_size=model_cfg.image_size,
                                   channels=model_cfg.channels,
                                   n_classes=model_cfg.n_classes)
    parts = dirichlet_partition(base.y, n_neighbors + 1,
                                alpha=DIRICHLET_ALPHA, seed=seed)
    splits = [train_test_split(p, seed=seed + 1) for p in parts]
    return Federation(
        model_cfg=model_cfg,
        train_sets=make_client_datasets(base, [s[0] for s in splits]),
        test_sets=make_client_datasets(base, [s[1] for s in splits]),
        participants=np.concatenate([[True], sc.selected]),
        p_err=np.concatenate([[0.0], sc.p_err]).astype(np.float32))


def make_sim(fed: Federation, **sim_kw) -> FederatedSimulation:
    """A simulation of ``fed`` with the ``FedSimConfig`` defaults, the
    smoke's rounds, and any overrides in ``sim_kw``."""
    cfg = dict(rounds=ROUNDS, eval_every=EVAL_EVERY, seed=SEED)
    cfg.update(sim_kw)
    return FederatedSimulation(fed.model_cfg, fed.train_sets, fed.test_sets,
                               fed.participants, fed.p_err,
                               FedSimConfig(**cfg))


def _span_seconds(sim: FederatedSimulation, name: str, method: str) -> float:
    return sum(e["dur"] for e in sim.recorder.tracer.events
               if e["name"] == name and e["args"].get("method") == method
               ) / 1e6


def run_method(sim: FederatedSimulation, method: str) -> Dict[str, Any]:
    """Run one method and return its history, tapped train losses and
    readings. Each simulation runs a method once, so the recorder's spans
    for ``method`` are this run's. ``peak_bytes_in_use`` is device 0's
    peak over the process so far."""
    hist = sim.run(method)
    run_id = sim.recorder.run_id
    losses = [e["train_loss"] for e in sim.recorder.events
              if e["type"] == "round" and e["run_id"] == run_id]
    stats = device_stats()
    return {
        "history": hist,
        "train_loss": losses,
        "reading": {
            "method": method,
            "engine": sim.engine,
            "compile_s": _span_seconds(sim, "compile", method),
            "exec_s": _span_seconds(sim, "block_exec", method),
            "M": int(np.sum(np.asarray(sim.participants)[1:])),
            "steps_per_round": sim.steps_per_round,
            "peak_bytes_in_use": stats[0].get("peak_bytes_in_use"),
        },
    }


def device_stats() -> List[Dict[str, int]]:
    """``memory_stats()`` of every device ({} where the backend has none)."""
    import jax
    return [d.memory_stats() or {} for d in jax.devices()]


def check_run(method: str, result: Dict[str, Any], *, rounds: int,
              n_classes: int) -> None:
    """Raise if the run's outputs are wrong: a non-finite tapped train
    loss, a π off the simplex, an accuracy outside [0, 1], a missing round
    or eval, or pfedwn's last target accuracy at or below chance."""
    hist, losses = result["history"], result["train_loss"]
    if len(losses) != rounds:
        raise RuntimeError(f"{method}: {len(losses)} tapped rounds, "
                           f"expected {rounds}")
    if not all(math.isfinite(v) for row in losses for v in row):
        raise RuntimeError(f"{method}: non-finite tapped train loss")
    accs = hist["target_acc"] + hist["mean_participant_acc"]
    if not all(0.0 <= a <= 1.0 for a in accs):
        raise RuntimeError(f"{method}: accuracy outside [0, 1]: {accs}")
    if method == "pfedwn":
        if len(hist["pi"]) != len(hist["target_acc"]):
            raise RuntimeError("pfedwn: one π per eval expected")
        for pi in hist["pi"]:
            if pi.size and (np.any(pi < 0) or abs(pi.sum() - 1.0) > 1e-4
                            or not np.all(np.isfinite(pi))):
                raise RuntimeError(f"pfedwn: π off the simplex: {pi}")
        chance = 1.0 / n_classes
        if not hist["target_acc"][-1] > chance:
            raise RuntimeError(
                f"pfedwn: last target accuracy {hist['target_acc'][-1]} "
                f"is not above chance {chance}")


def _print_reading(reading: Dict[str, Any]) -> None:
    print("smoke reading (not a benchmark number): " + json.dumps(reading),
          flush=True)


def run_fused(fed: Federation, methods=METHODS, **sim_kw
              ) -> Dict[str, Dict[str, Any]]:
    """All ``methods`` on the fused engine, each checked and its readings
    printed."""
    sim = make_sim(fed, **sim_kw)
    return {m: _run_checked(sim, m) for m in methods}


def _run_checked(sim: FederatedSimulation, method: str) -> Dict[str, Any]:
    result = run_method(sim, method)
    check_run(method, result, rounds=sim.sim.rounds,
              n_classes=sim.model_cfg.n_classes)
    _print_reading(result["reading"])
    return result


def run_sharded_vs_fused(fed: Federation, chips: int, methods=METHODS,
                         **sim_kw) -> Dict[str, float]:
    """The client-sharded engine over a ``chips``-device mesh and the fused
    engine on one device, on the same federation and seeds, evaluated
    after every round (``SHARDED_EVAL_EVERY``). Prints each device's bytes
    in use once the sharded data alone is staged, and each method's
    largest target-accuracy gap; raises above ``PARITY_TOL``."""
    sim_kw = {"eval_every": SHARDED_EVAL_EVERY, **sim_kw}
    sharded = make_sim(fed, sharded=True, shard_devices=chips, **sim_kw)
    sharded.staged_data()
    _print_reading({"sharded_staged_bytes_in_use":
                    [s.get("bytes_in_use") for s in device_stats()]})
    fused = make_sim(fed, **sim_kw)
    res_s = {m: _run_checked(sharded, m) for m in methods}
    res_f = {m: _run_checked(fused, m) for m in methods}
    gaps, accs = {}, {}
    for m in methods:
        a = np.asarray(res_s[m]["history"]["target_acc"])
        b = np.asarray(res_f[m]["history"]["target_acc"])
        gaps[m] = float(np.max(np.abs(a - b)))
        accs[m] = {"sharded": a.tolist(), "fused": b.tolist()}
    print("target acc per eval: " + json.dumps(accs), flush=True)
    print("sharded-vs-fused max |target acc gap|: " + json.dumps(gaps),
          flush=True)
    _print_reading({"peak_bytes_in_use":
                    [s.get("peak_bytes_in_use") for s in device_stats()]})
    bad = {m: g for m, g in gaps.items() if g > PARITY_TOL}
    if bad:
        raise RuntimeError(f"sharded/fused disagree beyond {PARITY_TOL}: "
                           f"{bad}")
    return gaps


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, SHARDED_CHIPS),
                    default=1,
                    help=f"{SHARDED_CHIPS}: run only the client-sharded "
                         f"engine over {SHARDED_CHIPS} chips and its fused "
                         f"comparison")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}, {len(devices)} "
              f"device(s))", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"compile cache: {compat.enable_compilation_cache()}", flush=True)

    fed = build_federation()
    if args.chips == 1:
        run_fused(fed)
    else:
        run_sharded_vs_fused(fed, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
