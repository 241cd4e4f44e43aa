"""CI smokes of the round engine on a tiny federation (XLA:CPU, seconds):
``obs_smoke`` runs an instrumented simulation and checks its RunRecord,
``sharded_smoke`` checks the client-mesh placement against one device on
all six methods. Both print ``name,us_per_call,derived`` CSV lines
(harness convention); neither is a speed measurement.
"""
from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Optional

import numpy as np

from benchmarks.common import emit
from repro.configs.paper_cnn import CNNConfig
from repro.core.fedsim import METHODS, FederatedSimulation, FedSimConfig
from repro.data import (make_client_datasets, synthetic_image_dataset,
                        train_test_split)


def build_sim(n_clients: int, *, rounds: int, eval_every: int,
              samples: int = 0, image_size: int = 8, batch: int = 32,
              seed: int = 0, sharded: bool = False,
              shard_devices: Optional[int] = None,
              record_dir: str | None = None,
              run_name: str | None = None) -> FederatedSimulation:
    """All-participants network with mild random link error. Clients get
    an even random shard (~64 samples each by default) rather than a
    Dirichlet split, so every client runs about the same number of steps
    per round."""
    samples = samples or 64 * n_clients
    base = synthetic_image_dataset(seed, samples, image_size=image_size,
                                   n_classes=10)
    rng = np.random.default_rng(seed)
    parts = np.array_split(rng.permutation(samples), n_clients)
    train_sets = make_client_datasets(
        base, [train_test_split(p, seed=seed + 1)[0] for p in parts])
    test_sets = make_client_datasets(
        base, [train_test_split(p, seed=seed + 1)[1] for p in parts])
    pm = np.ones(n_clients, bool)
    rng = np.random.default_rng(seed + 2)
    p_err = np.concatenate(
        [[0.0], rng.uniform(0.0, 0.1, n_clients - 1)]).astype(np.float32)
    model_cfg = CNNConfig(image_size=image_size, widths=(4, 8), hidden=16,
                          n_classes=10)
    cfg = FedSimConfig(rounds=rounds, batch_size=batch, lr=0.05, alpha=0.7,
                       em_iters=2, em_subset=32, adapt_subset=32,
                       eval_every=eval_every, seed=seed, sharded=sharded,
                       shard_devices=shard_devices, record_dir=record_dir,
                       run_name=run_name)
    return FederatedSimulation(model_cfg, train_sets, test_sets, pm, p_err,
                               cfg)


def obs_smoke() -> None:
    """CI stage entry (seconds): run a tiny instrumented simulation,
    emit obs_smoke.jsonl + Chrome trace, and validate the RunRecord schema
    in-process. ci.sh follows up with `python -m repro.obs.report` on the
    same file.

    The artifacts land in ``$OBS_SMOKE_DIR`` when set (ci.sh points it at a
    mktemp dir so CI runs never clobber real run records under runs/), and
    in a fresh private temp dir otherwise."""
    from repro.obs import validate_jsonl_lines
    t0 = time.perf_counter()
    out_dir = os.environ.get("OBS_SMOKE_DIR") or tempfile.mkdtemp(
        prefix="fedsim_obs_smoke_")
    os.makedirs(out_dir, exist_ok=True)
    sim = build_sim(4, rounds=3, eval_every=2, samples=400,
                    image_size=8, batch=16, record_dir=out_dir,
                    run_name="obs_smoke")
    sim.run("pfedwn")
    jsonl = os.path.join(out_dir, "obs_smoke.jsonl")
    trace = os.path.join(out_dir, "obs_smoke.trace.json")
    assert os.path.exists(jsonl), "RunRecord JSONL not emitted"
    assert os.path.exists(trace), "Chrome trace not emitted"
    with open(jsonl) as f:
        lines = f.readlines()
    errors = validate_jsonl_lines(lines)
    assert not errors, f"RunRecord schema violations: {errors[:5]}"
    types = [json.loads(ln)["type"] for ln in lines]
    for expected in ("meta", "compile", "round", "eval", "summary"):
        assert expected in types, f"missing {expected!r} event"
    # the tap must not break the host-sync-only-at-eval-boundaries property
    assert sim.last_run_stats["device_calls"] == 2
    emit("obs_smoke", (time.perf_counter() - t0) * 1e6,
         f"events={len(types)};rounds={types.count('round')};ok")


def sharded_smoke() -> None:
    """CI guard for the client-mesh placement (expects forced host devices
    via XLA_FLAGS, as ci.sh sets): all six methods on a tiny shape, sharded
    over a 4-device client mesh vs one device, identical seeds. rounds=2 with
    eval_every=2 gives blocks [1, 1] — one executable per (method, engine),
    which keeps the six-method sweep in CI seconds-to-a-minute territory."""
    import jax
    t0 = time.perf_counter()
    n_dev = len(jax.devices())
    if n_dev < 4:
        raise RuntimeError(
            f"sharded_smoke needs >=4 devices, have {n_dev}: run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    common = dict(rounds=2, eval_every=2, samples=400, image_size=8,
                  batch=16)
    fused = build_sim(4, **common)
    sharded = build_sim(4, sharded=True, shard_devices=4, **common)
    worst = 0.0
    for method in METHODS:
        hf, hs = fused.run(method), sharded.run(method)
        gap = max(abs(a - b) for a, b in zip(hf["target_acc"],
                                             hs["target_acc"]))
        worst = max(worst, gap)
        if gap > 5e-3:
            raise AssertionError(
                f"sharded/fused disagree on {method}: |Δacc|={gap:.4f}")
    assert sharded.last_run_stats["engine"] == "sharded"
    emit("fedsim_sharded_smoke", (time.perf_counter() - t0) * 1e6,
         f"devices=4;methods={len(METHODS)};worst_gap={worst:.1e};ok")
