"""A tiny cell for CPU rehearsals of the harness: four clients of 8x8
grey images, two conv blocks, three classes. Written into a temporary
benchmark directory beside copies of the real metric readers and peaks, so
that the harness finds it by name as it finds the real cells."""
import json
import os
import shutil

from bench import harness

CELL = "tiny.pfedwn"

CONFIG = {
    "name": "tiny",
    "source": "https://arxiv.org/abs/2501.09822",
    "model": "cnn",
    "dtype": "float32",
    "matmul_precision": "default",
    "model_config": {"image_size": 8, "channels": 1, "n_classes": 3,
                     "widths": [4, 8], "hidden": 8},
    "train": {"batch_size": 8, "lr": 0.05, "alpha": 0.5, "em_iters": 3,
              "em_component_steps": 1, "em_subset": 16, "adapt_subset": 8,
              "maml_inner_lr": 0.01, "em_min_weight": 1e-06},
    "population": {"clients": 4, "samples": 133, "dirichlet_alpha": 0.1,
                   "test_frac": 0.25, "noise": 0.35},
    "layout": {
        "participants": [True, True, False, True],
        "p_err": [0.0, 0.1, 0.9, 0.3],
        "train_class_counts": [[14, 4, 0], [2, 25, 3], [9, 9, 9], [0, 3, 22]],
        "test_class_counts": [[5, 1, 0], [1, 8, 1], [3, 3, 3], [0, 1, 7]],
    },
    "expect": {"M": 2, "k_max": 30, "test_k_max": 10, "steps_per_round": 4},
    "reduced": [],
}

# on the CPU the program and the reference agree to float rounding
LIMITS = {"loss_shift.r1": 1e-4, "loss_gap": 1e-3, "pi_gap.r1": 1e-3,
          "link_gap": 0.0, "target_acc_gap": 1e-6, "mean_acc_gap": 1e-6}


def make_bench(tmp: str, method: str = "pfedwn") -> harness.Catalog:
    """A benchmark directory under ``tmp`` holding the tiny cell for
    ``method`` and the real per-layer metric readers."""
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(tmp, sub), exist_ok=True)
    shutil.copytree(os.path.join(harness.BENCH, "metrics"),
                    os.path.join(tmp, "metrics"), dirs_exist_ok=True)
    real = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    cell = f"tiny.{method}"
    _write(tmp, "configs/tiny.json", CONFIG)
    _write(tmp, f"traffic/{method}.json",
           {"method": method, "engine": "fused", "chips": 1,
            "eval_every": 1, "rounds_per_call": 2})
    _write(tmp, f"limits/{cell}.json", LIMITS if method == "pfedwn" else {
        k: v for k, v in LIMITS.items() if k not in ("pi_gap.r1", "link_gap")})
    peaks = json.load(open(os.path.join(harness.BENCH, "peaks.json")))
    peaks["devices"]["cpu"] = {"flops_per_s": 1e12}
    _write(tmp, "peaks.json", peaks)
    bench = {**real, "workloads": [{"name": cell, "config": "tiny",
                                    "traffic": method, "chips": 1,
                                    "why": "CPU rehearsal"}]}
    bench["end_to_end"] = [{k: v for k, v in m.items() if k != "workloads"}
                           for m in real["end_to_end"]]
    bench["per_layer"] = [{k: v for k, v in m.items() if k != "workloads"}
                          for m in real["per_layer"]]
    return harness.Catalog(tmp, bench)


def _write(tmp, rel, obj):
    with open(os.path.join(tmp, rel), "w") as f:
        json.dump(obj, f)
