"""The benchmark's files, layouts, FLOP counts and command, on the CPU."""
import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import data, flops, harness, reference
from bench.models import cnn

CONFIGS = tuple(c["name"] for c in harness.Catalog().benchmark["configs"])
METHODS = sorted(f[:-3] for f in os.listdir(os.path.join(harness.BENCH,
                                                         "methods"))
                 if f.endswith(".py"))
# the widths of configs/paper_cnn.cifar10_cnn, for the hand count
CIFAR_CNN = {"image_size": 32, "channels": 3, "n_classes": 10,
             "widths": [32, 64, 64], "hidden": 128}


def _config(name):
    return harness.Catalog().config(name)


def test_flop_counts_match_hand_counts():
    # CIFAR: conv 32x32x27x32, 16x16x288x64, 8x8x576x64; fc 1024x128, 128x10
    cifar = 2 * (1024 * 27 * 32 + 256 * 288 * 64 + 64 * 576 * 64
                 + 1024 * 128 + 128 * 10)
    # MNIST: conv 28x28x9x16, 14x14x144x32, 7x7x288x32; fc 288x64, 64x10
    mnist = 2 * (784 * 9 * 16 + 196 * 144 * 32 + 49 * 288 * 32
                 + 288 * 64 + 64 * 10)
    assert cifar == 16_189_952 and mnist == 2_973_440
    assert cnn.forward_flops(CIFAR_CNN) == cifar
    assert cnn.forward_flops(
        _config("mnist_cnn_n32_dense")["model_config"]) == mnist
    assert cnn.train_flops(CIFAR_CNN) == 3 * cifar - 2 * 1024 * 27 * 32


def test_round_flops_add_up_per_method():
    cfg = _config("mnist_cnn_n32_dense")
    mnist = 2_973_440
    train = 3 * mnist - 2 * 784 * 9 * 16
    sgd = 32 * 68 * 64 * train                       # every client's steps
    ev = (sum(map(sum, cfg["layout"]["test_class_counts"]))
          + sum(cfg["layout"]["test_class_counts"][0])) * mnist
    n_params = cnn.param_count(cfg["model_config"])
    assert flops.round_flops(cfg, "fedavg") == sgd + 2 * 29 * n_params + ev
    em = 28 * 512 * (5 * mnist + 4 * (train - mnist))
    assert flops.round_flops(cfg, "pfedwn") == (
        sgd + em + 2 * 28 * n_params + 68 * 64 * train + ev)


@pytest.mark.parametrize("method", METHODS)
def test_every_method_file_is_whole(method):
    mod = reference.method(method)
    assert callable(mod.Round)
    assert set(reference.faults(method)) >= set(reference.FAULTS)
    assert flops.round_flops(_config("mnist_cnn_n32_dense"), method) > 0
    with pytest.raises(ModuleNotFoundError):
        reference.method("no_such_method")


@pytest.mark.parametrize("name", CONFIGS)
def test_seeds_change_values_not_shapes(name):
    cfg = _config(name)
    want = data.layout_sizes(cfg)
    shapes, firsts = [], []
    for seed in (1, 2**31 + 77):
        fed = data.make_federation(cfg, seed)
        shapes.append([x.shape for x, _ in fed.train + fed.test])
        firsts.append(fed.train[0][0][0].copy())
        k_max = max(len(y) for _, y in fed.train)
        assert k_max == want["k_max"]
        assert int(fed.participants[1:].sum()) == want["M"]
        assert -(-k_max // cfg["train"]["batch_size"]) == \
            want["steps_per_round"]
        del fed
    assert shapes[0] == shapes[1]
    assert not np.array_equal(firsts[0], firsts[1])


@pytest.mark.parametrize("name", CONFIGS)
def test_layout_check_fails_on_a_changed_file(name):
    cfg = _config(name)
    moved = copy.deepcopy(cfg)
    counts = moved["layout"]["train_class_counts"]
    big = int(np.argmax([sum(r) for r in counts]))
    c = int(np.argmax(counts[big]))
    counts[big][c] -= 1                      # same total, shorter stack
    counts[(big + 1) % len(counts)][c] += 1
    with pytest.raises(data.LayoutError):
        data.layout_sizes(moved)
    dropped = copy.deepcopy(cfg)
    part = dropped["layout"]["participants"]
    part[part.index(True, 1)] = False        # one neighbour fewer
    with pytest.raises(data.LayoutError):
        data.layout_sizes(dropped)


def test_derived_seed_is_31_bits_and_stable():
    seeds = [0, 1, 2**31 - 1, 2**31 + 5, 2**40 + 3]
    got = [data.derived_seed(s) for s in seeds]
    assert got == [data.derived_seed(s) for s in seeds]
    assert len(set(got)) == len(seeds)
    assert all(0 <= g < 2**31 - 8 for g in got)


def test_files_are_found_by_name(tmp_path):
    for sub in ("configs", "traffic", "metrics", "limits"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "newcfg.json").write_text(
        json.dumps({"name": "newcfg", "model": "cnn"}))
    (tmp_path / "traffic" / "newmix.json").write_text(
        json.dumps({"method": "fedavg", "rounds_per_call": 3}))
    (tmp_path / "limits" / "newcfg.newmix.json").write_text(
        json.dumps({"loss_gap": 0.5}))
    (tmp_path / "peaks.json").write_text(
        json.dumps({"devices": {"chip x": {"flops_per_s": 5.0}}}))
    (tmp_path / "metrics" / "new_metric.py").write_text(
        "def read(r):\n    return 2.0 * r.window_rounds\n")
    bench = {"workloads": [{"name": "newcfg.newmix", "config": "newcfg",
                            "traffic": "newmix", "chips": 1, "why": "t"}],
             "end_to_end": [], "per_layer": [
                 {"name": "new_metric", "unit": "ms", "better": "lower",
                  "source": "program_span", "layer": "l", "moves": "x"}]}
    cat = harness.Catalog(str(tmp_path), bench)
    wl = cat.workload("newcfg.newmix")
    assert cat.config(wl["config"])["name"] == "newcfg"
    assert cat.traffic(wl["traffic"])["rounds_per_call"] == 3
    assert cat.limits("newcfg.newmix") == {"loss_gap": 0.5}
    assert cat.metrics("per_layer", "newcfg.newmix") == ["new_metric"]
    reading = harness.Reading([], [], 4, 1.0, 1.0, 1.0)
    assert cat.reader("new_metric")(reading) == 8.0
    assert cat.peak_flops("chip x") == 5.0
    with pytest.raises(KeyError):
        cat.peak_flops("no such chip")


def test_every_benchmark_file_is_where_benchmark_json_says():
    cat = harness.Catalog()
    for c in cat.benchmark["configs"]:
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
        assert cat.config(c["name"])["name"] == c["name"]
    for w in cat.benchmark["workloads"]:
        assert cat.traffic(w["traffic"])["method"] in METHODS
        assert set(cat.limits(w["name"])) >= {"loss_shift.r1", "loss_gap"}
    for m in cat.benchmark["per_layer"]:
        assert callable(cat.reader(m["name"]))
    assert cat.peak_flops("TPU v5 lite") == 197e12


def _run_command(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mnist_cnn_n32_dense.pfedwn", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_exits_nonzero_without_a_chip():
    p = _run_command(harness.ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no accelerator" in p.stderr


def test_command_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_command(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout
