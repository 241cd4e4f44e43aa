"""The program against the benchmark's plain reference at the paper's
CIFAR-10 CNN widths (32x32x3 images, conv 32/64/64, hidden 128), on the
CPU, from seeded random weights: the model's logits and gradients, plain
and vmapped over clients, and a 3-client federation's two rounds through
``FederatedSimulation.run``. On the CPU both sides compute in float32, so
they differ only in the order of their sums."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from bench import compare, harness, reference  # noqa: E402
from bench import data as bdata  # noqa: E402
from bench.models import cnn as ref_cnn  # noqa: E402
from repro import obs  # noqa: E402
from repro.configs.paper_cnn import cifar10_cnn  # noqa: E402
from repro.core.fedsim import batch_rows  # noqa: E402
from repro.models import cnn  # noqa: E402

_PRESET = cifar10_cnn()
CIFAR = {"image_size": _PRESET.image_size, "channels": _PRESET.channels,
         "n_classes": _PRESET.n_classes, "widths": list(_PRESET.widths),
         "hidden": _PRESET.hidden}

# three clients, all selected; 3 SGD steps of 8 images a round
CONFIG = {
    "name": "cifar_tiny_federation",
    "model": "cnn",
    "dtype": "float32",
    "matmul_precision": "default",
    "model_config": CIFAR,
    "train": {"batch_size": 8, "lr": 0.05, "alpha": 0.5, "em_iters": 3,
              "em_component_steps": 1, "em_subset": 16, "adapt_subset": 8,
              "maml_inner_lr": 0.01, "em_min_weight": 1e-06},
    "population": {"clients": 3, "samples": 77, "noise": 0.35},
    "layout": {
        "participants": [True, True, True],
        "p_err": [0.0, 0.2, 0.5],
        "train_class_counts": [[6, 4, 0, 0, 2, 0, 0, 3, 0, 5],
                               [0, 9, 3, 0, 0, 0, 7, 0, 1, 0],
                               [2, 0, 0, 8, 0, 6, 0, 0, 0, 4]],
        "test_class_counts": [[2, 1, 0, 0, 1, 0, 0, 1, 0, 1],
                              [0, 2, 1, 0, 0, 0, 2, 0, 0, 0],
                              [1, 0, 0, 2, 0, 2, 0, 0, 0, 1]],
    },
    "expect": {"M": 2, "k_max": 20, "test_k_max": 6, "steps_per_round": 3},
}

# relative gaps of float32 sums taken in another order, through 2 rounds
# of 3 SGD steps and the EM: orders of magnitude under what a lost update
# or half a batch moves (both move every loss by percents)
LIMITS = {"loss_shift.r1": 1e-4, "loss_gap": 1e-3, "pi_gap.r1": 1e-3,
          "link_gap": 0.0, "target_acc_gap": 1e-6, "mean_acc_gap": 1e-6}
SEED = 2**31 + 15


def _params(n):
    keys = jax.random.split(jax.random.PRNGKey(SEED % 2**31), n)
    return reference.stack([ref_cnn.init_params(k, CIFAR) for k in keys])


def _batch(n, b=16):
    rng = np.random.default_rng(SEED)
    x = rng.uniform(0.0, 1.0, (n, b, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, (n, b)).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def _ref_loss(p, x, y):
    return jnp.mean(ref_cnn.per_sample_nll(p, x, y))


@pytest.mark.parametrize("channels", [1, 3])
def test_batch_rows_is_the_plain_gather(channels):
    """The flat-row gather returns exactly ``stack[rows]``, for one client
    and vmapped over clients, rows repeated and in any order."""
    rng = np.random.default_rng(channels)
    stacks = rng.normal(size=(2, 7, 5, 4, channels)).astype(np.float32)
    rows = rng.integers(0, 7, (2, 3, 4)).astype(np.int32)
    np.testing.assert_array_equal(batch_rows(stacks[0], rows[0]),
                                  stacks[0][rows[0]])
    got = jax.jit(jax.vmap(batch_rows))(stacks, rows)
    np.testing.assert_array_equal(got, np.stack([s[r] for s, r in
                                                 zip(stacks, rows)]))


def test_program_draws_the_reference_weights():
    key = jax.random.PRNGKey(SEED % 2**31)
    got, want = cnn.init_params(key, _PRESET), ref_cnn.init_params(key, CIFAR)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
def test_model_matches_the_reference(vmapped):
    """Logits and every parameter's gradient of one minibatch; float32
    sums in another order agree to a few parts in a million."""
    n = 3 if vmapped else 1
    params, (x, y) = _params(n), _batch(n)

    def both(apply, loss):
        def f(p, xb, yb):
            return apply(p, xb), jax.grad(loss)(p, xb, yb)
        if vmapped:
            return jax.jit(jax.vmap(f))(params, x, y)
        one = jax.jit(f)(*jax.tree.map(lambda a: a[0], (params, x, y)))
        return jax.tree.map(lambda a: a[None], one)

    logits, grads = both(cnn.apply, cnn.loss)
    ref_logits, ref_grads = both(ref_cnn.apply, _ref_loss)
    np.testing.assert_allclose(logits, ref_logits, rtol=1e-5, atol=1e-5)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        for i in range(n):
            err = np.linalg.norm(g[i] - r[i]) / np.linalg.norm(r[i])
            assert err < 1e-5, (jax.tree_util.keystr(path), i, err)


@pytest.mark.parametrize("method", ["pfedwn", "fedavg"])
def test_federation_matches_the_reference(method):
    """Two rounds of a 3-client federation at CIFAR widths through
    ``FederatedSimulation.run``, against the reference client by client."""
    traffic = {"method": method, "engine": "fused", "eval_every": 1,
               "rounds_per_call": 2}
    fed = bdata.make_federation(CONFIG, SEED)
    key_seed = bdata.derived_seed(SEED)
    rec = obs.RunRecorder()
    sim = harness.build_sim(CONFIG, traffic, fed, key_seed, rec)
    assert sim.steps_per_round == CONFIG["expect"]["steps_per_round"]
    sim.run(method)
    prog = harness.call_outputs(rec, rec.run_id)
    ref = reference.Reference(CONFIG, method, fed, key_seed).run(2)
    limits = LIMITS if method == "pfedwn" else {
        k: v for k, v in LIMITS.items() if k not in ("pi_gap.r1", "link_gap")}
    checks = compare.checks(compare.numbers(prog, ref), limits)
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
