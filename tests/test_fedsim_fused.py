"""The scan-over-rounds engine on one device: agreement with plain
client-by-client rounds, single-executable round blocks, the fedprox
single-pass fix, and the shared EM refine loop."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import PFLConfig
from repro.configs.paper_cnn import CNNConfig
from repro.core import aggregation, baselines, em, pfedwn
from repro.core.fedsim import (METHODS, FederatedSimulation, FedSimConfig,
                               block_schedule)
from repro.data import (dirichlet_partition, make_client_datasets,
                        synthetic_image_dataset, train_test_split)
from repro.core.selection import link_success_mask
from repro.lint import hlo as lint_hlo
from repro.models import cnn


def _tiny_setup(n_clients=4, seed=0):
    model_cfg = CNNConfig(image_size=8, widths=(4,), hidden=16, n_classes=4)
    base = synthetic_image_dataset(seed, 600, image_size=8, n_classes=4)
    parts = dirichlet_partition(base.y, n_clients, alpha=0.3, seed=seed)
    train = make_client_datasets(
        base, [train_test_split(p, seed=1)[0] for p in parts])
    test = make_client_datasets(
        base, [train_test_split(p, seed=1)[1] for p in parts])
    # one non-participant so the masked branches (fedprox, aggregation)
    # are exercised by the comparison with plain rounds
    pm = np.array([True] * (n_clients - 1) + [False])
    p_err = np.linspace(0.0, 0.2, n_clients).astype(np.float32)
    return model_cfg, train, test, pm, p_err


def _cfg(**kw):
    base = dict(rounds=3, batch_size=16, lr=0.05, em_iters=2, em_subset=64,
                adapt_subset=32, eval_every=2, seed=0)
    base.update(kw)
    return FedSimConfig(**base)


@pytest.fixture(scope="module")
def fused_sim():
    model_cfg, train, test, pm, p_err = _tiny_setup()
    return FederatedSimulation(model_cfg, train, test, pm, p_err, _cfg())


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _unstack(tree, n):
    return [jax.tree.map(lambda p: p[i], tree) for i in range(n)]


def _plain_rounds(sim, method, rounds):
    """``rounds`` rounds of ``method`` written client by client: no vmap
    and no scan. Each round draws the engine's index stream (the round key
    from ``PRNGKey(seed + 7)``, split as the engine splits it, one
    ``randint`` per client), gathers each minibatch with numpy, and steps
    on ``cnn.loss`` gradients one client at a time; the mixing is the
    ``baselines`` / ``aggregation`` functions'. Returns the stacked params,
    π, and the target's and the participants' mean test accuracy."""
    cfg, n = sim.sim, sim.n
    steps, B, lr = sim.steps_per_round, cfg.batch_size, cfg.lr
    pm = np.asarray(sim.participants)
    nbr = np.where(pm & (np.arange(n) != 0))[0]
    train, test = sim.train_sets, sim.test_sets
    fns = pfedwn.ModelFns(per_sample_loss=cnn.per_sample_nll, loss=cnn.loss,
                          accuracy=cnn.accuracy)

    grad_of = functools.cache(lambda f: jax.jit(jax.value_and_grad(f)))

    def sgd(p, i, idx, objective=cnn.loss, *extra):
        grad = grad_of(objective)
        for it in idx:
            _, g = grad(p, jnp.asarray(train[i].x[it]),
                        jnp.asarray(train[i].y[it]), *extra)
            p = jax.tree.map(lambda w, gw: w - lr * gw, p, g)
        return p

    def prox(p, x, y, anchor, active):
        return cnn.loss(p, x, y) + active * baselines.prox_term(
            p, anchor, cfg.prox_mu)

    def adopt_mean(params):
        stacked = _stack(params)
        g = baselines.fedavg_aggregate(stacked, sim.sizes, pm)
        return _unstack(baselines.broadcast_global(g, stacked, pm), n)

    maml_step = jax.jit(lambda p, x, y: baselines.perfedavg_step(
        cnn.loss, p, x[:B // 2], y[:B // 2], x[B // 2:], y[B // 2:],
        cfg.maml_inner_lr, lr)[0])
    params = _unstack(sim.params0, n)
    pi = jnp.full((len(nbr),), 1.0 / len(nbr), jnp.float32)
    key = jax.random.PRNGKey(cfg.seed + 7)
    for _ in range(rounds):
        key, k_sample, k_erase = jax.random.split(key, 3)
        ks = jax.random.split(k_sample, n)
        idx = [np.asarray(jax.random.randint(ks[i], (steps, B), 0,
                                             len(train[i])))
               for i in range(n)]
        if method in ("local", "fedavg", "pfedwn"):
            params = [sgd(params[i], i, idx[i]) for i in range(n)]
        if method == "fedprox":
            g = baselines.fedavg_aggregate(_stack(params), sim.sizes, pm)
            params = [sgd(params[i], i, idx[i], prox, g, float(pm[i]))
                      for i in range(n)]
        if method == "perfedavg":
            for i in range(n):
                for it in idx[i]:
                    params[i] = maml_step(params[i],
                                          jnp.asarray(train[i].x[it]),
                                          jnp.asarray(train[i].y[it]))
        if method == "fedamp":
            stacked = _stack(params)
            xi = baselines.fedamp_weights(stacked, cfg.fedamp_sigma, pm,
                                          cfg.fedamp_self_weight)
            cloud = _unstack(baselines.fedamp_cloud_models(stacked, xi), n)
            params = [sgd(params[i], i, idx[i], prox, cloud[i], 1.0)
                      for i in range(n)]
        if method in ("fedavg", "fedprox", "perfedavg"):
            params = adopt_mean(params)
        if method == "pfedwn":
            neighbors = _stack([params[j] for j in nbr])
            _, pi, _ = pfedwn.em_refine_loop(
                fns, neighbors, pi, jnp.asarray(train[0].x[:cfg.em_subset]),
                jnp.asarray(train[0].y[:cfg.em_subset]), iters=cfg.em_iters,
                lr=lr, min_weight=PFLConfig().em_min_weight,
                component_steps=cfg.em_component_steps)
            link_ok = link_success_mask(k_erase, sim.p_err[nbr])
            mixed = aggregation.mix_params_with_erasures(
                params[0], neighbors, pi, cfg.alpha, link_ok)
            params[0] = sgd(mixed, 0, idx[0])
    target = params[0]
    if method == "perfedavg":
        target = baselines.maml_adapt(
            cnn.loss, target, jnp.asarray(train[0].x[:cfg.adapt_subset]),
            jnp.asarray(train[0].y[:cfg.adapt_subset]), cfg.maml_inner_lr)
    t_acc = float(cnn.accuracy(target, test[0].x, test[0].y))
    mean_acc = float(np.mean([cnn.accuracy(params[i], test[i].x, test[i].y)
                              for i in np.where(pm)[0]]))
    return _stack(params), pi, t_acc, mean_acc


@pytest.mark.parametrize("method", METHODS)
def test_engine_matches_plain_rounds(fused_sim, method):
    """Three rounds of the engine's block equal three rounds written client
    by client (:func:`_plain_rounds`) on the same seed: the parameters
    within float32 rounding carried through three rounds of SGD (atol
    1e-6, about 17 ulps at the weights' largest magnitude, 0.78, plus rtol
    1e-5: vmapped, grouped convolutions sum in another order than one
    client's; XLA:CPU differs by at most 1.2e-7), π within 1e-4 and the
    accuracies within 5e-3."""
    sim = fused_sim
    block = sim.block_fn(method).jit(3)
    (params, pi, _), (t_acc, mean_acc, _, _) = block(sim.initial_state(),
                                                     sim.staged_data())
    ref_params, ref_pi, ref_t_acc, ref_mean = _plain_rounds(sim, method, 3)
    for got, want in zip(jax.tree.leaves(params),
                         jax.tree.leaves(ref_params)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6, err_msg=method)
    np.testing.assert_allclose(np.asarray(pi), np.asarray(ref_pi), atol=1e-4)
    assert abs(float(t_acc) - ref_t_acc) <= 5e-3
    assert abs(float(mean_acc) - ref_mean) <= 5e-3


def test_block_schedule_matches_legacy_eval_points():
    # evaluate when rnd % e == 0 or rnd == rounds-1
    for rounds, e in [(1, 1), (4, 1), (5, 2), (6, 3), (9, 4), (8, 4)]:
        blocks = block_schedule(rounds, e)
        assert sum(blocks) == rounds
        evals = {r for r in range(rounds) if r % e == 0 or r == rounds - 1}
        assert len(blocks) == len(evals)
        assert blocks[0] == 1                      # eval after round 0


def test_fused_syncs_only_at_eval_boundaries(fused_sim):
    """The fused engine performs exactly one device call per eval boundary
    (rounds=3, eval_every=2 => blocks [1, 2])."""
    fused = fused_sim
    h = fused.run("local")
    assert fused.last_run_stats["blocks"] == [1, 2]
    assert fused.last_run_stats["device_calls"] == 2
    assert len(h["target_acc"]) == 2


def test_fused_block_is_single_executable_without_host_transfers(fused_sim):
    """A whole round block lowers to ONE compiled executable whose HLO has
    no host callbacks/infeed/outfeed, with the rounds scanned inside it (a
    `while` op), so no per-round host transfer can exist."""
    fused = fused_sim
    block = fused.block_fn("pfedwn")
    state = fused.initial_state()
    lowered = block.lower(state, fused.staged_data(), 3)
    # the shared analyzer checks: no host markers/callback custom-calls,
    # donated carry, rounds scanned inside (while op), nonzero flops, and
    # no collectives on the single-device fused block
    report = lint_hlo.assert_round_block(lowered, expect_collectives=False)
    assert report.has_scan_loop and report.donated


def _constant_elements(lowered_text):
    """Element counts of every constant in a lowered StableHLO module."""
    counts = []
    for dims in re.findall(r"stablehlo\.constant\s+dense(?:_resource)?<[^>]*>"
                           r"\s*:\s*tensor<([^>]*)>", lowered_text):
        counts.append(int(np.prod([int(d) for d in dims.split("x")[:-1]])))
    return counts


@pytest.mark.parametrize("method", ["local", "pfedwn"])
def test_fused_block_takes_client_data_as_argument(fused_sim, method):
    """The staged client data reaches the block as an argument, never as
    a constant: the executable's argument bytes cover the padded train and
    test stacks, and no constant in the lowered module is as large as one
    client's train rows."""
    fused = fused_sim
    data = fused.staged_data()
    lowered = fused.block_fn(method).lower(fused.initial_state(), data, 2)
    stacks = (data.train_x, data.train_y, data.test_x, data.test_y,
              data.test_mask)
    data_bytes = sum(x.nbytes for x in stacks)
    mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes >= data_bytes
    consts = _constant_elements(lowered.as_text())
    assert consts, "no constants parsed: the pattern no longer matches"
    assert max(consts) < data.train_x[0].size


def test_runs_record_their_own_compiles():
    """Every block compiles inside the run that first needs it: each event
    is schema-valid, each compile event carries its run's id, and each
    run's summary counts exactly its own compiles. A repeated method
    reuses its executables and records none."""
    model_cfg, train, test, pm, p_err = _tiny_setup()
    sim = FederatedSimulation(model_cfg, train, test, pm, p_err,
                              _cfg())
    for method in ("local", "pfedwn", "pfedwn"):
        sim.run(method)
    events = sim.recorder.events
    assert all(obs.validate_event(e) == [] for e in events)
    summaries = {e["run_id"]: e for e in events if e["type"] == "summary"}
    assert list(summaries) == ["local/fused#1", "pfedwn/fused#2",
                               "pfedwn/fused#3"]
    for run_id, want in zip(summaries, (2, 2, 0)):   # block lengths {1, 2}
        names = [e["name"] for e in events
                 if e["type"] == "compile" and e["run_id"] == run_id]
        assert len(names) == want, (run_id, names)
        counters = summaries[run_id]["metrics"]["counters"]
        assert counters.get("compile_events", 0) == want


def test_fedprox_single_pass_masking():
    """With nobody participating, the prox pull is inactive for every client
    and fedprox must degenerate to plain local training — the single-pass
    `active`-gated objective replaces the old double (_prox_all + _local_all)
    sweep."""
    model_cfg, train, test, _, p_err = _tiny_setup()
    pm_none = np.zeros(len(train), bool)
    sim = FederatedSimulation(model_cfg, train, test, pm_none, p_err,
                              _cfg())
    h_prox = sim.run("fedprox")
    sim2 = FederatedSimulation(model_cfg, train, test, pm_none, p_err,
                               _cfg())
    h_local = sim2.run("local")
    np.testing.assert_allclose(h_prox["target_acc"], h_local["target_acc"],
                               atol=1e-6)
    np.testing.assert_allclose(h_prox["mean_participant_acc"],
                               h_local["mean_participant_acc"], atol=1e-6)


def test_em_refine_loop_shared_body():
    """pfedwn.em_refine_loop (the body shared by pfedwn_round and the fused
    simulator) reproduces the fixed-loss EM fixed point of em.em_weights
    when component refinement is off."""
    def psl(w, x, y):
        return jnp.sum((w[None, :] - x) ** 2, axis=1)

    fns = pfedwn.ModelFns(
        per_sample_loss=psl,
        loss=lambda w, x, y: jnp.mean(psl(w, x, y)),
        accuracy=lambda w, x, y: -jnp.mean(psl(w, x, y)))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(1.0, 0.1, (32, 4)))
    comps = jnp.stack([jnp.full((4,), 1.0), jnp.full((4,), -5.0)])
    pi0 = jnp.array([0.5, 0.5])
    out_comps, pi_star, hist = pfedwn.em_refine_loop(
        fns, comps, pi0, x, None, iters=6, lr=0.05, min_weight=1e-8,
        component_steps=0)
    losses = pfedwn.component_losses(fns, comps, x, None)
    pi_ref, _ = em.em_weights(pi0, losses, iters=6, min_weight=1e-8)
    np.testing.assert_allclose(np.asarray(pi_star), np.asarray(pi_ref),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_comps), np.asarray(comps))
    assert hist.shape == (6, 2)
    assert float(pi_star[0]) > 0.9                 # similar component wins


def test_restrict_target_train_restages_device_data():
    model_cfg, train, test, pm, p_err = _tiny_setup()
    sim = FederatedSimulation(model_cfg, train, test, pm, p_err,
                              _cfg())
    before = int(sim._train_len[0])
    sim.run("local")
    sim.restrict_target_train(24)
    assert int(sim._train_len[0]) == 24
    assert int(sim.sizes[0]) == 24
    assert before > 24
    h = sim.run("pfedwn")                          # rebuilt engine still runs
    assert 0.0 <= h["max_target_acc"] <= 1.0


def test_host_driven_engine_is_refused():
    with pytest.raises(ValueError, match="host-driven engine"):
        _cfg(fused=False)
