"""N-client federated simulator — the paper-faithful engine behind
Tables II/III and Figs 1, 8, 9.

Clients hold stacked params (N, ...); all per-client math is vmapped and
jitted. The wireless layer supplies (participant mask, per-link P_err); this
module runs the learning side for any of:

  local | fedavg | fedprox | perfedavg | fedamp | pfedwn

Three execution engines share the same round mathematics:

  **fused** (default, ``FedSimConfig.fused=True``): all train/test tensors
  move to the device once, on the first run (padded + stacked via
  ``data.synthetic.stack_datasets``), and every round block takes them as
  a non-donated argument (:class:`BlockData`), so no client data is baked
  into an executable; minibatch indices are drawn with
  ``jax.random`` *inside* the jitted step; one donated round-step per method
  fuses local-SGD → EM → erasure-gated aggregation → post-aggregation local
  training, and ``eval_every``-sized blocks of rounds run through a single
  ``jax.lax.scan`` so the host only syncs at eval boundaries. Evaluation
  maps ``cnn.masked_accuracy`` over the clients' padded test stacks a few
  clients at a time (:func:`client_accuracies`), which bounds its memory.

  **sharded** (``FedSimConfig.sharded=True``): the fused round block under
  ``repro.compat.shard_map`` over a ``("clients",)`` mesh — clients are a
  server-free D2D population, i.e. naturally data-parallel. The stacked
  per-client state (params, opt state, device-resident data, tap buffers)
  is partitioned along the client axis and every cross-client exchange is
  an explicit collective: a ``psum`` for the fedavg/fedprox/perfedavg
  global mean, ONE per-round ``all_gather`` of the peer models for
  pfedwn's EM components and fedamp's attention (hoisted out of the EM
  iteration loop — collectives ride the scan, never the inner loops), and
  a psum-reduced vmapped eval. The sharded block keeps every fused-engine
  invariant: donated, one executable per (method, block length), no host
  callbacks, device-side taps riding the scan, and the same ``jax.random``
  index stream (drawn replicated, sliced locally), so sharded == fused ==
  legacy trajectories per method. ``shard_devices`` picks the mesh size
  (default: every visible device); it must divide N.

  **legacy** (``fused=False``): the original host-driven loop — per-round
  numpy batch materialization + upload, one jitted dispatch per phase, and
  a Python per-client eval loop. Kept callable for parity testing and
  debugging; it draws the *same* ``jax.random`` index stream as the other
  engines, so identical seeds produce identical trajectories (the parity
  tests assert this).

Paper fidelity notes:
  - optimizer: plain SGD (Eq 2), E local epochs per round, lr η
  - pFedWN target aggregation per Algorithm 2; EM weights per Algorithm 1
    (the shared ``pfedwn.em_refine_loop`` body)
  - baselines restricted to the channel-selected participants (Sec V-A)
  - local epochs are approximated by a fixed number of minibatch steps per
    round (max over participants of ceil(k_n / B)) with per-client
    with-replacement sampling — necessary for vmap; distributional effect
    is negligible at these scales.

Telemetry (``repro.obs``): every simulation owns a ``RunRecorder``. The
fused engine's **device-side metrics tap** (``FedSimConfig.taps``) emits
per-round scalars — per-client train loss (free: the forward value already
computed by ``value_and_grad``), EM weight entropy, effective neighbor
count, link success rate — as outputs of the round scan, stacked on device
and drained only at eval boundaries, so instrumentation adds no host syncs
and the round block stays a single executable. The legacy engine records
the same scalars host-side, so fused and legacy RunRecords are
schema-identical. Set ``FedSimConfig.record_dir`` to persist the JSONL
RunRecord + Chrome trace (``python -m repro.obs.report`` summarizes them).

Config fields that change compiled behavior (``lr``, ``alpha``,
``em_uniform``, ``taps``, …) are read when a method's engine is first
built; mutate them before the first ``run`` of a method, or call
``invalidate_caches``.
"""
from __future__ import annotations

import functools
import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat, obs
from repro.configs.base import PFLConfig
from repro.configs.paper_cnn import CNNConfig
from repro.core import aggregation, baselines
from repro.core.pfedwn import (ModelFns, effective_neighbors, em_refine_loop,
                               pi_entropy)
from repro.core.selection import link_success_mask, link_success_rate
from repro.data.synthetic import SyntheticImageDataset, stack_datasets
from repro.models import cnn
from repro.sharding.rules import (client_axis_spec, client_stack_shardings,
                                  client_tap_spec)

PyTree = Any

METHODS = ("local", "fedavg", "fedprox", "perfedavg", "fedamp", "pfedwn")

# the named scopes of a round block's phases (a method runs a subset); a
# trace reads each phase's device time from them (``device_scopes``)
PHASES = ("local_sgd", "em", "mix", "post_sgd", "eval")

# test rows whose activations one eval chunk keeps live (at the paper's
# CIFAR-10 width on a v5e, one client's 1,198 test rows take 0.6 GiB of
# temporaries; two clients' take 3.5 GiB)
EVAL_CHUNK_ROWS = 2048


class BlockData(NamedTuple):
    """The device tensors a round block reads. Every block call takes them
    as a non-donated argument; a block that closed over them would bake
    every client's data into its executable as constants."""
    train_x: Any       # (N, K, H, W, C) padded train stacks
    train_y: Any       # (N, K)
    test_x: Any        # (N, Kt, H, W, C) padded test stacks
    test_y: Any        # (N, Kt)
    test_mask: Any     # (N, Kt) real (unpadded) test rows
    em_x: Any          # the target's EM E-step subset
    em_y: Any
    adapt_x: Any       # the target's Per-FedAvg adaptation subset
    adapt_y: Any


class TargetData(NamedTuple):
    """The target's own train/test tensors, replicated on every shard of
    the client mesh (the sharded engine's target math runs on each)."""
    train_x: Any
    train_y: Any
    test_x: Any
    test_y: Any
    test_mask: Any


def client_accuracies(params, test_x, test_y, test_mask) -> jax.Array:
    """Per-client masked test accuracy over a leading client axis. Clients
    are evaluated a few at a time (about ``EVAL_CHUNK_ROWS`` test rows per
    chunk): all N clients' padded test stacks at once do not fit one chip
    at the paper's widths."""
    n, k_test = test_y.shape
    chunk = max(1, min(n, EVAL_CHUNK_ROWS // max(k_test, 1)))
    return jax.lax.map(lambda a: cnn.masked_accuracy(*a),
                       (params, test_x, test_y, test_mask),
                       batch_size=chunk)


@dataclass
class FedSimConfig:
    rounds: int = 50
    batch_size: int = 64
    lr: float = 0.05
    alpha: float = 0.5                 # Eq (1) self-weight
    em_iters: int = 5
    em_component_steps: int = 1
    em_subset: int = 512               # target samples driving the EM E-step
    adapt_subset: int = 256            # Per-FedAvg eval-time adaptation set
    prox_mu: float = 0.1               # FedProx
    maml_inner_lr: float = 0.01        # Per-FedAvg
    fedamp_sigma: float = 1e4
    fedamp_self_weight: float = 0.5
    erasures: bool = True              # re-sample link failures each round
    eval_every: int = 1
    seed: int = 0
    fused: bool = True                 # scan-over-rounds engine (see module doc)
    sharded: bool = False              # scan engine under shard_map("clients")
    shard_devices: Optional[int] = None  # client-mesh size (None: all devices)
    em_uniform: bool = False           # ablation: uniform π instead of EM
    taps: bool = True                  # device-side per-round metrics tap
    record_dir: Optional[str] = None   # persist RunRecord JSONL + trace here
    run_name: Optional[str] = None     # record file stem (default: derived)


def batch_rows(stack: jax.Array, rows: jax.Array) -> jax.Array:
    """``stack[rows]`` of one client's (K, H, W, C) image stack, gathered
    as flat rows of H*W*C values and reshaped back to images.

    On a v5e, two rounds of vmapped local SGD at 32x32x3 departed from the
    reference (clients' loss 13% high) with the gather of (H, W, C) slices,
    from a stack the compiler lays out with C outside H and W, and followed
    it with flat rows. That gather alone returns the right rows there;
    which op of the SGD program departs is not isolated. At 28x28x1 the
    flat rows give the same results and run no slower."""
    flat = stack.reshape(stack.shape[0], -1)[rows]
    return flat.reshape(rows.shape + stack.shape[1:])


def block_schedule(rounds: int, eval_every: int) -> List[int]:
    """Round-block lengths between host syncs. Matches the legacy eval
    schedule exactly: evaluate after round r when ``r % eval_every == 0`` or
    ``r == rounds - 1`` — so blocks are [1, eval_every, ..., tail]."""
    evals = sorted(set(range(0, rounds, max(eval_every, 1))) | {rounds - 1})
    blocks, prev = [], -1
    for r in evals:
        blocks.append(r - prev)
        prev = r
    return blocks


class RoundBlock:
    """One method's round block: ``fn(state, data, length)`` jitted once per
    block length, with donated ``state``. Each jit is named for the method
    and the length (``pfedwn_block_r1``), so a device trace's module names
    say which block ran and no two executables share op names."""

    def __init__(self, fn: Callable, name: str):
        self._fn, self._name = fn, name
        self._jits: Dict[int, Any] = {}

    def jit(self, length: int):
        """The donated jit scanning ``length`` rounds."""
        length = int(length)
        if length not in self._jits:
            fn = self._fn

            def at_length(state, data):
                return fn(state, data, length)

            at_length.__name__ = f"{self._name}_r{length}"
            self._jits[length] = jax.jit(at_length, donate_argnums=(0,))
        return self._jits[length]

    def lower(self, state, data, length: int):
        return self.jit(length).lower(state, data)


class FederatedSimulation:
    """target client = index 0 by convention; clients 1..N-1 are neighbors."""

    def __init__(self, model_cfg: CNNConfig,
                 train_sets: List[SyntheticImageDataset],
                 test_sets: List[SyntheticImageDataset],
                 participant_mask: np.ndarray,     # (N,) bool, incl. target
                 p_err: np.ndarray,                # (N,) target-link P_err
                 sim: FedSimConfig,
                 recorder: Optional[obs.RunRecorder] = None):
        self.model_cfg = model_cfg
        self.sim = sim
        self.n = len(train_sets)
        self.recorder = recorder or self._default_recorder()
        self.train_sets = train_sets
        self.test_sets = test_sets
        self.participants = jnp.asarray(participant_mask, bool)
        self.p_err = jnp.asarray(p_err, jnp.float32)
        self.sizes = jnp.asarray([len(d) for d in train_sets], jnp.float32)

        self.fns = ModelFns(
            per_sample_loss=lambda p, x, y: cnn.per_sample_nll(p, x, y),
            loss=lambda p, x, y: cnn.loss(p, x, y),
            accuracy=lambda p, x, y: cnn.accuracy(p, x, y),
        )
        key = jax.random.PRNGKey(sim.seed)
        keys = jax.random.split(key, self.n)
        self.params0 = jax.vmap(
            lambda k: cnn.init_params(k, model_cfg))(keys)

        self._neighbor_idx = np.where(np.asarray(self.participants)
                                      & (np.arange(self.n) != 0))[0]
        self._m = len(self._neighbor_idx)
        self._data = None                      # staged on the first run
        self._stage_data()
        self._blocks: Dict[str, RoundBlock] = {}  # method -> round block
        self._block_execs: Dict[Tuple, Any] = {}  # (engine, method, len) AOT
        self._legacy: Dict[str, Any] = {}      # per-phase jits, built lazily
        self._sharded_blocks: Dict[str, RoundBlock] = {}
        self._client_mesh = None               # built on first sharded run
        self.last_run_stats: Dict[str, Any] = {}

    @property
    def engine(self) -> str:
        """Active engine name: ``sharded`` wins over ``fused``/``legacy``."""
        if self.sim.sharded:
            return "sharded"
        return "fused" if self.sim.fused else "legacy"

    def _default_recorder(self) -> obs.RunRecorder:
        """In-memory RunRecorder, persisted when ``record_dir`` is set."""
        sim = self.sim
        jsonl = trace = None
        if sim.record_dir:
            engine = self.engine
            name = sim.run_name or f"fedsim_{engine}_N{self.n}_seed{sim.seed}"
            jsonl = os.path.join(sim.record_dir, f"{name}.jsonl")
            trace = os.path.join(sim.record_dir, f"{name}.trace.json")
        return obs.RunRecorder(jsonl_path=jsonl, trace_path=trace)

    # ------------------------------------------------------------- staging

    def _stage_data(self) -> None:
        """Per-client sample counts and the round's step count; the data
        itself moves to the device on the first scan-engine run
        (:meth:`staged_data`)."""
        lengths = [len(d) for d in self.train_sets]
        self._train_len = jnp.asarray(lengths, jnp.int32)
        self.steps_per_round = max(
            1, int(np.ceil(max(lengths) / self.sim.batch_size)))
        self._data = None

    def _host_block_data(self) -> BlockData:
        """The padded client stacks and the target's un-padded EM/adapt
        subsets, as host arrays."""
        sim = self.sim
        tx, ty, _, _ = stack_datasets(self.train_sets)
        ex, ey, _, emask = stack_datasets(self.test_sets)
        d0 = self.train_sets[0]
        return BlockData(tx, ty, ex, ey, emask,
                         d0.x[:sim.em_subset], d0.y[:sim.em_subset],
                         d0.x[:sim.adapt_subset], d0.y[:sim.adapt_subset])

    def staged_data(self):
        """The data argument of this engine's round blocks, moved to the
        device once and kept: a :class:`BlockData` on the default device
        for the fused engine; for the sharded engine a ``(BlockData,
        TargetData)`` pair with the client stacks split over the client
        mesh and everything else replicated."""
        if self._data is None:
            with self.recorder.span("stage_data", n_clients=self.n,
                                    engine=self.engine):
                if self.engine == "sharded":
                    self._data = self._stage_sharded()
                else:
                    self._data = jax.device_put(self._host_block_data())
        return self._data

    def restrict_target_train(self, keep: int) -> None:
        """Shrink the target's train set to its first ``keep`` samples (the
        data-poor-target ablations) and restage device tensors + caches."""
        d = self.train_sets[0]
        d.x, d.y = d.x[:keep], d.y[:keep]
        self.sizes = self.sizes.at[0].set(float(len(d)))
        self.invalidate_caches()

    def invalidate_caches(self) -> None:
        """Rebuild device staging and drop compiled engines — call after
        mutating ``self.sim`` or any dataset in place."""
        self._stage_data()
        self._blocks.clear()
        self._block_execs.clear()
        self._legacy.clear()
        self._sharded_blocks.clear()
        self._client_mesh = None

    # ---------------------------------------------------- shared round math
    #
    # One closure set per build: the *same* per-method round body is scanned
    # by the fused engine and (phase-split) dispatched by the legacy engine,
    # so the two paths agree bit-for-bit given the same index stream.

    def _sample_idx_fn(self):
        """(N, steps, B) with-replacement minibatch indices, drawn on device
        from a single round key — shared by both engines."""
        steps, B, N = self.steps_per_round, self.sim.batch_size, self.n
        train_len = jnp.maximum(self._train_len, 1)

        def sample_idx(key):
            ks = jax.random.split(key, N)
            return jax.vmap(
                lambda k, n: jax.random.randint(k, (steps, B), 0, n)
            )(ks, train_len)

        return sample_idx

    def _sgd_one_fn(self):
        """Per-client SGD over a round's minibatch indices; the batch gather
        happens on device inside the scan body (no (N, steps, B, ...) batch
        tensor is ever materialized). Returns ``(params, mean minibatch
        loss)`` — the loss is the forward value ``value_and_grad`` computes
        anyway, so the metrics tap costs nothing here (and XLA dead-code
        eliminates it when taps are off)."""
        fns, lr = self.fns, self.sim.lr

        def sgd_one(p, dx, dy, idx):
            def step(p, it):
                l, g = jax.value_and_grad(fns.loss)(p, batch_rows(dx, it),
                                                    dy[it])
                return jax.tree.map(lambda w, gw: w - lr * gw, p, g), l

            out, losses = jax.lax.scan(step, p, idx)
            return out, jnp.mean(losses)

        return sgd_one

    def _trainers(self) -> Dict[str, Any]:
        """The per-client trainer closures shared by the fused and sharded
        round bodies. Every ``*_all`` is a vmap over a leading client axis
        and is indifferent to whether that axis is the full N-client stack
        (fused) or one shard's S-client slab (sharded)."""
        sim, fns = self.sim, self.fns
        lr, B = sim.lr, sim.batch_size
        sgd_one = self._sgd_one_fn()

        def prox_one(p, anchor, active, dx, dy, idx):
            # single pass over all clients: the prox pull is gated by
            # `active`, so non-participants take plain local-SGD gradients
            # (no second `_local_all` sweep + merge).
            def obj(pp, x, y):
                return fns.loss(pp, x, y) + active * baselines.prox_term(
                    pp, anchor, sim.prox_mu)

            def step(pp, it):
                l, g = jax.value_and_grad(obj)(pp, batch_rows(dx, it),
                                                dy[it])
                return jax.tree.map(lambda w, gw: w - lr * gw, pp, g), l

            out, losses = jax.lax.scan(step, p, idx)
            return out, jnp.mean(losses)

        def maml_one(p, dx, dy, idx):
            half = B // 2

            def step(pp, it):
                x, y = batch_rows(dx, it), dy[it]
                pp, l = baselines.perfedavg_step(
                    fns.loss, pp, x[:half], y[:half], x[half:], y[half:],
                    sim.maml_inner_lr, lr)
                return pp, l

            out, losses = jax.lax.scan(step, p, idx)
            return out, jnp.mean(losses)

        def amp_one(p, cloud, dx, dy, idx):
            def obj(pp, x, y):
                return fns.loss(pp, x, y) + baselines.prox_term(
                    pp, cloud, sim.prox_mu)

            def step(pp, it):
                l, g = jax.value_and_grad(obj)(pp, batch_rows(dx, it),
                                                dy[it])
                return jax.tree.map(lambda w, gw: w - lr * gw, pp, g), l

            out, losses = jax.lax.scan(step, p, idx)
            return out, jnp.mean(losses)

        return {"sgd_one": sgd_one,
                "local_all": jax.vmap(sgd_one),
                "prox_all": jax.vmap(prox_one,
                                     in_axes=(0, None, 0, 0, 0, 0)),
                "maml_all": jax.vmap(maml_one),
                "amp_all": jax.vmap(amp_one)}

    def _make_round_body(self, method: str):
        """Build ``body(data, state, _) -> (state, tap)`` for one round of
        `method`. data is the block's :class:`BlockData`; state = (params
        (N,...), pi (M,), key); ``tap`` is the per-round metrics dict when
        ``sim.taps`` (stacked by the block scan, drained at eval boundaries)
        or None when taps are off."""
        sim, fns = self.sim, self.fns
        taps_on = sim.taps
        lr = sim.lr
        pm = self.participants
        pmf = pm.astype(jnp.float32)
        sizes = self.sizes
        nbr = jnp.asarray(self._neighbor_idx)
        M = self._m
        p_err_nbr = self.p_err[nbr] if M else jnp.zeros((0,), jnp.float32)
        em_min_w = PFLConfig().em_min_weight
        sample_idx = self._sample_idx_fn()
        tr = self._trainers()
        sgd_one, local_all = tr["sgd_one"], tr["local_all"]
        prox_all, maml_all, amp_all = (tr["prox_all"], tr["maml_all"],
                                       tr["amp_all"])

        # non-collaborative / all-participant defaults for the tap scalars;
        # the pfedwn branch overwrites them with its channel-aware values
        nbr_count = jnp.maximum(jnp.sum(pmf) - 1.0, 0.0)

        def body(data: BlockData, state, _):
            train_x, train_y = data.train_x, data.train_y
            params, pi, key = state
            key, k_sample, k_erase = jax.random.split(key, 3)
            link_rate = jnp.float32(1.0)
            with jax.named_scope("local_sgd"):
                idx = sample_idx(k_sample)

            if method == "local":
                with jax.named_scope("local_sgd"):
                    params, train_loss = local_all(params, train_x, train_y,
                                                   idx)
                eff_nbr = jnp.float32(0.0)

            elif method == "fedavg":
                with jax.named_scope("local_sgd"):
                    params, train_loss = local_all(params, train_x, train_y,
                                                   idx)
                with jax.named_scope("mix"):
                    g = baselines.fedavg_aggregate(params, sizes, pm)
                    params = baselines.broadcast_global(g, params, pm)
                eff_nbr = nbr_count

            elif method == "fedprox":
                with jax.named_scope("mix"):
                    g = baselines.fedavg_aggregate(params, sizes, pm)
                with jax.named_scope("local_sgd"):
                    params, train_loss = prox_all(params, g, pmf, train_x,
                                                  train_y, idx)
                with jax.named_scope("mix"):
                    g = baselines.fedavg_aggregate(params, sizes, pm)
                    params = baselines.broadcast_global(g, params, pm)
                eff_nbr = nbr_count

            elif method == "perfedavg":
                with jax.named_scope("local_sgd"):
                    params, train_loss = maml_all(params, train_x, train_y,
                                                  idx)
                with jax.named_scope("mix"):
                    g = baselines.fedavg_aggregate(params, sizes, pm)
                    params = baselines.broadcast_global(g, params, pm)
                eff_nbr = nbr_count

            elif method == "fedamp":
                with jax.named_scope("mix"):
                    xi = baselines.fedamp_weights(params, sim.fedamp_sigma,
                                                  pm, sim.fedamp_self_weight)
                    cloud = baselines.fedamp_cloud_models(params, xi)
                with jax.named_scope("local_sgd"):
                    params, train_loss = amp_all(params, cloud, train_x,
                                                 train_y, idx)
                eff_nbr = nbr_count

            elif method == "pfedwn":
                # 1. everyone trains locally (neighbors included)
                with jax.named_scope("local_sgd"):
                    params, train_loss = local_all(params, train_x, train_y,
                                                   idx)
                # 2-4. target: EM weights + erasure-gated aggregation
                with jax.named_scope("em"):
                    target = jax.tree.map(lambda p: p[0], params)
                    neighbors = jax.tree.map(lambda p: p[nbr], params)
                    if sim.em_uniform:
                        pi_new = jnp.full((M,), 1.0 / max(M, 1))
                    else:
                        _, pi_new, _ = em_refine_loop(
                            fns, neighbors, pi, data.em_x, data.em_y,
                            iters=sim.em_iters, lr=lr, min_weight=em_min_w,
                            component_steps=sim.em_component_steps)
                with jax.named_scope("mix"):
                    if sim.erasures:
                        link_ok = link_success_mask(k_erase, p_err_nbr)
                    else:
                        link_ok = jnp.ones((M,), bool)
                    mixed = aggregation.mix_params_with_erasures(
                        target, neighbors, pi_new, sim.alpha, link_ok)
                # 5. target trains locally from the aggregate
                with jax.named_scope("post_sgd"):
                    mixed, loss0 = sgd_one(mixed, train_x[0], train_y[0],
                                           idx[0])
                    params = jax.tree.map(
                        lambda s, t: s.at[0].set(t.astype(s.dtype)),
                        params, mixed)
                pi = pi_new
                # the target's tap entry tracks the post-aggregation pass
                train_loss = train_loss.at[0].set(loss0)
                link_rate = link_success_rate(link_ok)
                eff_nbr = effective_neighbors(pi_new, link_ok)

            else:
                raise ValueError(f"unknown method {method!r}")

            tap = None
            if taps_on:
                tap = {"train_loss": train_loss,
                       "em_entropy": pi_entropy(pi),
                       "link_success_rate": link_rate,
                       "effective_neighbors": eff_nbr}
            return (params, pi, key), tap

        return body

    def _make_eval_fn(self, method: str):
        """(params, data) -> (target_acc, mean_participant_acc) over every
        client's padded test stack."""
        sim = self.sim
        pmf = self.participants.astype(jnp.float32)
        fns = self.fns

        @jax.named_scope("eval")
        def eval_fn(params, data: BlockData):
            test_x, test_y, test_mask = (data.test_x, data.test_y,
                                         data.test_mask)
            tgt = jax.tree.map(lambda p: p[0], params)
            if method == "perfedavg":
                tgt = baselines.maml_adapt(fns.loss, tgt, data.adapt_x,
                                           data.adapt_y, sim.maml_inner_lr)
            t_acc = cnn.masked_accuracy(tgt, test_x[0], test_y[0],
                                        test_mask[0])
            accs = client_accuracies(params, test_x, test_y, test_mask)
            mean_acc = jnp.sum(accs * pmf) / jnp.maximum(jnp.sum(pmf), 1.0)
            return t_acc, mean_acc

        return eval_fn

    # --------------------------------------------------------- fused engine

    def block_fn(self, method: str) -> RoundBlock:
        """The donated round-block runner for ``method``: ``block.lower(state,
        data, length)`` lowers the jit that scans ``length`` rounds and
        evaluates, all in one compiled executable (``state`` buffers are
        donated so params update in place where the backend allows;
        ``data`` is :meth:`staged_data`, not donated)."""
        method = method.lower()
        if method not in self._blocks:
            body = self._make_round_body(method)
            eval_fn = self._make_eval_fn(method)

            def block(state, data, length):
                # tap scalars are stacked by the scan (device-side) and
                # leave the executable only here, with the eval outputs
                state, taps = jax.lax.scan(functools.partial(body, data),
                                           state, None, length=length)
                params, pi, _ = state
                t_acc, mean_acc = eval_fn(params, data)
                return state, (t_acc, mean_acc, pi, taps)

            self._blocks[method] = RoundBlock(block, f"{method}_block")
        return self._blocks[method]

    def _compiled_block(self, method: str, length: int, state,
                        data) -> Any:
        """AOT-compiled executable for one (engine, method, block length)
        shape, cached; compilation is spanned and the span and a compile
        event carry its FLOP/byte cost estimate."""
        key = (self.engine, method, int(length))
        exe = self._block_execs.get(key)
        if exe is None:
            block = (self.sharded_block_fn(method)
                     if self.engine == "sharded" else self.block_fn(method))
            with self.recorder.span("compile", cat="compile", method=method,
                                    rounds=length) as sp:
                exe = block.lower(state, data, length).compile()
            sp.set(**self.recorder.record_compile(
                f"{method}/block{length}", compiled=exe,
                seconds=sp.duration_s))
            self._block_execs[key] = exe
        return exe

    def device_scopes(self) -> Dict[str, Dict[str, Tuple]]:
        """``{module name: {op: (phase or None, layer or None)}}`` for every
        round block compiled so far: the scope of each op of its optimized
        HLO (:func:`repro.obs.scopes.op_scopes`), for attributing a device
        trace's op time to :data:`PHASES` and the model's
        :data:`repro.models.cnn.SCOPES`. Built only when called."""
        out = {}
        for exe in self._block_execs.values():
            text = exe.as_text()
            out[obs.hlo_module_name(text)] = obs.op_scopes(
                text, PHASES, cnn.SCOPES)
        return out

    def initial_state(self) -> Tuple[PyTree, jax.Array, jax.Array]:
        """(params, π, key) at round 0. Params are a fresh copy so donated
        block calls can't consume ``self.params0``."""
        params = jax.tree.map(jnp.copy, self.params0)
        pi = jnp.full((self._m,), 1.0 / max(self._m, 1), jnp.float32)
        key = jax.random.PRNGKey(self.sim.seed + 7)
        return params, pi, key

    # ------------------------------------------------------- sharded engine
    #
    # The fused round block under shard_map over a ("clients",) mesh. Each
    # of D devices owns a contiguous slab of S = N/D clients — params and
    # data stacks partitioned on their leading client axis, π/key, the
    # EM/adapt subsets and the target's own tensors replicated.
    # Cross-client exchange is explicit collectives riding the round scan
    # (never the inner EM/SGD loops): one psum for
    # the fedavg-family global mean, ONE all_gather per round for
    # pfedwn/fedamp peer models, and a psum-reduced eval. Small per-client
    # (N,)-vectors (sizes, masks, P_err) stay replicated closure constants
    # and are dynamic-sliced per shard; the minibatch index stream is drawn
    # replicated at full (N, steps, B) and sliced locally, so the sharded
    # trajectory matches fused/legacy bit-for-bit in expectation and to
    # float tolerance in practice. Target-only math (EM, Eq-1 mix, the
    # post-aggregation SGD pass) is computed redundantly on every shard
    # (SPMD style — cheaper than a host round-trip or a point-to-point
    # send) and written back only where the global client index is 0.

    def _client_mesh_info(self) -> Tuple[Any, int, int]:
        """(mesh, D, S): the ("clients",) mesh over the first D devices.
        D = ``sim.shard_devices`` (default: every visible device) and must
        divide N so each shard owns an equal contiguous slab of S clients."""
        if self._client_mesh is None:
            devs = jax.devices()
            d = self.sim.shard_devices or len(devs)
            if self.n % d != 0:
                raise ValueError(
                    f"client count N={self.n} must be divisible by the "
                    f"client-mesh size D={d}")
            if d > len(devs):
                raise ValueError(
                    f"shard_devices={d} but only {len(devs)} devices "
                    f"are visible")
            mesh = compat.make_mesh((d,), ("clients",),
                                    devices=np.asarray(devs[:d]))
            self._client_mesh = (mesh, d, self.n // d)
        return self._client_mesh

    def _stage_sharded(self) -> Tuple[BlockData, TargetData]:
        """The sharded engine's block data, placed straight from the host:
        the client stacks split along their leading N axis over "clients"
        (no device holds more than its slab), the EM/adapt subsets and the
        target's own tensors replicated."""
        mesh, _, _ = self._client_mesh_info()
        rep = NamedSharding(mesh, P())
        host = self._host_block_data()
        data = BlockData(*(
            jax.device_put(x, NamedSharding(mesh, client_axis_spec(x.ndim)))
            for x in host[:5]), *jax.device_put(host[5:], rep))
        target = TargetData(*jax.device_put(
            tuple(x[0] for x in host[:5]), rep))
        return data, target

    def initial_sharded_state(self) -> Tuple[PyTree, jax.Array, jax.Array]:
        """:meth:`initial_state` values, placed on the client mesh: params
        partitioned over "clients", π and the round key replicated."""
        mesh, _, _ = self._client_mesh_info()
        params, pi, key = self.initial_state()
        rep = NamedSharding(mesh, P())
        return (jax.device_put(params, client_stack_shardings(mesh, params)),
                jax.device_put(pi, rep), jax.device_put(key, rep))

    def _make_sharded_round_body(self, method: str, S: int):
        """``make_body(tx, ty, x0, y0, tx0, ty0) -> body(state, _)``: the
        per-shard round body factory. ``tx``/``ty`` are this shard's (S,
        ...) train slabs, ``x0``/``y0`` the EM subset and ``tx0``/``ty0``
        the target's train tensors, replicated (all bound inside
        shard_map); state = (params slab (S, ...), π (M,) replicated, key
        replicated). Mirrors :meth:`_make_round_body` step for step —
        same trainers, same ``jax.random`` stream — with the cross-client
        reads lowered to the two ``aggregation`` collectives."""
        sim, fns = self.sim, self.fns
        taps_on = sim.taps
        lr = sim.lr
        pm = self.participants
        pmf = pm.astype(jnp.float32)
        nbr = jnp.asarray(self._neighbor_idx)
        M = self._m
        p_err_nbr = self.p_err[nbr] if M else jnp.zeros((0,), jnp.float32)
        em_min_w = PFLConfig().em_min_weight
        sample_idx = self._sample_idx_fn()
        tr = self._trainers()
        sgd_one, local_all = tr["sgd_one"], tr["local_all"]
        prox_all, maml_all, amp_all = (tr["prox_all"], tr["maml_all"],
                                       tr["amp_all"])
        nbr_count = jnp.maximum(jnp.sum(pmf) - 1.0, 0.0)
        # globally-normalized fedavg weights (replicated); each shard
        # contracts its slice, the psum completes the sum over clients
        w_glob = self.sizes * pmf
        w_glob = w_glob / jnp.maximum(jnp.sum(w_glob), 1e-30)

        def slab(a, ofs):
            return jax.lax.dynamic_slice_in_dim(a, ofs, S, 0)

        def gmean(params, ofs):
            return aggregation.client_weighted_mean(params, slab(w_glob, ofs))

        def bcast(g, params, ofs):
            # broadcast_global on the local slab: participants adopt g
            pm_l = slab(pm, ofs)

            def bc(gl, p):
                m = pm_l.reshape((-1,) + (1,) * (p.ndim - 1))
                return jnp.where(m, gl[None].astype(p.dtype), p)

            return jax.tree.map(bc, g, params)

        def make_body(tx, ty, x0, y0, tx0, ty0):
            # the target's EM/mix/post-agg update runs redundantly on
            # every shard and lands only on global index 0
            def body(state, _):
                params, pi, key = state
                ofs = jax.lax.axis_index("clients") * S
                key, k_sample, k_erase = jax.random.split(key, 3)
                link_rate = jnp.float32(1.0)
                with jax.named_scope("local_sgd"):
                    idx = sample_idx(k_sample)   # replicated full-N draw:
                    idx_l = slab(idx, ofs)       # same stream as fused/legacy

                if method == "local":
                    with jax.named_scope("local_sgd"):
                        params, train_loss = local_all(params, tx, ty, idx_l)
                    eff_nbr = jnp.float32(0.0)

                elif method == "fedavg":
                    with jax.named_scope("local_sgd"):
                        params, train_loss = local_all(params, tx, ty, idx_l)
                    with jax.named_scope("mix"):
                        params = bcast(gmean(params, ofs), params, ofs)
                    eff_nbr = nbr_count

                elif method == "fedprox":
                    with jax.named_scope("mix"):
                        g = gmean(params, ofs)
                    with jax.named_scope("local_sgd"):
                        params, train_loss = prox_all(
                            params, g, slab(pmf, ofs), tx, ty, idx_l)
                    with jax.named_scope("mix"):
                        params = bcast(gmean(params, ofs), params, ofs)
                    eff_nbr = nbr_count

                elif method == "perfedavg":
                    with jax.named_scope("local_sgd"):
                        params, train_loss = maml_all(params, tx, ty, idx_l)
                    with jax.named_scope("mix"):
                        params = bcast(gmean(params, ofs), params, ofs)
                    eff_nbr = nbr_count

                elif method == "fedamp":
                    # one gather; attention rows for the local slab only
                    allp = aggregation.gather_clients(params)
                    with jax.named_scope("mix"):
                        xi = baselines.fedamp_weights(
                            allp, sim.fedamp_sigma, pm,
                            sim.fedamp_self_weight)
                        xi_l = slab(xi, ofs)                    # (S, N)
                        cloud_l = jax.tree.map(
                            lambda p: jnp.einsum(
                                "sm,m...->s...", xi_l.astype(jnp.float32),
                                p.astype(jnp.float32)).astype(p.dtype),
                            allp)
                    with jax.named_scope("local_sgd"):
                        params, train_loss = amp_all(params, cloud_l, tx, ty,
                                                     idx_l)
                    eff_nbr = nbr_count

                elif method == "pfedwn":
                    # 1. everyone trains locally on their shard
                    with jax.named_scope("local_sgd"):
                        params, train_loss = local_all(params, tx, ty, idx_l)
                    # 2-4. ONE per-round gather of the peer stack; EM and
                    # the erasure-gated Eq-1 mix run replicated
                    allp = aggregation.gather_clients(params)
                    with jax.named_scope("em"):
                        target = jax.tree.map(lambda p: p[0], allp)
                        neighbors = jax.tree.map(lambda p: p[nbr], allp)
                        if sim.em_uniform:
                            pi_new = jnp.full((M,), 1.0 / max(M, 1))
                        else:
                            _, pi_new, _ = em_refine_loop(
                                fns, neighbors, pi, x0, y0,
                                iters=sim.em_iters, lr=lr,
                                min_weight=em_min_w,
                                component_steps=sim.em_component_steps)
                    with jax.named_scope("mix"):
                        if sim.erasures:
                            link_ok = link_success_mask(k_erase, p_err_nbr)
                        else:
                            link_ok = jnp.ones((M,), bool)
                        mixed = aggregation.mix_params_with_erasures(
                            target, neighbors, pi_new, sim.alpha, link_ok)
                    # 5. target post-aggregation pass, written back only on
                    # the shard holding global client 0
                    with jax.named_scope("post_sgd"):
                        mixed, loss0 = sgd_one(mixed, tx0, ty0, idx[0])
                        is0 = (jnp.arange(S) + ofs) == 0
                        params = jax.tree.map(
                            lambda s, t: jnp.where(
                                is0.reshape((-1,) + (1,) * (s.ndim - 1)),
                                t[None].astype(s.dtype), s),
                            params, mixed)
                    pi = pi_new
                    train_loss = jnp.where(is0, loss0, train_loss)
                    link_rate = link_success_rate(link_ok)
                    eff_nbr = effective_neighbors(pi_new, link_ok)

                else:
                    raise ValueError(f"unknown method {method!r}")

                tap = None
                if taps_on:
                    # train_loss is the (S,) local slab — reassembled to
                    # (rounds, N) by the tap out_spec; scalars replicated
                    tap = {"train_loss": train_loss,
                           "em_entropy": pi_entropy(pi),
                           "link_success_rate": link_rate,
                           "effective_neighbors": eff_nbr}
                return (params, pi, key), tap

            return body

        return make_body

    def _make_sharded_eval(self, method: str, S: int):
        """Per-shard eval: vmapped ``masked_accuracy`` on the local test
        slab, psum-reduced to the participant mean; the target model is
        extracted with a masked sum + psum and scored (replicated) against
        the target's test tensors. The extraction adds only zeros to the
        target's weights, so it is exact at any matmul precision."""
        sim, fns = self.sim, self.fns
        pmf = self.participants.astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(pmf), 1.0)

        @jax.named_scope("eval")
        def eval_fn(params, data: BlockData, target: TargetData):
            ofs = jax.lax.axis_index("clients") * S
            is0 = (jnp.arange(S) + ofs) == 0
            tgt = jax.tree.map(
                lambda p: jax.lax.psum(jnp.sum(jnp.where(
                    is0.reshape((-1,) + (1,) * (p.ndim - 1)), p, 0), axis=0),
                    "clients"),
                params)
            if method == "perfedavg":
                tgt = baselines.maml_adapt(fns.loss, tgt, data.adapt_x,
                                           data.adapt_y, sim.maml_inner_lr)
            t_acc = cnn.masked_accuracy(tgt, target.test_x, target.test_y,
                                        target.test_mask)
            accs = client_accuracies(params, data.test_x, data.test_y,
                                     data.test_mask)
            pmf_l = jax.lax.dynamic_slice_in_dim(pmf, ofs, S, 0)
            mean_acc = jax.lax.psum(jnp.sum(accs * pmf_l), "clients") / denom
            return t_acc, mean_acc

        return eval_fn

    def sharded_block_fn(self, method: str) -> RoundBlock:
        """Sharded analogue of :meth:`block_fn`: the same scan-over-rounds
        block wrapped in ``compat.shard_map`` over the client mesh —
        donated state, one executable per (method, block length), taps
        riding the scan, no host callbacks."""
        method = method.lower()
        if method not in self._sharded_blocks:
            mesh, _, S = self._client_mesh_info()
            make_body = self._make_sharded_round_body(method, S)
            eval_fn = self._make_sharded_eval(method, S)
            taps_on = self.sim.taps

            p_specs = jax.tree.map(lambda p: client_axis_spec(p.ndim),
                                   self.params0)
            tap_specs = None
            if taps_on:
                tap_specs = {"train_loss": client_tap_spec(2),
                             "em_entropy": client_tap_spec(1),
                             "link_success_rate": client_tap_spec(1),
                             "effective_neighbors": client_tap_spec(1)}

            def inner_of(length):
                def inner(params, pi, key, data, target):
                    body = make_body(data.train_x, data.train_y, data.em_x,
                                     data.em_y, target.train_x,
                                     target.train_y)
                    state, taps = jax.lax.scan(body, (params, pi, key),
                                               None, length=length)
                    params, pi, _ = state
                    t_acc, mean_acc = eval_fn(params, data, target)
                    return state, (t_acc, mean_acc, pi, taps)

                return inner

            def block(state, data, length):
                stacks, target = data
                # client stacks split over "clients"; the subsets and the
                # target's tensors replicated
                data_specs = (
                    BlockData(*(client_axis_spec(x.ndim)
                                for x in stacks[:5]), *(P(),) * 4),
                    TargetData(*(P(),) * 5))
                mapped = compat.shard_map(
                    inner_of(length), mesh=mesh,
                    in_specs=(p_specs, P(), P()) + data_specs,
                    out_specs=((p_specs, P(), P()),
                               (P(), P(), P(), tap_specs)),
                    axis_names={"clients"}, check_vma=False)
                return mapped(*state, *data)

            self._sharded_blocks[method] = RoundBlock(
                block, f"{method}_sharded_block")
        return self._sharded_blocks[method]

    def _run_scan(self, method: str) -> Dict[str, Any]:
        """The block-scan driver shared by the fused and sharded engines:
        both call ``exe(state, data)``; only the staging, the initial
        state's placement and the cache key differ."""
        sim, rec = self.sim, self.recorder
        data = self.staged_data()
        with rec.span("init_state", engine=self.engine):
            state = (self.initial_sharded_state()
                     if self.engine == "sharded" else self.initial_state())
        blocks = block_schedule(sim.rounds, sim.eval_every)
        history: Dict[str, Any] = {"target_acc": [], "pi": [],
                                   "mean_participant_acc": []}
        rnd = 0
        for length in blocks:
            exe = self._compiled_block(method, length, state, data)
            t0 = time.perf_counter()
            with rec.span("block_exec", method=method, rounds=length):
                state, (t_acc, mean_acc, pi, taps) = exe(state, data)
                # host sync happens here, once per eval boundary
                t_acc, mean_acc = float(t_acc), float(mean_acc)
            rec.observe_round_latency(
                (time.perf_counter() - t0) / length * 1e3, n=length)
            with rec.span("drain", method=method, rounds=length):
                if taps is not None:
                    tl = np.asarray(taps["train_loss"])
                    ent = np.asarray(taps["em_entropy"])
                    lsr = np.asarray(taps["link_success_rate"])
                    eff = np.asarray(taps["effective_neighbors"])
                    for i in range(length):
                        rec.record_round(
                            rnd + i, train_loss=tl[i].tolist(),
                            em_entropy=float(ent[i]),
                            link_success_rate=float(lsr[i]),
                            effective_neighbors=float(eff[i]))
                pi_host = np.asarray(pi) if method == "pfedwn" else None
            rnd += length
            history["target_acc"].append(t_acc)
            history["mean_participant_acc"].append(mean_acc)
            if method == "pfedwn":
                history["pi"].append(pi_host)
            rec.record_eval(rnd - 1, target_acc=t_acc,
                            mean_participant_acc=mean_acc,
                            pi=None if pi_host is None else pi_host.tolist())
        history["max_target_acc"] = float(np.max(history["target_acc"]))
        self.last_run_stats = {"engine": self.engine, "blocks": blocks,
                               "device_calls": len(blocks)}
        return history

    # -------------------------------------------------------- legacy engine

    def _legacy_fns(self) -> Dict[str, Any]:
        """The original per-phase jits (one dispatch each per round), plus a
        jitted index sampler whose output is pulled to host so batches are
        re-materialized with numpy and re-uploaded every round — the
        host-driven cost profile the fused engine removes."""
        if self._legacy:
            return self._legacy
        fns, sim = self.fns, self.sim
        lr = sim.lr

        # each phase returns (params, mean minibatch loss) — the same
        # value_and_grad forward value the fused tap records, so the two
        # engines' RunRecords agree numerically as well as in schema
        def sgd_steps(params, xs, ys):
            def step(p, batch):
                x, y = batch
                l, g = jax.value_and_grad(fns.loss)(p, x, y)
                return jax.tree.map(lambda w, gw: w - lr * gw, p, g), l

            out, losses = jax.lax.scan(step, params, (xs, ys))
            return out, jnp.mean(losses)

        def prox_steps(params, anchor, xs, ys, active):
            def obj(p, x, y):
                return fns.loss(p, x, y) + active * baselines.prox_term(
                    p, anchor, sim.prox_mu)

            def step(p, batch):
                x, y = batch
                l, g = jax.value_and_grad(obj)(p, x, y)
                return jax.tree.map(lambda w, gw: w - lr * gw, p, g), l

            out, losses = jax.lax.scan(step, params, (xs, ys))
            return out, jnp.mean(losses)

        def maml_steps(params, xs, ys):
            half = xs.shape[1] // 2

            def step(p, batch):
                x, y = batch
                p, l = baselines.perfedavg_step(
                    fns.loss, p, x[:half], y[:half], x[half:], y[half:],
                    sim.maml_inner_lr, lr)
                return p, l

            out, losses = jax.lax.scan(step, params, (xs, ys))
            return out, jnp.mean(losses)

        def amp_steps(params, cloud, xs, ys):
            def obj(p, x, y):
                return fns.loss(p, x, y) + baselines.prox_term(
                    p, cloud, sim.prox_mu)

            def step(p, batch):
                x, y = batch
                l, g = jax.value_and_grad(obj)(p, x, y)
                return jax.tree.map(lambda w, gw: w - lr * gw, p, g), l

            out, losses = jax.lax.scan(step, params, (xs, ys))
            return out, jnp.mean(losses)

        def em_round(components, pi, x, y):
            _, pi_star, hist = em_refine_loop(
                fns, components, pi, x, y, iters=sim.em_iters, lr=lr,
                min_weight=PFLConfig().em_min_weight,
                component_steps=sim.em_component_steps)
            return pi_star, hist

        self._legacy = {
            "local_all": jax.jit(jax.vmap(sgd_steps)),
            "prox_all": jax.jit(jax.vmap(prox_steps,
                                         in_axes=(0, None, 0, 0, 0))),
            "maml_all": jax.jit(jax.vmap(maml_steps)),
            "amp_all": jax.jit(jax.vmap(amp_steps)),
            "em_round": jax.jit(em_round),
            "sample_idx": jax.jit(self._sample_idx_fn()),
        }
        return self._legacy

    def _sample_batches(self, idx: np.ndarray):
        """(N, steps, B, H, W, C) / (N, steps, B) stacked batches, gathered
        on host and uploaded — the legacy path's per-round transfer."""
        xs = np.stack([d.x[idx[i]] for i, d in enumerate(self.train_sets)])
        ys = np.stack([d.y[idx[i]] for i, d in enumerate(self.train_sets)])
        return jnp.asarray(xs), jnp.asarray(ys)

    def _eval_target(self, params_target) -> float:
        d = self.test_sets[0]
        return float(self.fns.accuracy(params_target, jnp.asarray(d.x),
                                       jnp.asarray(d.y)))

    def _take(self, stacked: PyTree, i: int) -> PyTree:
        return jax.tree.map(lambda p: p[i], stacked)

    def _put(self, stacked: PyTree, i: int, tree: PyTree) -> PyTree:
        return jax.tree.map(lambda s, t: s.at[i].set(t.astype(s.dtype)),
                            stacked, tree)

    def _run_legacy(self, method: str) -> Dict[str, Any]:
        sim, rec = self.sim, self.recorder
        jits = self._legacy_fns()
        params = self.params0
        pm = self.participants
        key = jax.random.PRNGKey(sim.seed + 7)
        neighbor_idx = self._neighbor_idx
        M = self._m
        pi = jnp.full((M,), 1.0 / max(M, 1))
        history: Dict[str, Any] = {"target_acc": [], "pi": [],
                                   "mean_participant_acc": []}
        device_calls = 0
        nbr_count = max(float(np.sum(np.asarray(pm))) - 1.0, 0.0)

        for rnd in range(sim.rounds):
            t_round = time.perf_counter()
            key, k_sample, k_erase = jax.random.split(key, 3)
            idx = np.asarray(jits["sample_idx"](k_sample))   # host round-trip
            xs, ys = self._sample_batches(idx)
            device_calls += 1
            link_rate, eff_nbr = 1.0, nbr_count

            if method == "local":
                params, train_loss = jits["local_all"](params, xs, ys)
                eff_nbr = 0.0
                device_calls += 1

            elif method == "fedavg":
                params, train_loss = jits["local_all"](params, xs, ys)
                g = baselines.fedavg_aggregate(params, self.sizes, pm)
                params = baselines.broadcast_global(g, params, pm)
                device_calls += 3

            elif method == "fedprox":
                g = baselines.fedavg_aggregate(params, self.sizes, pm)
                active = pm.astype(jnp.float32)
                params, train_loss = jits["prox_all"](params, g, xs, ys,
                                                      active)
                g = baselines.fedavg_aggregate(params, self.sizes, pm)
                params = baselines.broadcast_global(g, params, pm)
                device_calls += 4

            elif method == "perfedavg":
                params, train_loss = jits["maml_all"](params, xs, ys)
                g = baselines.fedavg_aggregate(params, self.sizes, pm)
                params = baselines.broadcast_global(g, params, pm)
                device_calls += 3

            elif method == "fedamp":
                xi = baselines.fedamp_weights(params, sim.fedamp_sigma, pm,
                                              sim.fedamp_self_weight)
                cloud = baselines.fedamp_cloud_models(params, xi)
                params, train_loss = jits["amp_all"](params, cloud, xs, ys)
                device_calls += 3

            elif method == "pfedwn":
                params, train_loss = jits["local_all"](params, xs, ys)
                target = self._take(params, 0)
                neighbors = jax.tree.map(
                    lambda p: p[jnp.asarray(neighbor_idx)], params)
                d0 = self.train_sets[0]
                x0 = jnp.asarray(d0.x[:sim.em_subset])
                y0 = jnp.asarray(d0.y[:sim.em_subset])
                if sim.em_uniform:
                    pi = jnp.full((M,), 1.0 / max(M, 1))
                else:
                    pi, _ = jits["em_round"](neighbors, pi, x0, y0)
                if sim.erasures:
                    link_ok = link_success_mask(
                        k_erase, self.p_err[jnp.asarray(neighbor_idx)])
                else:
                    link_ok = jnp.ones((M,), bool)
                mixed = aggregation.mix_params_with_erasures(
                    target, neighbors, pi, sim.alpha, link_ok)
                mixed, loss0 = jits["local_all"](
                    jax.tree.map(lambda p: p[None], mixed),
                    xs[0][None], ys[0][None])
                params = self._put(params, 0, self._take(mixed, 0))
                train_loss = train_loss.at[0].set(loss0[0])
                link_rate = float(link_success_rate(link_ok))
                eff_nbr = float(effective_neighbors(pi, link_ok))
                device_calls += 5
            else:
                raise ValueError(f"unknown method {method!r}")

            if sim.taps:
                # same scalars as the fused tap, recorded host-side
                rec.record_round(
                    rnd, train_loss=np.asarray(train_loss).tolist(),
                    em_entropy=float(pi_entropy(pi)),
                    link_success_rate=link_rate,
                    effective_neighbors=eff_nbr)
            rec.observe_round_latency(
                (time.perf_counter() - t_round) * 1e3)

            if rnd % sim.eval_every == 0 or rnd == sim.rounds - 1:
                with rec.span("eval", method=method, round=rnd):
                    tgt = self._take(params, 0)
                    if method == "perfedavg":
                        d0 = self.train_sets[0]
                        tgt = baselines.maml_adapt(
                            self.fns.loss, tgt,
                            jnp.asarray(d0.x[:sim.adapt_subset]),
                            jnp.asarray(d0.y[:sim.adapt_subset]),
                            sim.maml_inner_lr)
                    history["target_acc"].append(self._eval_target(tgt))
                    accs = []
                    for i in np.where(np.asarray(pm))[0]:
                        d = self.test_sets[i]
                        accs.append(float(self.fns.accuracy(
                            self._take(params, int(i)), jnp.asarray(d.x),
                            jnp.asarray(d.y))))
                        device_calls += 1
                    history["mean_participant_acc"].append(
                        float(np.mean(accs)))
                    pi_host = np.asarray(pi) if method == "pfedwn" else None
                    if method == "pfedwn":
                        history["pi"].append(pi_host)
                    rec.record_eval(
                        rnd, target_acc=history["target_acc"][-1],
                        mean_participant_acc=(
                            history["mean_participant_acc"][-1]),
                        pi=None if pi_host is None else pi_host.tolist())
        history["max_target_acc"] = float(np.max(history["target_acc"]))
        self.last_run_stats = {"engine": "legacy",
                               "device_calls": device_calls}
        return history

    # ---------------------------------------------------------------- entry

    def run(self, method: str) -> Dict[str, Any]:
        method = method.lower()
        if method not in METHODS:
            raise ValueError(f"unknown method {method!r}; have {METHODS}")
        sim, rec = self.sim, self.recorder
        engine = self.engine
        rec.begin_run(method=method, engine=engine, meta={
            "n_clients": self.n, "rounds": sim.rounds,
            "eval_every": sim.eval_every, "batch_size": sim.batch_size,
            "lr": sim.lr, "seed": sim.seed, "taps": sim.taps,
            "steps_per_round": self.steps_per_round})
        history = (self._run_legacy(method) if engine == "legacy"
                   else self._run_scan(method))
        rec.end_run(method=method, engine=engine, rounds=sim.rounds,
                    max_target_acc=history["max_target_acc"],
                    final_target_acc=history["target_acc"][-1],
                    extra={"device_calls":
                           self.last_run_stats["device_calls"]})
        return history
