"""N-client federated simulator — the paper-faithful engine behind
Tables II/III and Figs 1, 8, 9.

Clients hold stacked params (N, ...); all per-client math is vmapped and
jitted. The wireless layer supplies (participant mask, per-link P_err); this
module runs the learning side for any of:

  local | fedavg | fedprox | perfedavg | fedamp | pfedwn

One round engine. All train/test tensors move to the device once, on the
first run (padded + stacked via ``data.synthetic.stack_datasets``), and
every round block takes them as a non-donated argument (:class:`BlockData`),
so no client data is baked into an executable; minibatch indices are drawn
with ``jax.random`` *inside* the jitted step; one donated round-step per
method fuses local-SGD → EM → erasure-gated aggregation → post-aggregation
local training, and ``eval_every``-sized blocks of rounds run through a
single ``jax.lax.scan`` so the host only syncs at eval boundaries.
Evaluation maps ``cnn.masked_accuracy`` over the clients' padded test stacks
a few clients at a time (:func:`client_accuracies`), which bounds its
memory.

The round is written once, against the *placement* of the clients: where
the client stack lives and how a round reads across clients.

  **one device** (:class:`OneDevice`, engine ``fused``, the default): the
  whole (N, ...) stack on the default device; a read across clients is an
  index or a reduction of the stack.

  **client mesh** (:class:`ClientMesh`, engine ``sharded``,
  ``FedSimConfig.sharded=True``): the block under ``repro.compat.shard_map``
  over a ``("clients",)`` mesh — clients are a server-free D2D population,
  i.e. naturally data-parallel. Each of D devices owns a contiguous slab of
  S = N/D clients: params and data stacks partitioned on their leading
  client axis; π, the key, the EM/adapt subsets and the target's own
  tensors (:class:`TargetData`) replicated. Every cross-client read is an
  explicit collective riding the round scan, never the inner EM/SGD loops:
  a ``psum`` for the fedavg/fedprox/perfedavg global mean, ONE per-round
  ``all_gather`` of the peer models for pfedwn's EM components and fedamp's
  attention, and a psum-reduced eval. The index stream is drawn replicated
  at full (N, steps, B) and sliced per shard, so both placements follow the
  same trajectory; the target's math runs redundantly on every shard and
  lands only where the global client index is 0. ``shard_devices`` picks
  the mesh size (default: every visible device); it must divide N.

Both placements keep the engine invariants: donated, one executable per
(method, block length), no host callbacks, device-side taps riding the scan.

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
engine's **device-side metrics tap** (``FedSimConfig.taps``) emits
per-round scalars — per-client train loss (free: the forward value already
computed by ``value_and_grad``), EM weight entropy, effective neighbor
count, link success rate — as outputs of the round scan, stacked on device
and drained only at eval boundaries, so instrumentation adds no host syncs
and the round block stays a single executable. Set
``FedSimConfig.record_dir`` to persist the JSONL RunRecord + Chrome trace
(``python -m repro.obs.report`` summarizes them).

Config fields that change compiled behavior (``lr``, ``alpha``,
``em_uniform``, ``taps``, ``sharded``, …) are read when a method's block is
first built; mutate them before the first ``run`` of a method, or call
``invalidate_caches``.
"""
from __future__ import annotations

import functools
import os
import time
from dataclasses import dataclass
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Tuple,
                    Union)

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
    the client mesh (the target's math runs on each)."""
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
    fused: bool = True                 # only True: bench/harness.py passes it
    sharded: bool = False              # clients over a ("clients",) mesh
    shard_devices: Optional[int] = None  # client-mesh size (None: all devices)
    em_uniform: bool = False           # ablation: uniform π instead of EM
    taps: bool = True                  # device-side per-round metrics tap
    record_dir: Optional[str] = None   # persist RunRecord JSONL + trace here
    run_name: Optional[str] = None     # record file stem (default: derived)

    def __post_init__(self):
        if not self.fused:
            raise ValueError(
                "FedSimConfig.fused must be True: the host-driven engine it "
                "selected was removed")


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


def sgd_scan(objective: Callable, lr: float) -> Callable:
    """Per-client SGD on ``objective(params, x, y)`` over a round's
    minibatch indices: ``fn(p, dx, dy, idx) -> (p, mean minibatch loss)``.
    The batch gather happens on device inside the scan body (no (N, steps,
    B, ...) batch tensor is ever materialized). The loss is the forward
    value ``value_and_grad`` computes anyway, so the metrics tap costs
    nothing here (and XLA dead-code eliminates it when taps are off)."""
    def sgd(p, dx, dy, idx):
        def step(p, it):
            l, g = jax.value_and_grad(objective)(p, batch_rows(dx, it),
                                                 dy[it])
            return jax.tree.map(lambda w, gw: w - lr * gw, p, g), l

        out, losses = jax.lax.scan(step, p, idx)
        return out, jnp.mean(losses)

    return sgd


def block_schedule(rounds: int, eval_every: int) -> List[int]:
    """Round-block lengths between host syncs: evaluate after round r when
    ``r % eval_every == 0`` or ``r == rounds - 1`` — so blocks are [1,
    eval_every, ..., tail]."""
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


# ------------------------------------------------------------ placements
#
# Where the client stack lives and how a round reads across clients. The
# round body, the eval and the block scan read across clients only through
# these methods; ``rows`` of an (N, ...) value gives the clients a
# placement holds.


class OneDevice:
    """The whole ``(N, ...)`` client stack on the default device: a read
    across clients is an index or a reduction of the stack."""

    def __init__(self, sizes: jax.Array, participants: jax.Array):
        self.sizes, self.participants = sizes, participants

    def stage(self, host: BlockData) -> BlockData:
        return jax.device_put(host)

    def place_state(self, state):
        return state

    def stacks(self, data) -> BlockData:
        return data

    def target(self, data, *fields) -> Tuple:
        """Client 0's rows of the named :class:`BlockData` stacks."""
        return tuple(getattr(data, f)[0] for f in fields)

    def rows(self, v):
        return v

    def peers(self, params):
        """Every client's model, ``(N, ...)``."""
        return params

    def mean(self, params):
        """The participants' size-weighted mean model."""
        return baselines.fedavg_aggregate(params, self.sizes,
                                          self.participants)

    def total(self, x):
        """The sum over clients of the partial sums ``x``."""
        return x

    def target_model(self, params):
        return jax.tree.map(lambda p: p[0], params)

    def put_target(self, stack, value):
        """``stack`` with client 0's entry replaced by ``value``."""
        return jax.tree.map(lambda s, t: s.at[0].set(t.astype(s.dtype)),
                            stack, value)

    def round_block(self, block: Callable, method: str) -> RoundBlock:
        return RoundBlock(block, f"{method}_block")


class ClientMesh:
    """The client stack split over a ``("clients",)`` mesh: each device
    holds a contiguous slab of S = N/D clients, and a read across clients
    is a collective over "clients". Small (N,) vectors (sizes, masks) stay
    replicated constants, sliced per shard."""

    def __init__(self, mesh, n: int, sizes: jax.Array,
                 participants: jax.Array, taps: bool):
        self.mesh, self.S, self.taps = mesh, n // mesh.size, taps
        # globally normalized fedavg weights: each shard contracts its
        # rows, the psum completes the sum over clients
        w = sizes * participants.astype(jnp.float32)
        self._w = w / jnp.maximum(jnp.sum(w), 1e-30)
        self._is_target = jnp.arange(n) == 0

    def stage(self, host: BlockData) -> Tuple[BlockData, TargetData]:
        """The client stacks split along their leading N axis over
        "clients" (no device holds more than its slab), the EM/adapt
        subsets and the target's own tensors replicated."""
        rep = NamedSharding(self.mesh, P())
        data = BlockData(*(
            jax.device_put(x, NamedSharding(self.mesh,
                                            client_axis_spec(x.ndim)))
            for x in host[:5]), *jax.device_put(host[5:], rep))
        target = TargetData(*jax.device_put(
            tuple(x[0] for x in host[:5]), rep))
        return data, target

    def place_state(self, state):
        """Params partitioned over "clients", π and the key replicated."""
        params, pi, key = state
        rep = NamedSharding(self.mesh, P())
        return (jax.device_put(params,
                               client_stack_shardings(self.mesh, params)),
                jax.device_put(pi, rep), jax.device_put(key, rep))

    def stacks(self, data) -> BlockData:
        return data[0]

    def target(self, data, *fields) -> Tuple:
        return tuple(getattr(data[1], f) for f in fields)

    def rows(self, v):
        ofs = jax.lax.axis_index("clients") * self.S
        return jax.lax.dynamic_slice_in_dim(v, ofs, self.S, 0)

    def peers(self, params):
        return aggregation.gather_clients(params)

    def mean(self, params):
        return aggregation.client_weighted_mean(params, self.rows(self._w))

    def total(self, x):
        return jax.lax.psum(x, "clients")

    def target_model(self, params):
        """Client 0's model on every shard, by a masked sum and a psum:
        they add only zeros to its weights, so it is exact at any matmul
        precision."""
        is0 = self.rows(self._is_target)
        return jax.tree.map(
            lambda p: self.total(jnp.sum(jnp.where(
                is0.reshape((-1,) + (1,) * (p.ndim - 1)), p, 0), axis=0)),
            params)

    def put_target(self, stack, value):
        # written only on the shard holding global client 0
        return baselines.broadcast_global(value, stack,
                                          self.rows(self._is_target))

    def round_block(self, block: Callable, method: str) -> RoundBlock:
        tap_specs = None
        if self.taps:
            # train_loss is the (S,) local slab, reassembled to (rounds, N);
            # the scalars are replicated
            tap_specs = {"train_loss": client_tap_spec(2),
                         "em_entropy": client_tap_spec(1),
                         "link_success_rate": client_tap_spec(1),
                         "effective_neighbors": client_tap_spec(1)}

        def sharded(state, data, length):
            p_specs = jax.tree.map(lambda p: client_axis_spec(p.ndim),
                                   state[0])
            data_specs = (
                BlockData(*(client_axis_spec(x.ndim) for x in data[0][:5]),
                          *(P(),) * 4),
                TargetData(*(P(),) * 5))
            state_specs = (p_specs, P(), P())
            mapped = compat.shard_map(
                lambda state, data: block(state, data, length),
                mesh=self.mesh, in_specs=(state_specs, data_specs),
                out_specs=(state_specs, (P(), P(), P(), tap_specs)),
                axis_names={"clients"}, check_vma=False)
            return mapped(state, data)

        return RoundBlock(sharded, f"{method}_sharded_block")


Placement = Union[OneDevice, ClientMesh]


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
        self._place: Optional[Placement] = None  # built on first use
        self._blocks: Dict[str, RoundBlock] = {}  # method -> round block
        self._block_execs: Dict[Tuple, Any] = {}  # (method, len) AOT
        self.last_run_stats: Dict[str, Any] = {}

    @property
    def engine(self) -> str:
        """``sharded`` on a client mesh, else ``fused``."""
        return "sharded" if self.sim.sharded else "fused"

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

    def placement(self) -> Placement:
        """Where the client stack lives: :class:`ClientMesh` over the first
        D = ``sim.shard_devices`` devices (default: every visible device)
        with ``sim.sharded``, else :class:`OneDevice`. D must divide N so
        each shard owns an equal contiguous slab of clients."""
        if self._place is None:
            if self.sim.sharded:
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
                self._place = ClientMesh(mesh, self.n, self.sizes,
                                         self.participants, self.sim.taps)
            else:
                self._place = OneDevice(self.sizes, self.participants)
        return self._place

    # ------------------------------------------------------------- staging

    def _stage_data(self) -> None:
        """Per-client sample counts and the round's step count; the data
        itself moves to the device on the first run (:meth:`staged_data`)."""
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
        """The data argument of the round blocks, moved to the device once
        and kept: a :class:`BlockData` on the default device; on a client
        mesh a ``(BlockData, TargetData)`` pair with the client stacks
        split over the mesh and everything else replicated."""
        if self._data is None:
            with self.recorder.span("stage_data", n_clients=self.n,
                                    engine=self.engine):
                self._data = self.placement().stage(self._host_block_data())
        return self._data

    def restrict_target_train(self, keep: int) -> None:
        """Shrink the target's train set to its first ``keep`` samples (the
        data-poor-target ablations) and restage device tensors + caches."""
        d = self.train_sets[0]
        d.x, d.y = d.x[:keep], d.y[:keep]
        self.sizes = self.sizes.at[0].set(float(len(d)))
        self.invalidate_caches()

    def invalidate_caches(self) -> None:
        """Rebuild device staging and drop compiled blocks — call after
        mutating ``self.sim`` or any dataset in place."""
        self._stage_data()
        self._place = None
        self._blocks.clear()
        self._block_execs.clear()

    # ---------------------------------------------------------- round math

    def _sample_idx_fn(self):
        """(N, steps, B) with-replacement minibatch indices, drawn on device
        from a single round key."""
        steps, B, N = self.steps_per_round, self.sim.batch_size, self.n
        train_len = jnp.maximum(self._train_len, 1)

        def sample_idx(key):
            ks = jax.random.split(key, N)
            return jax.vmap(
                lambda k, n: jax.random.randint(k, (steps, B), 0, n)
            )(ks, train_len)

        return sample_idx

    def _sgd_one_fn(self):
        """Per-client plain SGD, ``sgd_one(p, dx, dy, idx) -> (params,
        mean minibatch loss)`` (:func:`sgd_scan` on the model's loss)."""
        return sgd_scan(self.fns.loss, self.sim.lr)

    def _trainers(self) -> Dict[str, Any]:
        """The per-client trainer closures. Every ``*_all`` is a vmap over a
        leading client axis and is indifferent to whether that axis is the
        full N-client stack or one shard's S-client slab."""
        sim, fns = self.sim, self.fns
        lr, B = sim.lr, sim.batch_size
        sgd_one = self._sgd_one_fn()

        def prox_one(p, anchor, active, dx, dy, idx):
            # single pass over all clients: the prox pull is gated by
            # `active`, so non-participants take plain local-SGD gradients
            # (no second `_local_all` sweep + merge).
            return sgd_scan(lambda pp, x, y: fns.loss(pp, x, y)
                            + active * baselines.prox_term(pp, anchor,
                                                           sim.prox_mu),
                            lr)(p, dx, dy, idx)

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
            return sgd_scan(lambda pp, x, y: fns.loss(pp, x, y)
                            + baselines.prox_term(pp, cloud, sim.prox_mu),
                            lr)(p, dx, dy, idx)

        return {"sgd_one": sgd_one,
                "local_all": jax.vmap(sgd_one),
                "prox_all": jax.vmap(prox_one,
                                     in_axes=(0, None, 0, 0, 0, 0)),
                "maml_all": jax.vmap(maml_one),
                "amp_all": jax.vmap(amp_one)}

    def _make_round_body(self, method: str, place: Placement):
        """Build ``body(data, state, _) -> (state, tap)`` for one round of
        `method`. data is the block's staged data; state = (params (the
        clients ``place`` holds), pi (M,), key); ``tap`` is the per-round
        metrics dict when ``sim.taps`` (stacked by the block scan, drained
        at eval boundaries) or None when taps are off."""
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

        # non-collaborative / all-participant defaults for the tap scalars;
        # the pfedwn branch overwrites them with its channel-aware values
        nbr_count = jnp.maximum(jnp.sum(pmf) - 1.0, 0.0)

        def body(data, state, _):
            stacks = place.stacks(data)
            train_x, train_y = stacks.train_x, stacks.train_y
            params, pi, key = state
            key, k_sample, k_erase = jax.random.split(key, 3)
            link_rate = jnp.float32(1.0)
            with jax.named_scope("local_sgd"):
                idx = sample_idx(k_sample)     # the full-N draw everywhere
                idx_l = place.rows(idx)

            if method == "local":
                with jax.named_scope("local_sgd"):
                    params, train_loss = local_all(params, train_x, train_y,
                                                   idx_l)
                eff_nbr = jnp.float32(0.0)

            elif method == "fedavg":
                with jax.named_scope("local_sgd"):
                    params, train_loss = local_all(params, train_x, train_y,
                                                   idx_l)
                with jax.named_scope("mix"):
                    g = place.mean(params)
                    params = baselines.broadcast_global(g, params,
                                                        place.rows(pm))
                eff_nbr = nbr_count

            elif method == "fedprox":
                with jax.named_scope("mix"):
                    g = place.mean(params)
                with jax.named_scope("local_sgd"):
                    params, train_loss = prox_all(params, g, place.rows(pmf),
                                                  train_x, train_y, idx_l)
                with jax.named_scope("mix"):
                    g = place.mean(params)
                    params = baselines.broadcast_global(g, params,
                                                        place.rows(pm))
                eff_nbr = nbr_count

            elif method == "perfedavg":
                with jax.named_scope("local_sgd"):
                    params, train_loss = maml_all(params, train_x, train_y,
                                                  idx_l)
                with jax.named_scope("mix"):
                    g = place.mean(params)
                    params = baselines.broadcast_global(g, params,
                                                        place.rows(pm))
                eff_nbr = nbr_count

            elif method == "fedamp":
                allp = place.peers(params)
                with jax.named_scope("mix"):
                    xi = baselines.fedamp_weights(allp, sim.fedamp_sigma,
                                                  pm, sim.fedamp_self_weight)
                    cloud = baselines.fedamp_cloud_models(allp,
                                                          place.rows(xi))
                with jax.named_scope("local_sgd"):
                    params, train_loss = amp_all(params, cloud, train_x,
                                                 train_y, idx_l)
                eff_nbr = nbr_count

            elif method == "pfedwn":
                # 1. everyone trains locally (neighbors included)
                with jax.named_scope("local_sgd"):
                    params, train_loss = local_all(params, train_x, train_y,
                                                   idx_l)
                # 2-4. target: EM weights + erasure-gated aggregation, over
                # the round's one exchange of the peer models
                allp = place.peers(params)
                with jax.named_scope("em"):
                    target = jax.tree.map(lambda p: p[0], allp)
                    neighbors = jax.tree.map(lambda p: p[nbr], allp)
                    if sim.em_uniform:
                        pi_new = jnp.full((M,), 1.0 / max(M, 1))
                    else:
                        _, pi_new, _ = em_refine_loop(
                            fns, neighbors, pi, stacks.em_x, stacks.em_y,
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
                    mixed, loss0 = sgd_one(
                        mixed, *place.target(data, "train_x", "train_y"),
                        idx[0])
                    params = place.put_target(params, mixed)
                pi = pi_new
                # the target's tap entry tracks the post-aggregation pass
                train_loss = place.put_target(train_loss, loss0)
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

    def _make_eval_fn(self, method: str, place: Placement):
        """(params, data) -> (target_acc, mean_participant_acc) over every
        client's padded test stack."""
        sim = self.sim
        pmf = self.participants.astype(jnp.float32)
        fns = self.fns

        @jax.named_scope("eval")
        def eval_fn(params, data):
            stacks = place.stacks(data)
            tgt = place.target_model(params)
            if method == "perfedavg":
                tgt = baselines.maml_adapt(fns.loss, tgt, stacks.adapt_x,
                                           stacks.adapt_y, sim.maml_inner_lr)
            t_acc = cnn.masked_accuracy(
                tgt, *place.target(data, "test_x", "test_y", "test_mask"))
            accs = client_accuracies(params, stacks.test_x, stacks.test_y,
                                     stacks.test_mask)
            mean_acc = (place.total(jnp.sum(accs * place.rows(pmf)))
                        / jnp.maximum(jnp.sum(pmf), 1.0))
            return t_acc, mean_acc

        return eval_fn

    # -------------------------------------------------------- round blocks

    def block_fn(self, method: str) -> RoundBlock:
        """The donated round-block runner for ``method`` on this
        simulation's placement: ``block.lower(state, data, length)`` lowers
        the jit that scans ``length`` rounds and evaluates, all in one
        compiled executable (``state`` buffers are donated so params update
        in place where the backend allows; ``data`` is :meth:`staged_data`,
        not donated)."""
        method = method.lower()
        if method not in self._blocks:
            place = self.placement()
            body = self._make_round_body(method, place)
            eval_fn = self._make_eval_fn(method, place)

            def block(state, data, length):
                # tap scalars are stacked by the scan (device-side) and
                # leave the executable only here, with the eval outputs
                state, taps = jax.lax.scan(functools.partial(body, data),
                                           state, None, length=length)
                params, pi, _ = state
                t_acc, mean_acc = eval_fn(params, data)
                return state, (t_acc, mean_acc, pi, taps)

            self._blocks[method] = place.round_block(block, method)
        return self._blocks[method]

    def _compiled_block(self, method: str, length: int, state,
                        data) -> Any:
        """AOT-compiled executable for one (method, block length) shape,
        cached; compilation is spanned and the span and a compile event
        carry its FLOP/byte cost estimate."""
        key = (method, int(length))
        exe = self._block_execs.get(key)
        if exe is None:
            with self.recorder.span("compile", cat="compile", method=method,
                                    rounds=length) as sp:
                exe = self.block_fn(method).lower(state, data,
                                                  length).compile()
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
        """(params, π, key) at round 0, placed as the blocks take them.
        Params are a fresh copy so donated block calls can't consume
        ``self.params0``."""
        params = jax.tree.map(jnp.copy, self.params0)
        pi = jnp.full((self._m,), 1.0 / max(self._m, 1), jnp.float32)
        key = jax.random.PRNGKey(self.sim.seed + 7)
        return self.placement().place_state((params, pi, key))

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
        data = self.staged_data()
        with rec.span("init_state", engine=engine):
            state = self.initial_state()
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
        self.last_run_stats = {"engine": engine, "blocks": blocks,
                               "device_calls": len(blocks)}
        rec.end_run(method=method, engine=engine, rounds=sim.rounds,
                    max_target_acc=history["max_target_acc"],
                    final_target_acc=history["target_acc"][-1],
                    extra={"device_calls":
                           self.last_run_stats["device_calls"]})
        return history
