"""Compiles for a described TPU v5e, with no chip attached: the Pallas
kernels at real widths, and the fused pfedwn round block (local SGD, EM,
mix, post-aggregation pass and eval) at chip_smoke.py's shapes with every
one of the 31 neighbours selected. What the chip's compiler would refuse
(a kernel layout, a program larger than the chip's memory) fails here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.configs.paper_cnn import cifar10_cnn
from repro.core.fedsim import (PHASES, BlockData, FederatedSimulation,
                               FedSimConfig)
from repro.data.synthetic import SyntheticImageDataset
from repro.kernels.em_posterior import em_posterior
from repro.kernels.flash_attention import flash_attention
from repro.kernels.weighted_agg import weighted_agg
from repro.models import cnn
from repro.utils.hlo_text import CALLEE_RE, callee_names, parse_computations

V5E_HBM_BYTES = 16 * 2 ** 30

# chip_smoke.py's federation: 32 clients, padded train/test stacks of the
# largest client (4,795 samples, split 75/25), FedSimConfig defaults
SMOKE_N, SMOKE_K_TRAIN, SMOKE_K_TEST = 32, 3597, 1198


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return make


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_em_posterior_compiles_for_v5e(one_chip):
    s = _sds(one_chip)
    M, T, V = 8, 4096, 50_176
    compiled = _compile(
        lambda pi, lg, y: em_posterior(pi, lg, y, interpret=False),
        s((M,), jnp.float32), s((M, T, V), jnp.bfloat16), s((T,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_weighted_agg_compiles_for_v5e(one_chip):
    s = _sds(one_chip)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(
        jax.eval_shape(lambda k: cnn.init_params(k, cifar10_cnn()),
                       jax.random.PRNGKey(0))))
    M = 31
    compiled = _compile(
        lambda o, nb, pi: weighted_agg(o, nb, pi, 0.5, interpret=False),
        s((n_params,), jnp.float32), s((M, n_params), jnp.float32),
        s((M,), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    s = _sds(one_chip)
    shape = (1, 1024, 8, 128)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        *(s(shape, jnp.bfloat16) for _ in range(3)))
    assert "tpu_custom_call" in compiled.as_text()


def _placeholder_sets(n, k, model_cfg):
    """n datasets of k rows that own no memory: only their lengths are
    read before the block's data is staged."""
    img = (model_cfg.image_size, model_cfg.image_size, model_cfg.channels)
    x = np.broadcast_to(np.zeros(img, np.float32), (k,) + img)
    y = np.zeros(k, np.int32)
    return [SyntheticImageDataset(x, y, model_cfg.n_classes)] * n


@pytest.fixture(scope="module")
def pfedwn_block(one_chip):
    """The worst case of chip_smoke.py, M=31 neighbours: the fused pfedwn
    block compiled for one v5e, with the staged data's shapes."""
    mc = cifar10_cnn()
    n, k, kt = SMOKE_N, SMOKE_K_TRAIN, SMOKE_K_TEST
    sim = FederatedSimulation(
        mc, _placeholder_sets(n, k, mc), _placeholder_sets(n, kt, mc),
        np.ones(n, bool), np.zeros(n, np.float32),
        FedSimConfig(rounds=4, eval_every=2))
    s = _sds(one_chip)
    img = (mc.image_size, mc.image_size, mc.channels)
    cfg = sim.sim
    data = BlockData(
        s((n, k) + img, jnp.float32), s((n, k), jnp.int32),
        s((n, kt) + img, jnp.float32), s((n, kt), jnp.int32),
        s((n, kt), jnp.bool_),
        s((cfg.em_subset,) + img, jnp.float32), s((cfg.em_subset,), jnp.int32),
        s((cfg.adapt_subset,) + img, jnp.float32),
        s((cfg.adapt_subset,), jnp.int32))
    state = jax.tree.map(lambda a: s(a.shape, a.dtype), sim.initial_state())
    assert state[1].shape == (31,)
    return sim.block_fn("pfedwn").lower(state, data, 2).compile(), data


def test_fused_pfedwn_block_fits_one_v5e(pfedwn_block):
    """Arguments, outputs and temporaries of the whole block, eval
    included, fit one chip's HBM."""
    compiled, data = pfedwn_block
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    data_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                     for x in data)
    assert mem.argument_size_in_bytes >= data_bytes
    assert total < V5E_HBM_BYTES, (
        f"pfedwn block needs {total / 2**30:.2f} GiB "
        f"(args {mem.argument_size_in_bytes / 2**30:.2f}, temp "
        f"{mem.temp_size_in_bytes / 2**30:.2f})")


def test_fused_pfedwn_block_pools_without_windows(pfedwn_block):
    """The CNN's max-pool compiles to plain reductions, in every phase,
    forward and backward: no select-and-scatter anywhere in the block and
    no reduce-window from the `pool` scope. Under vmap over client weights
    the windowed form ran far below the chip's HBM roofline."""
    text = pfedwn_block[0].as_text()
    assert "select-and-scatter" not in text
    pool_windows = [line.strip() for line in text.splitlines()
                    if " reduce-window(" in line and "pool" in line]
    assert not pool_windows, pool_windows[:3]


def _block_cycles(text):
    """``{op: the compiler's estimated cycles x its loops' trip counts}``
    for every op of the block with an estimate: the entry runs once, a
    loop's body as often as its condition's bound (each loop is a scan
    from 0), a branch as often as its caller. Fused computations and
    reducers are inside their callers' estimates."""
    comps = parse_computations(text)
    runs = {c.name: 1 for c in comps.values() if c.is_entry}
    todo = list(runs)
    while todo:
        name = todo.pop()
        for line in comps[name].lines:
            loop = re.search(r" while\(.*condition=%?([\w.\-]+), "
                             r"body=%?([\w.\-]+)", line)
            if loop:
                bound = re.search(r"constant\((\d+)\)",
                                  "\n".join(comps[loop.group(1)].lines))
                trips = int(bound.group(1))
                runs[loop.group(1)] = runs[name] * (trips + 1)
                runs[loop.group(2)] = runs[name] * trips
                todo.extend(loop.groups())
            for m in CALLEE_RE.finditer(line):
                if m.group(1) in ("true_computation", "false_computation",
                                  "branch_computations"):
                    for callee in callee_names(m):
                        runs[callee] = runs[name]
                        todo.append(callee)
    out = {}
    for name, n in runs.items():
        for line in comps[name].lines:
            op = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s", line)
            est = re.search(r'"estimated_cycles":"(\d+)"', line)
            if op and est:
                out[op.group(1)] = int(est.group(1)) * n
    return out


def test_fused_pfedwn_block_puts_its_work_in_phases(pfedwn_block):
    """The scopes that ``FederatedSimulation.device_scopes`` reads
    (``obs.op_scopes``) leave under 1% of the block's estimated device
    cycles without a phase: the block's staging copies, the round key's
    split, the taps and what the compiler hoists out of a phase's loop."""
    text = pfedwn_block[0].as_text()
    scopes = obs.op_scopes(text, PHASES, cnn.SCOPES)
    cycles = _block_cycles(text)
    unscoped = {op: c for op, c in cycles.items() if scopes[op][0] is None}
    share = sum(unscoped.values()) / sum(cycles.values())
    assert share < 0.01, (share, sorted(unscoped.items(),
                                        key=lambda kv: -kv[1])[:5])


def test_fused_pfedwn_block_gathers_images_as_flat_rows(pfedwn_block):
    """Every minibatch gather from the (K, H, W, C) image stacks slices
    flat rows of H*W*C values (`fedsim.batch_rows`). At C = 3 the compiler
    lays the stack out with C outside H and W; on a v5e, local SGD that
    gathered (H, W, C) slices from that layout departed from the reference
    after two rounds, and with flat rows it did not."""
    text = pfedwn_block[0].as_text()
    sizes = re.findall(r" gather\(.*?slice_sizes=\{([\d,]+)\}", text)
    assert not [s for s in sizes if s.endswith(",32,32,3")], sizes
    assert [s for s in sizes if s.endswith(",3072")], sizes


def test_fused_pfedwn_block_convolves_without_patches(pfedwn_block):
    """The CNN's convs compile to convolutions, in every phase, forward and
    backward: no concatenate from the `conv` scope. Nine shifted views
    concatenated into a (B, H, W, 9*C) patch tensor and one matmul read
    3.3 times the bytes of a conv per SGD step, and took half of every
    round on a v5e."""
    text = pfedwn_block[0].as_text()
    scopes = obs.op_scopes(text, PHASES, cnn.SCOPES)
    ops = {}
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?\s"
                     r"(concatenate|convolution)\(", line)
        if m and scopes[m.group(1)][1] == "conv":
            ops.setdefault(m.group(2), []).append(m.group(1))
    assert not ops.get("concatenate"), ops.get("concatenate")[:3]
    assert ops.get("convolution"), sorted(ops)
