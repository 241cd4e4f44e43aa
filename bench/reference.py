"""Plain reference of the federated rounds the benchmark times.

Written from the paper's description and the simulator's documented
semantics, importing nothing of the program: every client's local training over
with-replacement minibatches drawn from the round key and what the method
does with the models (both in ``methods/<method>.py``: its ``Round``),
then the eval: each
client's accuracy on its own test rows, the target's and the participants'
mean. Clients run one after another, each through the same small jitted
programs, so nothing is vmapped over clients. The reference runs in float32
at the matmul precision the configuration states (on a TPU, ``default``
rounds each matmul's operands to bfloat16 once and accumulates in float32);
``control_kwargs`` gives the control one precision below it.

The key schedule is the program's documented one: ``PRNGKey(seed)`` split
per client for the initial weights, ``PRNGKey(seed + 7)`` as the round key,
split each round into (next key, sampling key, erasure key).
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.data import Federation, padded_stack

# planted faults a test or a calibration can switch on (None: sound), on
# top of the ``FAULTS`` of the method's own file: SGD leaves the params
# unchanged; each step uses half its minibatch; every client reports the
# next client's loss; every prediction moves one class on
FAULTS = ("unchanged", "half_batch", "altered_loss", "altered_eval")


def method(name: str):
    """The method's file, ``methods/<name>.py``: its ``Round``, its
    ``round_flops`` and the ``FAULTS`` only it can have."""
    return importlib.import_module(f"bench.methods.{name}")


def faults(method_name: str) -> Tuple[str, ...]:
    return FAULTS + tuple(method(method_name).FAULTS)


def control_kwargs(cfg: Dict) -> Dict:
    """The control: the reference one precision below the configuration's
    float32 at the ``default`` matmul precision, bfloat16 weights and
    activations."""
    if (cfg["dtype"], cfg["matmul_precision"]) != ("float32", "default"):
        raise ValueError("a control is defined for float32 at the default "
                         "matmul precision only")
    return {"dtype": jnp.bfloat16}


def stack(trees):
    return jax.tree.map(lambda *a: jnp.stack(a), *trees)


class Reference:
    def __init__(self, cfg: Dict, method_name: str, fed: Federation,
                 seed: int, dtype=jnp.float32, fault: Optional[str] = None):
        self.cfg, self.fed = cfg, fed
        self.method = method(method_name)
        self.model = importlib.import_module(f"bench.models.{cfg['model']}")
        self.mc, self.tr = cfg["model_config"], cfg["train"]
        self.seed, self.dtype, self.fault = int(seed), dtype, fault
        if fault is not None and fault not in faults(method_name):
            raise ValueError(f"no fault {fault!r} for {method_name!r}")
        self.n = len(fed.train)
        self.part = np.asarray(fed.participants, bool)
        self.nbr = np.where(self.part & (np.arange(self.n) != 0))[0]
        self.sizes = np.asarray([len(y) for _, y in fed.train], np.float32)
        self.steps = int(np.ceil(self.sizes.max() / self.tr["batch_size"]))

    # ------------------------------------------------------------ pieces

    def nll(self, p, x, y):
        return self.model.per_sample_nll(p, x.astype(self.dtype), y)

    def _loss(self, p, x, y):
        return jnp.mean(self.nll(p, x, y))

    def _sgd_pass(self, p, x, y, idx):
        """SGD over one round's minibatch rows ``idx`` (steps, B); returns
        the params and the mean minibatch loss."""
        lr, half = self.tr["lr"], self.tr["batch_size"] // 2

        def step(p, it):
            xb, yb = x[it], y[it]
            if self.fault == "half_batch":
                xb, yb = xb[:half], yb[:half]
            l, g = jax.value_and_grad(self._loss)(p, xb, yb)
            if self.fault != "unchanged":
                p = jax.tree.map(lambda w, gw: (w - lr * gw).astype(w.dtype),
                                 p, g)
            return p, l

        p, losses = jax.lax.scan(step, p, idx)
        return p, jnp.mean(losses.astype(jnp.float32))

    def _accuracy(self, p, x, y, mask):
        pred = jnp.argmax(self.model.apply(p, x.astype(self.dtype)), axis=-1)
        if self.fault == "altered_eval":
            pred = (pred + 1) % self.mc["n_classes"]
        ok = (pred == y) & mask
        return jnp.sum(ok) / jnp.maximum(jnp.sum(mask), 1)

    def local_sgd(self, params: List, idx) -> Tuple[List, np.ndarray]:
        """Every client's SGD pass over its rows ``idx[i]``, one client
        after another: the params and each client's mean minibatch loss."""
        res = [self.sgd(p, self.tx[i], self.ty[i], idx[i])
               for i, p in enumerate(params)]
        return [p for p, _ in res], np.asarray([float(l) for _, l in res])

    def _draw(self):
        tr, n, steps = self.tr, self.n, self.steps
        lengths = np.maximum(self.sizes, 1).astype(np.int32)
        p_err = jnp.asarray(self.fed.p_err)[jnp.asarray(self.nbr)]

        def draw(key):
            key, k_sample, k_erase = jax.random.split(key, 3)
            ks = jax.random.split(k_sample, n)
            idx = jnp.stack([jax.random.randint(
                ks[i], (steps, tr["batch_size"]), 0, int(lengths[i]))
                for i in range(n)])
            return key, idx, jax.random.uniform(k_erase, p_err.shape) >= p_err

        return jax.jit(draw)

    # ------------------------------------------------------------ rounds

    def run(self, rounds: int) -> List[Dict]:
        """Each round's outputs as the program reports them: every client's
        mean minibatch loss, π (where the method has one), the link success
        rate and the target's and participants' mean test accuracy."""
        with jax.default_matmul_precision(self.cfg["matmul_precision"]):
            return self._run(rounds)

    def _run(self, rounds: int) -> List[Dict]:
        n = self.n
        # what a method's ``Round`` uses: ``sgd``, ``local_sgd``, ``nll``
        self.sgd = jax.jit(self._sgd_pass)
        self.tx, self.ty, _, _ = jax.device_put(padded_stack(self.fed.train))
        ex, ey, emask, _ = jax.device_put(padded_stack(self.fed.test))
        keys = jax.random.split(jax.random.PRNGKey(self.seed), n)
        params = [jax.tree.map(lambda p: p.astype(self.dtype),
                               self.model.init_params(keys[i], self.mc))
                  for i in range(n)]
        key = jax.random.PRNGKey(self.seed + 7)
        draw, acc = self._draw(), jax.jit(self._accuracy)
        step = self.method.Round(self)

        out = []
        for _ in range(rounds):
            key, idx, ok = draw(key)
            params, loss, extra = step(params, idx, ok)
            accs = np.asarray([float(acc(params[i], ex[i], ey[i], emask[i]))
                               for i in range(n)])
            if self.fault == "altered_loss":   # each loss to the next client
                loss = np.roll(loss, 1)
            out.append({"train_loss": loss, "pi": extra.get("pi"),
                        "link_success_rate": extra.get("link_success_rate",
                                                       1.0),
                        "target_acc": float(acc(params[0], ex[0], ey[0],
                                                emask[0])),
                        "mean_participant_acc": float(
                            np.sum(accs * self.part) / np.sum(self.part))})
        return out
