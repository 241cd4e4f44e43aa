"""pFedWN: every client's local SGD, then at the target Algorithm 1's EM
over its channel-selected neighbours (E-step posterior, M-step mean, one
λ-weighted SGD step of every component between iterations, Eq 9-11), the
erasure-gated Eq-1 mix and a post-aggregation SGD pass of the target on
its own minibatches, whose loss is the target's reported loss.

``altered_pi`` rolls π as the EM hands it on, before the mix, so the mix,
the target's pass and the next round's prior all take the rolled weights.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.reference import stack

FAULTS = ("altered_pi",)


class Round:
    def __init__(self, ref):
        self.ref = ref
        m = len(ref.nbr)
        x0, y0 = ref.fed.train[0]
        rows = ref.tr["em_subset"]
        self.em_x, self.em_y = jax.device_put((x0[:rows], y0[:rows]))
        self.pi = jnp.full((m,), 1.0 / max(m, 1), jnp.float32)
        self.em, self.mix = jax.jit(self._em), jax.jit(self._mix)

    def _em(self, comps, pi, x, y):
        """``em_iters`` E/M iterations, each but the last followed by one
        λ-weighted SGD step of every component; returns π."""
        ref, tr = self.ref, self.ref.tr
        m, floor = len(ref.nbr), tr["em_min_weight"]

        def losses(c):                                     # (n, M)
            return jax.lax.map(lambda p: ref.nll(p, x, y), c).T

        for it in range(tr["em_iters"]):
            logit = jnp.log(jnp.maximum(pi, 1e-30))[None, :] - losses(comps)
            lam = jax.nn.softmax(logit, axis=-1)
            scale = max(1.0 - m * floor, 0.0)
            lam = lam * scale + (1.0 - scale) / m
            pi_new = jnp.mean(lam, axis=0)
            pi_new = pi_new / jnp.maximum(jnp.sum(pi_new), 1e-30)
            if it < tr["em_iters"] - 1:
                wts = lam / jnp.maximum(jnp.sum(lam, axis=0, keepdims=True),
                                        1e-30)

                def step(args):
                    c, w = args
                    g = jax.grad(lambda q: jnp.sum(w * ref.nll(q, x, y)))(c)
                    return jax.tree.map(lambda a, b: a - tr["lr"] * b, c, g)

                comps = jax.lax.map(step, (comps, wts.T))
            pi = pi_new
        return pi

    def _mix(self, own, comps, pi, ok):
        w = pi * ok.astype(pi.dtype)
        tot = jnp.sum(w)
        w = jnp.where(tot > 0, w / jnp.maximum(tot, 1e-30), w)
        a = self.ref.tr["alpha"]

        def mix(o, ns):
            out = a * o.astype(jnp.float32) + (1 - a) * jnp.tensordot(
                w, ns.astype(jnp.float32), axes=1)
            return jnp.where(jnp.any(ok), out, o).astype(o.dtype)

        return jax.tree.map(mix, own, comps)

    def __call__(self, params, idx, ok):
        ref = self.ref
        params, loss = ref.local_sgd(params, idx)
        comps = stack([params[i] for i in ref.nbr])
        pi = self.em(comps, self.pi, self.em_x, self.em_y)
        if ref.fault == "altered_pi":
            pi = jnp.roll(pi, 1)
        mixed = self.mix(params[0], comps, pi, ok)
        params[0], loss0 = ref.sgd(mixed, ref.tx[0], ref.ty[0], idx[0])
        loss[0] = float(loss0)
        self.pi = pi
        return params, loss, {
            "pi": np.asarray(pi, np.float64),
            "link_success_rate": float(jnp.mean(ok.astype(jnp.float32)))}


def round_flops(s: flops.Shapes) -> float:
    """Local SGD, the E-step forwards and weight-gradient pullbacks over the
    EM rows, the Eq-1 mix, the target's post-aggregation pass, the eval."""
    m = int(s.participants[1:].sum())
    rows = min(s.tr["em_subset"], int(s.sizes[0]))
    it = s.tr["em_iters"]
    em = m * rows * (it * s.model.forward_flops(s.mc)
                     + (it - 1) * s.model.weight_grad_flops(s.mc))
    post = s.steps * s.batch * s.model.train_flops(s.mc)
    return (flops.local_sgd(s) + em + 2 * m * s.n_params + post
            + flops.evaluation(s))
