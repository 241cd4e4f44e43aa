"""FedAvg: every client's local SGD, then every participant adopts the
size-weighted mean of the participants' models; the others keep their own.

A method's file gives the reference its ``Round`` (called each round with
the clients' params, the round's minibatch rows and link draws; returns the
params, every client's loss as the program reports it, and ``pi`` and
``link_success_rate`` where the method has them), the FLOPs of a whole
round from shapes, and the faults only this method can have.
"""
import jax
import jax.numpy as jnp

from bench import flops
from bench.reference import stack

FAULTS = ()


class Round:
    def __init__(self, ref):
        self.ref = ref
        w = ref.sizes * ref.part
        w = jnp.asarray(w / w.sum(), jnp.float32)
        pm = jnp.asarray(ref.part)

        def mean(params):
            def agg(p):
                g = jnp.tensordot(w, p.astype(jnp.float32), axes=1)
                m = pm.reshape((-1,) + (1,) * (p.ndim - 1))
                return jnp.where(m, g.astype(p.dtype)[None], p)

            return jax.tree.map(agg, params)

        self.mean = jax.jit(mean)

    def __call__(self, params, idx, ok):
        params, loss = self.ref.local_sgd(params, idx)
        mean = self.mean(stack(params))
        return ([jax.tree.map(lambda a: a[i], mean)
                 for i in range(len(params))], loss, {})


def round_flops(s: flops.Shapes) -> float:
    """Local SGD, the weighted mean over the participants, the eval."""
    return (flops.local_sgd(s) + 2 * int(s.participants.sum()) * s.n_params
            + flops.evaluation(s))
