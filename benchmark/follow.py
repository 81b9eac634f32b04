"""Drive a plain reference through the first steps of a cell's traffic
and return its ``Trail`` (see check.py).

The reference takes the batch a block of rows at a time (``lax.scan``
with the gradients summed), so that float32 activations fit beside its
float32 weights and optimizer state. On several chips the rows of a
block lie one share per chip and XLA partitions the plain program.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.check import Trail
from benchmark.references import common


def make_step(reference, cfg, precision, mesh=None):
    _, update = common.OPTIMIZERS[cfg["optimizer"]["name"]]

    def step(params, aux, opt_state, blocks):
        n = jax.tree.leaves(blocks)[0].shape[0]

        def one(carry, block):
            gsum, lsum, aux = carry
            (loss, aux), grads = jax.value_and_grad(
                reference.loss_fn, has_aux=True)(
                    params, aux, block, cfg, precision)
            return (jax.tree.map(jnp.add, gsum, grads), lsum + loss,
                    aux), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (gsum, lsum, aux), _ = lax.scan(
            one, (zeros, jnp.zeros(()), aux), blocks)
        grads = jax.tree.map(lambda g: g / n, gsum)
        params, opt_state = update(params, opt_state, grads,
                                   cfg["optimizer"])
        return params, aux, opt_state, lsum / n, common.leaf_sqnorms(grads)

    kwargs = {}
    if mesh is not None and mesh.devices.size > 1:
        kwargs["out_shardings"] = NamedSharding(mesh, P())
    return jax.jit(step, donate_argnums=(0, 1, 2), **kwargs)


def follow(reference, cfg, feed, make_params, steps, step, mesh=None):
    """``step`` is ``make_step``'s; ``make_params()`` gives the seeded
    weights (called twice: the second copy is what the change is
    measured from)."""
    init_opt, _ = common.OPTIMIZERS[cfg["optimizer"]["name"]]
    params = make_params()
    names = common.leaf_names(params)
    aux, opt_state = reference.init_aux(cfg), init_opt(params)
    losses, grad_norms = [], None
    for k in range(steps):
        params, aux, opt_state, loss, gsq = step(
            params, aux, opt_state, feed.reference_blocks(k, mesh))
        losses.append(float(loss))
        if k == 0:
            grad_norms = [math.sqrt(x) for x in gsq.tolist()]
    update_norms = change_norms(params, make_params())
    return Trail(losses, grad_norms, update_norms, names)


@jax.jit
def _change_sqnorms(after, before):
    return common.leaf_sqnorms(jax.tree.map(jnp.subtract, after, before))


def change_norms(after, before):
    return [math.sqrt(x) for x in _change_sqnorms(after, before).tolist()]
