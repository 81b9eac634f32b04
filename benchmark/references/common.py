"""What the references share: the matrix product in a stated precision,
plain optimizers, and a training loop over blocks of rows.

``precision`` names how the operands of every matrix product (and
convolution) are held. "float32" is the reference itself. The others
are the controls, the precision below the one a configuration states,
with which the comparison has to come out false. "bfloat16" rounds the
operands to it (products of the rounded values are exact in float32
accumulation, as on the MXU). "int8" and "float8_e4m3fn" quantise both
operands of the forward product with one scale per tensor; the two
backward products take the quantised operands and the gradient as it
comes. "int8_all" and "float8_e4m3fn_all" quantise that gradient too, so
that all three products have operands in the lower precision.
"""

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("float32", "bfloat16", "int8", "float8_e4m3fn", "int8_all",
              "float8_e4m3fn_all")


def _quantise(x, precision):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if precision.startswith("int8"):
        scale = amax / 127.0
        q = jnp.round(x / scale)
    else:
        scale = amax / 448.0          # largest finite float8_e4m3fn
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def product(fn, a, b, precision):
    """``fn(a, b)``, a matrix product or convolution, with its operands
    held in ``precision``."""
    if precision == "float32":
        return fn(a, b)
    if precision == "bfloat16":
        return fn(a.astype(jnp.bfloat16).astype(jnp.float32),
                  b.astype(jnp.bfloat16).astype(jnp.float32))
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")

    def q(x):
        return _quantise(x, precision)

    @jax.custom_vjp
    def quantised(a, b):
        return fn(q(a), q(b))

    def forward(a, b):
        return jax.vjp(fn, q(a), q(b))

    def backward(vjp, g):
        # "..._all": the gradient operand of the two backward products
        # is quantised too, as the MXU's int8 or fp8 path would need it.
        return vjp(q(g) if precision.endswith("_all") else g)

    quantised.defvjp(forward, backward)
    return quantised(a, b)


def einsum(spec, a, b, precision):
    return product(
        lambda a, b: jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32),
        a, b, precision)


def softmax_xent_mean(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


# ---- plain optimizers: state and update written out ----------------------

def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"count": jnp.zeros((), jnp.int32), "mu": zeros,
            "nu": jax.tree.map(jnp.zeros_like, params)}


def adamw_update(params, state, grads, opt):
    lr, b1, b2 = opt["learning_rate"], opt["b1"], opt["b2"]
    eps, wd = opt["eps"], opt["weight_decay"]
    count = state["count"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                      state["nu"], grads)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p), params, mu, nu)
    return new, {"count": count, "mu": mu, "nu": nu}


def sgd_init(params):
    return {}


def sgd_update(params, state, grads, opt):
    lr = opt["learning_rate"]
    return jax.tree.map(lambda p, g: p - lr * g, params, grads), state


OPTIMIZERS = {"adamw": (adamw_init, adamw_update),
              "sgd": (sgd_init, sgd_update)}


def leaf_sqnorms(tree):
    """Squared L2 norm of every leaf, as one vector in leaf order."""
    return jnp.stack([jnp.sum(jnp.square(x.astype(jnp.float32)))
                      for x in jax.tree.leaves(tree)])


def leaf_names(tree):
    return [jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_leaves_with_path(tree)]
