"""Varying-axes helper shared by the shard_map train steps."""

import jax
from jax import lax


def pvary(x, axis_name):
    """Mark ``x`` device-varying along ``axis_name`` (no-op if it
    already is; ``lax.pcast`` rejects a varying input).

    The cast's transpose is a psum. Differentiating with respect to the
    cast value gives the per-replica gradient (``make_train_step``
    reduces it explicitly); differentiating with respect to the
    replicated input gives the gradient already summed over the axis."""
    if axis_name in jax.typeof(x).vma:
        return x
    return lax.pcast(x, axis_name, to="varying")
