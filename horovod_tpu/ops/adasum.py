"""Adasum: scale-invariant adaptive summation.

Re-implementation of the reference's Adasum reduction (reference:
horovod/common/ops/adasum/adasum.h:194-343; pairwise rule at :397-407):

    a' = (1 - dot(a,b) / (2*||a||^2)) * a  +  (1 - dot(a,b) / (2*||b||^2)) * b

applied over a binary tree of rank pairs (rank r combines with r XOR 2^t in
round t — the vector-halving distance-doubling schedule). The reference
restricts Adasum to power-of-2 rank counts
(reference: horovod/tensorflow/__init__.py:138-154); we keep that contract.

On TPU the whole tree is one jitted XLA program: in single-controller mode
the stacked operand already holds every rank's tensor, so the tree is pure
compute (XLA schedules any ICI moves); for in-jit use inside shard_map see
``adasum_axis`` which runs the same schedule with ppermute exchanges.
"""

import jax
import jax.numpy as jnp
from jax import lax


def _is_pow2(n):
    return n > 0 and (n & (n - 1)) == 0


def adasum_pair(a, b, eps=0.0):
    """Combine two gradient tensors with the Adasum rule (fp32 math,
    zero-norm guarded like the reference's CheckPointerSendRecv path)."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    dot = jnp.sum(af * bf)
    na = jnp.sum(af * af)
    nb = jnp.sum(bf * bf)
    ca = jnp.where(na > eps, 1.0 - dot / (2.0 * jnp.maximum(na, 1e-30)), 1.0)
    cb = jnp.where(nb > eps, 1.0 - dot / (2.0 * jnp.maximum(nb, 1e-30)), 1.0)
    return (ca * af + cb * bf).astype(a.dtype)


def adasum_tree(stacked):
    """Reduce a stacked (n, ...) tensor down the VHDD pair tree; returns the
    combined tensor of shape ``stacked.shape[1:]``."""
    n = stacked.shape[0]
    if not _is_pow2(n):
        raise ValueError(
            f"Adasum requires a power-of-2 number of ranks, got {n} "
            "(reference restriction, horovod/tensorflow/__init__.py:138)")
    xs = [stacked[i] for i in range(n)]
    dist = 1
    while dist < n:
        for i in range(0, n, 2 * dist):
            xs[i] = adasum_pair(xs[i], xs[i + dist])
        dist *= 2
    return xs[0]


def adasum_pair_np(a, b):
    """Numpy float64 reference of the pairwise rule — the ONE oracle
    shared by the host-plane SPMD test, the compiled-plane tests, and
    the multichip dryrun leg (duplicating it risks the copies drifting
    on the zero-norm guard / promotion details)."""
    import numpy as np
    af = np.asarray(a, np.float64).ravel()
    bf = np.asarray(b, np.float64).ravel()
    dot = float(af @ bf)
    na = float(af @ af)
    nb = float(bf @ bf)
    ca = 1.0 - dot / (2.0 * na) if na > 0 else 1.0
    cb = 1.0 - dot / (2.0 * nb) if nb > 0 else 1.0
    return (ca * np.asarray(a, np.float64)
            + cb * np.asarray(b, np.float64))


def adasum_vhdd_np(stack):
    """Numpy pairwise VHDD tree over a list/stack of tensors."""
    import numpy as np
    xs = [np.asarray(x, np.float64) for x in stack]
    while len(xs) > 1:
        xs = [adasum_pair_np(xs[i], xs[i + 1])
              for i in range(0, len(xs), 2)]
    return xs[0]


def adasum_allreduce_stacked(backend, arrays, process_set, prescale=None,
                             postscale=None):
    """Eager stacked Adasum used by XlaSingleBackend (one jitted program per
    fusion bucket)."""
    mesh = backend._mesh(process_set)
    n = mesh.devices.size
    key = ("adasum", process_set.process_set_id)

    def build():
        def fn(scales, *xs):
            pre, post = scales
            outs = []
            for x in xs:
                if pre is not None:
                    x = x * pre.astype(x.dtype)
                y = adasum_tree(x)
                if post is not None:
                    y = y * post.astype(y.dtype)
                outs.append(jnp.broadcast_to(y[None], (n,) + y.shape))
            return tuple(outs)
        return jax.jit(fn)

    fn = backend._cached(key, build)
    pre = jnp.asarray(1.0 if prescale is None else prescale, jnp.float32)
    post = jnp.asarray(1.0 if postscale is None else postscale, jnp.float32)
    ins = tuple(backend.shard(process_set, jnp.asarray(a)) for a in arrays)
    outs = fn((pre, post), *ins)
    return [backend.shard(process_set, o) for o in outs]


def adasum_axis(x, axis_name):
    """In-jit Adasum over a mesh axis, for use inside shard_map/pjit.

    Runs the VHDD schedule with ppermute exchanges: in round t each rank
    swaps its current accumulator with partner = rank XOR 2^t and applies the
    pairwise rule. All ranks converge to the tree reduction. This is the
    compiled-data-plane analog of the reference's AdasumMPI recursive
    halving (reference: horovod/common/ops/adasum/adasum_mpi.cc).
    """
    n = lax.axis_size(axis_name)
    if not _is_pow2(n):
        raise ValueError(f"Adasum requires power-of-2 axis size, got {n}")
    idx = lax.axis_index(axis_name)
    acc = x
    dist = 1
    while dist < n:
        perm = [(i, i ^ dist) for i in range(n)]
        other = lax.ppermute(acc, axis_name, perm)
        # Ordering: the lower rank of the pair is 'a', higher is 'b', so both
        # sides compute the identical (symmetric) combination.
        is_low = (idx & dist) == 0
        acc = adasum_pair(jnp.where(is_low, acc, other),
                          jnp.where(is_low, other, acc))
        dist *= 2
    return acc
