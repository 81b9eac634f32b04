"""Bucket plans and the packing of leaves into one buffer.

What is left of Horovod's fusion-buffer idea (reference:
horovod/common/controller.cc FuseResponses) once XLA schedules the
compiled step's exchange by itself:

- :func:`plan_buckets` groups leaves by dtype into buckets of a byte
  budget. The ZeRO legs (``ops/zero.py``, ``parallel/twod.py``) shard
  their state by that plan, and the redistribution planner
  (``resharding/spec.py``) reads its offsets; it walks the leaves last
  to first, so a plan's first bucket holds the gradients backprop
  produces first.
- ``_pack`` / ``_unpack`` concatenate a bucket's leaves into one flat
  buffer and slice it apart again: the ZeRO legs' wire format, and how
  ``horovod_tpu.jax._reduce_in_axis`` lets the small leaves of a dtype
  share one all-reduce in a step compiled under ``_OVERLAP_OPTIONS``.
  An elementwise collective (psum / pmean) of the concatenation is the
  per-leaf collective element for element, bit for bit
  (tests/test_exchange_schedule.py). Adasum and the wire codecs are
  never packed: both are defined per tensor.
- ``DEFAULT_BUCKET_BYTES`` is the default of ``HVDTPU_BUCKET_BYTES``,
  the eager plane's bucket under ``HVDTPU_OVERLAP`` (coordinator.py),
  and of ``HVDTPU_ZERO_BUCKET_BYTES``.

The compiled step has no bucketed exchange of its own: on a TPU XLA's
combiner merges per-bucket collectives again, so they hide nothing
(docs/performance.md section 2).
"""

import numpy as np

import jax.numpy as jnp
from jax import lax

DEFAULT_BUCKET_BYTES = 16 * 1024 * 1024


class Bucket:
    """One planned fusion bucket: leaf indices (all sharing ``dtype``)
    and the payload byte count."""

    __slots__ = ("indices", "dtype", "nbytes")

    def __init__(self, indices, dtype, nbytes):
        self.indices = indices
        self.dtype = dtype
        self.nbytes = nbytes

    def __repr__(self):
        return (f"Bucket(n={len(self.indices)}, dtype={self.dtype}, "
                f"bytes={self.nbytes})")


def plan_buckets(leaves, bucket_bytes=DEFAULT_BUCKET_BYTES, reverse=True):
    """Group leaf indices into per-dtype buckets of at most
    ``bucket_bytes`` payload (a single leaf larger than the budget gets
    its own bucket — tensors are never split). ``reverse`` walks the
    leaves last-to-first so bucket order approximates backprop
    availability order; the relative order WITHIN the returned index
    lists is always ascending, so unbucketing is a stable scatter.
    """
    bucket_bytes = max(int(bucket_bytes), 1)
    order = range(len(leaves) - 1, -1, -1) if reverse \
        else range(len(leaves))
    open_buckets = {}   # dtype -> (indices, nbytes)
    closed = []
    for i in order:
        leaf = leaves[i]
        dtype = jnp.asarray(leaf).dtype if not hasattr(leaf, "dtype") \
            else leaf.dtype
        nbytes = int(np.prod(leaf.shape)) * dtype.itemsize \
            if leaf.ndim else dtype.itemsize
        cur = open_buckets.get(str(dtype))
        if cur is not None and cur[1] + nbytes > bucket_bytes:
            closed.append(Bucket(sorted(cur[0]), dtype, cur[1]))
            cur = None
        if cur is None:
            cur = ([], 0)
        cur[0].append(i)
        open_buckets[str(dtype)] = (cur[0], cur[1] + nbytes)
    for indices, nbytes in open_buckets.values():
        dtype = leaves[indices[0]].dtype
        closed.append(Bucket(sorted(indices), dtype, nbytes))
    return closed


def _pack(leaves, bucket):
    flats = [jnp.ravel(leaves[i]) for i in bucket.indices]
    return flats[0] if len(flats) == 1 else jnp.concatenate(flats)


def _unpack(buf, leaves, bucket, out):
    sizes = [int(np.prod(leaves[i].shape)) for i in bucket.indices]
    offset = 0
    for i, size in zip(bucket.indices, sizes):
        out[i] = lax.slice(buf, (offset,), (offset + size,)).reshape(
            leaves[i].shape)
        offset += size
