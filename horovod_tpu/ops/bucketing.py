"""Gradient bucketing for comm/compute overlap (``HVDTPU_OVERLAP``).

Horovod's core performance idea is to overlap gradient communication
with the remaining backward pass: gradients are packed into fixed-size
buckets and each bucket's collective is dispatched as soon as its
members are ready, so the reduction of layer N runs under the gradient
compute of layer N-1 (reference: horovod/common/controller.cc
FuseResponses; *Densifying Assumed-sparse Tensors*, arXiv:1905.04035,
on why dense bucketed accumulation beats per-tensor dispatch).

The in-jit realization here is dependency-driven rather than
hook-driven: :func:`bucketed_reduce_axis` emits ONE collective per
bucket whose operands are only that bucket's gradient leaves. Because
backprop produces gradients in reverse layer order, a bucket holding
late-layer gradients is ready while early layers are still
differentiating — XLA's latency-hiding scheduler is then free to run
its collective under the remaining backward compute, which a single
fused all-gradient barrier (or a reduction depending on the full tree)
structurally forbids. Buckets are planned over the REVERSED leaf order
for exactly that reason: leaf trees flatten roughly first-layer-first,
so reversing approximates gradient-availability order and the first
bucket issued is the first one ready.

Read from the compiled schedule for a TPU v5e 2x2 (PERF.md section 6,
PR 35): by itself this hides nothing there. XLA's all-reduce combiner
merges the buckets again into tuple all-reduces, synchronous and after
the last backward kernel. What keeps all-reduces apart, and
asynchronous, is ``horovod_tpu.jax._OVERLAP_OPTIONS``, which
``make_train_step`` passes on a TPU mesh of more than one chip for the
per-leaf exchange, which then overlaps without this module's two
copies; with ``HVDTPU_OVERLAP`` on, the step keeps the program it had
(docs/performance.md).

Numerics: splitting an elementwise collective (psum/pmean) into
per-bucket concatenated calls performs the identical per-element
cross-replica reduction, so the bucketed path is bit-identical to the
per-leaf path for Sum/Average — pinned by
tests/test_overlap.py::test_overlap_bit_exact_vs_barrier. Wire-codec
buckets (int8/fp8) quantize the CONCATENATED bucket, so quantization
blocks may span tensor boundaries; that changes rounding relative to
per-tensor quantization (never relative to OVERLAP=0 plain fp32, which
stays exact) and is documented in docs/performance.md.

Adasum is excluded: its scale-invariant combination is defined per
tensor, and concatenating tensors into one vector would change the dot
products it is built from. Callers keep Adasum on the per-leaf path.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import reduce_ops

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


def bucketed_reduce_axis(leaves, op, axis_name, *,
                         bucket_bytes=DEFAULT_BUCKET_BYTES,
                         prescale=None, postscale=None,
                         wire_codec=None, block=256):
    """Per-bucket gradient reduction over a shard_map axis.

    Plain path (``wire_codec=None``): one ``psum``/``pmean`` per bucket
    — bit-identical to the per-leaf reduction, but with per-bucket data
    dependencies the XLA scheduler can overlap with remaining backprop.
    Wire path: one EQuARX quantized pipeline per bucket
    (``quantized_allreduce_axis`` on the concatenated buffer), so both
    collective legs of every bucket ride the narrow format.

    Returns the reduced leaves in the original order.
    """
    if op not in (reduce_ops.Average, reduce_ops.Sum):
        raise ValueError(
            "bucketed_reduce_axis supports Average/Sum only (Adasum's "
            f"per-tensor combination cannot be bucketed); got "
            f"{reduce_ops.op_name(op)}")
    if not leaves:
        return []
    out = [None] * len(leaves)
    for bucket in plan_buckets(leaves, bucket_bytes):
        buf = _pack(leaves, bucket)
        if prescale is not None:
            buf = buf * jnp.asarray(prescale).astype(buf.dtype)
        if wire_codec is not None:
            from ..compression.codecs import quantized_allreduce_axis
            buf = quantized_allreduce_axis(
                buf, axis_name, codec=wire_codec, block=block,
                average=(op == reduce_ops.Average))
        elif op == reduce_ops.Average:
            buf = lax.pmean(buf, axis_name)
        else:
            buf = lax.psum(buf, axis_name)
        if postscale is not None:
            buf = buf * jnp.asarray(postscale).astype(buf.dtype)
        _unpack(buf, leaves, bucket, out)
    return out
