"""Codec layer: one interface over wire quantization and dtype casts.

The EQuARX result (PAPERS.md: *Efficient Quantized AllReduce in XLA*,
arXiv:2506.17615) is that block-wise quantization pays for itself when
it is fused INTO the collective: quantize → reduce-scatter the narrow
blocks → dequantize-accumulate in a wide dtype → requantize → allgather
→ final dequantize. Accumulation never happens in the narrow dtype, so
the error stays bounded by the per-block quantization step instead of
growing with the cohort size.

Two codec families behind one :class:`Codec` interface:

- **Block codecs** (``int8``, ``fp8``): ``encode`` splits the last axis
  into fixed-size blocks and emits a narrow-dtype payload plus one f32
  scale per block (scale = blockwise max-abs / qmax). These are *wire*
  codecs: the collective itself must run the quantized pipeline
  (summing raw int8 payloads would be garbage), so the dispatch layer
  routes them to ``allreduce_quantized`` instead of wrapping a plain
  allreduce.
- **Cast codecs** (``none``, ``fp16``, ``bf16``): ``encode`` is an
  astype, scales are None, and a plain allreduce carries the narrow
  payload (the reference's ``horovod/tensorflow/compression.py``
  semantics).

Everything here is jit-traceable (shapes static under trace): the
backends call these helpers from inside compiled shard_map bodies, and
:func:`quantized_allreduce_axis` is the in-jit spelling for user train
steps (DistributedOptimizer's axis path).
"""

import jax.numpy as jnp
from jax import lax

DEFAULT_BLOCK = 256

_INT8_QMAX = 127.0
_FP8_DTYPE = getattr(jnp, "float8_e4m3fn", None)


def fp8_supported():
    """True when this jax build ships float8_e4m3fn (the fp8 codec is
    registered either way; selecting it without support is a loud
    error at dispatch, not a silent fp32 fallback)."""
    return _FP8_DTYPE is not None


class Codec:
    """One compression scheme for collective payloads.

    ``wire=True`` marks block codecs whose payload cannot ride a plain
    reduction (the collective must dequantize before accumulating);
    ``wire=False`` marks casts a plain allreduce can carry directly.
    """

    name = "abstract"
    wire = False
    lossy = False

    def encode(self, x, block):
        """(payload, scales) — scales is None for cast codecs."""
        raise NotImplementedError

    def decode(self, payload, scales, block, dtype=jnp.float32):
        raise NotImplementedError

    def wire_bytes(self, nelems, block, orig_itemsize):
        """Payload + scale bytes this codec puts on the wire for
        ``nelems`` values of an ``orig_itemsize``-wide input."""
        raise NotImplementedError


def _block_view(x, block):
    """Reshape the last axis into (nblocks, block); the caller pads to a
    multiple of ``block`` first (dispatch does)."""
    if x.shape[-1] % block:
        raise ValueError(
            f"codec input last axis {x.shape[-1]} is not a multiple of "
            f"block size {block} (the dispatch layer pads first)")
    return x.reshape(x.shape[:-1] + (x.shape[-1] // block, block))


class _BlockCodec(Codec):
    """Shared block-wise scheme: per-block scale = max-abs / qmax."""

    wire = True
    lossy = True
    qmax = None          # largest representable magnitude of the payload
    payload_np = None    # numpy-spellable wire dtype of the payload
    payload_itemsize = 1

    def _to_payload(self, v):
        raise NotImplementedError

    def _from_payload(self, q):
        raise NotImplementedError

    def encode(self, x, block):
        xb = _block_view(x.astype(jnp.float32), block)
        maxabs = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
        scale = maxabs / self.qmax
        # All-zero blocks: scale 0 would divide to nan; payload is all
        # zeros either way, so any nonzero divisor is correct.
        safe = jnp.where(scale > 0.0, scale, 1.0)
        q = self._to_payload(xb / safe)
        return (q.reshape(x.shape),
                jnp.squeeze(scale, axis=-1).astype(jnp.float32))

    def decode(self, payload, scales, block, dtype=jnp.float32):
        qb = self._from_payload(_block_view(payload, block))
        return (qb * scales[..., None].astype(jnp.float32)).reshape(
            payload.shape).astype(dtype)

    def wire_bytes(self, nelems, block, orig_itemsize):
        nblocks = -(-nelems // block)
        return nelems * self.payload_itemsize + nblocks * 4


class Int8BlockCodec(_BlockCodec):
    """Symmetric per-block int8: q = round(x * 127 / max|block|).
    Round-trip error is bounded by scale/2 = max|block| / 254."""

    name = "int8"
    qmax = _INT8_QMAX
    payload_np = "int8"

    def _to_payload(self, v):
        return jnp.clip(jnp.round(v), -_INT8_QMAX, _INT8_QMAX).astype(
            jnp.int8)

    def _from_payload(self, q):
        return q.astype(jnp.float32)


class FP8BlockCodec(_BlockCodec):
    """Per-block-scaled float8_e4m3fn: the block max maps to the fp8
    max-finite (448), keeping 3 mantissa bits of relative precision
    across the block's dynamic range. Payloads ride collectives as
    bitcast uint8 (not every backend reduces/permutes fp8 natively)."""

    name = "fp8"
    qmax = 448.0
    payload_np = "uint8"  # fp8 bits ride collectives bitcast to uint8

    def _to_payload(self, v):
        if _FP8_DTYPE is None:
            raise NotImplementedError(
                "the fp8 codec needs a jax build with "
                "jnp.float8_e4m3fn; use HVDTPU_COMPRESSION=int8")
        return lax.bitcast_convert_type(v.astype(_FP8_DTYPE), jnp.uint8)

    def _from_payload(self, q):
        if _FP8_DTYPE is None:
            raise NotImplementedError(
                "the fp8 codec needs a jax build with "
                "jnp.float8_e4m3fn; use HVDTPU_COMPRESSION=int8")
        return lax.bitcast_convert_type(q, _FP8_DTYPE).astype(jnp.float32)


class _CastCodec(Codec):
    """astype-on-the-wire codecs (reference compression semantics): a
    plain allreduce carries the narrow payload, accumulation happens in
    the narrow dtype — cheap, and fine for fp16/bf16."""

    lossy = True
    cast_dtype = None
    cast_itemsize = 2

    def encode(self, x, block):
        del block
        return x.astype(self.cast_dtype), None

    def decode(self, payload, scales, block, dtype=jnp.float32):
        del scales, block
        return payload.astype(dtype)

    def wire_bytes(self, nelems, block, orig_itemsize):
        del block
        return nelems * self.cast_itemsize


class FP16CastCodec(_CastCodec):
    name = "fp16"
    cast_dtype = jnp.float16


class BF16CastCodec(_CastCodec):
    name = "bf16"
    cast_dtype = jnp.bfloat16


class NoneCodec(Codec):
    name = "none"

    def encode(self, x, block):
        del block
        return x, None

    def decode(self, payload, scales, block, dtype=jnp.float32):
        del scales, block
        return payload.astype(dtype)

    def wire_bytes(self, nelems, block, orig_itemsize):
        del block
        return nelems * orig_itemsize


CODECS = {c.name: c for c in (NoneCodec(), FP16CastCodec(),
                              BF16CastCodec(), Int8BlockCodec(),
                              FP8BlockCodec())}


def get_codec(name):
    codec = CODECS.get(name)
    if codec is None:
        raise ValueError(
            f"unknown compression codec {name!r}; available: "
            f"{', '.join(sorted(CODECS))}")
    if name == "fp8" and not fp8_supported():
        raise ValueError(
            "codec 'fp8' selected but this jax build has no "
            "jnp.float8_e4m3fn; use 'int8' (or upgrade jax)")
    return codec


def padded_len(nelems, nranks, block):
    """Smallest length >= nelems divisible by nranks * block (every rank
    owns an equal whole number of blocks after the reduce-scatter)."""
    unit = nranks * block
    return -(-nelems // unit) * unit


def quantized_allreduce_axis(x, axis_name, codec="int8",
                             block=DEFAULT_BLOCK, average=True):
    """In-jit EQuARX allreduce over a shard_map axis.

    ``x`` is this replica's (un-reduced) array; returns the cross-replica
    sum (or mean) with both collective legs carried in the codec's wire
    format: quantize → all_to_all (the reduce-scatter leg) → dequantized
    f32 accumulation → requantize → all_gather → dequantize. Stateless —
    error feedback lives on the eager dispatch plane (ResidualStore),
    not inside jit (docs/compression.md, "Convergence caveats").
    """
    c = get_codec(codec) if isinstance(codec, str) else codec
    if not c.wire:
        raise ValueError(
            f"quantized_allreduce_axis needs a wire codec, got {c.name!r}")
    n = lax.axis_size(axis_name)
    orig_shape = x.shape
    orig_dtype = x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    nelems = flat.shape[0]
    padded = padded_len(nelems, n, block)
    if padded != nelems:
        flat = jnp.pad(flat, (0, padded - nelems))
    rows = flat.reshape(n, padded // n)
    q, s = c.encode(rows, block)
    # Reduce-scatter leg: rank r keeps every rank's quantized copy of
    # chunk r, accumulates in f32.
    q = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                       tiled=True)
    s = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0,
                       tiled=True)
    red = jnp.sum(c.decode(q, s, block), axis=0)
    if average:
        red = red / n
    # Allgather leg: requantized shard back out to every rank.
    q2, s2 = c.encode(red, block)
    qg = lax.all_gather(q2, axis_name, tiled=True)
    sg = lax.all_gather(s2, axis_name, tiled=True)
    out = c.decode(qg, sg, block)
    if padded != nelems:
        out = out[:nelems]
    return out.reshape(orig_shape).astype(orig_dtype)
