"""Ring attention: context parallelism over the 'sp' mesh axis.

Long-context training shards the sequence across devices; each device holds
a contiguous query chunk and the k/v chunks rotate around the ring via
``lax.ppermute`` (one ICI hop per step) while flash-attention partials are
merged with the online-softmax rule. Communication overlaps compute: XLA
schedules the next ppermute concurrently with the current chunk's kernel.

The reference framework has no sequence-axis scaling at all (SURVEY.md §5.7)
— this module is the TPU rebuild's first-class long-context story. Causality
is handled in *global* coordinates by the flash kernel's chunk offsets, so
fully-future chunks contribute zero (lse = -inf) and merge away; no
host-side control flow depends on the ring step.

Differentiability: the ring is an unrolled loop of differentiable pieces
(flash custom-VJP, ppermute, softmax-merge), so JAX autodiff produces the
reverse ring schedule automatically.
"""

import jax
import jax.numpy as jnp
from jax import lax


from ..ops.flash_attention import (
    _NEG_INF, flash_attention, reference_attention)


def _merge(o1, lse1, o2, lse2):
    """Merge two normalized attention partials via their log-sum-exps.
    Accumulates in fp32 — the ring loop casts back to the input dtype only
    after the final merge (avoids n-1 bf16 rounding round-trips)."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    denom = w1 + w2
    safe = jnp.where(denom == 0.0, 1.0, denom)
    o = (o1.astype(jnp.float32) * w1[..., None]
         + o2.astype(jnp.float32) * w2[..., None]) / safe[..., None]
    lse = jnp.where(denom == 0.0, _NEG_INF, m + jnp.log(safe))
    return o, lse


def ring_attention(q, k, v, axis_name="sp", *, causal=True, sm_scale=None,
                   impl="flash", block_q=256, block_k=256):
    """Blockwise ring attention (call inside shard_map over ``axis_name``).

    Args:
      q, k, v: local chunks (batch, heads, seq_local, head_dim); the global
        sequence is ``axis_size * seq_local``, device i holding positions
        [i*seq_local, (i+1)*seq_local).
      impl: 'flash' (pallas kernel) or 'einsum' (oracle fallback for tiny
        shapes).
    Returns the local output chunk (batch, heads, seq_local, head_dim).
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    s_local = q.shape[2]
    q_off = idx * s_local
    perm = [(i, (i + 1) % n) for i in range(n)]

    def partial_attn(kc, vc, k_off):
        if impl == "flash":
            return flash_attention(
                q, kc, vc, causal=causal, sm_scale=sm_scale,
                q_offset=q_off, k_offset=k_off,
                block_q=block_q, block_k=block_k, with_lse=True)
        return reference_attention(q, kc, vc, causal=causal,
                                   sm_scale=sm_scale, q_offset=q_off,
                                   k_offset=k_off, with_lse=True)

    o = lse = None
    kc, vc = k, v
    for t in range(n):
        src = (idx - t) % n
        k_off = src * s_local
        if t < n - 1:
            # Launch the rotation before consuming the chunk so XLA can
            # overlap the ICI transfer with the attention kernel.
            kn = lax.ppermute(kc, axis_name, perm)
            vn = lax.ppermute(vc, axis_name, perm)
        o_t, lse_t = partial_attn(kc, vc, k_off)
        if o is None:
            o, lse = o_t.astype(jnp.float32), lse_t
        else:
            o, lse = _merge(o, lse, o_t, lse_t)
        if t < n - 1:
            kc, vc = kn, vn
    return o.astype(q.dtype)
