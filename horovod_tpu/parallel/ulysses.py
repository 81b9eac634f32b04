"""Ulysses-style sequence parallelism: head-scatter all_to_all attention.

The second sequence-parallel flavor (DeepSpeed-Ulysses): instead of rotating
k/v chunks (ring_attention), one all_to_all re-shards the activations from
sequence-sharded to head-sharded, attention runs over the *full* sequence
with a subset of heads per device, and a second all_to_all restores sequence
sharding. Two collectives total — cheaper than a ring when
heads >= axis_size and sequence fits per-device memory; the ring wins for
extreme sequence lengths. Both compose with tp/dp via the mesh (mesh.py).
"""

from jax import lax


from ..ops.flash_attention import flash_attention, reference_attention


def ulysses_attention(q, k, v, axis_name="sp", *, causal=True, sm_scale=None,
                      impl="flash", block_q=256, block_k=256):
    """Sequence-parallel attention (call inside shard_map over ``axis_name``).

    Args:
      q, k, v: local chunks (batch, heads, seq_local, head_dim); heads must
        be divisible by the axis size.
    Returns the local output chunk (batch, heads, seq_local, head_dim).
    """
    n = lax.axis_size(axis_name)
    heads = q.shape[1]
    if heads % n != 0:
        raise ValueError(
            f"ulysses_attention needs heads ({heads}) divisible by the "
            f"'{axis_name}' axis size ({n}); use ring_attention otherwise")

    def scatter_heads(x):
        # (B, H, S/n, D) -> (B, H/n, S, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    if impl == "flash":
        oh = flash_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale,
                             block_q=block_q, block_k=block_k)
    else:
        oh = reference_attention(qh, kh, vh, causal=causal,
                                 sm_scale=sm_scale)
    # (B, H/n, S, D) -> (B, H, S/n, D)
    return lax.all_to_all(oh, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)
