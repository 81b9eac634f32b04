"""Flash-attention kernel correctness vs the einsum oracle (interpret mode
on the CPU mesh; same kernel code compiles on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import (
    flash_attention, reference_attention)


def _rand(shape, seed, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.normal(size=shape), dtype=dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(1, 2, 128, 32), (2, 2, 256, 64)])
def test_forward_matches_reference(causal, shape):
    b, h, s, d = shape
    q, k, v = (_rand(shape, i) for i in range(3))
    out = flash_attention(q, k, v, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_forward_unaligned_seq_and_dim():
    # 100 queries / head_dim 48: exercises the padding wrapper.
    q, k, v = (_rand((1, 2, 100, 48), i) for i in range(3))
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kv_len_masks_padding():
    q = _rand((1, 1, 128, 32), 0)
    k = _rand((1, 1, 128, 32), 1)
    v = _rand((1, 1, 128, 32), 2)
    out = flash_attention(q, k, v, kv_len=77)
    ref = reference_attention(q, k[:, :, :77], v[:, :, :77])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_offsets_shift_causal_mask():
    # With q_offset = seq_k, every key is visible (block-causal "past chunk").
    q = _rand((1, 1, 64, 32), 0)
    k = _rand((1, 1, 64, 32), 1)
    v = _rand((1, 1, 64, 32), 2)
    out = flash_attention(q, k, v, causal=True, q_offset=64, k_offset=0)
    ref = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # With k entirely in the future, output is all zeros.
    out2 = flash_attention(q, k, v, causal=True, q_offset=0, k_offset=64)
    np.testing.assert_allclose(np.asarray(out2), 0.0, atol=1e-6)


def test_lse_matches_reference():
    q, k, v = (_rand((1, 2, 128, 32), i) for i in range(3))
    _, lse = flash_attention(q, k, v, with_lse=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(32)
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(causal):
    q, k, v = (_rand((1, 2, 128, 32), i) for i in range(3))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_bfloat16_inputs():
    q, k, v = (_rand((1, 2, 128, 128), i, jnp.bfloat16) for i in range(3))
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)


def test_lse_cotangent_flows_through_kernel_vjp():
    # Direct kernel path (no shard_map fallback): gradient of a loss that
    # uses BOTH outputs must match the einsum oracle — regression for the
    # ring-attention-on-TPU backward path.
    q, k, v = (_rand((1, 2, 128, 32), i) for i in range(3))

    def loss_kernel(q, k, v):
        o, lse = flash_attention(q, k, v, causal=True, with_lse=True)
        return jnp.sum(o ** 2) + jnp.sum(jnp.where(lse > -1e29, lse, 0.0) ** 2)

    def loss_ref(q, k, v):
        o, lse = reference_attention(q, k, v, causal=True, with_lse=True)
        return jnp.sum(o ** 2) + jnp.sum(jnp.where(lse > -1e29, lse, 0.0) ** 2)

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("tile", [128, 256])
def test_head_dimension_256_matches_reference(tile):
    """Latent attention's shape: q.k and v 256 wide (two lane tiles a
    head), value and all three gradients, at a tile the sequence spans
    twice and at one it fills."""
    q, k, v = (_rand((1, 2, 256, 256), i) for i in range(3))
    weights = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)

    def loss(attend, q, k, v):
        return jnp.sum(attend(q, k, v) * weights)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=tile,
                               block_k=tile)

    def plain(q, k, v):
        return reference_attention(q, k, v, causal=True)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(plain(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    g1 = jax.grad(loss, argnums=(1, 2, 3))(flash, q, k, v)
    g2 = jax.grad(loss, argnums=(1, 2, 3))(plain, q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_transformer_attention_impl_parity():
    """TransformerLM(attention_impl='flash') matches the einsum path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.models import TransformerLM, TransformerConfig

    kw = dict(vocab_size=128, hidden=64, layers=2, heads=2, max_len=32,
              causal=True, use_rope=True, dtype=jnp.float32)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, size=(2, 32)))
    m_e = TransformerLM(TransformerConfig(**kw, attention_impl="einsum"))
    m_f = TransformerLM(TransformerConfig(**kw, attention_impl="flash"))
    params = m_e.init(jax.random.PRNGKey(0), tokens)
    out_e = m_e.apply(params, tokens)
    out_f = m_f.apply(params, tokens)
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_f),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_dropout_mask_matches_reference(causal):
    """Explicit-dropout-mask kernel path vs the einsum oracle using the
    SAME bernoulli mask (exact semantics: probs dropped after softmax,
    normalizer keeps the undropped sum, kept probs rescaled)."""
    b, h, s, d = 2, 2, 192, 32
    q, k, v = (_rand((b, h, s, d), i) for i in range(3))
    rate = 0.2
    dm = jax.random.bernoulli(jax.random.PRNGKey(9), 1.0 - rate,
                              (b, h, s, s))
    out = flash_attention(q, k, v, causal=causal, dropout_mask=dm,
                          dropout_rate=rate)
    ref = reference_attention(q, k, v, causal=causal, dropout_mask=dm,
                              dropout_rate=rate)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_dropout_mask_gradients_match_reference():
    b, h, s, d = 1, 2, 128, 32
    q, k, v = (_rand((b, h, s, d), i) for i in range(3))
    g = _rand((b, h, s, d), 7)
    rate = 0.1
    dm = jax.random.bernoulli(jax.random.PRNGKey(11), 1.0 - rate,
                              (b, h, s, s))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a, causal=True, dropout_mask=dm,
                                     dropout_rate=rate) * g)

    g1 = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=5e-4, rtol=5e-4)


def test_dropout_zero_mask_is_identity_path():
    """rate=0.0 ignores the mask entirely (no kernel-path change)."""
    q, k, v = (_rand((1, 1, 64, 32), i) for i in range(3))
    dm = jnp.zeros((1, 1, 64, 64), bool)
    out = flash_attention(q, k, v, dropout_mask=dm, dropout_rate=0.0)
    ref = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))
