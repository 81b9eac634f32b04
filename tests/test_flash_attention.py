"""Flash-attention kernel correctness vs the einsum oracle (interpret mode
on the CPU mesh; same kernel code compiles on TPU)."""

import functools

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


# ---------------------------------------------------------------------------
# The one backward kernel: dq, dk and dv from one pass over the tiles
# ---------------------------------------------------------------------------

def _grads(attend, q, k, v, seed=5, **kwargs):
    """Gradients of a fixed random projection of every output (the value
    and, with ``with_lse``, the visible rows' log-sum-exp)."""
    def loss(q, k, v):
        outs = jax.tree.leaves(attend(q, k, v, **kwargs))
        total = jnp.sum(outs[0] * _rand(outs[0].shape, seed))
        for lse in outs[1:]:
            total += jnp.sum(jnp.where(lse > -1e29, lse, 0.0)
                             * _rand(lse.shape, seed + 1))
        return total
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _assert_grads_match(q, k, v, flash_kwargs=None, **kwargs):
    g1 = _grads(flash_attention, q, k, v, **kwargs, **(flash_kwargs or {}))
    g2 = _grads(reference_attention, q, k, v, **kwargs)
    for a, b in zip(g1, g2):
        assert a.shape == b.shape and np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)
    return g1


# (sq, sk, d, block_q, block_k, kwargs): what the two backward kernels
# covered between them, each case with several tiles on the axis it names.
FUSED_BACKWARD_CASES = {
    "sq_lt_sk_kv_len_padding": (
        64, 160, 32, 32, 32, dict(causal=False, kv_len=150)),
    "sq_gt_sk_kv_len_padding": (
        160, 96, 32, 32, 32, dict(causal=False, kv_len=70)),
    "causal_3x5_tiles_offset": (
        96, 160, 32, 32, 32, dict(causal=True, q_offset=64, kv_len=150)),
    "causal_5x2_tiles_wide_keys": (
        160, 128, 32, 32, 64, dict(causal=True, q_offset=0)),
    "causal_unaligned_padded": (
        100, 100, 48, 64, 32, dict(causal=True)),
    "lse_cotangent_with_offsets": (
        96, 128, 32, 32, 32,
        dict(causal=True, q_offset=32, k_offset=0, with_lse=True)),
    "lse_cotangent_future_keys": (
        64, 64, 32, 32, 32,
        dict(causal=True, q_offset=16, k_offset=32, with_lse=True)),
    "head_dim_256_3x2_tiles": (
        384, 256, 256, 128, 128, dict(causal=True, q_offset=128)),
}


@pytest.mark.parametrize("case", sorted(FUSED_BACKWARD_CASES))
def test_fused_backward_matches_reference(case):
    sq, sk, d, block_q, block_k, kwargs = FUSED_BACKWARD_CASES[case]
    q = _rand((1, 2, sq, d), 0)
    k, v = _rand((1, 2, sk, d), 1), _rand((1, 2, sk, d), 2)
    _assert_grads_match(q, k, v, dict(block_q=block_q, block_k=block_k),
                        **kwargs)


# A ring step's kernel call: offsets traced under jit, one compiled
# kernel for every position of the key chunk.
RING_POSITIONS = {"all_visible": (128, 0), "diagonal": (128, 128),
                  "all_skipped": (0, 128)}


@pytest.mark.parametrize("with_lse", [False, True], ids=["out", "out_lse"])
@pytest.mark.parametrize("position", sorted(RING_POSITIONS))
def test_fused_backward_ring_positions_traced_offsets(position, with_lse):
    q, k, v = (_rand((1, 2, 128, 32), i) for i in range(3))

    @functools.partial(jax.jit, static_argnums=0)
    def grads(attend, q_offset, k_offset):
        return _grads(attend, q, k, v, causal=True, q_offset=q_offset,
                      k_offset=k_offset, with_lse=with_lse)

    offsets = [jnp.int32(o) for o in RING_POSITIONS[position]]
    g1 = grads(functools.partial(flash_attention, block_q=32, block_k=64),
               *offsets)
    g2 = grads(reference_attention, *offsets)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)
    if position == "all_skipped":
        # Every tile skipped: the accumulators' zeros, not a rounding.
        for a in g1:
            assert not np.asarray(a).any()


@pytest.mark.parametrize("variant", ["plain", "lse_offsets", "dropout"])
def test_fused_backward_query_chunks(monkeypatch, variant):
    """A query range whose dq accumulator would not fit goes through the
    same kernel in chunks (here 2 + 2 + 1 tiles), dk and dv summed."""
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setattr(
        fa, "_DQ_RESIDENT_BYTES",
        2 * fa._dq_resident_bytes(32, 32, jnp.float32))
    q = _rand((1, 2, 160, 32), 0)
    k, v = _rand((1, 2, 96, 32), 1), _rand((1, 2, 96, 32), 2)
    kwargs = {
        "plain": dict(causal=True, kv_len=90),
        "lse_offsets": dict(causal=True, q_offset=8, k_offset=40,
                            with_lse=True),
        "dropout": dict(causal=True, dropout_rate=0.25,
                        dropout_mask=jax.random.bernoulli(
                            jax.random.PRNGKey(3), 0.75, (1, 2, 160, 96))),
    }[variant]
    calls = []
    chunk = fa._bwd_chunk
    monkeypatch.setattr(fa, "_bwd_chunk", lambda q, *a, **kw: (
        calls.append((kw["qb0"], q.shape[1])), chunk(q, *a, **kw))[1])
    _assert_grads_match(q, k, v, dict(block_q=32, block_k=32), **kwargs)
    assert calls == [(0, 64), (2, 64), (4, 32)]
