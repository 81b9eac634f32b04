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


# ---------------------------------------------------------------------------
# The forward's sub-tiles: a tile on the diagonal is walked at the
# granularity of the mask, key-major (statistics as rows along lanes)
# ---------------------------------------------------------------------------

@pytest.fixture
def sub_tile(monkeypatch):
    """Small blocks hold 2 x 2 and 4 x 4 sub-tiles where the sub-tile is
    small too: the rule's constant, set for the test."""
    from horovod_tpu.ops import flash_attention as fa

    def set_to(sub):
        monkeypatch.setattr(fa, "_SUB_TILE", sub)
    return set_to


# (sq, sk, d, block, sub-tile, dtype, kwargs of both sides); offsets
# marked traced go through jit as arguments (a ring step's call).
SUBTILE_CASES = {
    "diagonal_2x2": (128, 128, 32, 64, 32, jnp.float32, dict(causal=True)),
    "diagonal_4x4": (128, 128, 32, 64, 16, jnp.float32, dict(causal=True)),
    "diagonal_shifted_a_block_4x4": (
        128, 192, 32, 64, 16, jnp.float32, dict(causal=True, q_offset=64)),
    "ring_on_the_diagonal_traced": (
        128, 128, 32, 64, 16, jnp.float32,
        dict(causal=True, q_offset=256, k_offset=256)),
    "ring_off_every_boundary_traced": (
        128, 128, 32, 64, 16, jnp.float32,
        dict(causal=True, q_offset=7, k_offset=3)),
    "ring_whole_rows_masked_traced": (
        128, 128, 32, 64, 16, jnp.float32,
        dict(causal=True, q_offset=0, k_offset=40)),
    "kv_len_cuts_a_sub_tile_causal": (
        128, 128, 32, 64, 16, jnp.float32, dict(causal=True, kv_len=77)),
    "kv_len_cuts_a_sub_tile": (
        128, 128, 32, 64, 16, jnp.float32, dict(causal=False, kv_len=77)),
    "unaligned_seq_and_head_dim": (
        100, 100, 48, 64, 16, jnp.float32, dict(causal=True)),
    "head_dim_256_2x2": (       # two lane tiles a head: half the side
        256, 256, 256, 128, 128, jnp.float32, dict(causal=True)),
    "bfloat16_4x4": (128, 128, 64, 64, 16, jnp.bfloat16, dict(causal=True)),
    "scale_not_a_power_of_two": (
        128, 128, 32, 64, 16, jnp.float32, dict(causal=True, sm_scale=0.3)),
    "blocks_not_square": (
        128, 128, 32, 64, 32, jnp.float32, dict(causal=True, block_k=32)),
}


@pytest.mark.parametrize("case", sorted(SUBTILE_CASES))
def test_subtiled_forward_matches_reference(sub_tile, case):
    """Value, log-sum-exp and the gradients through both (the new
    forward's lse into the unchanged backward) against the oracle."""
    sq, sk, d, block, sub, dtype, kwargs = SUBTILE_CASES[case]
    sub_tile(sub)
    kwargs = dict(kwargs)
    blocks = dict(block_q=block, block_k=kwargs.pop("block_k", block))
    q = _rand((1, 2, sq, d), 0, dtype)
    k, v = _rand((1, 2, sk, d), 1, dtype), _rand((1, 2, sk, d), 2, dtype)
    traced = {name: jnp.int32(kwargs.pop(name))
              for name in ("q_offset", "k_offset")
              if case.endswith("_traced")}

    @functools.partial(jax.jit, static_argnums=0)
    def run(attend, offsets):
        attend = functools.partial(attend, **kwargs, **offsets,
                                   with_lse=True)
        return attend(q, k, v), _grads(attend, q, k, v)

    (o, lse), g1 = run(functools.partial(flash_attention, **blocks), traced)
    (ro, rlse), g2 = run(reference_attention, traced)
    value, grad = (3e-2, 6e-2) if dtype == jnp.bfloat16 else (2e-5, 5e-4)
    assert o.dtype == dtype
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ro, np.float32),
                               atol=value, rtol=value)
    # Rows that see no key: l == 0, and the log-sum-exp says so.
    unseen = np.asarray(rlse) < -1e29
    assert (np.asarray(lse)[unseen] < -1e29).all()
    assert unseen.any() == (case == "ring_whole_rows_masked_traced")
    np.testing.assert_allclose(np.asarray(lse)[~unseen],
                               np.asarray(rlse)[~unseen],
                               atol=max(value, 1e-4), rtol=value)
    for a, b in zip(g1, g2):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=grad, rtol=grad)


@pytest.mark.parametrize("causal", [False, True])
def test_subtiled_dropout_mask_forward_and_gradient(sub_tile, causal):
    """The keep-mask's block is sliced a sub-tile's rows and keys at a
    time; forward and backward use the same pattern."""
    sub_tile(16)
    q, k, v = (_rand((1, 2, 128, 32), i) for i in range(3))
    dm = jax.random.bernoulli(jax.random.PRNGKey(13), 0.8, (1, 2, 128, 128))
    kwargs = dict(causal=causal, dropout_mask=dm, dropout_rate=0.2)
    out = flash_attention(q, k, v, block_q=64, block_k=64, **kwargs)
    ref = reference_attention(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    _assert_grads_match(q, k, v, dict(block_q=64, block_k=64), **kwargs)


# ---------------------------------------------------------------------------
# The backward's strips: the block on the diagonal walked as the forward
# walks it, at a side of the backward's own
# ---------------------------------------------------------------------------

@pytest.fixture
def bwd_strips(monkeypatch):
    """Force the backward's strip side so that a block holds ``strips``
    strips, and see which side each backward kernel call was given."""
    from horovod_tpu.ops import flash_attention as fa

    def set_to(block, strips):
        monkeypatch.setattr(fa, "_SUB_TILE_BWD", block // strips)
        given, chunk = [], fa._bwd_chunk
        monkeypatch.setattr(fa, "_bwd_chunk", lambda *a, **kw: (
            given.append((kw["qb0"], kw["sub"])), chunk(*a, **kw))[1])
        return given
    return set_to


# (sq, sk, d, block, dtype, kwargs of both sides); offsets marked traced
# go through jit as arguments (a ring step's call). Where the blocks are
# square and the mask causal the kernel is given the forced side; the
# diagonal's blocks then walk strips unless the case says what stops them.
BWD_STRIP_CASES = {
    "one_block": (64, 64, 32, 64, jnp.float32, dict(causal=True)),
    "several_blocks_3x3": (192, 192, 32, 64, jnp.float32,
                           dict(causal=True)),
    "head_dim_64": (128, 128, 64, 64, jnp.float32, dict(causal=True)),
    "head_dim_128": (128, 128, 128, 64, jnp.float32, dict(causal=True)),
    "head_dim_256": (256, 256, 256, 128, jnp.float32, dict(causal=True)),
    "padded_sequence_and_head": (100, 100, 48, 64, jnp.float32,
                                 dict(causal=True)),
    # The second block on the diagonal has padded keys: the general mask.
    "kv_len_short_of_the_block": (128, 128, 32, 64, jnp.float32,
                                  dict(causal=True, kv_len=100)),
    "ring_on_the_diagonal_traced": (
        128, 128, 32, 64, jnp.float32,
        dict(causal=True, q_offset=256, k_offset=256)),
    "ring_a_block_off_the_diagonal_traced": (
        128, 128, 32, 64, jnp.float32,
        dict(causal=True, q_offset=192, k_offset=128)),
    "ring_off_every_boundary_traced": (
        128, 128, 32, 64, jnp.float32,
        dict(causal=True, q_offset=7, k_offset=3)),
    "lse_cotangent": (128, 128, 32, 64, jnp.float32,
                      dict(causal=True, with_lse=True)),
    "lse_cotangent_shifted_a_block": (
        128, 192, 32, 64, jnp.float32,
        dict(causal=True, q_offset=64, with_lse=True)),
    "bfloat16": (128, 128, 64, 64, jnp.bfloat16, dict(causal=True)),
    "scale_not_a_power_of_two": (
        128, 128, 32, 64, jnp.float32, dict(causal=True, sm_scale=0.3)),
    "dropout_mask": (128, 128, 32, 64, jnp.float32,
                     dict(causal=True, dropout_rate=0.25)),
}


@pytest.mark.parametrize("strips", [1, 2, 4])
@pytest.mark.parametrize("case", sorted(BWD_STRIP_CASES))
def test_backward_strips_match_reference(bwd_strips, case, strips):
    sq, sk, d, block, dtype, kwargs = BWD_STRIP_CASES[case]
    given = bwd_strips(block, strips)
    kwargs = dict(kwargs)
    q = _rand((1, 2, sq, d), 0, dtype)
    k, v = _rand((1, 2, sk, d), 1, dtype), _rand((1, 2, sk, d), 2, dtype)
    if "dropout_rate" in kwargs:
        kwargs["dropout_mask"] = jax.random.bernoulli(
            jax.random.PRNGKey(3), 1 - kwargs["dropout_rate"],
            (1, 2, sq, sk))
    traced = {name: jnp.int32(kwargs.pop(name))
              for name in ("q_offset", "k_offset")
              if case.endswith("_traced")}

    @functools.partial(jax.jit, static_argnums=0)
    def grads(attend, offsets):
        return _grads(attend, q, k, v, **kwargs, **offsets)

    g1 = grads(functools.partial(flash_attention, block_q=block,
                                 block_k=block), traced)
    g2 = grads(reference_attention, traced)
    assert given == [(0, block // strips)]
    tol = 6e-2 if dtype == jnp.bfloat16 else 5e-4
    for a, b in zip(g1, g2):
        assert a.shape == b.shape
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("strips", [1, 2, 4])
@pytest.mark.parametrize("variant", ["plain", "dropout"])
def test_backward_strips_in_query_chunks(monkeypatch, bwd_strips, variant,
                                         strips):
    """The chunked query range: the blocks on the diagonal of the second
    and third chunk (``qb0`` 2 and 4) are found by the tile's index in
    the whole sequence, and walk strips there too."""
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setattr(
        fa, "_DQ_RESIDENT_BYTES",
        2 * fa._dq_resident_bytes(32, 32, jnp.float32))
    given = bwd_strips(32, strips)
    q, k, v = (_rand((1, 2, 160, 32), i) for i in range(3))
    kwargs = dict(causal=True)
    if variant == "dropout":
        kwargs.update(dropout_rate=0.25, dropout_mask=jax.random.bernoulli(
            jax.random.PRNGKey(3), 0.75, (1, 2, 160, 160)))
    _assert_grads_match(q, k, v, dict(block_q=32, block_k=32), **kwargs)
    assert given == [(qb0, 32 // strips) for qb0 in (0, 2, 4)]


@pytest.mark.parametrize("strips", [1, 2, 4])
def test_backward_strips_slice_the_seeded_layouts_pattern(monkeypatch,
                                                          bwd_strips,
                                                          strips):
    """The on-chip dropout variant draws one (queries, keys) pattern a
    tile in both kernels; the strips slice it as the forward does. The
    prng has no CPU lowering, so a pattern of the tile's coordinates
    stands in for the draw, and the oracle gets the same one whole."""
    from horovod_tpu.ops import flash_attention as fa
    rate, seq, block = 0.2, 128, 64

    def pattern(rows, cols, seed):
        return (rows * 7 + cols * 13 + seed) % 5 != 0

    def draw(lens_ref, qb, kb, block_q, block_k, dropout_rate):
        shape = (block_q, block_k)
        keep = pattern(
            qb * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            kb * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1),
            lens_ref[3])
        return keep.astype(jnp.float32) * (1.0 / (1.0 - dropout_rate))

    monkeypatch.setattr(fa, "_seeded_keep_scale", draw)
    given = bwd_strips(block, strips)
    q, k, v = (_rand((1, 2, seq, 64), i) for i in range(3))
    w = _rand((2, seq, 64), 4)
    lens = jnp.asarray([0, 0, seq, 3], jnp.int32)

    def seeded(q, k, v):
        flat = (x.reshape(2, seq, 64) for x in (q, k, v))
        return jnp.sum(w * fa._flash_seeded(*flat, lens, 0.125, True,
                                            block, block, rate))

    def oracle(q, k, v):
        at = jnp.arange(seq)
        mask = pattern(at[:, None], at[None, :], 3)[None, None]
        return jnp.sum(w * reference_attention(
            q, k, v, causal=True, dropout_mask=mask,
            dropout_rate=rate)[0])

    np.testing.assert_allclose(seeded(q, k, v), oracle(q, k, v), rtol=1e-5)
    g1 = jax.grad(seeded, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
    assert given == [(0, block // strips)]
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_lowered_gradient_holds_each_kernels_body_once(monkeypatch):
    """The layers of a model make the same two kernel calls, and both go
    through ``jax.jit``: the lowered gradient of four layers holds one
    forward and one backward kernel body, each called four times."""
    from horovod_tpu.models import TransformerConfig, TransformerLM
    from horovod_tpu.ops import flash_attention as fa
    # The kernel asks the default backend whether to interpret; here
    # that is the CPU, and the lowering is for the TPU.
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    model = TransformerLM(TransformerConfig(
        vocab_size=64, hidden=64, layers=4, heads=2, max_len=256,
        attention_impl="flash"))
    tokens = jnp.zeros((1, 256), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)

    def loss(params, tokens):
        return model.apply(params, tokens).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss)).trace(params, tokens).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
    for wrapper in ("_fwd_jit", "_bwd_chunk"):
        assert text.count(f"func.func private @{wrapper}(") == 1
        assert text.count(f"call @{wrapper}(") == 4


# (n_q, n_k, block_q, block_k, q_offset, k_offset, kv_len)
KV_MAP_CASES = {
    "square_4x4": (4, 4, 64, 64, 0, 0, 256),
    "ring_past_chunk": (2, 4, 64, 64, 256, 0, 256),
    "ring_future_chunk": (2, 2, 64, 64, 0, 128, 128),
    "off_every_boundary": (3, 5, 32, 64, 7, 3, 320),
    "negative_numerator": (4, 3, 32, 32, 0, 40, 96),
    "kv_len_ends_early": (4, 4, 64, 64, 0, 0, 130),
    "wide_query_blocks": (2, 6, 128, 32, 16, 0, 192),
}


@pytest.mark.parametrize("case", sorted(KV_MAP_CASES))
def test_forward_kv_index_map_fetches_once_on_skipped_steps(case):
    """The K/V index map alone, offsets as the traced scalars an index
    map sees: a visible step names its own block; the skipped steps
    after it all name one block inside the grid, block 0, which the
    next visible step (the next query block's first) names too, so of
    a row's skipped steps at most the first fetches. The dropout
    mask's map keeps the row's last visible block, and fetches
    nothing."""
    from horovod_tpu.ops import flash_attention as fa
    n_q, n_k, block_q, block_k, q_offset, k_offset, kv_len = \
        KV_MAP_CASES[case]
    lens = jnp.asarray([q_offset, k_offset, kv_len], jnp.int32)
    grid_of = (n_k, block_q, block_k, True)
    skipped_steps = 0
    for i in range(n_q):
        held = 0
        last = int(fa._last_key_block(jnp.int32(i), lens, *grid_of))
        assert 0 <= last < n_k
        for j in range(n_k):
            named = int(fa._kv_block(jnp.int32(i), jnp.int32(j), lens,
                                     *grid_of))
            if fa._block_skip(True, q_offset, k_offset, kv_len, i, j,
                              block_q, block_k):
                skipped_steps += 1
                # (a row that sees nothing holds block 0 throughout)
                assert named == 0 and (j > last or last == 0), (i, j)
                assert min(j, last) == held, (i, j)
            else:
                assert named == j and j <= last, (i, j)
                held = j
    assert (skipped_steps > 0) == (case != "ring_past_chunk")
    # Without a causal mask, or with one key block, the map is the
    # identity (a single block is held from step to step anyway).
    for i in range(n_q):
        for j in range(n_k):
            assert fa._kv_block(i, j, lens, n_k, block_q, block_k,
                                False) == j
    assert fa._kv_block(3, 0, lens, 1, block_q, block_k, True) == 0
    assert fa._last_key_block(3, lens, 1, block_q, block_k, True) is None


# The nine cells that run the kernel, a kind of call each: (seq,
# head_dim, window) at 1024 blocks, and per (batch, head) the forward's
# sub-tiles by kind (interior, masked, skipped, hidden by the window
# alone), the live tiles, which are the steps either grid runs, and of
# those the steps that fetch nothing. Without a window that is one: the
# forward's second query block starts with key block 0, held since the
# first's only step, and the backward's last key block sees the last
# query block alone, held since the row before. Under the 512 window a
# query block sees its own key block and the one before it, which is
# held already: 7 of the 15 steps.
CELL_COUNTS = {
    "lm365m-seq8192-1chip": (8192, 64, None, (120, 16, 120, 0), 36, 1),
    "lm365m-seq2048-1chip": (2048, 64, None, (6, 4, 6, 0), 3, 1),
    "lm365m-seq2048-4chip": (2048, 64, None, (6, 4, 6, 0), 3, 1),
    "lm365m-seq512-1chip": (512, 64, None, (0, 1, 0, 0), 1, 0),
    "glm47flash-seq4096-1chip": (4096, 256, None, (120, 16, 120, 0), 10, 1),
    "ouro26b-seq4096-1chip": (4096, 128, None, (28, 8, 28, 0), 10, 1),
    "phi4miniflash-seq8192-1chip": (8192, 64, None, (120, 16, 120, 0), 36,
                                    1),
    "phi4miniflash-seq8192-1chip-window512": (
        8192, 64, 512, (0, 31, 120, 105), 15, 7),
    "smallthinker21b-seq16384-1chip": (
        16384, 128, None, (496, 32, 496, 0), 136, 1),
    "smallthinker21b-seq16384-1chip-window4096": (
        16384, 128, 4096, (196, 56, 496, 276), 70, 1),
    "lfm2moe24b-seq8192-1chip": (8192, 64, None, (120, 16, 120, 0), 36, 1),
}


def _counts(interior, masked, skipped, window, unfetched):
    counts = {"interior": interior, "masked": masked, "skipped": skipped,
              "steps_without_fetch": unfetched}
    if window:
        counts["window"] = window
    return counts


@pytest.mark.parametrize("cell", sorted(CELL_COUNTS))
def test_fwd_subtile_counts_at_the_cells_shapes(cell):
    from horovod_tpu.ops import flash_attention as fa
    seq, d, window, kinds, live, unfetched = CELL_COUNTS[cell]
    counts = fa.fwd_subtile_counts(seq, seq, 1024, 1024, True, head_dim=d,
                                   window=window)
    assert counts == _counts(*kinds, unfetched)
    block = min(seq, 1024)
    sub = fa._sub_tile(True, block, block, d) or block
    assert sum(kinds) == (seq // sub) ** 2
    # Every sub-tile is on or under the diagonal, over it, or crossed;
    # the window hides some of those on or under it.
    n = seq // sub
    interior, masked, skipped, hidden = kinds
    assert (interior + masked + hidden, skipped) == (n * (n + 1) // 2,
                                                     n * (n - 1) // 2)
    assert window or (masked, hidden) == (n, 0)
    # The grid runs the tiles that do something and no other.
    for kernel in ("fwd", "bwd"):
        assert fa.grid_steps(kernel, seq, seq, 1024, 1024, True,
                             window=window) == {"run": live, "live": live}


# The backward at its own side, 128 at every head width: sub-tiles by
# kind. The steps that fetch no q/do block are the blocks' and not the
# side's: as many as the forward's that fetch no K/V.
BWD_CELL_SUBTILES = {
    (8192, None): (2016, 64, 2016, 0), (4096, None): (496, 32, 496, 0),
    (2048, None): (120, 16, 120, 0), (512, None): (6, 4, 6, 0),
    (16384, None): (8128, 128, 8128, 0),
    (8192, 512): (186, 124, 2016, 1770),
    (16384, 4096): (3472, 224, 8128, 4560)}


@pytest.mark.parametrize("cell", sorted(CELL_COUNTS))
def test_bwd_subtile_counts_at_the_cells_shapes(cell, monkeypatch):
    from horovod_tpu.ops import flash_attention as fa
    seq, d, window, forwards, _, unfetched = CELL_COUNTS[cell]
    assert fa.bwd_subtile_counts(
        seq, seq, 1024, 1024, True, head_dim=d, window=window) == _counts(
            *BWD_CELL_SUBTILES[seq, window], unfetched)
    # At the forward's side it counts what the forward counts (seq 2048
    # at 512: interior 6, masked 4, skipped 6 a head).
    block = min(seq, 1024)
    monkeypatch.setattr(fa, "_SUB_TILE_BWD",
                        fa._sub_tile(True, block, block, d))
    assert fa.bwd_subtile_counts(
        seq, seq, 1024, 1024, True, head_dim=d, window=window) == _counts(
            *forwards, unfetched)


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_subtile_counts_follow_offsets_and_kv_len(kernel, monkeypatch):
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_SUB_TILE_BWD", fa._SUB_TILE)
    counts = functools.partial(fa.subtile_counts, kernel)
    # Off the sub-tiles' corners nothing is walked in sub-tiles: the
    # three tiles the diagonal touches go through the mask whole.
    assert counts(2048, 2048, 1024, 1024, True, q_offset=7, k_offset=3) == {
        "interior": 4, "masked": 12, "skipped": 0, "steps_without_fetch": 0}
    # No mask: tiles are not walked in sub-tiles, and count as one each.
    assert counts(2048, 2048, 1024, 1024, False) == {
        "interior": 4, "masked": 0, "skipped": 0, "steps_without_fetch": 0}
    # Key padding: the last key block is cut, the one before is whole.
    # The block past them all keeps one step of the backward's grid,
    # which writes its dk and dv, zeros, and names the query block held.
    assert counts(256, 384, 128, 128, False, kv_len=200) == {
        "interior": 2, "masked": 2, "skipped": 2,
        "steps_without_fetch": int(kernel == "bwd")}
    assert fa.grid_steps(kernel, 256, 384, 128, 128, False, kv_len=200) == {
        "run": 4 + (kernel == "bwd"), "live": 4}


def test_bwd_subtile_counts_name_the_blocks_the_index_map_names():
    """Two key blocks by two query blocks on the diagonal: the second
    key block's only step names the query block that is held since the
    first key block's last step, and fetches nothing (the tile over the
    diagonal is no step of the grid). With ``kv_len`` short of the
    second key block, the block on the diagonal there goes through the
    general mask whole."""
    from horovod_tpu.ops import flash_attention as fa
    at = dict(q_offset=128, k_offset=128)
    assert fa.bwd_subtile_counts(256, 256, 128, 128, True, **at)[
        "steps_without_fetch"] == 1
    assert fa.grid_steps("bwd", 256, 256, 128, 128, True, **at) == {
        "run": 3, "live": 3}
    cut = fa.bwd_subtile_counts(256, 256, 128, 128, True, kv_len=250, **at)
    n = 128 // fa._sub_tile(True, 128, 128, 64, backward=True)
    assert cut["masked"] == n * n + n     # block (1, 1) whole, (0, 0) walked
    assert cut["skipped"] == n * n + n * (n - 1) // 2


def test_subtile_gauges_are_set_when_metrics_are_on(monkeypatch):
    from horovod_tpu import telemetry
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    monkeypatch.setattr(fa, "_SUB_TILE_BWD", 64)
    telemetry.reset()
    try:
        q, k, v = (_rand((1, 1, 256, 32), i) for i in range(3))
        jax.jit(functools.partial(flash_attention, causal=True,
                                  block_q=128, block_k=128))(q, k, v)

        def values(name):
            return {s["labels"]["kind"]: s["value"] for s in
                    telemetry.registry().families()[name].samples()}
        assert values("hvd_flash_fwd_subtiles") == {
            "interior": 1.0, "masked": 2.0, "skipped": 1.0,
            "steps_without_fetch": 1.0}
        # The backward at a side of 64: 4 x 4 sub-tiles a block.
        assert values("hvd_flash_bwd_subtiles") == {
            "interior": 6.0, "masked": 4.0, "skipped": 6.0,
            "steps_without_fetch": 1.0}

        def steps():
            return {(s["labels"]["kernel"], s["labels"]["kind"]): s["value"]
                    for s in telemetry.registry().families()[
                        "hvd_flash_grid_steps"].samples()}
        # Three of the four tiles do something, and the grids run those.
        assert steps() == {(kernel, kind): 3.0 for kernel in ("fwd", "bwd")
                           for kind in ("run", "live")}
        # Traced offsets: the schedule is not known here. The grids run
        # every tile, and no other count is set.
        telemetry.reset()
        jax.jit(lambda o: flash_attention(q, k, v, causal=True,
                                          q_offset=o))(jnp.int32(0))
        assert not {"hvd_flash_fwd_subtiles", "hvd_flash_bwd_subtiles"} \
            & set(telemetry.registry().families())
        assert steps() == {("fwd", "run"): 4.0, ("bwd", "run"): 4.0}
    finally:
        monkeypatch.delenv("HOROVOD_TPU_METRICS", raising=False)
        telemetry.reset()


# ---------------------------------------------------------------------------
# The grid's steps: one axis over the tiles that do something, from a
# table made while tracing (every tile, from one made on the device,
# where the offsets are traced)
# ---------------------------------------------------------------------------

# (sq, sk, block_q, block_k, causal, q_offset, k_offset, kv_len, window,
# the backward's qb0): the cells' calls, and offsets off the corners, a
# short ``kv_len``, outputs no tile is left of, a chunk of a query range.
STEP_TABLE_CASES = {
    **{cell: (seq, seq, min(seq, 1024), min(seq, 1024), True, 0, 0, seq,
              window, 0)
       for cell, (seq, _, window, *_) in CELL_COUNTS.items()},
    "off_the_corners": (320, 448, 64, 64, True, 7, 3, 448, None, 0),
    "off_the_corners_window": (512, 512, 64, 64, True, 70, 5, 512, 100, 0),
    "window_and_short_kv_len": (512, 512, 64, 64, True, 0, 0, 130, 96, 0),
    "short_kv_len": (256, 384, 64, 64, False, 0, 0, 130, None, 0),
    "short_kv_len_causal": (256, 384, 64, 64, True, 128, 0, 200, None, 0),
    "rows_that_see_nothing": (256, 256, 64, 64, True, 0, 128, 256, None, 0),
    "nothing_visible": (128, 128, 64, 64, True, 0, 128, 128, None, 0),
    "no_mask": (192, 128, 64, 64, False, 0, 0, 128, None, 0),
    "wide_query_blocks": (256, 192, 128, 32, True, 16, 0, 192, None, 0),
    "second_chunk_of_the_queries": (128, 320, 64, 64, True, 0, 0, 320, None,
                                    2),
    "last_chunk_under_a_window": (64, 320, 64, 64, True, 0, 0, 320, 100, 4),
}


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("case", sorted(STEP_TABLE_CASES))
def test_step_table_lists_the_live_tiles_once_in_the_grids_order(case,
                                                                 kernel):
    from horovod_tpu.ops import flash_attention as fa
    (sq, sk, block_q, block_k, causal, q_offset, k_offset, kv_len, window,
     qb0) = STEP_TABLE_CASES[case]
    backward = kernel == "bwd"
    n_q, n_k = sq // block_q, sk // block_k
    where = (q_offset, k_offset, kv_len)
    table = fa._step_table(backward, where, n_q, n_k, block_q, block_k,
                           causal, window, qb0)
    assert isinstance(table, np.ndarray) and table.dtype == np.int32
    row, inner, fetch, flags = table.reshape(4, -1)
    steps = list(zip(*((inner, row) if backward else (row, inner))))
    live = {(i, j) for i in range(n_q) for j in range(n_k)
            if not fa._block_skip(causal, *where, qb0 + i, j, block_q,
                                  block_k, window)}
    # Every tile not skipped exactly once; a skipped one only as the one
    # step of an output's block that no tile is left of.
    assert len(set(steps)) == len(steps) and live <= set(steps)
    for i, j in set(steps) - live:
        assert not any(a == i for a, _ in live) or (
            backward and not any(b == j for _, b in live)), (i, j)
    assert set(row) == set(range(n_k if backward else n_q))
    assert not backward or set(inner) == set(range(n_q))
    # Rows ascend, and the steps inside one; the flags sit on a row's
    # first and last step, and a query block's of the backward.
    assert sorted(zip(row, inner)) == list(zip(row, inner))
    turns = [a != b for a, b in zip(row[1:], row[:-1])]
    assert [bool(f & fa._ROW_FIRST) for f in flags] == [True] + turns
    assert [bool(f & fa._ROW_LAST) for f in flags] == turns + [True]
    for bit, first in ((fa._DQ_FIRST, True), (fa._DQ_LAST, False)):
        met = list(inner) if first else list(inner)[::-1]
        want = [met.index(qb) == at for at, qb in enumerate(met)]
        assert [bool(f & bit) for f in flags] == (
            (want if first else want[::-1]) if backward
            else [False] * len(steps))
    # Every step fetches its own block: none is there to hold one.
    assert (fetch == inner).all()
    if not qb0:
        assert fa.grid_steps(kernel, sq, sk, block_q, block_k, causal,
                             q_offset, k_offset, kv_len, window) == {
            "run": len(steps), "live": len(live)}
        # No idle step wherever every block of an output has a live
        # tile, as in every cell's call.
        unseen = n_q - len({i for i, _ in live}) + backward * (
            n_k - len({j for _, j in live}))
        assert (len(steps) == len(live)) == (unseen == 0)
        assert unseen == 0 or case not in CELL_COUNTS


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("case", ["off_the_corners", "off_the_corners_window",
                                  "short_kv_len_causal",
                                  "rows_that_see_nothing", "no_mask",
                                  "wide_query_blocks",
                                  "second_chunk_of_the_queries"])
def test_step_table_of_traced_offsets_lists_every_tile(case, kernel):
    """The offsets as the traced scalars a ring step hands in: every
    tile in the rectangular grid's order, the flags on a row's ends, and
    in the fetched block's column what ``_kv_block`` / ``_q_block``
    name, so a skipped step holds a block and fetches nothing."""
    from horovod_tpu.ops import flash_attention as fa
    (sq, sk, block_q, block_k, causal, q_offset, k_offset, kv_len, window,
     qb0) = STEP_TABLE_CASES[case]
    backward = kernel == "bwd"
    n_q, n_k = sq // block_q, sk // block_k
    lens = jnp.asarray([q_offset, k_offset, kv_len], jnp.int32)
    qb0 = qb0 if backward else 0
    table = jax.jit(lambda lens: fa._step_table(
        backward, lens, n_q, n_k, block_q, block_k, causal, window, qb0))(
            lens)
    row, inner, fetch, flags = np.asarray(table).reshape(4, -1)
    n_rows, n_inner = (n_k, n_q) if backward else (n_q, n_k)
    assert list(zip(row, inner)) == [(a, b) for a in range(n_rows)
                                     for b in range(n_inner)]
    for a, b, named, flag in zip(row, inner, fetch, flags):
        if backward:
            want = fa._q_block(jnp.int32(a), jnp.int32(b), lens, n_q,
                               block_q, block_k, causal, qb0, window, n_k)
        else:
            want = fa._kv_block(jnp.int32(a), jnp.int32(b), lens, n_k,
                                block_q, block_k, causal, window, n_q)
        assert named == int(want), (a, b)
        skipped = fa._block_skip(
            causal, q_offset, k_offset, kv_len,
            *((qb0 + b, a) if backward else (a, b)), block_q, block_k,
            window)
        assert skipped or named == b, (a, b)
        assert flag == (
            fa._ROW_FIRST * (b == 0) + fa._ROW_LAST * (b == n_inner - 1)
            + backward * (fa._DQ_FIRST * (a == 0)
                          + fa._DQ_LAST * (a == n_rows - 1)))
    assert fa.grid_steps(kernel, sq, sk, block_q, block_k, causal,
                         lens[0], k_offset, kv_len, window) == {
        "run": n_q * n_k}


# (q's shape, K/V heads, blocks, kwargs): the same call with its offsets
# known while tracing (the grids run the live tiles) and traced (every
# tile, the skipped ones guarded in the body).
LIVE_TABLE_CASES = {
    "causal": ((1, 2, 256, 32), 2, 64, dict(causal=True)),
    "causal_offsets_short_kv_len": (
        (1, 2, 256, 32), 2, 64, dict(causal=True, q_offset=70, k_offset=5,
                                     kv_len=200)),
    "window_512_of_8192_in_miniature": (
        (1, 2, 512, 32), 2, 64, dict(causal=True, window=32)),
    "window_four_blocks_long": (
        (1, 2, 512, 32), 2, 64, dict(causal=True, window=256)),
    "grouped_heads": ((1, 4, 256, 32), 2, 64, dict(causal=True)),
    "grouped_heads_window": (
        (1, 6, 320, 32), 2, 64, dict(causal=True, window=100)),
    "query_chunks": ((1, 2, 320, 32), 2, 64, dict(causal=True)),
    "query_chunks_rows_that_see_nothing": (
        (1, 2, 320, 32), 2, 64, dict(causal=True, k_offset=100)),
    "lse_cotangent": ((1, 2, 256, 32), 2, 64,
                      dict(causal=True, with_lse=True)),
    "dropout_mask": ((1, 2, 256, 32), 2, 64,
                     dict(causal=True, dropout_rate=0.25)),
    "wide_value_bfloat16": ((1, 2, 256, 64), 2, 64, dict(causal=True)),
}


@pytest.mark.parametrize("case", sorted(LIVE_TABLE_CASES))
def test_live_table_is_bit_equal_to_every_tile(monkeypatch, case):
    """Outputs and all three gradients: the live steps run in the order
    they had in the rectangular grid, so every running sum adds the same
    terms in the same order."""
    from horovod_tpu.ops import flash_attention as fa
    shape, kv_heads, block, kwargs = LIVE_TABLE_CASES[case]
    kwargs = dict(kwargs)
    dtype = jnp.bfloat16 if "bfloat16" in case else jnp.float32
    kv_shape = (shape[0], kv_heads) + shape[2:]
    q, k = _rand(shape, 0, dtype), _rand(kv_shape, 1, dtype)
    v = _rand(kv_shape[:3] + (128,) if "wide_value" in case else kv_shape,
              2, dtype)
    if case.startswith("query_chunks"):
        monkeypatch.setattr(
            fa, "_DQ_RESIDENT_BYTES",
            2 * fa._dq_resident_bytes(block, shape[3], dtype))
    if "dropout_rate" in kwargs:
        kwargs["dropout_mask"] = jax.random.bernoulli(
            jax.random.PRNGKey(3), 1 - kwargs["dropout_rate"],
            shape[:3] + shape[2:3])
    offsets = {name: kwargs.pop(name, 0) for name in ("q_offset", "k_offset")}
    grids, table = [], fa._step_table
    monkeypatch.setattr(fa, "_step_table", lambda backward, where, *a: (
        grids.append((backward, isinstance(where, tuple))),
        table(backward, where, *a))[1])

    @jax.jit
    def run(traced):
        attend = functools.partial(
            flash_attention, block_q=block, block_k=block, **kwargs,
            **{**offsets, **traced})
        return attend(q, k, v), _grads(attend, q, k, v)

    live = run({})
    assert grids and all(static for _, static in grids)
    chunks = 3 if case.startswith("query_chunks") else 1
    assert sum(backward for backward, _ in grids) == chunks
    del grids[:]
    every = run({name: jnp.int32(at) for name, at in offsets.items()})
    assert grids and not any(static for _, static in grids)
    for a, b in zip(jax.tree.leaves(live), jax.tree.leaves(every)):
        assert a.dtype == b.dtype and np.isfinite(
            np.asarray(a, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# layout="bshd": the operands as the projections leave them. At width 64
# the kernels read and write [batch x heads, width, seq] (``addressing``:
# "seq_minor"), which is how XLA holds such arrays; every other width is
# the "bhsd" call of the transposed operands.
# ---------------------------------------------------------------------------

# (batch, seq, query heads, K/V heads, width, v's width, tile): width 64
# with as many K/V heads as query heads, with four and with two query
# heads to a K/V head, an odd head count, differential attention's wide
# values; the widths that fill lane tiles and one under them that is not
# 64, which are head-major; each at one tile a head and at several.
BSHD_SHAPES = {
    "w64_16over16": (1, 128, 16, 16, 64, 64),
    "w64_32over8": (1, 128, 32, 8, 64, 64),
    "w64_40over20": (1, 128, 40, 20, 64, 64),
    "w64_3over3_batch2": (2, 192, 3, 3, 64, 64),
    "w64_v128_4over2": (2, 128, 4, 2, 64, 128),
    "w128_28over4": (1, 128, 28, 4, 128, 128),
    "w128_32over4": (1, 128, 32, 4, 128, 128),
    "w256_v128_2over2": (2, 128, 2, 2, 256, 128),
    "w32_4over2": (2, 128, 4, 2, 32, 32),
}
BSHD_MASKS = {
    "causal": dict(causal=True),
    "window": dict(causal=True, window=70),
    "mask_lse": dict(causal=True, with_lse=True),   # and mask=
    "lse": dict(causal=True, with_lse=True),
}


def _bshd_operands(shape, dtype, masks):
    b, s, h, g, d, dv = BSHD_SHAPES[shape]
    q, k, v = (_rand((b, s, n, width), i, dtype) for i, (n, width)
               in enumerate(((h, d), (g, d), (g, dv))))
    kwargs = dict(BSHD_MASKS[masks])
    if masks == "mask_lse":
        keep = np.random.RandomState(3).rand(b, s, s) < 0.5
        kwargs["mask"] = jnp.asarray(keep | np.eye(s, dtype=bool), jnp.int8)
    return q, k, v, kwargs


def _as_bhsd(attend):
    """``attend`` on "bshd" operands through the "bhsd" call: the
    transposes are the caller's."""
    def call(q, k, v, **kwargs):
        out = attend(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                     **kwargs)
        if kwargs.get("with_lse"):
            return out[0].swapaxes(1, 2), out[1]
        return out.swapaxes(1, 2)
    return call


def _bshd_cases():
    """Every shape under the causal mask; under the others the shapes of
    few heads (the interpreter runs a grid row a head), and ``with_lse``
    without a mask where the call allows it: as many K/V heads as query
    heads, and values as wide as the keys."""
    for shape, (b, s, h, g, d, dv) in sorted(BSHD_SHAPES.items()):
        for masks in sorted(BSHD_MASKS):
            if masks != "causal" and h > 16:
                continue
            if masks == "lse" and (g != h or dv != d):
                continue
            yield shape, masks


@pytest.mark.parametrize("tile", [128, 64], ids=["one_tile", "tiles"])
@pytest.mark.parametrize("shape,masks", list(_bshd_cases()))
def test_bshd_layout_matches_reference_and_the_bhsd_call(shape, masks, tile):
    b, s, h, g, d, dv = BSHD_SHAPES[shape]
    q, k, v, kwargs = _bshd_operands(shape, jnp.float32, masks)
    blocks = dict(block_q=tile, block_k=tile)
    held = functools.partial(flash_attention, layout="bshd", **blocks)
    outs = jax.tree.leaves(held(q, k, v, **kwargs))
    want = jax.tree.leaves(_as_bhsd(reference_attention)(q, k, v, **kwargs))
    same = jax.tree.leaves(_as_bhsd(functools.partial(
        flash_attention, **blocks))(q, k, v, **kwargs))
    assert outs[0].shape == (b, s, h, dv)
    for out, ref, bhsd in zip(outs, want, same):
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        # The same sums in the same order, whichever way the blocks lie:
        # in float32 the interpreter's products agree bit for bit.
        np.testing.assert_array_equal(np.asarray(out), np.asarray(bhsd))
    grads = _grads(held, q, k, v, **kwargs)
    for got, ref, bhsd in zip(
            grads, _grads(_as_bhsd(reference_attention), q, k, v, **kwargs),
            _grads(_as_bhsd(functools.partial(flash_attention, **blocks)),
                   q, k, v, **kwargs)):
        assert got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=5e-4, rtol=5e-4)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(bhsd))


@pytest.mark.parametrize("shape", ["w64_16over16", "w64_v128_4over2",
                                   "w128_28over4"])
def test_bshd_layout_in_bfloat16(shape):
    """bfloat16 operands, as the cells run: the interpreter's products of
    bfloat16 do not round alike for every order of the dimensions, so the
    two layouts agree to a unit in the last place of bfloat16."""
    q, k, v, kwargs = _bshd_operands(shape, jnp.bfloat16, "window")
    blocks = dict(block_q=64, block_k=64)
    out = flash_attention(q, k, v, layout="bshd", **blocks, **kwargs)
    ref = _as_bhsd(functools.partial(flash_attention, **blocks))(
        q, k, v, **kwargs)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2 ** -7, rtol=2 ** -7)
    for got, want in zip(
            _grads(functools.partial(flash_attention, layout="bshd",
                                     **blocks), q, k, v, **kwargs),
            _grads(_as_bhsd(functools.partial(flash_attention, **blocks)),
                   q, k, v, **kwargs)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2 ** -5, rtol=2 ** -6)


def test_bshd_layout_pads_the_positions_and_takes_traced_offsets():
    q, k, v = (_rand((2, 100, 2, 64), i) for i in range(3))
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          kv_len=90, layout="bshd")
    ref = _as_bhsd(reference_attention)(q, k, v, causal=True, kv_len=90)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    out = jax.jit(lambda o: flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64, q_offset=o,
        layout="bshd"))(jnp.int32(32))
    ref = _as_bhsd(reference_attention)(q, k, v, causal=True, q_offset=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_bshd_layout_with_dropout_is_the_head_major_call():
    q, k, v = (_rand((1, 128, 2, 64), i) for i in range(3))
    keep = jnp.asarray(np.random.RandomState(4).rand(1, 2, 128, 128) < 0.9)
    kwargs = dict(causal=True, dropout_mask=keep, dropout_rate=0.1)
    out = flash_attention(q, k, v, layout="bshd", **kwargs)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(_as_bhsd(flash_attention)(q, k, v, **kwargs)))


def test_layout_is_one_of_two():
    q = _rand((1, 2, 128, 64), 0)
    with pytest.raises(ValueError, match="layout"):
        flash_attention(q, q, q, layout="sbhd")


@pytest.mark.parametrize("shape", sorted(BSHD_SHAPES))
def test_addressing_is_chosen_from_the_widths(shape):
    from horovod_tpu.ops.flash_attention import (
        HEAD_MAJOR, SEQ_MINOR, addressing)
    b, s, h, g, d, dv = BSHD_SHAPES[shape]
    assert addressing(d, dv) == (SEQ_MINOR if d == 64 else HEAD_MAJOR)
    assert addressing(d, dv, layout="bhsd") == HEAD_MAJOR
    assert addressing(d, dv, dropout=True) == HEAD_MAJOR


def test_layout_counter_says_how_each_traced_call_addressed_a_head(
        monkeypatch):
    from horovod_tpu import telemetry
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    telemetry.reset()
    try:
        def count():
            families = telemetry.registry().families()
            return {s["labels"]["kind"]: s["value"]
                    for s in families["hvd_flash_layout"].samples()}
        for shape, calls in (("w64_16over16", {"seq_minor": 1.0}),
                             ("w64_v128_4over2", {"seq_minor": 2.0}),
                             ("w32_4over2", {"seq_minor": 2.0,
                                             "head_major": 1.0}),
                             ("w128_28over4", {"seq_minor": 2.0,
                                               "head_major": 2.0})):
            q, k, v, kwargs = _bshd_operands(shape, jnp.float32, "causal")
            jax.jit(functools.partial(flash_attention, layout="bshd",
                                      **kwargs)).lower(q, k, v)
            assert count() == calls
        # Head-major operands are head-major calls, whatever their width.
        q = _rand((1, 2, 128, 64), 0)
        jax.jit(functools.partial(flash_attention, causal=True)).lower(
            q, q, q)
        assert count() == {"seq_minor": 2.0, "head_major": 3.0}
    finally:
        monkeypatch.delenv("HOROVOD_TPU_METRICS", raising=False)
        telemetry.reset()
