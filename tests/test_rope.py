"""Rotary position embeddings of ``models/transformer.py``: the rotation
with its own backward against a rotate-half formula written here with
slices in float32, and what the TPU compiler makes of one attention
layer with it (compile only, for a described v5e: nothing runs).

How close: the two sides take the same float32 products and one float32
sum of two of them, and round once. In float32 they differ by a
contraction of the multiply-add at most; a bfloat16 result therefore
differs only where that moved the float32 value across a rounding
boundary: by one ulp, and in fewer than one element of a hundred.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P, SingleDeviceSharding

from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import (
    Attention, TransformerConfig, TransformerLM)

BATCH, HEADS = 2, 2


def _rotate_half_formula(x):
    """x: [b, s, n, d] -> float32. Base 10000, lane i paired with lane
    i + d // 2; everything after the cast is float32."""
    seq, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = 1.0 / (10000.0 ** (np.arange(half) / half))
    angles = jnp.asarray(np.arange(seq)[:, None] * freqs[None, :],
                         jnp.float32)[None, :, None, :]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ulps_apart(a, b):
    """Distance in units of the last place, elementwise, of two arrays of
    one floating dtype."""
    bits = {2: np.int16, 4: np.int32}[a.dtype.itemsize]

    def ordered(x):
        i = np.asarray(x).view(bits).astype(np.int64)
        return np.where(i < 0, -(i & np.iinfo(bits).max), i)
    return np.abs(ordered(a) - ordered(b))


def _assert_same(ours, theirs, dtype):
    assert ours.dtype == dtype and ours.shape == theirs.shape
    if dtype == jnp.bfloat16:
        apart = _ulps_apart(ours, theirs)
        assert apart.max() <= 1
        assert (apart > 0).mean() < 0.01
    else:
        np.testing.assert_allclose(ours, theirs, rtol=0, atol=4e-6)


def _both_ways(rope, x, w):
    """The rotation of ``x`` and the gradient of a weighted sum of it."""
    def loss(x):
        return jnp.sum(rope(x).astype(jnp.float32) * w)
    return rope(x), jax.grad(loss)(x)


def _ours(x):
    return transformer._rope(x, x)[0]


def _theirs(x):
    return _rotate_half_formula(x).astype(x.dtype)


@pytest.mark.parametrize("how", ["eager", "jit", "shard_map"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("seq", [7, 512, 2048])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_rotation_and_its_gradient_match_the_formula(head_dim, seq, dtype,
                                                     how):
    kx, kw = jax.random.split(jax.random.PRNGKey(seq + head_dim))
    shape = (BATCH, seq, HEADS, head_dim)
    x = jax.random.normal(kx, shape, jnp.float32).astype(dtype)
    w = jax.random.normal(kw, shape, jnp.float32)

    def run(rope):
        fn = lambda x, w: _both_ways(rope, x, w)    # noqa: E731
        if how == "jit":
            fn = jax.jit(fn)
        elif how == "shard_map":
            mesh = Mesh(np.array(jax.devices()[:BATCH]), ("hvd",))
            fn = jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
                check_vma=True))
        return fn(x, w)

    (y, dx), (y_ref, dx_ref) = run(_ours), run(_theirs)
    _assert_same(y, y_ref, dtype)
    _assert_same(dx, dx_ref, dtype)


def test_q_and_k_are_rotated_alike_and_positions_matter():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 2, 64))
    q, k = transformer._rope(x, 2.0 * x)
    np.testing.assert_allclose(k, 2.0 * q, rtol=1e-6)
    # Position 0 is not rotated; a rotation keeps each pair's length.
    np.testing.assert_array_equal(q[:, 0], x[:, 0])
    np.testing.assert_allclose(jnp.linalg.norm(q, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    assert not np.allclose(q[:, 1:], x[:, 1:], atol=1e-3)


@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 2e-5),
                                             (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_transformer_lm_equals_the_parents_formula(monkeypatch, dtype,
                                                   tolerance):
    cfg = TransformerConfig(vocab_size=128, hidden=128, layers=2, heads=2,
                            max_len=32, dtype=dtype,
                            attention_impl="einsum")
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    params = model.init(jax.random.PRNGKey(2), tokens)

    def logits_and_grads():
        def loss(p):
            logits = model.apply(p, tokens)
            return jnp.mean(jax.nn.logsumexp(logits, -1) ** 2), logits
        grads, logits = jax.grad(loss, has_aux=True)(params)
        return logits, grads

    ours = logits_and_grads()
    # The parent's rope: autodiff through slices of the head dimension.
    monkeypatch.setattr(transformer, "_rope",
                        lambda q, k, theta: (_theirs(q), _theirs(k)))
    theirs = logits_and_grads()
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=0, atol=tolerance * scale)


# What the TPU compiler makes of it. (batch, seq) a chip of the three
# lm365m cells; the layer at their widths.
LAYER_SHAPES = [(2, 8192), (24, 512), (6, 2048)]
HIDDEN, LAYER_HEADS = 1024, 16
# By ``cost_analysis()``, which counts a slice fused into its consumer as
# a read of the whole operand (so q's and k's rotation each as a read of
# all of qkv): rope reads 0.62 / 0.31 / 0.31 GB above the layer without
# it. With slices of the head dimension and autodiff's backward it read
# 3.18 / 2.56 / 2.47 GB above.
ROPE_BYTES_ABOVE = 0.65e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops it, skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # An entry written for a described chip cannot be read back.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_layer_gradient(one_chip, batch, seq, use_rope):
    layer = Attention(TransformerConfig(
        hidden=HIDDEN, heads=LAYER_HEADS, max_len=seq, use_rope=use_rope,
        attention_impl="flash"))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    x = jax.ShapeDtypeStruct((batch, seq, HIDDEN), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)

    def loss(params, x):
        return jnp.sum(layer.apply(params, x).astype(jnp.float32))
    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        on_chip(params), on_chip(x)).compile()


def _elements(shape):
    return int(np.prod([int(n) for n in shape.split(",") if n]))


@pytest.mark.parametrize("batch,seq", LAYER_SHAPES,
                         ids=["seq8192", "seq512", "seq2048"])
def test_attention_layer_compiles_to_one_pass_of_rope_on_v5e(
        one_chip, monkeypatch, batch, seq):
    # The kernel asks the default backend whether to interpret; here
    # that is the CPU, and the compile is for the TPU.
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    with_rope = _compiled_layer_gradient(one_chip, batch, seq, True)
    without = _compiled_layer_gradient(one_chip, batch, seq, False)
    text = with_rope.as_text()
    # Forward and the one backward kernel, and no other custom kernel.
    assert text.count("tpu_custom_call") == 2
    # Under scope rope, forward and backward, nothing is written out in
    # float32 at q's size: the entry computation's instructions are what
    # goes to memory (a fusion's own body stays in registers).
    q_elements = batch * seq * HIDDEN
    roped = [line for line in text[text.index("\nENTRY "):].splitlines()
             if re.search(r'op_name="[^"]*/rope/', line)]
    assert any("transpose(" in line for line in roped)
    assert any("transpose(" not in line for line in roped)
    wide = [line for line in roped
            for shape in re.findall(r"= f32\[([\d,]*)\]", line)
            if _elements(shape) >= q_elements]
    assert wide == []
    above = (with_rope.cost_analysis()["bytes accessed"]
             - without.cost_analysis()["bytes accessed"])
    assert above <= ROPE_BYTES_ABOVE


# The flash kernels alone, at a chip's (batch, heads, seq, head_dim) of
# lm365m-seq8192-1chip, the two lm365m seq-2048 cells and
# glm47flash-seq4096-1chip (kept in this file: the TPU's library goes to
# one test process).
FLASH_SHAPES = [(2, 16, 8192, 64), (6, 16, 2048, 64), (2, 20, 4096, 256)]


@pytest.mark.parametrize("shape", FLASH_SHAPES,
                         ids=["seq8192", "seq2048", "glm47flash"])
def test_flash_gradient_compiles_to_two_kernels_on_v5e(
        one_chip, monkeypatch, shape):
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, block_q=1024,
                                 block_k=1024)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    # The forward, and one backward that makes dq, dk and dv: dq's
    # accumulator over the whole query range fits the chip's VMEM.
    assert text.count("tpu_custom_call") == 2
    for name in (fa.KERNEL_FWD, fa.KERNEL_BWD_DKDV):
        assert len(re.findall(rf'{name}[.\d]* = [^\n]*custom_call_target='
                              r'"tpu_custom_call"', text)) == 1
