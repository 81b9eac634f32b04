"""Each plain reference against the program's model at a tiny size, both
in float32 on the CPU: the same weights give the same logits, loss and
gradients. (On the chip the comparison is the benchmark's ``correct``,
at full width; see benchmark/check.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.references import common, resnet, transformer_lm

LM = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
          intermediate_size=256, vocab_size=512, layer_norm_eps=1e-6)
RESNET = dict(stage_sizes=[1, 2], num_filters=8, num_classes=10,
              image_size=32, channels=3, batch_norm_momentum=0.9,
              batch_norm_eps=1e-5, residual_scale_init=0.1)


def worst(a, b):
    return max(float(jnp.max(jnp.abs(x - y)) / (jnp.max(jnp.abs(y)) + 1e-30))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("query_block", [1024, 64])   # one block, three
def test_transformer_lm_reference_matches_model(query_block, monkeypatch):
    monkeypatch.setattr(transformer_lm, "QUERY_BLOCK", query_block)
    seq = 192
    from horovod_tpu.models import TransformerConfig, TransformerLM
    model = TransformerLM(TransformerConfig(
        vocab_size=512, hidden=64, layers=2, heads=4, mlp_ratio=4,
        max_len=seq, dtype=jnp.float32, attention_impl="einsum"))
    params = transformer_lm.init_params(LM, jax.random.PRNGKey(1))
    # Non-zero biases, so that a bias left out would show.
    params = jax.tree.map(
        lambda x: x + 0.01 * jnp.cos(jnp.arange(x.size).reshape(x.shape)),
        params)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, seq), jnp.int32))
    assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(
        lambda x: x.shape, shapes)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, seq + 1), 0, 512)
    batch = (tokens[:, :-1], tokens[:, 1:])

    def model_loss(p):
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(p, batch[0]), batch[1]).mean()

    def ref_loss(p):
        return transformer_lm.loss_fn(p, {}, batch, LM)[0]

    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(model_loss))(params)
        logits = jax.jit(model.apply)(params, batch[0])
    got, got_grads = jax.jit(jax.value_and_grad(ref_loss))(params)
    ref_logits = jax.jit(
        lambda p, t: transformer_lm.logits_fn(p, t, LM))(params, batch[0])
    assert float(jnp.max(jnp.abs(ref_logits - logits))) < 2e-4
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert worst(got_grads, want_grads) < 2e-4


def test_resnet_reference_matches_model():
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet
    model = ResNet(stage_sizes=[1, 2], block_cls=BottleneckBlock,
                   num_classes=10, num_filters=8, dtype=jnp.float32)
    params = resnet.init_params(RESNET, jax.random.PRNGKey(3))
    aux = resnet.init_aux(RESNET)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 32, 32, 3))))
    assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(
        lambda x: x.shape, shapes["params"])
    assert jax.tree.map(lambda x: x.shape, aux["batch_stats"]) == (
        jax.tree.map(lambda x: x.shape, shapes["batch_stats"]))
    images = jax.random.uniform(jax.random.PRNGKey(4), (8, 32, 32, 3))
    labels = jax.random.randint(jax.random.PRNGKey(5), (8,), 0, 10)

    def model_loss(p):
        logits, new = model.apply({"params": p, **aux}, images,
                                  mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean(), new

    def ref_loss(p):
        return resnet.loss_fn(p, aux, (images, labels), RESNET)

    with jax.default_matmul_precision("highest"):
        (want, want_aux), want_grads = jax.jit(jax.value_and_grad(
            model_loss, has_aux=True))(params)
    (got, got_aux), got_grads = jax.jit(jax.value_and_grad(
        ref_loss, has_aux=True))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert worst(got_grads, want_grads) < 1e-3
    assert worst(got_aux, want_aux) < 1e-4


def test_plain_adamw_matches_optax():
    opt = {"name": "adamw", "learning_rate": 1e-2, "b1": 0.9, "b2": 0.999,
           "eps": 1e-8, "weight_decay": 1e-4}
    params = {"w": jnp.linspace(-1.0, 1.0, 12).reshape(3, 4),
              "b": jnp.ones((4,))}
    tx = optax.adamw(1e-2)
    theirs, state = params, tx.init(params)
    ours, ours_state = params, common.adamw_init(params)
    for k in range(4):
        grads = jax.tree.map(lambda p: jnp.sin(p * (k + 1)) + 0.1, ours)
        updates, state = tx.update(grads, state, theirs)
        theirs = optax.apply_updates(theirs, updates)
        ours, ours_state = common.adamw_update(ours, ours_state, grads, opt)
    assert worst(ours, theirs) < 1e-6
    # What the harness reads the first gradient from.
    np.testing.assert_allclose(ours_state["mu"]["b"], state[0].mu["b"],
                               rtol=1e-5)


@pytest.mark.parametrize("precision,worse_than", [
    ("bfloat16", 1e-4), ("int8", 1e-3), ("float8_e4m3fn", 1e-2),
    ("int8_all", 1e-3), ("float8_e4m3fn_all", 1e-2)])
def test_lower_precisions_move_the_product(precision, worse_than):
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 256))
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 32))
    exact = common.einsum("ij,jk->ik", a, b, "float32")
    rough = common.einsum("ij,jk->ik", a, b, precision)
    err = float(jnp.linalg.norm(rough - exact) / jnp.linalg.norm(exact))
    assert worse_than < err < 0.2
    # The gradient reaches the operands, through the quantised ones.
    def loss(a, precision):
        return jnp.sum(jnp.sin(common.einsum("ij,jk->ik", a, b, precision)))
    exact_g = jax.grad(loss)(a, "float32")
    rough_g = jax.grad(loss)(a, precision)
    err = float(jnp.linalg.norm(rough_g - exact_g)
                / jnp.linalg.norm(exact_g))
    assert worse_than < err < 1.0
