"""GLM-4.7-Flash's mechanisms at a small size on the CPU, seeded: the
program's model (latent attention, the dropless expert layer with its
shared expert, the multi-token-prediction module) against the plain
reference of ``benchmark/references/glm4_moe_lite.py`` for loss, MTP
loss, every gradient leaf and three AdamW steps; the shares of an expert
layer adding up to the uncut layer; routing's corner cases. (On the chip
the comparison is the benchmark's ``correct``, at the published widths.)
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.builders import glm4_moe_lite as builder  # noqa: E402
from benchmark.references import common  # noqa: E402
from benchmark.references import glm4_moe_lite as reference  # noqa: E402
from horovod_tpu.models import TransformerLM  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402
from moe_fixtures import telemetry_plane  # noqa: E402, F401 (a fixture)

SEQ = 32


def small_cfg(**overrides):
    """The configuration file's keys at the issue's small size: hidden
    64, 2 heads, 8 experts top-2 of which 2 are held, vocabulary slice
    64, seq 32."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "glm47flash.json")) as f:
        cfg = json.load(f)
    cfg.update(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=48,
        num_attention_heads=2, n_routed_experts_published=8,
        experts_held=[2, 4], num_experts_per_tok=2, num_hidden_layers=3,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=32, vocab_size=64,
        router_bias_scale=0.05, attention_impl="einsum")
    cfg.update(overrides)
    return cfg


def worst(a, b):
    return max(float(jnp.max(jnp.abs(x - y)) / (jnp.max(jnp.abs(y)) + 1e-30))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.fixture(scope="module")
def seeded():
    cfg = small_cfg()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 1), 0,
                                cfg["vocab_size"])
    model = TransformerLM(dataclasses.replace(
        builder.model_config(cfg, {"seq_len": SEQ}), dtype=jnp.float32))
    return (cfg, model, reference.init_params(cfg, jax.random.PRNGKey(3)),
            reference.init_aux(cfg), (tokens[:, :-1], tokens[:, 1:]))


def program_losses(model, params, aux, batch, weight):
    (main, mtp), new_aux = model.apply(
        {**params, **aux}, batch[0], next_tokens=batch[1],
        mutable=list(aux))
    xent = optax.softmax_cross_entropy_with_integer_labels
    main = xent(main, batch[1]).mean()
    mtp = xent(mtp[:, :-1], batch[1][:, 1:]).mean()
    return main + weight * mtp, (main, mtp, new_aux)


def test_reference_reads_the_models_tree(seeded):
    cfg, model, params, aux, batch = seeded
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), batch[0],
                            next_tokens=batch[1])
    for ours, theirs in ((params["params"], shapes["params"]),
                         (aux["moe_state"], shapes["moe_state"])):
        assert jax.tree.map(lambda x: x.shape, ours) == jax.tree.map(
            lambda x: x.shape, theirs)


def test_loss_mtp_loss_and_every_gradient_leaf_match_reference(seeded):
    cfg, model, params, aux, batch = seeded
    with jax.default_matmul_precision("highest"):
        (want, (main, mtp, _)), want_grads = jax.jit(jax.value_and_grad(
            lambda p: program_losses(model, p, aux, batch,
                                     cfg["mtp_loss_weight"]),
            has_aux=True))(params)
    got, got_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_fn(p, aux, batch, cfg)[0]))(params)
    ref_main, (ref_mtp,) = reference.loss_terms(params, aux, batch, cfg)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(ref_main) == pytest.approx(float(main), rel=1e-5)
    assert float(ref_mtp) == pytest.approx(float(mtp), rel=1e-5)
    assert float(mtp) != pytest.approx(float(main), rel=1e-3)
    names = common.leaf_names(params)
    for name, a, b in zip(names, jax.tree.leaves(got_grads),
                          jax.tree.leaves(want_grads)):
        assert float(jnp.max(jnp.abs(b))) > 0, name   # every leaf is reached
        assert worst(a, b) < 2e-4, name


def test_three_adamw_steps_match_reference(seeded):
    cfg, model, params, aux, batch = seeded
    opt = dict(cfg["optimizer"], learning_rate=1e-3)
    tx = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                     eps=opt["eps"], weight_decay=opt["weight_decay"])
    ours, state = params, tx.init(params)
    theirs, their_state = params, common.adamw_init(params)
    grad = jax.jit(jax.grad(lambda p: program_losses(
        model, p, aux, batch, cfg["mtp_loss_weight"])[0]))
    ref_grad = jax.jit(jax.grad(
        lambda p: reference.loss_fn(p, aux, batch, cfg)[0]))
    for _ in range(3):
        with jax.default_matmul_precision("highest"):
            updates, state = tx.update(grad(ours), state, ours)
        ours = optax.apply_updates(ours, updates)
        theirs, their_state = common.adamw_update(
            theirs, their_state, ref_grad(theirs), opt)
    # As the benchmark compares them: every leaf's change, by its norm.
    for name, a, b, start in zip(common.leaf_names(params),
                                 *map(jax.tree.leaves,
                                      (ours, theirs, params))):
        a, b = jnp.linalg.norm(a - start), jnp.linalg.norm(b - start)
        assert float(b) > 0 and float(abs(a - b) / b) < 1e-3, name


def test_flash_path_matches_einsum_path(seeded):
    cfg, model, params, aux, batch = seeded
    flash = TransformerLM(dataclasses.replace(
        model.cfg, attention_impl="flash"))
    a = program_losses(model, params, aux, batch, 0.3)[0]
    b = program_losses(flash, params, aux, batch, 0.3)[0]
    assert float(a) == pytest.approx(float(b), rel=1e-5)


def test_step_counts_the_tokens_each_expert_drew(seeded):
    cfg, model, params, aux, batch = seeded
    new_aux = program_losses(model, params, aux, batch, 0.3)[1][2]
    layers = new_aux["moe_state"]["backbone"]
    drawn = [layers["block_1"]["moe"], layers["block_2"]["moe"],
             layers["mtp_0"]["block"]["moe"]]
    for layer in drawn:
        assert float(layer["expert_tokens"].sum()) == (
            2 * SEQ * cfg["num_experts_per_tok"])
        # The selection bias passes through unchanged.
    np.testing.assert_array_equal(
        layers["block_1"]["moe"]["bias"],
        aux["moe_state"]["backbone"]["block_1"]["moe"]["bias"])


# ---- the expert layer alone ------------------------------------------------

def layer_params(key, d=16, f=24, experts=8, held=(0, 8), shared=True):
    keys = jax.random.split(key, 7)
    n = held[1] - held[0]

    def normal(k, shape):
        return jax.random.normal(k, shape) / np.sqrt(shape[-2])

    full = {"router": normal(keys[0], (d, experts)),
            "w_gate": normal(keys[1], (experts, d, f)),
            "w_up": normal(keys[2], (experts, d, f)),
            "w_down": normal(keys[3], (experts, f, d))}
    out = {"router": full["router"],
           **{k: full[k][held[0]:held[1]]
              for k in ("w_gate", "w_up", "w_down")}}
    if shared:
        out.update(shared_gate=normal(keys[4], (d, f)),
                   shared_up=normal(keys[5], (d, f)),
                   shared_down=normal(keys[6], (f, d)))
    assert out["w_gate"].shape[0] == n
    return out


def test_the_shares_add_up_to_the_uncut_reference_layer():
    """The 4 shares of an 8-expert layer (2 experts each), the shared
    expert counted once, sum to what the uncut reference gives for the
    whole layer: value and the gradient with respect to the tokens."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(jax.random.PRNGKey(8), (40, 16))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(9), (8,))
    whole = layer_params(key)
    cfg = {"num_experts_per_tok": 2, "experts_held": [0, 8],
           "routed_scaling_factor": 1.8}

    def uncut(x):
        return reference.expert_ffn(x[None], whole, bias, cfg)[0]

    def shares(x):
        total = moe.swiglu(x, whole["shared_gate"], whole["shared_up"],
                           whole["shared_down"])
        for first in (0, 2, 4, 6):
            share = layer_params(key, held=(first, first + 2), shared=False)
            total = total + moe.moe_apply(x, share, bias, k=2, scale=1.8,
                                          first_held=first)[0]
        return total

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(shares(x), uncut(x), atol=2e-5,
                                   rtol=2e-5)
        weights = jnp.cos(jnp.arange(40 * 16.0)).reshape(40, 16)
        np.testing.assert_allclose(
            jax.grad(lambda x: jnp.sum(shares(x) * weights))(x),
            jax.grad(lambda x: jnp.sum(uncut(x) * weights))(x),
            atol=2e-5, rtol=2e-4)


def test_no_token_is_dropped_when_every_token_picks_the_same_expert():
    params = layer_params(jax.random.PRNGKey(1), held=(0, 2), shared=False)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 16))
    # A bias that makes expert 1 every token's first choice: all 64
    # tokens go to one held expert, none is lost and each row is that
    # expert's output times the token's weight for it.
    bias = jnp.zeros((8,)).at[1].set(10.0)
    y, drawn = moe.moe_apply(x, params, bias, k=1, scale=1.0)
    assert float(drawn[1]) == 64 and float(drawn.sum()) == 64
    want = moe.swiglu(x, params["w_gate"][1], params["w_up"][1],
                      params["w_down"][1])      # k = 1: weight s / s = 1
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=1e-5)
    assert not np.any(np.all(np.asarray(y) == 0.0, axis=-1))


def test_selection_bias_changes_the_choice_and_not_the_weights():
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 16))
    router = jax.random.normal(jax.random.PRNGKey(4), (16, 8)) / 4.0
    plain, w_plain, _ = moe.route(x, router, jnp.zeros((8,)), k=2, scale=1.8)
    tilted = jnp.zeros((8,)).at[5].set(10.0)
    chosen, weights, drawn = moe.route(x, router, tilted, k=2, scale=1.8)
    assert float(drawn[5]) == 32 and not np.array_equal(plain, chosen)
    # The weights are the plain scores of the chosen, normalised over
    # the chosen and scaled: the bias is nowhere in them.
    scores = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        weights, 1.8 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.8, rtol=1e-5)
    # And it takes no gradient.
    g = jax.grad(lambda b: moe.route(x, router, b, k=2,
                                     scale=1.8)[1].sum())(tilted)
    assert float(jnp.abs(g).max()) == 0.0


def test_expert_tokens_reach_the_telemetry_plane(telemetry_plane):
    telemetry = telemetry_plane
    state = {"moe_state": {"block_1": {"moe": {
        "bias": jnp.zeros((4,)),
        "expert_tokens": jnp.asarray([6.0, 2.0, 0.0, 8.0])}}}}
    moe.publish_expert_tokens(state, held=(0, 2))
    families = telemetry.snapshot()["families"]
    samples = {s["labels"]["expert"]: s["value"]
               for s in families["hvd_moe_expert_tokens"]["samples"]}
    assert samples == {"0": 6.0, "1": 2.0, "2": 0.0, "3": 8.0}
    assert families["hvd_moe_held_share"]["samples"][0]["value"] == 0.5


def test_buffer_rows_and_sized_layers_reach_the_telemetry_plane(
        telemetry_plane):
    """1024 pairs over 8 experts of which 2 are held: 512 rows. The
    first layer's held draw (400) fits in them, the second's (700) does
    not and ran on a row for every pair."""
    telemetry = telemetry_plane
    fits = jnp.asarray([150.0, 250, 104, 104, 104, 104, 104, 104])
    over = jnp.asarray([300.0, 400, 54, 54, 54, 54, 54, 54])
    state = {"moe_state": {
        "block_1": {"moe": {"bias": jnp.zeros((8,)), "expert_tokens": fits}},
        "block_2": {"moe": {"bias": jnp.zeros((8,)),
                            "expert_tokens": over}}}}
    moe.publish_expert_tokens(state, held=(0, 2), width=32)
    families = telemetry.snapshot()["families"]
    assert {s["labels"]["layer"]: s["value"]
            for s in families["hvd_moe_buffer_rows"]["samples"]} == {
        "moe_state/block_1/moe": 512.0, "moe_state/block_2/moe": 512.0}
    assert families["hvd_moe_sized_layers"]["samples"][0]["value"] == 1.0
    # Both layers keep 512 rows of 2 x 32 two-byte numbers and an index
    # for the way back, the second unread.
    assert moe.kept_bytes(512, 32) == 512 * (64 * 2 + 4)
    assert {s["labels"]["layer"]: s["value"]
            for s in families["hvd_moe_kept_bytes"]["samples"]} == {
        "moe_state/block_1/moe": 67584.0, "moe_state/block_2/moe": 67584.0}
    # Every expert held: one path, buffers of a row for every pair,
    # nothing kept.
    moe.publish_expert_tokens(state, width=32)
    families = telemetry.snapshot()["families"]
    assert {s["value"] for s in
            families["hvd_moe_buffer_rows"]["samples"]} == {1024.0}
    assert families["hvd_moe_sized_layers"]["samples"][0]["value"] == 0.0
    assert {s["value"] for s in
            families["hvd_moe_kept_bytes"]["samples"]} == {0.0}


def test_publishing_is_a_no_op_with_metrics_off(monkeypatch):
    from horovod_tpu.telemetry import core as telemetry
    monkeypatch.setattr(telemetry, "_ENABLED", False)
    before = dict(telemetry.registry().families())
    moe.publish_expert_tokens({"x": {"expert_tokens": jnp.ones((4,))}})
    assert dict(telemetry.registry().families()) == before
