"""A looped language model's mechanisms at a small size on the CPU,
seeded: the program's model (a stack of sandwich-normed blocks run
several times with the same weights, the final norm after every pass, an
exit gate and one head on every pass's state, ``looped_lm_loss``)
against the plain reference of ``benchmark/references/looped_lm.py`` for
loss, exit shares, every gradient leaf and three AdamW steps; the tie to
the unrolled model; the recomputation policies; what a model that runs
its stack once still is. (On the chip the comparison is the benchmark's
``correct``, at the published widths.)
"""

import dataclasses
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.builders import glm4_moe_lite as glm_builder  # noqa: E402
from benchmark.builders import looped_lm as builder  # noqa: E402
from benchmark.references import common  # noqa: E402
from benchmark.references import glm4_moe_lite as glm_reference  # noqa: E402
from benchmark.references import looped_lm as reference  # noqa: E402
from benchmark.references import transformer_lm as lm_reference  # noqa: E402
from horovod_tpu.models import (  # noqa: E402
    TransformerConfig, TransformerLM, looped_lm_loss, publish_exit_shares)
from horovod_tpu.models import transformer  # noqa: E402

SEQ = 32
PASSES, LAYERS = 3, 2


def read_cfg(name):
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


def small_cfg(**overrides):
    """The configuration file's keys at a small size: 2 heads of the
    published 128, 2 layers run 3 times, vocabulary 64, seq 32."""
    cfg = read_cfg("ouro26b")
    cfg.update(hidden_size=256, num_attention_heads=2,
               num_key_value_heads=2, intermediate_size=96,
               num_hidden_layers=LAYERS, total_ut_steps=PASSES,
               vocab_size=64, attention_impl="einsum", remat=False)
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
    params = reference.init_params(cfg, jax.random.PRNGKey(3))
    # The gate starts at 0, where every pass has the same logit; moved
    # off it, the passes' shares and the gate's gradient tell them apart.
    gate = params["params"]["exit_gate"]
    gate["kernel"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4),
                                              gate["kernel"].shape)
    gate["bias"] = jnp.asarray([0.3])
    return cfg, model, params, (tokens[:, :-1], tokens[:, 1:])


def program_loss(model, params, batch, beta):
    xent, gate_logits = model.apply(params, batch[0], targets=batch[1])
    return looped_lm_loss(xent, gate_logits, beta)


def test_reference_reads_the_models_tree(seeded):
    cfg, model, params, batch = seeded
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), batch[0])
    assert (jax.tree.map(lambda x: x.shape, shapes)
            == jax.tree.map(lambda x: x.shape, params))
    block = params["params"]["backbone"]["block_0"]
    assert set(block) == {"ln1", "ln1_out", "ln2", "ln2_out", "attn",
                          "mlp_gate", "mlp_in", "mlp_out"}
    assert params["params"]["exit_gate"]["kernel"].shape == (256, 1)


def test_loss_exit_shares_and_every_gradient_leaf_match_reference(seeded):
    cfg, model, params, batch = seeded
    beta = cfg["exit_entropy_beta"]
    with jax.default_matmul_precision("highest"):
        (want, shares), want_grads = jax.jit(jax.value_and_grad(
            lambda p: program_loss(model, p, batch, beta),
            has_aux=True))(params)
    (got, aux), got_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_fn(p, reference.init_aux(cfg), batch, cfg),
        has_aux=True))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    ref_shares = aux["loop_state"]["exit_share"]
    assert shares.shape == ref_shares.shape == (PASSES,)
    assert float(jnp.sum(shares)) == pytest.approx(1.0, abs=1e-6)
    assert jnp.allclose(shares, ref_shares, atol=1e-6)
    assert len({round(float(s), 3) for s in shares}) == PASSES
    for name, a, b in zip(common.leaf_names(params),
                          jax.tree.leaves(got_grads),
                          jax.tree.leaves(want_grads)):
        assert float(jnp.max(jnp.abs(b))) > 0, name   # every leaf is reached
        assert worst(a, b) < 2e-4, name


def test_three_adamw_steps_match_reference(seeded):
    cfg, model, params, batch = seeded
    beta = cfg["exit_entropy_beta"]
    opt = dict(cfg["optimizer"], learning_rate=1e-3)
    tx = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                     eps=opt["eps"], weight_decay=opt["weight_decay"])
    ours, state = params, tx.init(params)
    theirs, their_state = params, common.adamw_init(params)
    grad = jax.jit(jax.grad(
        lambda p: program_loss(model, p, batch, beta)[0]))
    ref_grad = jax.jit(jax.grad(lambda p: reference.loss_fn(
        p, reference.init_aux(cfg), batch, cfg)[0]))
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


def test_flash_path_matches_einsum_path_at_head_dim_128(seeded):
    cfg, model, params, batch = seeded
    assert model.cfg.hidden // model.cfg.heads == 128
    flash = TransformerLM(dataclasses.replace(
        model.cfg, attention_impl="flash"))
    (a, share_a), ga = jax.value_and_grad(
        lambda p: program_loss(model, p, batch, 0.05), has_aux=True)(params)
    (b, share_b), gb = jax.value_and_grad(
        lambda p: program_loss(flash, p, batch, 0.05), has_aux=True)(params)
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    assert jnp.allclose(share_a, share_b, atol=1e-6)
    assert worst(ga, gb) < 2e-4


class Unrolled(nn.Module):
    """``PASSES x LAYERS`` untied blocks with a final norm of its own
    after every group of ``LAYERS``, from the program's own ``Block``:
    what the looped model is when every use of a weight gets a copy."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, targets):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=cfg.dtype,
                     name="tok_embed")(tokens)
        head = nn.Dense(cfg.vocab_size, dtype=cfg.dtype, use_bias=False,
                        name="lm_head")
        gate = nn.Dense(1, dtype=jnp.float32, name="exit_gate")
        xent, gates = [], []
        for t in range(PASSES):
            for i in range(LAYERS):
                x = transformer.Block(cfg, name=f"block_{t}_{i}")(x)
            x = transformer._norm(cfg, f"ln_f_{t}")(x)
            xent.append(optax.softmax_cross_entropy_with_integer_labels(
                head(x).astype(jnp.float32), targets))
            gates.append(gate(x)[..., 0])
        return jnp.stack(xent), jnp.stack(gates)


def test_tie_to_the_unrolled_model(seeded):
    """``T`` passes over ``L`` blocks give the loss of ``T x L`` untied
    blocks that hold copies of the weights, and each shared leaf's
    gradient is the sum of its copies' gradients."""
    cfg, model, params, batch = seeded
    p = params["params"]
    bb = p["backbone"]
    copies = {"tok_embed": bb["tok_embed"], "lm_head": p["lm_head"],
              "exit_gate": p["exit_gate"]}
    for t in range(PASSES):
        copies[f"ln_f_{t}"] = bb["ln_f"]
        for i in range(LAYERS):
            copies[f"block_{t}_{i}"] = bb[f"block_{i}"]
    unrolled = Unrolled(dataclasses.replace(model.cfg, passes=1))

    def unrolled_loss(copies):
        return looped_lm_loss(*unrolled.apply({"params": copies}, *batch),
                              0.05)[0]

    want, copy_grads = jax.value_and_grad(unrolled_loss)(copies)
    got, grads = jax.value_and_grad(
        lambda p: program_loss(model, p, batch, 0.05)[0])(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)

    def summed(name):
        return jax.tree.map(lambda *xs: sum(xs), *[
            copy_grads[name.format(t=t)] for t in range(PASSES)])

    shared = grads["params"]["backbone"]
    for i in range(LAYERS):
        assert worst(shared[f"block_{i}"],
                     summed("block_{t}_%d" % i)) < 1e-5, i
    assert worst(shared["ln_f"], summed("ln_f_{t}")) < 1e-5
    # A copy's gradient alone is not the shared leaf's.
    assert worst(shared["block_0"], copy_grads["block_0_0"]) > 0.1
    for name in ("lm_head", "exit_gate"):
        assert worst(grads["params"][name], copy_grads[name]) < 1e-5, name
    assert worst(shared["tok_embed"], copy_grads["tok_embed"]) < 1e-5


@pytest.mark.parametrize("impl", ["einsum", "flash"])
@pytest.mark.parametrize("policy", [True, "dots", "flash"])
def test_every_recomputation_policy_gives_the_loss_and_gradients_of_none(
        seeded, policy, impl):
    cfg, model, params, batch = seeded
    plain = TransformerLM(dataclasses.replace(model.cfg,
                                              attention_impl=impl))
    remat = TransformerLM(dataclasses.replace(plain.cfg, remat=policy))

    def both(m):
        return jax.jit(jax.value_and_grad(
            lambda p: program_loss(m, p, batch, 0.05)[0]))(params)

    (want, want_grads), (got, got_grads) = both(plain), both(remat)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert worst(got_grads, want_grads) < 1e-5


def test_the_flash_policy_does_not_run_the_forward_kernel_again(seeded):
    """Whole-block recomputation makes the kernel's output again;
    policy ``"flash"`` keeps it (and the log-sum-exp), so a step holds
    one forward kernel a block where ``True`` holds two."""
    cfg, model, params, batch = seeded

    def forward_kernels(policy):
        m = TransformerLM(dataclasses.replace(
            model.cfg, attention_impl="flash", remat=policy))
        # The forward kernel goes through one jitted function, which
        # the lowered text calls once a use.
        return jax.jit(jax.grad(
            lambda p: program_loss(m, p, batch, 0.05)[0])).lower(
                params).as_text().count("call @_fwd_jit")

    assert forward_kernels(False) == LAYERS       # the scan body, once
    assert forward_kernels("flash") == LAYERS
    assert forward_kernels(True) == 2 * LAYERS


def test_without_targets_every_pass_hands_out_its_logits(seeded):
    cfg, model, params, batch = seeded
    logits, gate_logits = model.apply(params, batch[0])
    assert logits.shape == (PASSES, 2, SEQ, cfg["vocab_size"])
    assert logits.dtype == gate_logits.dtype == jnp.float32
    xent, _ = model.apply(params, batch[0], targets=batch[1])
    want = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.broadcast_to(batch[1], logits.shape[:-1]))
    assert jnp.allclose(xent, want, atol=1e-5)
    ungated = TransformerLM(dataclasses.replace(model.cfg, exit_gate=False))
    only = {"params": {k: v for k, v in params["params"].items()
                       if k != "exit_gate"}}
    again, none = ungated.apply(only, batch[0])
    assert none is None and jnp.array_equal(again, logits)


def test_the_exit_distribution_is_finite_where_a_gate_saturates():
    gates = jnp.asarray([[80.0, -80.0], [-80.0, 80.0], [0.0, 0.0]])
    xent = jnp.ones((3, 2))
    loss, share = looped_lm_loss(xent, gates, 0.05)
    assert jnp.isfinite(loss) and float(loss) == pytest.approx(1.0)
    assert jnp.allclose(share, jnp.asarray([0.5, 0.5, 0.0]), atol=1e-6)
    grad = jax.grad(lambda g: looped_lm_loss(xent, g, 0.05)[0])(gates)
    assert bool(jnp.all(jnp.isfinite(grad)))


def test_a_stack_that_runs_several_times_takes_no_expert_layer():
    cfg = glm_builder.model_config(
        dict(read_cfg("glm47flash"), hidden_size=64, num_attention_heads=2,
             attention_impl="einsum"), {"seq_len": SEQ})
    model = TransformerLM(dataclasses.replace(cfg, passes=2))
    with pytest.raises(ValueError, match="passes > 1"):
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, SEQ), jnp.int32))


# ---- a stack that runs once is what it was --------------------------------

def tiny_lm365m():
    cfg = dict(read_cfg("lm365m"), hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, intermediate_size=256, vocab_size=64)
    model = TransformerLM(TransformerConfig(
        vocab_size=64, hidden=64, layers=2, heads=4, max_len=SEQ,
        dtype=jnp.float32))
    return cfg, model, lm_reference, {}


def tiny_glm47flash():
    cfg = dict(
        read_cfg("glm47flash"), hidden_size=64, intermediate_size=128,
        moe_intermediate_size=48, num_attention_heads=2,
        n_routed_experts_published=8, experts_held=[2, 4],
        num_experts_per_tok=2, num_hidden_layers=3, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
        v_head_dim=32, vocab_size=64, attention_impl="einsum")
    model = TransformerLM(dataclasses.replace(
        glm_builder.model_config(cfg, {"seq_len": SEQ}), dtype=jnp.float32))
    return cfg, model, glm_reference, glm_reference.init_aux(cfg)


@pytest.mark.parametrize("tiny", [tiny_lm365m, tiny_glm47flash])
def test_one_pass_without_sandwich_norms_and_gate_is_the_model_it_was(tiny):
    """The configurations the benchmark had keep their parameter trees
    (no leaf of the looped model's: the trees are their references') and
    their logits: a pair with a gate is handed out only where the stack
    runs several times."""
    cfg, model, ref, aux = tiny()
    assert (model.cfg.passes, model.cfg.sandwich_norm,
            model.cfg.exit_gate) == (1, False, False)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, SEQ + 1), 0, 64)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens[:, :-1],
                           next_tokens=tokens[:, 1:]))
    params = ref.init_params(cfg, jax.random.PRNGKey(1))
    assert (jax.tree.map(lambda x: x.shape, shapes["params"])
            == jax.tree.map(lambda x: x.shape, params["params"]))
    names = "".join(common.leaf_names(params))
    assert "_out" not in names.replace("mlp_out", "") and (
        "exit_gate" not in names)
    with jax.default_matmul_precision("highest"):
        if aux:
            got = model.apply({**params, **aux}, tokens[:, :-1],
                              next_tokens=tokens[:, 1:])[0]
            want = ref.logits_fn(params, aux, tokens[:, :-1], tokens[:, 1:],
                                 cfg)[0]
        else:
            got = model.apply(params, tokens[:, :-1])
            want = ref.logits_fn(params, tokens[:, :-1], cfg)
    assert got.shape == (2, SEQ, 64)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4


# ---- counters --------------------------------------------------------------

def test_exit_shares_reach_the_telemetry_plane(monkeypatch):
    from horovod_tpu.telemetry import core as telemetry
    monkeypatch.setattr(telemetry, "_ENABLED", True)
    publish_exit_shares(jnp.asarray([0.5, 0.25, 0.125, 0.125]))
    families = telemetry.snapshot()["families"]
    samples = {s["labels"]["step"]: s["value"]
               for s in families["hvd_loop_exit_share"]["samples"]}
    assert samples == {"1": 0.5, "2": 0.25, "3": 0.125, "4": 0.125}
    assert families["hvd_loop_passes"]["samples"][0]["value"] == 4.0


def test_publishing_is_a_no_op_with_metrics_off(monkeypatch):
    from horovod_tpu.telemetry import core as telemetry
    monkeypatch.setattr(telemetry, "_ENABLED", False)
    before = dict(telemetry.registry().families())
    publish_exit_shares(jnp.ones((4,)) / 4)
    assert dict(telemetry.registry().families()) == before
