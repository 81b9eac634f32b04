"""NVIDIA-Nemotron-3-Nano-30B-A3B's mechanisms at a small size on the CPU,
seeded: the Mamba-2 recurrence by chunked products (``ops/ssd.py``)
against the recurrence walked position by position, forward and in
every operand's gradient, at chunk lengths that do and do not divide the
row; ``Mamba2Mixer``; the expert layer whose experts have no gate matrix
(sized path, fallback, every expert held) against a dense sum; blocks
that are a mixer or an FFN alone; and the program's model (the published
pattern's first nine layers, ``MEMEM*EME``) against the plain reference
of ``benchmark/references/nemotron_h.py`` for loss, every gradient leaf
and three AdamW steps; the sixteen shares of an expert layer adding up
to the uncut layer. (On the chip the comparison is the benchmark's
``correct``, at the published widths.)
"""

import dataclasses
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.builders import nemotron_h as builder  # noqa: E402
from benchmark.references import common  # noqa: E402
from benchmark.references import nemotron_h as reference  # noqa: E402
from horovod_tpu.models import TransformerLM  # noqa: E402
from horovod_tpu.models import ssm, transformer  # noqa: E402
from horovod_tpu.ops import ssd  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402
from moe_fixtures import clear_traces, telemetry_plane  # noqa: E402, F401

SEQ = 48
HIDDEN = 64
EXPERTS = 16


def small_cfg(**overrides):
    """The configuration file's keys at a small size: hidden 64, 8 Mamba
    heads of 8 over 2 groups with a state of 16, 8 query heads of 8 over
    2 K/V heads, 16 experts 24 wide top-6 of which 4 are held and a
    shared one 48 wide, vocabulary 64."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron3nano30b.json")) as f:
        cfg = json.load(f)
    cfg.update(
        hidden_size=HIDDEN, num_attention_heads=8, num_key_value_heads=2,
        head_dim=8, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
        ssm_state_size=16, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=48,
        n_routed_experts_published=EXPERTS, experts_held=[4, 8],
        vocab_size=64, embedding_fan_in=1, attention_impl="einsum",
        remat=False)
    cfg.update(overrides)
    return cfg


def make_model(cfg, **replace):
    return TransformerLM(dataclasses.replace(
        builder.model_config(cfg, {"seq_len": SEQ}), dtype=jnp.float32,
        **replace))


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


@pytest.fixture(scope="module")
def seeded():
    cfg = small_cfg()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 1), 0,
                                cfg["vocab_size"])
    return (cfg, make_model(cfg),
            reference.init_params(cfg, jax.random.PRNGKey(3)),
            reference.init_aux(cfg), (tokens[:, :-1], tokens[:, 1:]))


def program_loss(model, params, aux, batch):
    logits, new_aux = model.apply({**params, **aux}, batch[0],
                                  mutable=list(aux))
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, batch[1]).mean(), new_aux


# ---- the recurrence by chunks ---------------------------------------------

def operands(seq, seed=0, batch=2, heads=4, width=8, groups=2, n=16,
             dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(keys[0], (batch, seq, heads, width), dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, heads)))
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    b = jax.random.normal(keys[3], (batch, seq, groups, n), dtype)
    c = jax.random.normal(keys[4], (batch, seq, groups, n), dtype)
    weigh = jax.random.normal(keys[5], (batch, seq, heads, width))
    return (u, dt, a, b, c), weigh


# (row, chunk): chunks that divide the row, one that does not, a row
# shorter than a chunk, a row of one chunk, the default.
CASES = [(40, 8), (40, 16), (40, 24), (5, 8), (16, 16), (40, None),
         (130, 64)]


@pytest.mark.parametrize("seq,chunk", CASES)
def test_chunked_form_is_the_recurrence(seq, chunk):
    args, _ = operands(seq)
    got = ssd.ssd(*args, chunk=chunk)
    want = ssd.reference_ssd(*args)
    assert got.shape == want.shape == args[0].shape
    assert worst(got, want) < 5e-6


@pytest.mark.parametrize("seq,chunk", CASES)
def test_chunked_forms_gradients_are_the_recurrences(seq, chunk):
    """Every operand's: ``u``, ``dt``, ``A``, ``B``, ``C``; the carry's
    adjoint across the chunk boundaries is written by hand."""
    args, weigh = operands(seq, seed=1)
    got = jax.grad(lambda *xs: jnp.sum(ssd.ssd(*xs, chunk=chunk) * weigh),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *xs: jnp.sum(ssd.reference_ssd(*xs) * weigh),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("u dt A B C".split(), got, want):
        assert a.shape == b.shape and worst(a, b) < 1e-4, name


def test_the_state_decays_and_a_long_row_stays_finite():
    """Every exponent is <= 0: large steps over many chunks neither
    overflow the decays nor the gradients, and the first chunk's
    positions are forgotten by the last."""
    (u, dt, a, b, c), weigh = operands(256, seed=2)
    dt = 20.0 * dt
    value, grads = jax.value_and_grad(
        lambda *xs: jnp.sum(ssd.ssd(*xs, chunk=16) * weigh),
        argnums=(0, 1, 2, 3, 4))(u, dt, a, b, c)
    assert np.isfinite(float(value))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)
    moved = ssd.ssd(u.at[:, :16].add(1.0), dt, a, b, c, chunk=16)
    assert worst(moved[:, -16:],
                 ssd.ssd(u, dt, a, b, c, chunk=16)[:, -16:]) < 1e-6


def test_bfloat16_operands_give_the_float32_result_to_rounding():
    args, _ = operands(64, seed=3, dtype=jnp.bfloat16)
    got = ssd.ssd(*args, chunk=16)
    assert got.dtype == jnp.bfloat16
    want = ssd.reference_ssd(*args)
    assert float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want)) < 0.02


def test_groups_must_divide_the_heads():
    (u, dt, a, b, c), _ = operands(8, heads=4, groups=3)
    with pytest.raises(ValueError, match="4 heads over 3 groups"):
        ssd.ssd(u, dt, a, b, c)


def test_what_the_backward_pass_keeps_is_the_chunk_states_by_name():
    """Under ``remat="flash"``'s policy the forward pass is not made
    again: its output and chunk states are kept by name, and the
    backward pass's jaxpr holds one forward call, not two."""
    args, weigh = operands(32, seed=4)
    policy = jax.checkpoint_policies.save_only_these_names(*ssd.SAVED_NAMES)

    def loss(*xs):
        return jnp.sum(ssd.ssd(*xs, chunk=8) * weigh)

    for kept, calls in ((policy, 1), (None, 2)):
        text = str(jax.make_jaxpr(jax.grad(
            jax.checkpoint(loss, policy=kept)))(*args))
        assert text.count("name=_fwd_call") == calls, kept
    assert ssd.SAVED_NAMES == ("hvd_ssd_y", "hvd_ssd_states")
    assert ssd.state_bytes(1, 16384, 64, 64, 128) == 4 * 128 * 64 * 64 * 128
    assert ssd.ssd_chunks(16384) == 128 and ssd.ssd_chunks(5) == 1


def test_the_calls_shapes_reach_the_telemetry_plane(telemetry_plane):
    args, _ = operands(40)
    ssd.ssd(*args, chunk=16)
    families = telemetry_plane.snapshot()["families"]
    assert families["hvd_ssd_chunks"]["samples"][0]["value"] == 3.0
    assert families["hvd_ssd_chunk_len"]["samples"][0]["value"] == 16.0
    assert families["hvd_ssd_state_bytes"]["samples"][0]["value"] == (
        4.0 * 2 * 3 * 4 * 8 * 16)


# ---- the mixer --------------------------------------------------------------

def test_mamba2_mixer_matches_the_reference(seeded):
    cfg, model, params, _, _ = seeded
    p = params["params"]["backbone"]["block_0"]["mamba2"]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, HIDDEN))
    mixer = ssm.Mamba2Mixer(model.cfg)
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(
            lambda p, h: jnp.sum(jnp.sin(mixer.apply({"params": p}, h))),
            argnums=(0, 1))(p, h)
    want, want_grads = jax.value_and_grad(
        lambda p, h: jnp.sum(jnp.sin(reference.mamba2(h, p, cfg))),
        argnums=(0, 1))(p, h)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, a, b in zip(common.leaf_names(want_grads),
                          jax.tree.leaves(grads),
                          jax.tree.leaves(want_grads)):
        assert float(jnp.max(jnp.abs(b))) > 0 and worst(a, b) < 2e-4, name
    shapes = jax.tree.map(jnp.shape, p)
    assert shapes["in_proj"]["kernel"] == (HIDDEN, 64 + 64 + 2 * 32 + 8)
    assert shapes["conv_kernel"] == (4, 64 + 2 * 32)
    assert (shapes["A_log"], shapes["dt_bias"], shapes["D"],
            shapes["norm"]) == ((8,), (8,), (8,), (64,))


def test_the_norm_after_the_gate_is_over_each_group_alone(seeded):
    """Scaling one group's lanes of ``z`` moves no other group's
    output: the statistics are a group's own."""
    cfg, model, params, _, _ = seeded
    p = params["params"]["backbone"]["block_0"]["mamba2"]
    h = jax.random.normal(jax.random.PRNGKey(6), (1, SEQ, HIDDEN))
    out_proj = jnp.eye(64, HIDDEN)          # read the normed lanes as is
    p = {**p, "out_proj": {"kernel": out_proj}}
    scaled = p["in_proj"]["kernel"].at[:, :32].multiply(3.0)  # group 0's z
    mixer = ssm.Mamba2Mixer(model.cfg)
    a = mixer.apply({"params": p}, h)
    b = mixer.apply({"params": {**p, "in_proj": {"kernel": scaled}}}, h)
    assert worst(b[..., 32:], a[..., 32:]) < 1e-6
    assert worst(b[..., :32], a[..., :32]) > 1e-2


def test_sizes_that_do_not_make_heads_are_refused(seeded):
    _, model, _, _, _ = seeded
    bad = dataclasses.replace(model.cfg, ssm=dataclasses.replace(
        model.cfg.ssm, heads=7))
    with pytest.raises(ValueError, match="7 heads of 8"):
        ssm.Mamba2Mixer(bad).init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8, HIDDEN)))


# ---- experts without a gate matrix -----------------------------------------

def layer_params(key, held=(0, EXPERTS), d=32, width=24, shared=48):
    keys = jax.random.split(key, 5)
    n = held[1] - held[0]

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape) / np.sqrt(fan_in)

    return {"router": normal(keys[0], (d, EXPERTS), d),
            "w_up": normal(keys[1], (n, d, width), d),
            "w_down": normal(keys[2], (n, width, d), width),
            "shared_up": normal(keys[3], (d, shared), d),
            "shared_down": normal(keys[4], (shared, d), shared)}


def dense_layer(x, params, bias, held, k=6, scale=2.5):
    """The layer's share as a sum over the held experts, each applied to
    every token."""
    scores = jax.nn.sigmoid(jnp.dot(x, params["router"],
                                    precision="highest"))
    _, chosen = jax.lax.top_k(scores + bias, k)
    picked = scores * jnp.sum(jax.nn.one_hot(chosen, EXPERTS), axis=-2)
    weights = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    y = jnp.square(jax.nn.relu(x @ params["shared_up"])) @ params[
        "shared_down"]
    for e in range(held[0], held[1]):
        hidden = jnp.square(jax.nn.relu(x @ params["w_up"][e - held[0]]))
        y = y + weights[:, e, None] * (hidden @ params["w_down"][e - held[0]])
    return y


# (experts held, a bias that sends every token to them): the sized
# rows, the fallback when the draw does not fit, every expert held.
PATHS = {"sized": ((4, 6), False), "fallback": ((4, 6), True),
         "all_held": ((0, EXPERTS), False)}


@pytest.mark.parametrize("path", PATHS)
def test_ungated_experts_match_a_dense_sum_forward_and_back(path):
    held, crowd = PATHS[path]
    tokens, k = 1024, 6
    params = layer_params(jax.random.PRNGKey(7), held)
    x = jax.random.normal(jax.random.PRNGKey(8), (tokens, 32))
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(9), (EXPERTS,))
    if crowd:
        bias = bias.at[held[0]:held[1]].add(10.0)
    weigh = jax.random.normal(jax.random.PRNGKey(10), x.shape)

    def ours(x, params):
        y, drawn = moe.moe_apply(x, params, bias, k=k, scale=2.5,
                                 first_held=held[0], gate="relu2")
        return jnp.sum(y * weigh), drawn

    (got, drawn), grads = jax.value_and_grad(ours, argnums=(0, 1),
                                             has_aux=True)(x, params)
    want, want_grads = jax.value_and_grad(
        lambda x, p: jnp.sum(dense_layer(x, p, bias, held) * weigh),
        argnums=(0, 1))(x, params)
    rows = moe.sized_rows(tokens * k, held[1] - held[0], EXPERTS)
    assert (rows < tokens * k) == (path != "all_held")
    assert moe.took_sized_path(np.asarray(drawn), *held) == (path == "sized")
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    for name, a, b in zip(common.leaf_names(want_grads),
                          jax.tree.leaves(grads),
                          jax.tree.leaves(want_grads)):
        assert float(jnp.max(jnp.abs(b))) > 0 and worst(a, b) < 2e-5, name


def test_an_ungated_layer_has_two_leaves_an_expert_and_keeps_one_product():
    cfg = moe.MoEConfig(experts=EXPERTS, per_token=6, width=24, held=(4, 8),
                        shared=2, scale=2.5, gate="relu2")
    x = jnp.zeros((1, 64, 32))
    shapes = jax.eval_shape(lambda: moe.MoELayer(cfg).init(
        jax.random.PRNGKey(0), x))["params"]
    assert jax.tree.map(lambda s: s.shape, shapes) == {
        "router": (32, EXPERTS), "w_up": (4, 32, 24), "w_down": (4, 24, 32),
        "shared_up": (32, 48), "shared_down": (48, 32)}
    gated = jax.eval_shape(lambda: moe.MoELayer(dataclasses.replace(
        cfg, gate="relu")).init(jax.random.PRNGKey(0), x))["params"]
    assert set(gated) == set(shapes) | {"w_gate", "shared_gate"}
    # One product of the sized rows where the gated form keeps two.
    assert moe.kept_bytes(12288, 1856, "relu2") == 12288 * (1856 * 2 + 4)
    assert moe.kept_bytes(12288, 1856) == 12288 * (2 * 1856 * 2 + 4)
    assert moe.sized_rows(16384 * 6, 8, 128) == 12288
    assert set(moe.UNGATED) < set(moe.GATES)
    with pytest.raises(ValueError, match="gate 'relu3'"):
        moe.MoELayer(dataclasses.replace(cfg, gate="relu3")).init(
            jax.random.PRNGKey(0), x)


def test_the_sized_path_pulls_back_through_four_grouped_products():
    """Counted in the jaxpr of the layer's backward call, which holds
    both branches: the sized one, one product to the rows and one to the
    weights for each of the up and down matrices (four, where the gated
    form has six), and the fallback, which makes its two forward
    products again and pulls back through four more (three and six)."""
    held, tokens, k = (4, 6), 256, 6
    x = jax.random.normal(jax.random.PRNGKey(8), (tokens, 32))
    bias = jnp.zeros((EXPERTS,))
    params = layer_params(jax.random.PRNGKey(7), held)
    gated = {**params, "w_gate": params["w_up"],
             "shared_gate": params["shared_up"]}

    def grouped_products(params, gate):
        def loss(x, params):
            return jnp.sum(moe.moe_apply(x, params, bias, k=k,
                                         first_held=held[0], gate=gate)[0])
        clear_traces()
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, params))
        return text[text.index("name=_either_back"):].count("ragged_dot_general[")

    assert grouped_products(params, "relu2") == 4 + (2 + 4)
    assert grouped_products(gated, "relu") == 6 + (3 + 6)
    clear_traces()


# ---- blocks that are one sub-layer -----------------------------------------

def block_cfg(**replace):
    base = transformer.TransformerConfig(
        vocab_size=32, hidden=32, layers=1, heads=4, kv_heads=2, max_len=16,
        dtype=jnp.float32, norm="rmsnorm", bias=False, use_rope=False,
        positions=False, mlp="swiglu", mlp_width=48,
        ssm=ssm.SSMConfig(d_inner=32, dt_rank=0, d_state=8, heads=4,
                          head_dim=8, groups=2),
        moe=moe.MoEConfig(experts=4, per_token=2, width=16, shared=1,
                          gate="relu2"))
    return dataclasses.replace(base, **replace)


@pytest.mark.parametrize("mixer,ffn,leaves", [
    ("mamba2", "none", {"ln1", "mamba2"}),
    ("full", "none", {"ln1", "attn"}),
    ("none", "expert", {"ln2", "moe"}),
    ("none", "dense", {"ln2", "mlp_gate", "mlp_in", "mlp_out"}),
    ("full", "dense", {"ln1", "attn", "ln2", "mlp_gate", "mlp_in",
                       "mlp_out"})])
def test_a_block_holds_the_parts_it_is_given_and_no_other(mixer, ffn,
                                                          leaves):
    """One norm and one residual add a part: a part that is not there
    leaves no leaf, and the block is ``x + part(norm(x))``."""
    cfg = block_cfg()
    block = transformer.Block(cfg, ffn=ffn, mixer=mixer)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    variables = block.init(jax.random.PRNGKey(1), x)
    assert set(variables["params"]) == leaves
    (out, made), _ = block.apply(variables, x, mutable=["moe_state"])
    assert made is None and out.shape == x.shape
    if ffn == "none" and mixer == "mamba2":
        normed = nn.RMSNorm(epsilon=cfg.norm_eps).apply(
            {"params": variables["params"]["ln1"]}, x)
        part = ssm.Mamba2Mixer(cfg).apply(
            {"params": variables["params"]["mamba2"]}, normed)
        assert worst(out, x + part) < 1e-6


def test_a_block_with_neither_part_is_refused():
    block = transformer.Block(block_cfg(), ffn="none", mixer="none")
    with pytest.raises(ValueError, match="neither a mixer nor an FFN"):
        block.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 32)))


@pytest.mark.parametrize("replace,match", [
    (dict(layers=2, mixers=("mamba2", "none"), ffns=("none",)),
     "one of"),
    (dict(layers=2, mixers=("mamba2", "none"), ffns=("none", "gated")),
     "one of"),
    (dict(layers=1, mixers=None, ffns=("none",)), "a stack of mixers"),
    (dict(layers=1, mixers=("full",), ffns=("expert",), moe=None),
     "needs moe"),
    (dict(layers=1, mixers=("none",), ffns=("none",)),
     "neither a mixer nor an FFN")])
def test_a_stacks_description_is_checked_as_it_is_built(replace, match):
    model = TransformerLM(block_cfg(**replace))
    with pytest.raises(ValueError, match=match):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_without_a_description_the_rule_of_first_dense_holds():
    """``ffns`` None: the expert layer from ``moe.first_dense`` on, as
    before there was a description."""
    cfg = block_cfg(layers=3, moe=dataclasses.replace(
        block_cfg().moe, first_dense=1))
    params = jax.eval_shape(lambda: TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    has = ["moe" in params["backbone"][f"block_{i}"] for i in range(3)]
    assert has == [False, True, True]
    described = jax.eval_shape(lambda: TransformerLM(dataclasses.replace(
        cfg, ffns=("expert", "dense", "expert"))).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert ["moe" in described["backbone"][f"block_{i}"]
            for i in range(3)] == [True, False, True]


# ---- the whole program against the reference -------------------------------

def test_the_stack_is_the_published_pattern_at_the_layers_held(seeded):
    cfg, model, params, aux, _ = seeded
    assert reference.pattern(cfg) == "MEMEM*EME"
    assert model.cfg.mixers == ("mamba2", "none", "mamba2", "none",
                                "mamba2", "full", "none", "mamba2", "none")
    assert model.cfg.ffns == ("none", "expert", "none", "expert", "none",
                              "none", "expert", "none", "expert")
    assert builder.ATTENTION == "full" and "full_rope" in transformer.PLAIN
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32)))
    assert jax.tree.map(jnp.shape, shapes["params"]) == jax.tree.map(
        jnp.shape, params["params"])
    assert jax.tree.map(jnp.shape, shapes["moe_state"]) == jax.tree.map(
        jnp.shape, aux["moe_state"])
    blocks = params["params"]["backbone"]
    for i, kind in enumerate("MEMEM*EME"):
        want = {"M": {"ln1", "mamba2"}, "E": {"ln2", "moe"},
                "*": {"ln1", "attn"}}[kind]
        assert set(blocks[f"block_{i}"]) == want, i
    assert "w_gate" not in blocks["block_1"]["moe"]
    assert params["params"]["lm_head"]["kernel"].shape == (HIDDEN, 64)


def test_loss_and_every_gradient_leaf_match_reference(seeded):
    cfg, model, params, aux, batch = seeded
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: program_loss(model, p, aux, batch)[0]))(params)
    got, got_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_fn(p, aux, batch, cfg)[0]))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, a, b in zip(common.leaf_names(params),
                          jax.tree.leaves(got_grads),
                          jax.tree.leaves(want_grads)):
        assert float(jnp.max(jnp.abs(b))) > 0, name   # every leaf is reached
        assert worst(a, b) < 2e-4, name


@pytest.mark.parametrize("remat", ["dots", "flash", True])
def test_recomputation_changes_no_gradient(seeded, remat):
    cfg, model, params, aux, batch = seeded
    grad = jax.jit(jax.grad(lambda p: program_loss(
        make_model(cfg, remat=remat), p, aux, batch)[0]))
    plain = jax.jit(jax.grad(
        lambda p: program_loss(model, p, aux, batch)[0]))
    for name, a, b in zip(common.leaf_names(params),
                          jax.tree.leaves(grad(params)),
                          jax.tree.leaves(plain(params))):
        assert worst(a, b) < 1e-5, name


def test_the_references_blocks_change_no_number(seeded, monkeypatch):
    """States kept every 8 positions, score rows 4 at a time and logits
    6 positions at a time, against one block of each: the same loss and
    gradients."""
    cfg, _, params, aux, batch = seeded
    grad = jax.value_and_grad(
        lambda p: reference.loss_fn(p, aux, batch, cfg)[0])
    whole, whole_grads = grad(params)
    monkeypatch.setattr(reference, "STATE_BLOCK", 8)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 4)
    monkeypatch.setattr(reference, "LOGIT_BLOCK", 6)
    blocked, blocked_grads = grad(params)
    assert float(blocked) == pytest.approx(float(whole), rel=1e-6)
    for name, a, b in zip(common.leaf_names(params),
                          jax.tree.leaves(blocked_grads),
                          jax.tree.leaves(whole_grads)):
        assert worst(a, b) < 1e-5, name


def test_three_adamw_steps_match_reference(seeded):
    cfg, model, params, aux, batch = seeded
    opt = dict(cfg["optimizer"], learning_rate=1e-3)
    tx = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                     eps=opt["eps"], weight_decay=opt["weight_decay"])
    ours, state = params, tx.init(params)
    theirs, their_state = params, common.adamw_init(params)
    grad = jax.jit(jax.grad(
        lambda p: program_loss(model, p, aux, batch)[0]))
    ref_grad = jax.jit(jax.grad(
        lambda p: reference.loss_fn(p, aux, batch, cfg)[0]))
    for _ in range(3):
        with jax.default_matmul_precision("highest"):
            updates, state = tx.update(grad(ours), state, ours)
        ours = optax.apply_updates(ours, updates)
        theirs, their_state = common.adamw_update(
            theirs, their_state, ref_grad(theirs), opt)
    for name, a, b, start in zip(common.leaf_names(params),
                                 *map(jax.tree.leaves,
                                      (ours, theirs, params))):
        a, b = jnp.linalg.norm(a - start), jnp.linalg.norm(b - start)
        assert float(b) > 0 and float(abs(a - b) / b) < 1e-3, name


def test_attention_has_no_positions(seeded):
    """A ``*`` layer without the causal mask would be blind to order;
    with it, the reference and the program agree and rope is nowhere:
    the alternative, ``"full_rope"``, gives another result."""
    cfg, model, params, aux, batch = seeded
    mixers = tuple("full_rope" if m == "full" else m
                   for m in model.cfg.mixers)
    base = program_loss(model, params, aux, batch)[0]
    roped = program_loss(make_model(cfg, mixers=mixers), params, aux,
                         batch)[0]
    assert abs(float(roped) - float(base)) > 1e-4


def test_the_sixteen_shares_add_up_to_the_uncut_reference_layer():
    """The guide's share test: each chip's routed part, plus the shared
    expert once, is the uncut layer."""
    d, width, shared, tokens = 32, 24, 48, 64
    whole = layer_params(jax.random.PRNGKey(11), d=d, width=width,
                         shared=shared)
    x = jax.random.normal(jax.random.PRNGKey(12), (1, tokens, d))
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(13), (EXPERTS,))
    cfg = small_cfg(hidden_size=d, experts_held=[0, EXPERTS])
    want = reference.expert_ffn(x, whole, bias, cfg)
    shared_part = reference._relu2_ffn(x, whole["shared_up"],
                                       whole["shared_down"], "float32")
    total = 0.0
    for first in range(EXPERTS):          # sixteen chips, an expert each
        share = {**whole, "w_up": whole["w_up"][first:first + 1],
                 "w_down": whole["w_down"][first:first + 1]}
        with jax.default_matmul_precision("highest"):
            y, _ = moe.moe_apply(x[0], share, bias, k=6, scale=2.5,
                                 first_held=first, gate="relu2")
        total = total + (y - shared_part[0])
    assert worst(total + shared_part[0], want[0]) < 2e-5


def test_the_models_layers_reach_the_telemetry_plane(seeded,
                                                     telemetry_plane):
    cfg, model, params, aux, batch = seeded
    program_loss(model, params, aux, batch)
    families = telemetry_plane.snapshot()["families"]
    kinds = {s["labels"]["kind"]: s["value"]
             for s in families["hvd_stack_layers"]["samples"]}
    assert set(kinds) == set(transformer.MIXERS)
    assert (kinds.pop("mamba2"), kinds.pop("none"), kinds.pop("full")) == (
        4.0, 4.0, 1.0)
    assert set(kinds.values()) == {0.0}
    blocks = {s["labels"]["kind"]: s["value"]
              for s in families["hvd_stack_ffns"]["samples"]}
    assert blocks == {"dense": 0.0, "expert": 4.0, "none": 5.0}
    assert families["hvd_ssd_chunks"]["samples"][0]["value"] == 1.0
    assert families["hvd_ssd_chunk_len"]["samples"][0]["value"] == SEQ


# ---- the grouped kernels at widths that are not whole lane tiles -----------

_BY_GROUP = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])

# (k, n, the groups' sizes of 1024 rows): a width in one block beside one
# cut into whole tiles (2304 = 3 x 768), a group that is empty and rows
# past the groups; a gradient to the weights whose block does not divide
# its width (640 = 384 + 256); groups that fill the rows; no group at all.
GROUPED = {"odd_widths": (2304, 116, [300, 0, 212, 129]),
           "cut_weights": (1856, 640, [300, 200, 100, 424]),
           "full": (168, 116, [256, 256, 500, 12]),
           "no_draw": (168, 116, [0, 0, 0, 0])}


@pytest.mark.parametrize("case", GROUPED)
def test_the_grouped_kernels_are_xlas_grouped_products(case):
    """In Pallas's interpreter: each of the three products the sized
    path makes, on the rows the groups hold (what either kernel leaves
    past them is not specified)."""
    from horovod_tpu.ops import grouped_product
    k, n, sizes = GROUPED[case]
    live, sizes = sum(sizes), jnp.asarray(sizes, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    a = jax.random.normal(keys[0], (1024, k), jnp.bfloat16)
    ct = jax.random.normal(keys[1], (1024, n), jnp.bfloat16)
    w = jax.random.normal(keys[2], (4, k, n), jnp.bfloat16) / np.sqrt(k)
    rows = grouped_product.rows_by_group(a, w, sizes)
    assert rows.shape == (1024, n) and rows.dtype == jnp.bfloat16
    back = grouped_product.rows_by_group(ct, w, sizes, transposed=True)
    if live:        # with no draw the calls run and nothing is read
        assert worst(rows[:live],
                     jax.lax.ragged_dot(a, w, sizes)[:live]) < 2e-2
        assert worst(back[:live], jax.lax.ragged_dot(
            ct, jnp.swapaxes(w, 1, 2), sizes)[:live]) < 2e-2
    to_w = grouped_product.weights_by_group(a, ct, sizes)
    assert to_w.shape == (4, k, n) and to_w.dtype == jnp.float32
    want = jax.lax.ragged_dot_general(
        a, ct, sizes, _BY_GROUP, preferred_element_type=jnp.float32)
    assert worst(to_w, want) < 1e-3 * max(1.0, float(jnp.abs(want).max()))


def test_which_widths_the_grouped_kernels_take_is_from_shapes_alone(
        monkeypatch):
    """Off the TPU none; on it this model's (an expert 14.5 lane tiles
    wide) and none of the accepted expert cells' (hidden, width), so
    their steps are what they were."""
    from horovod_tpu.ops import flash_attention, grouped_product
    assert not grouped_product.takes(12288, 2688, 1856, jnp.bfloat16)
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    for hidden, width in ((2688, 1856), (1856, 2688)):
        assert grouped_product.takes(12288, hidden, width, jnp.bfloat16)
    assert not grouped_product.takes(12288, 2688, 1856, jnp.float32)
    assert not grouped_product.takes(12288 + 256, 2688, 1856, jnp.bfloat16)
    for hidden, width in ((2048, 1536), (2560, 768), (2048, 1792),
                          (2048, 768)):
        assert not grouped_product.takes(8192, hidden, width, jnp.bfloat16)
        assert not grouped_product.takes(8192, width, hidden, jnp.bfloat16)
    # Blocks that VMEM does not hold twice stay with XLA's kernel.
    assert not grouped_product.takes(8192, 2048, 2000, jnp.bfloat16)


def test_the_sized_path_through_the_grouped_kernels(monkeypatch):
    """The layer with its sized rows' six products through the kernels
    (interpreted; steered here as a chip's shapes would) against the
    same layer through ``lax.ragged_dot``: the result and every
    gradient, in bfloat16 as on the chip."""
    from horovod_tpu.ops import grouped_product
    held, tokens, k = (4, 6), 1024, 6
    params = layer_params(jax.random.PRNGKey(7), held)
    x = jax.random.normal(jax.random.PRNGKey(8), (tokens, 32), jnp.bfloat16)
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(9), (EXPERTS,))
    weigh = jax.random.normal(jax.random.PRNGKey(10), x.shape)

    def run():
        def loss(x, params):
            y, drawn = moe.moe_apply(x, params, bias, k=k, scale=2.5,
                                     first_held=held[0], gate="relu2")
            return jnp.sum(y * weigh), drawn
        clear_traces()
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            x, params)

    (want, drawn), want_grads = run()
    assert moe.took_sized_path(np.asarray(drawn), *held)
    took = []
    monkeypatch.setattr(grouped_product, "takes",
                        lambda *shape: took.append(shape) or True)
    (got, _), got_grads = run()
    clear_traces()
    assert len(took) == 6
    assert {s[0] for s in took} == {moe.sized_rows(tokens * k, 2, EXPERTS)}
    assert abs(float(got) - float(want)) < 2e-2 * abs(float(want))
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(b.astype(jnp.float32)).max())
        assert worst(a, b) < 3e-2 * scale
