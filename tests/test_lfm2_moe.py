"""LFM2-24B-A2B's mechanisms at a small size on the CPU, seeded: the
program's model (the published pattern's five layers: a gated short
convolution with the dense FFN, then full attention and three more
convolutions with experts; 8 query heads of 8 over 2 K/V heads with a
norm on q and k; a sigmoid router with a selection bias and no shared
expert; a head tied to the embedding) against the plain reference of
``benchmark/references/lfm2_moe.py`` for loss, every gradient leaf and
three AdamW steps; the conv mixer alone against three explicit shifts;
the norm on q and k; the shares of an expert layer adding up to the
uncut layer; the tied table's gradient. (On the chip the comparison is
the benchmark's ``correct``, at the published widths.)
"""

import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.builders import lfm2_moe as builder  # noqa: E402
from benchmark.references import common  # noqa: E402
from benchmark.references import lfm2_moe as reference  # noqa: E402
from horovod_tpu.models import TransformerLM  # noqa: E402
from horovod_tpu.models import ssm, transformer  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402
from moe_fixtures import telemetry_plane  # noqa: E402, F401 (a fixture)

SEQ = 20        # the three taps many times over
HIDDEN = 64


def small_cfg(**overrides):
    """The configuration file's keys at a small size: hidden 64, 8
    heads of 8 in groups of 4 over 2 K/V heads, a dense FFN 96 wide, 16
    experts 24 wide top-4 of which 4 are held, vocabulary 64."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "lfm2moe24b.json")) as f:
        cfg = json.load(f)
    cfg.update(
        hidden_size=HIDDEN, num_attention_heads=8, num_key_value_heads=2,
        intermediate_size=96, moe_intermediate_size=24,
        num_experts_published=16, experts_held=[4, 8], vocab_size=64,
        embedding_fan_in=HIDDEN, attention_impl="einsum")
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


def test_the_stack_is_the_published_pattern_at_the_layers_held(seeded):
    cfg, model, params, aux, batch = seeded
    assert reference.layers(cfg) == [1, 2, 3, 4, 5]
    assert model.cfg.mixers == ("conv", "full_rope", "conv", "conv", "conv")
    # The whole model: 30 conv and 10 full, full at 2, 6, ..., 38.
    whole = dict(cfg, layers_held=[0, 40], num_hidden_layers=40)
    assert [i for i, k in enumerate(reference.kinds(whole))
            if k == "full_rope"] == list(range(2, 40, 4))
    assert (model.cfg.head_width, model.cfg.heads // model.cfg.kv_heads,
            model.cfg.conv_taps) == (8, 4, 3)
    assert model.cfg.qk_norm and model.cfg.tie_embeddings
    assert not (model.cfg.use_rope or model.cfg.positions or model.cfg.bias)
    assert (model.cfg.moe.shared, model.cfg.moe.first_dense,
            model.cfg.moe.scoring, model.cfg.moe.router_reads) == (
        0, 1, "sigmoid", "ffn")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), batch[0])
    for ours, theirs in ((params["params"], shapes["params"]),
                         (aux["moe_state"], shapes["moe_state"])):
        assert jax.tree.map(lambda x: x.shape, ours) == jax.tree.map(
            lambda x: x.shape, theirs)
    assert set(shapes["params"]) == {"backbone"}       # no head of its own
    blocks = shapes["params"]["backbone"]
    assert set(blocks["block_0"]) == {"ln1", "ln2", "conv", "mlp_gate",
                                      "mlp_in", "mlp_out"}
    assert set(blocks["block_1"]) == {"ln1", "ln2", "attn", "moe"}
    assert set(blocks["block_1"]["attn"]) == {"qkv", "q_norm", "k_norm",
                                              "proj"}
    assert blocks["block_1"]["attn"]["q_norm"]["scale"].shape == (8,)
    for i in (2, 3, 4):
        assert set(blocks[f"block_{i}"]) == {"ln1", "ln2", "conv", "moe"}
        assert set(blocks[f"block_{i}"]["moe"]) == {
            "router", "w_gate", "w_up", "w_down"}       # shared 0
        assert set(blocks[f"block_{i}"]["conv"]) == {
            "in_proj", "conv_kernel", "out_proj"}       # no bias
    assert blocks["block_0"]["conv"]["in_proj"]["kernel"].shape == (64, 192)
    assert blocks["block_0"]["conv"]["conv_kernel"].shape == (3, 64)
    assert set(aux["moe_state"]["backbone"]) == {
        f"block_{i}" for i in (1, 2, 3, 4)}


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


def test_the_references_blocks_change_no_number(seeded, monkeypatch):
    """Score rows 4 at a time and logits 5 positions at a time, against
    one block of each: the same loss and gradients."""
    cfg, _, params, aux, batch = seeded
    grad = jax.value_and_grad(
        lambda p: reference.loss_fn(p, aux, batch, cfg)[0])
    whole, whole_grads = grad(params)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 4)
    monkeypatch.setattr(reference, "LOGIT_BLOCK", 5)
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


def test_flash_path_matches_einsum_path(seeded):
    cfg, model, params, aux, batch = seeded
    flash = make_model(cfg, attention_impl="flash")
    a = program_loss(model, params, aux, batch)[0]
    b = program_loss(flash, params, aux, batch)[0]
    assert float(a) == pytest.approx(float(b), rel=1e-5)


# ---- the gated short convolution alone --------------------------------------

def conv_params(key=1):
    cfg = small_cfg()
    block = reference.init_params(cfg, jax.random.PRNGKey(key))[
        "params"]["backbone"]["block_0"]["conv"]
    return cfg, block


def test_the_conv_mixer_is_two_gates_around_three_explicit_shifts(seeded):
    """``W_out (C * (w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t))`` with ``u = B
    * z``, the thirds of ``h W_in`` in that order, written out position
    by position; the program's module, the reference's function and the
    statement agree."""
    _, model, _, _, _ = seeded
    cfg, p = conv_params()
    h = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, HIDDEN))
    with jax.default_matmul_precision("highest"):
        got = ssm.ShortConv(model.cfg).apply({"params": p}, h)
        bcz = np.asarray(h @ p["in_proj"]["kernel"])
    b, c, z = bcz[..., :64], bcz[..., 64:128], bcz[..., 128:]
    u, w = b * z, np.asarray(p["conv_kernel"])
    conv = np.zeros_like(u)
    for t in range(SEQ):
        for j in range(3):
            if t - 2 + j >= 0:      # zeros before the row's start
                conv[:, t] += w[j] * u[:, t - 2 + j]
    want = (c * conv) @ np.asarray(p["out_proj"]["kernel"])
    assert worst(got, want) < 1e-5
    assert worst(reference.short_conv(h, p), want) < 1e-5
    assert worst(reference.shifts(jnp.asarray(u), p["conv_kernel"]),
                 conv) < 1e-6
    assert worst(ssm.causal_conv(jnp.asarray(u), p["conv_kernel"]),
                 conv) < 1e-6


def test_a_position_reads_itself_and_the_two_before_it_in_its_own_row(
        seeded):
    """Moving position 7 of row 0 moves positions 7, 8 and 9 of that row
    and nothing else: nothing after a position reaches it, position 0
    reads zeros and not the other row's end, and the filter is three taps
    long."""
    _, model, _, _, _ = seeded
    _, p = conv_params(2)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, HIDDEN))
    module = ssm.ShortConv(model.cfg)
    for fn in (lambda x: module.apply({"params": p}, x),
               lambda x: reference.short_conv(x, p)):
        before, after = fn(h), fn(h.at[0, 7].add(1.0))
        moved = np.asarray(jnp.any(before != after, axis=-1))
        assert moved[0].tolist() == [t in (7, 8, 9) for t in range(SEQ)]
        assert not moved[1].any()
        # Position 0 of row 1 sees the last positions of row 0 nowhere.
        after = fn(h.at[0, SEQ - 1].add(1.0))
        assert np.asarray(jnp.any(before != after, axis=-1)).sum() == 1


def test_the_filter_takes_its_taps_from_the_configuration(seeded):
    cfg, model, _, _, batch = seeded
    four = make_model(small_cfg(conv_L_cache=4))
    shapes = jax.eval_shape(four.init, jax.random.PRNGKey(0), batch[0])
    assert shapes["params"]["backbone"]["block_0"]["conv"][
        "conv_kernel"].shape == (4, HIDDEN)


def test_without_a_bias_the_convolution_adds_no_tensor_of_zeros():
    """Mamba's call hands a bias and its program is as it was; the gated
    short convolution hands none and has one addition fewer, not an
    addition of zeros."""
    x = jnp.ones((1, 8, 4))
    kernel = jnp.ones((3, 4))
    with_bias = str(jax.make_jaxpr(ssm.causal_conv)(x, kernel, jnp.ones(4)))
    without = str(jax.make_jaxpr(ssm.causal_conv)(x, kernel))
    assert with_bias.count(" add ") == without.count(" add ") + 1
    np.testing.assert_allclose(
        ssm.causal_conv(x, kernel, jnp.full((4,), 0.5)),
        ssm.causal_conv(x, kernel) + 0.5)


# ---- the norm on q and k ----------------------------------------------------

def test_attention_matches_the_reference_with_the_norm_on_q_and_k(seeded):
    cfg, model, params, _, _ = seeded
    attn = params["params"]["backbone"]["block_1"]["attn"]
    attn = dict(attn, q_norm={"scale": 1.0 + jnp.arange(8.0) / 4.0},
                k_norm={"scale": 2.0 - jnp.arange(8.0) / 8.0})
    h = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, HIDDEN))
    want = reference.attention(h, attn, cfg)
    for impl in ("einsum", "flash"):
        module = transformer.Attention(
            dataclasses.replace(model.cfg, attention_impl=impl),
            kind="full_rope")
        with jax.default_matmul_precision("highest"):
            got = module.apply({"params": attn}, h)
        assert worst(got, want) < 1e-5, impl
    # Without the property the same weights give another layer, and its
    # parameter tree has no gains.
    plain = transformer.Attention(
        dataclasses.replace(model.cfg, qk_norm=False), kind="full_rope")
    assert set(jax.eval_shape(plain.init, jax.random.PRNGKey(0), h)[
        "params"]) == {"qkv", "proj"}
    off = plain.apply({"params": {k: attn[k] for k in ("qkv", "proj")}}, h)
    assert worst(off, want) > 1e-2


def test_the_gain_on_q_moves_the_scores_and_leaves_v_alone(seeded):
    """q and k are normed over each head's lanes before rope (every
    head's lanes have mean square 1 under a gain of 1, whatever the
    product gave), one gain for all heads; v is what the product gave."""
    cfg, _, params, _, _ = seeded
    attn = params["params"]["backbone"]["block_1"]["attn"]
    h = 3.0 * jax.random.normal(jax.random.PRNGKey(7), (1, SEQ, HIDDEN))
    q, k, v = reference.qkv(h, attn, cfg)
    for x in (q, k):        # rope is a rotation: it keeps the norm
        np.testing.assert_allclose(jnp.mean(x * x, axis=-1), 1.0, rtol=1e-3)
    raw = jnp.einsum("bsh,hnd->bsnd", h, attn["qkv"]["kernel"],
                     precision="highest")
    np.testing.assert_allclose(v, raw[:, :, 10:], rtol=1e-6)
    assert float(jnp.mean(raw[:, :, :8] ** 2)) > 4.0     # and q was not 1
    moved = dict(attn, q_norm={"scale": jnp.full((8,), 2.0)})
    q2, k2, v2 = reference.qkv(h, moved, cfg)
    np.testing.assert_allclose(q2, 2.0 * q, rtol=1e-6)
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, v)
    # Twice the scores: another softmax, another output.
    assert worst(reference.attention(h, moved, cfg),
                 reference.attention(h, attn, cfg)) > 1e-3


# ---- the expert layer: sigmoid scores, no shared expert, a mixed stack ------

def test_step_counts_the_tokens_each_expert_drew_in_the_four_expert_layers(
        seeded):
    cfg, model, params, aux, batch = seeded
    layers = program_loss(model, params, aux, batch)[1]["moe_state"][
        "backbone"]
    assert set(layers) == {f"block_{i}" for i in (1, 2, 3, 4)}
    for name, layer in layers.items():
        assert float(layer["moe"]["expert_tokens"].sum()) == 2 * SEQ * 4
        np.testing.assert_array_equal(      # the bias only selects
            layer["moe"]["bias"], aux["moe_state"]["backbone"][name]["moe"][
                "bias"])


def test_sigmoid_weights_are_the_chosen_scores_over_their_sum():
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 16))
    router = jax.random.normal(jax.random.PRNGKey(4), (16, 64)) / 4.0
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (64,))
    chosen, weights, drawn = moe.route(x, router, bias, k=4, scale=1.0)
    scores = jax.nn.sigmoid(x @ router)
    np.testing.assert_array_equal(chosen, jax.lax.top_k(scores + bias, 4)[1])
    assert not np.array_equal(chosen, jax.lax.top_k(scores, 4)[1])
    picked = jnp.take_along_axis(scores, chosen, -1)
    np.testing.assert_allclose(
        weights, picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    assert float(drawn.sum()) == 32 * 4
    # The reference's weights, 0 for the experts not chosen; its 1e-6 on
    # a sum of four sigmoids is under the comparison's resolution.
    theirs = reference.route(scores, bias, {
        "num_experts_per_tok": 4, "norm_topk_prob": True,
        "use_expert_bias": True, "routed_scaling_factor": 1})
    np.testing.assert_allclose(
        jnp.take_along_axis(theirs, chosen, -1), weights, rtol=2e-6)
    assert float((theirs > 0).sum()) == 32 * 4


EXPERTS, PER_TOKEN, D, F = 64, 4, 16, 24
LAYER_CFG = {"num_experts_per_tok": PER_TOKEN, "norm_topk_prob": True,
             "use_expert_bias": True, "routed_scaling_factor": 1,
             "experts_held": [0, EXPERTS]}


def layer_params(key, held=(0, EXPERTS)):
    keys = jax.random.split(key, 4)

    def normal(k, shape):
        return jax.random.normal(k, shape) / np.sqrt(shape[-2])

    full = {"router": normal(keys[0], (D, EXPERTS)),
            "w_gate": normal(keys[1], (EXPERTS, D, F)),
            "w_up": normal(keys[2], (EXPERTS, D, F)),
            "w_down": normal(keys[3], (EXPERTS, F, D))}
    return {"router": full["router"],
            **{k: full[k][held[0]:held[1]]
               for k in ("w_gate", "w_up", "w_down")}}


def test_the_eight_shares_add_up_to_the_uncut_reference_layer():
    """The 8 shares of a 64-expert layer (8 experts each; no shared
    expert, so nothing is counted once) sum to what the uncut reference
    gives for the whole layer: value and the gradient with respect to
    the tokens."""
    key = jax.random.PRNGKey(7)
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(6), (EXPERTS,))
    f = jax.random.normal(jax.random.PRNGKey(8), (40, D))
    whole = layer_params(key)

    def uncut(f):
        return reference.expert_ffn(f[None], whole, bias, LAYER_CFG)[0]

    def shares(f):
        return sum(moe.moe_apply(
            f, layer_params(key, (first, first + 8)), bias, k=PER_TOKEN,
            first_held=first, scoring="sigmoid")[0]
            for first in range(0, EXPERTS, 8))

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(shares(f), uncut(f), atol=2e-5, rtol=2e-5)
        weights = jnp.cos(jnp.arange(40.0 * D)).reshape(40, D)
        got = jax.grad(lambda f: jnp.sum(shares(f) * weights))(f)
        want = jax.grad(lambda f: jnp.sum(uncut(f) * weights))(f)
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
    # One share alone is not the layer.
    one = moe.moe_apply(f, layer_params(key, (0, 8)), bias, k=PER_TOKEN,
                        first_held=0, scoring="sigmoid")[0]
    assert worst(one, uncut(f)) > 0.1


# ---- the tied head ----------------------------------------------------------

def test_the_tied_tables_gradient_is_the_embeddings_plus_the_heads(seeded):
    """With the table handed in twice, once to the lookup and once to
    the head, the two gradients add up to the one leaf's."""
    cfg, _, params, aux, batch = seeded

    def loss(lookup, head):
        bb = params["params"]["backbone"]
        untied = {"params": {"backbone": {
            **bb, "tok_embed": {"embedding": lookup}}}}
        h = reference.hidden_fn(untied, aux, batch[0], cfg)
        logits = jnp.einsum("bsh,vh->bsv", h, head, precision="highest")
        return common.softmax_xent_mean(logits, batch[1])

    table = params["params"]["backbone"]["tok_embed"]["embedding"]
    g_lookup, g_head = jax.grad(loss, argnums=(0, 1))(table, table)
    assert float(jnp.abs(g_lookup).max()) > 0 < float(jnp.abs(g_head).max())
    tied = jax.grad(lambda p: reference.loss_fn(p, aux, batch, cfg)[0])(
        params)["params"]["backbone"]["tok_embed"]["embedding"]
    assert worst(g_lookup + g_head, tied) < 1e-5
    assert worst(g_head, tied) > 1e-2


# ---- spans and counters -----------------------------------------------------

def test_compiled_step_names_the_mixers_and_the_routers_place():
    """The scopes this model adds or uses (docs/tracing.md), forward and
    backward, as the benchmark's readers look for them: the conv mixers
    under ``hvd_shortconv`` with the gates and taps under ``mix`` inside
    it, the attention layer's kernel calls under ``hvd_attn_full``, the
    router's product under ``hvd_moe/route``."""
    import horovod_tpu.jax as hvd_jax
    from jax.sharding import Mesh

    from benchmark import scope_reduce, scope_sum
    cfg = small_cfg(num_hidden_layers=3, layers_held=[1, 4])
    model = TransformerLM(builder.model_config(cfg, {"seq_len": SEQ}))
    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    params = reference.init_params(cfg, jax.random.PRNGKey(0))
    aux = reference.init_aux(cfg)

    def loss_fn(p, aux, batch):
        logits, aux = model.apply({**p, **aux}, batch[0], mutable=list(aux))
        return logits.mean(), aux

    opt = hvd_jax.DistributedOptimizer(optax.adam(1e-2))
    step = hvd_jax.make_train_step(loss_fn, opt, mesh=mesh, has_aux=True,
                                   donate=False)
    lowered = step.lower(params, aux, opt.init(params), (tokens, tokens))
    names = re.findall(r'op_name="([^"]+)"', lowered.compile().as_text())
    parts = [scope_reduce._parts(n) for n in names]
    for scopes in (("block_0", "conv", "hvd_shortconv"),
                   ("block_0", "hvd_shortconv", "mix"),
                   ("block_2", "hvd_shortconv", "mix"),
                   ("block_1", "hvd_attn_full"),
                   ("block_1", "attn", "rope"),
                   ("block_1", "moe", "hvd_moe", "route"),
                   ("block_2", "hvd_moe", "experts")):
        assert [p for p in parts if scope_sum._within(scopes, p)], scopes
    assert not [p for p in parts
                if scope_sum._within(("block_1", "hvd_shortconv"), p)]
    assert not [p for p in parts
                if scope_sum._within(("block_0", "hvd_moe"), p)]
    for scope in ("hvd_shortconv", "hvd_attn_full", "hvd_moe"):
        assert any(scope in n and "transpose(" in n for n in names), scope
        assert any(scope in n and "transpose(" not in n for n in names)
    # The two products of a conv mixer lie under its scope and outside
    # ``mix``; the multiplies of the gates and taps inside it.
    text = lowered.as_text(debug_info=True)
    where = dict(re.findall(r'(#loc\d+) = loc\("([^"]+)"', text))

    def places(needle):
        return [where[re.search(r"loc\((#loc\d+)\)", line).group(1)]
                for line in text.splitlines() if needle in line
                and re.search(r"loc\((#loc\d+)\)", line)
                and re.search(r"loc\((#loc\d+)\)", line).group(1) in where]

    products = [p for p in places("stablehlo.dot_general")
                if "hvd_shortconv" in p and "transpose" not in p
                and "jvp" in p]
    assert len(products) == 4 and not any("/mix/" in p for p in products)
    assert any(p.endswith("/conv/hvd_shortconv/in_proj/dot_general")
               for p in products)
    gates = [p for p in places("stablehlo.multiply")
             if "hvd_shortconv/mix" in p]
    assert gates


def test_the_models_layers_reach_the_telemetry_plane(seeded,
                                                     telemetry_plane):
    from horovod_tpu.ops import flash_attention
    telemetry = telemetry_plane
    cfg, model, params, aux, batch = seeded
    new_aux = program_loss(make_model(cfg, attention_impl="flash"), params,
                           aux, batch)[1]
    families = telemetry.snapshot()["families"]
    # What stack ran: set as the backbone was built.
    kinds = {s["labels"]["kind"]: s["value"]
             for s in families["hvd_stack_layers"]["samples"]}
    assert set(kinds) == set(transformer.MIXERS)
    assert (kinds.pop("conv"), kinds.pop("full_rope")) == (4.0, 1.0)
    assert set(kinds.values()) == {0.0}
    # The attention layer's call published its sub-tiles.
    assert {"hvd_flash_fwd_subtiles", "hvd_flash_bwd_subtiles"} <= set(
        families)
    moe.publish_expert_tokens(new_aux, held=tuple(cfg["experts_held"]),
                              width=cfg["moe_intermediate_size"])
    families = telemetry.snapshot()["families"]
    layers = {s["labels"]["layer"]
              for s in families["hvd_moe_expert_tokens"]["samples"]}
    assert layers == {f"moe_state/backbone/block_{i}/moe"
                      for i in (1, 2, 3, 4)}
    assert len(families["hvd_moe_expert_tokens"]["samples"]) == 4 * 16
    # 160 pairs a layer, 4 of 16 held: 512 rows would hold the expected
    # draw over and over, so the buffers are a row for every pair.
    assert {s["value"] for s in
            families["hvd_moe_buffer_rows"]["samples"]} == {160.0}
    assert 0 < families["hvd_moe_held_share"]["samples"][0]["value"] < 1
    assert len(families["hvd_moe_kept_bytes"]["samples"]) == 4
    assert families["hvd_moe_sized_layers"]["samples"][0]["value"] == 0.0
    # The cell's call: 32 heads of 64 over 8,192 positions, no window.
    flash_attention._publish_subtiles(8192, 8192, 1024, 1024, True, 0, 0,
                                      8192, 64, None)
    families = telemetry.snapshot()["families"]
    want = flash_attention.subtile_counts(
        "fwd", 8192, 8192, 1024, 1024, True, head_dim=64)
    got = {s["labels"]["kind"]: s["value"] for s in
           families["hvd_flash_fwd_subtiles"]["samples"]}
    assert got == {k: float(v) for k, v in want.items()}
    assert got["interior"] > 0 and got["masked"] > 0


def test_a_stack_without_mixers_publishes_no_kinds(telemetry_plane):
    model = TransformerLM(transformer.TransformerConfig(
        vocab_size=32, hidden=16, layers=1, heads=2, max_len=8))
    model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert "hvd_stack_layers" not in telemetry_plane.snapshot()["families"]
    transformer.publish_stack_layers(["conv", "mamba", "conv"])
    kinds = {s["labels"]["kind"]: s["value"] for s in telemetry_plane.
             snapshot()["families"]["hvd_stack_layers"]["samples"]}
    assert (kinds["conv"], kinds["mamba"], kinds["full"]) == (2.0, 1.0, 0.0)


def test_publishing_the_stack_is_a_no_op_with_metrics_off(monkeypatch):
    from horovod_tpu.telemetry import core as telemetry
    monkeypatch.setattr(telemetry, "_ENABLED", False)
    before = dict(telemetry.registry().families())
    transformer.publish_stack_layers(("conv", "full_rope"))
    assert dict(telemetry.registry().families()) == before
