"""SmallThinker's mechanisms at a small size on the CPU, seeded: the
program's model (a stack of plain attention layers by the published
pattern, full without positions and windowed with rope, 14 query heads
of 8 over 2 K/V heads on a hidden size of 48, a softmax router that
reads the attention's input, ReGLU experts without a shared one)
against the plain reference of ``benchmark/references/smallthinker.py``
for loss, every gradient leaf and three AdamW steps; each kind of layer
against the reference's own mask; the shares of an expert layer adding
up to the uncut layer; the router's input; the sized path and its
fallback at a quarter of the experts held and six a token. (On the chip
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

from benchmark.builders import smallthinker as builder  # noqa: E402
from benchmark.references import common  # noqa: E402
from benchmark.references import smallthinker as reference  # noqa: E402
from horovod_tpu.models import TransformerLM  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402
from moe_fixtures import poison, telemetry_plane  # noqa: E402, F401 (fixtures)

SEQ = 20        # over two windows of 8


def small_cfg(**overrides):
    """The configuration file's keys at a small size: hidden 48, 14
    heads of 8 (112 wide, not 48) in groups of 7 over 2 K/V heads, a
    window of 8, 16 experts top-6 of which 4 are held, vocabulary 64."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "smallthinker21b.json")) as f:
        cfg = json.load(f)
    cfg.update(
        hidden_size=48, num_attention_heads=14, num_key_value_heads=2,
        head_dim=8, moe_ffn_hidden_size=24,
        moe_num_primary_experts_published=16, experts_held=[4, 8],
        vocab_size=64, sliding_window_size=8, attention_impl="einsum")
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


def test_the_stack_is_the_published_pattern_and_every_block_has_experts(
        seeded):
    cfg, model, params, aux, batch = seeded
    assert model.cfg.mixers == ("full", "sliding_rope", "sliding_rope",
                                "sliding_rope")
    assert (model.cfg.head_width, model.cfg.heads * model.cfg.head_width,
            model.cfg.hidden, model.cfg.heads // model.cfg.kv_heads) == (
        8, 112, 48, 7)
    assert not (model.cfg.use_rope or model.cfg.positions)
    assert (model.cfg.moe.shared, model.cfg.moe.first_dense) == (0, 0)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), batch[0])
    for ours, theirs in ((params["params"], shapes["params"]),
                         (aux["moe_state"], shapes["moe_state"])):
        assert jax.tree.map(lambda x: x.shape, ours) == jax.tree.map(
            lambda x: x.shape, theirs)
    blocks = shapes["params"]["backbone"]
    assert "pos_embed" not in blocks
    for i in range(4):      # first_dense 0: no dense FFN; shared 0
        assert set(blocks[f"block_{i}"]) == {"ln1", "ln2", "attn", "moe"}
        assert set(blocks[f"block_{i}"]["moe"]) == {
            "router", "w_gate", "w_up", "w_down"}
    assert blocks["block_0"]["attn"]["qkv"]["kernel"].shape == (48, 18, 8)
    assert blocks["block_0"]["attn"]["proj"]["kernel"].shape == (14, 8, 48)


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
    """Score rows 4 at a time (a window layer's block then sees 11 keys
    of the 20) and logits 5 positions at a time, against one block of
    each: the same loss and gradients."""
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


def test_step_counts_the_tokens_each_expert_drew(seeded):
    cfg, model, params, aux, batch = seeded
    layers = program_loss(model, params, aux, batch)[1]["moe_state"][
        "backbone"]
    assert set(layers) == {f"block_{i}" for i in range(4)}
    for layer in layers.values():
        assert float(layer["moe"]["expert_tokens"].sum()) == 2 * SEQ * 6


# ---- one attention layer of each kind --------------------------------------

@pytest.mark.parametrize("layer", [0, 1], ids=["full", "sliding_rope"])
@pytest.mark.parametrize("impl", ["einsum", "flash"])
def test_each_kind_of_layer_matches_the_references_own_mask(seeded, layer,
                                                            impl):
    """Layer 0 sees every key before the query and has no positions,
    layer 1 sees 8 keys and rotates q and k: the program's ``Attention``
    of that kind against the reference's attention of that layer, and
    the reference's mask against the statement of it."""
    cfg, model, params, _, _ = seeded
    kind = reference.kinds(cfg)[layer]
    assert transformer.PLAIN[kind] == (bool(layer), bool(layer))
    attn = params["params"]["backbone"]["block_0"]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 48))
    module = transformer.Attention(
        dataclasses.replace(model.cfg, attention_impl=impl), kind=kind)
    with jax.default_matmul_precision("highest"):
        got = module.apply({"params": attn}, h)
    want = reference.attention(h, attn, cfg, layer)
    assert worst(got, want) < 1e-5
    # Moving position 0's input moves position 12 in the full layer and
    # not under a window of 8; a layer without positions does not tell
    # a sequence from the same one moved along.
    moved = reference.attention(h.at[:, 0].add(1.0), attn, cfg, layer)
    assert bool(jnp.any(moved[:, 12] != want[:, 12])) == (layer == 0)
    keep = np.asarray(reference.keep_mask(
        jnp.arange(SEQ), jnp.arange(SEQ),
        cfg["sliding_window_size"] if layer else None))
    for i in range(SEQ):
        for j in range(SEQ):
            assert keep[i, j] == (j <= i and (layer == 0 or i - j < 8))


def test_a_layer_without_rope_has_no_positions_and_one_with_it_has(seeded):
    cfg, model, params, _, _ = seeded
    attn = params["params"]["backbone"]["block_0"]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(6), (1, SEQ, 48))
    # The same keys in another order before the last query: without
    # positions its output is the same set's average and does not move.
    swapped = h.at[:, 3].set(h[:, 5]).at[:, 5].set(h[:, 3])
    for kind, same in (("full", True), ("full_rope", False)):
        module = transformer.Attention(model.cfg, kind=kind)
        a = module.apply({"params": attn}, h)[:, -1]
        b = module.apply({"params": attn}, swapped)[:, -1]
        assert bool(worst(a, b) < 1e-5) == same, kind


# ---- the router -------------------------------------------------------------

def test_the_router_reads_what_attention_read(seeded):
    """Moving ``ln2``'s gain leaves block 0's chosen set as it was;
    moving ``ln1``'s does not."""
    cfg, model, params, aux, batch = seeded

    def drawn(name):
        block = dict(params["params"]["backbone"]["block_0"])
        gain = 1.0 + jnp.arange(48.0) / 6.0
        block[name] = {"scale": block[name]["scale"] * gain}
        moved = {"params": {**params["params"], "backbone": {
            **params["params"]["backbone"], "block_0": block}}}
        new = program_loss(model, moved, aux, batch)[1]
        return np.asarray(new["moe_state"]["backbone"]["block_0"]["moe"][
            "expert_tokens"])

    before = np.asarray(program_loss(model, params, aux, batch)[1][
        "moe_state"]["backbone"]["block_0"]["moe"]["expert_tokens"])
    np.testing.assert_array_equal(drawn("ln2"), before)
    assert not np.array_equal(drawn("ln1"), before)
    # And a router that reads the experts' input does the opposite.
    late = make_model(cfg, moe=dataclasses.replace(
        model.cfg.moe, router_reads="ffn"))
    logits = late.apply({**params, **aux}, batch[0], mutable=list(aux))[0]
    ours = model.apply({**params, **aux}, batch[0], mutable=list(aux))[0]
    assert worst(logits, ours) > 1e-3


def test_softmax_over_the_chosen_is_softmax_over_all_renormalised():
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 16))
    router = jax.random.normal(jax.random.PRNGKey(4), (16, 64)) / 4.0
    chosen, weights, drawn = moe.route(x, router, jnp.zeros((64,)), k=6,
                                       scale=1.0, scoring="softmax")
    logits = x @ router
    np.testing.assert_array_equal(chosen, jax.lax.top_k(logits, 6)[1])
    over_all = jnp.take_along_axis(jax.nn.softmax(logits, -1), chosen, -1)
    np.testing.assert_allclose(
        weights, over_all / over_all.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    assert float(drawn.sum()) == 32 * 6
    # The reference's weights, 0 for the experts not chosen.
    theirs = reference.route(logits, {"moe_num_active_primary_experts": 6})
    np.testing.assert_allclose(
        jnp.take_along_axis(theirs, chosen, -1), weights, rtol=1e-5)
    assert float((theirs > 0).sum()) == 32 * 6
    # The sigmoid form is as it was.
    _, sigmoid, _ = moe.route(x, router, jnp.zeros((64,)), k=6, scale=1.8)
    picked = jnp.take_along_axis(jax.nn.sigmoid(logits), chosen, -1)
    np.testing.assert_allclose(
        sigmoid, 1.8 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)


def test_unknown_scoring_or_gate_is_refused():
    for bad in (dict(scoring="tanh"), dict(gate="gelu"),
                dict(router_reads="embedding")):
        layer = moe.MoELayer(moe.MoEConfig(experts=4, per_token=2, width=8,
                                           shared=0, **bad))
        with pytest.raises(ValueError, match="MoEConfig"):
            layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


# ---- the expert layer alone -------------------------------------------------

EXPERTS, PER_TOKEN, D, F = 16, 6, 16, 24


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


LAYER_CFG = {"moe_num_active_primary_experts": PER_TOKEN,
             "experts_held": [0, EXPERTS]}


def share_apply(x, share, first, scores_from=None):
    return moe.moe_apply(x, share, jnp.zeros((EXPERTS,)), k=PER_TOKEN,
                         first_held=first, scoring="softmax", gate="relu",
                         scores_from=scores_from)


def test_the_gate_is_a_relu():
    p = layer_params(jax.random.PRNGKey(1), held=(0, 1))
    x = jax.random.normal(jax.random.PRNGKey(2), (8, D))
    got = moe.swiglu(x, p["w_gate"][0], p["w_up"][0], p["w_down"][0],
                     gate="relu")
    want = (jnp.maximum(x @ p["w_gate"][0], 0) * (x @ p["w_up"][0])
            ) @ p["w_down"][0]
    np.testing.assert_allclose(got, want, atol=1e-6)
    silu = moe.swiglu(x, p["w_gate"][0], p["w_up"][0], p["w_down"][0])
    assert worst(silu, want) > 1e-2


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """The 4 shares of a 16-expert layer (4 experts each; no shared
    expert, so nothing is counted once) sum to what the uncut reference
    gives for the whole layer, routed by another tensor than the
    experts': value and the gradients with respect to both."""
    key = jax.random.PRNGKey(7)
    u = jax.random.normal(jax.random.PRNGKey(8), (40, D))
    h = jax.random.normal(jax.random.PRNGKey(9), (40, D))
    whole = layer_params(key)

    def uncut(u, h):
        return reference.expert_ffn(u[None], h[None], whole, LAYER_CFG)[0]

    def shares(u, h):
        return sum(share_apply(u, layer_params(key, (first, first + 4)),
                               first, h)[0] for first in (0, 4, 8, 12))

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(shares(u, h), uncut(u, h), atol=2e-5,
                                   rtol=2e-5)
        weights = jnp.cos(jnp.arange(40.0 * D)).reshape(40, D)
        got = jax.grad(lambda u, h: jnp.sum(shares(u, h) * weights),
                       argnums=(0, 1))(u, h)
        want = jax.grad(lambda u, h: jnp.sum(uncut(u, h) * weights),
                        argnums=(0, 1))(u, h)
    for a, b in zip(got, want):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("path", ["_sized", "_routed"])
def test_sized_path_and_fallback_at_a_quarter_held_and_six_a_token(
        poison, path):
    """4 of 16 experts held, 6 a token, 512 tokens: 3072 pairs, 1536
    rows. A seeded draw (about 768 pairs) fits and runs on the sized
    buffers; a router tilted to the held experts draws 2048 and takes
    the fallback. Both against the reference's dense layer, with the
    gradients of tokens, router input and weights."""
    assert moe.sized_rows(98304, 16, 64) == 49152
    assert moe.sized_rows(512 * PER_TOKEN, 4, EXPERTS) == 1536
    first = 4
    share = layer_params(jax.random.PRNGKey(11), (first, first + 4))
    if path == "_routed":
        share["router"] = share["router"].at[:, first:first + 4].add(5.0)
    u = jax.random.normal(jax.random.PRNGKey(12), (512, D))
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(13), (512, D)))
    cfg = dict(LAYER_CFG, experts_held=[first, first + 4])
    poison({"_sized": "_routed", "_routed": "_sized"}[path])
    y, drawn = share_apply(u, share, first, h)
    assert moe.took_sized_path(np.asarray(drawn), first, first + 4) == (
        path == "_sized")
    assert (float(drawn[first:first + 4].sum()) == 2048) == (
        path == "_routed")

    def ours(u, h, p):
        return jnp.sum(share_apply(u, p, first, h)[0] ** 2)

    def theirs(u, h, p):
        return jnp.sum(reference.expert_ffn(u[None], h[None], p, cfg) ** 2)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            y, reference.expert_ffn(u[None], h[None], share, cfg)[0],
            atol=2e-5, rtol=2e-4)
        got = jax.grad(ours, argnums=(0, 1, 2))(u, h, share)
        want = jax.grad(theirs, argnums=(0, 1, 2))(u, h, share)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-3)


# ---- spans and counters -----------------------------------------------------

def test_compiled_step_names_each_kind_of_layer_and_the_routers_place():
    """The scopes this model adds (docs/tracing.md), forward and
    backward, as the benchmark's readers look for them: the full layer's
    attention under ``hvd_attn_full``, the window layers' under
    ``hvd_attn_window``, and the router's product, which reads what
    attention read, under ``hvd_moe/route`` in float32 at ``highest``."""
    import re

    import horovod_tpu.jax as hvd_jax
    from jax.sharding import Mesh

    from benchmark import scope_reduce, scope_sum
    cfg = small_cfg(num_hidden_layers=2)
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
    for scopes in (("block_0", "hvd_attn_full"),
                   ("block_1", "hvd_attn_window"),
                   ("block_1", "attn", "rope"),
                   ("block_0", "moe", "hvd_moe", "route"),
                   ("block_1", "hvd_moe", "experts")):
        assert [p for p in parts if scope_sum._within(scopes, p)], scopes
    assert not [p for p in parts
                if scope_sum._within(("block_0", "attn", "rope"), p)]
    assert not [p for p in parts
                if scope_sum._within(("block_0", "hvd_attn_window"), p)]
    for scope in ("hvd_attn_full", "hvd_attn_window", "hvd_moe"):
        assert any(scope in n and "transpose(" in n for n in names), scope
        assert any(scope in n and "transpose(" not in n for n in names)
    # The router's product: [tokens, 48] x [48, 16] in float32 at
    # ``highest``, under the expert layer's route scope.
    text = lowered.as_text(debug_info=True)
    where = dict(re.findall(r'(#loc\d+) = loc\("([^"]+)"', text))
    router = [line for line in text.splitlines()
              if "(tensor<20x48xf32>, tensor<48x16xf32>)" in line]
    assert len(router) == 2     # one a layer
    for line in router:
        assert "precision = [HIGHEST, HIGHEST]" in line
        assert where[re.search(r"loc\((#loc\d+)\)", line).group(1)].endswith(
            "/moe/hvd_moe/route/dot_general")


def test_the_models_four_layers_reach_the_telemetry_plane(seeded,
                                                          telemetry_plane):
    from horovod_tpu.ops import flash_attention
    telemetry = telemetry_plane
    cfg, model, params, aux, batch = seeded
    new_aux = program_loss(model, params, aux, batch)[1]
    moe.publish_expert_tokens(new_aux, held=tuple(cfg["experts_held"]))
    families = telemetry.snapshot()["families"]
    layers = {s["labels"]["layer"]
              for s in families["hvd_moe_expert_tokens"]["samples"]}
    assert layers == {f"moe_state/backbone/block_{i}/moe" for i in range(4)}
    assert len(families["hvd_moe_expert_tokens"]["samples"]) == 4 * 16
    # 240 pairs a layer, 4 of 16 held: 512 rows would hold the expected
    # draw twice over, so the buffers are a row for every pair.
    assert {s["value"] for s in
            families["hvd_moe_buffer_rows"]["samples"]} == {240.0}
    assert 0 < families["hvd_moe_held_share"]["samples"][0]["value"] < 1
    # A call at the cell's shape publishes the window's own kind, at the
    # backward's strips too.
    flash_attention._publish_subtiles(16384, 16384, 1024, 1024, True, 0, 0,
                                      16384, 128, 4096)
    families = telemetry.snapshot()["families"]
    for name in ("hvd_flash_fwd_subtiles", "hvd_flash_bwd_subtiles"):
        kinds = {s["labels"]["kind"]: s["value"]
                 for s in families[name]["samples"]}
        assert kinds["window"] > kinds["interior"] > 0, name
    want = flash_attention.subtile_counts(
        "fwd", 16384, 16384, 1024, 1024, True, head_dim=128, window=4096)
    got = {s["labels"]["kind"]: s["value"] for s in
           families["hvd_flash_fwd_subtiles"]["samples"]}
    assert got == {k: float(v) for k, v in want.items()}
