"""Keye-VL-2.0-30B-A3B's mechanisms at a small size on the CPU, seeded:
the program's model (sparse-attention layers: 4 query heads of 16 over 2
K/V heads with a norm on q and k, rope over three position streams on a
row of text, a 4 x 4 image and text, an indexer of 2 heads of 8 that
picks 8 keys a query on rows of 48; a softmax router over 8 experts of
which 2 are held; an untied head) against the plain reference of
``benchmark/references/keye_vl2.py`` for the three losses, every
gradient leaf and three AdamW steps; the selected set against the
reference's on every query, ties included; the flash kernels under a
mask against the einsum form; rope over text-only streams against
``_rope``; the alignment pass against autodiff; the shares of a layer
adding up to the uncut layer. (On the chip the comparison is the
benchmark's ``correct``, at the published widths.)
"""

import dataclasses
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.builders import keye_vl2 as builder  # noqa: E402
from benchmark.references import common  # noqa: E402
from benchmark.references import keye_vl2 as reference  # noqa: E402
from horovod_tpu.models import TransformerLM  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.ops import flash_attention as fa  # noqa: E402
from horovod_tpu.ops import sparse_attention as dsa  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402
from moe_fixtures import telemetry_plane  # noqa: E402, F401 (a fixture)

SEQ = 48
TOPK = 8
LAYOUT = [["text", 16], ["image", 4, 4], ["text", 16]]


def small_cfg(**overrides):
    """The configuration file's keys at a small size: hidden 64, 4
    heads of 16 in groups of 2 over 2 K/V heads, an indexer of 2 heads
    of 8 that picks 8 keys, 8 experts 24 wide top-2 of which 2 are
    held, vocabulary 64, two layers, a row of 16 text tokens, a 4 x 4
    image and 16 text tokens."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "keyevl30b.json")) as f:
        cfg = json.load(f)
    cfg.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_intermediate_size=24, num_experts_published=8,
        num_experts_per_tok=2, experts_held=[2, 4], vocab_size=64,
        num_hidden_layers=2, rope_scaling={"mrope_section": [2, 3, 3]},
        sa_config=dict(cfg["sa_config"], indexer_head_dim=8,
                       indexer_num_heads=2, topk=TOPK),
        row_layout=LAYOUT, embedding_fan_in=1)
    cfg.update(overrides)
    return cfg


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks a row of 48 is several of, so that every pass crosses a
    block's and a group's edge."""
    monkeypatch.setattr(dsa, "SELECT_BLOCK", 16)
    monkeypatch.setattr(dsa, "ALIGN_TILE_Q", 16)
    monkeypatch.setattr(dsa, "ALIGN_TILE_K", 8)
    monkeypatch.setattr(dsa, "FLASH_BLOCK", 32)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)


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


def program_losses(model, params, aux, batch, weight=1.0):
    """(total, (CE, sum of L_I, the new non-trained state))."""
    logits, new_aux = model.apply({**params, **aux}, batch[0],
                                  mutable=list(aux))
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits, batch[1]).mean()
    align = transformer.dsa_align_loss(new_aux)
    return ce + weight * align, (ce, align, new_aux)


def is_indexer(name):
    return "['indexer']" in name


def test_the_stack_is_sparse_layers_with_an_indexer_and_experts(seeded):
    cfg, model, params, aux, batch = seeded
    assert model.cfg.mixers == ("sparse_rope",) * 2
    assert (model.cfg.head_width, model.cfg.heads // model.cfg.kv_heads,
            model.cfg.indexer, model.cfg.rope_sections) == (
        16, 2, transformer.IndexerConfig(2, 8, TOPK), (2, 3, 3))
    assert model.cfg.qk_norm and not model.cfg.tie_embeddings
    assert not (model.cfg.use_rope or model.cfg.positions or model.cfg.bias)
    assert (model.cfg.moe.shared, model.cfg.moe.first_dense,
            model.cfg.moe.scoring, model.cfg.moe.gate,
            model.cfg.moe.router_reads) == (0, 0, "softmax", "silu", "ffn")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), batch[0])
    for collection in ("params", "moe_state", "dsa_state"):
        assert jax.tree.map(lambda x: x.shape, dict(
            {**params, **aux}[collection])) == jax.tree.map(
                lambda x: x.shape, dict(shapes[collection])), collection
    attn = shapes["params"]["backbone"]["block_1"]["attn"]
    assert set(attn) == {"qkv", "q_norm", "k_norm", "proj", "indexer"}
    assert jax.tree.map(lambda x: x.shape, dict(attn["indexer"])) == {
        "q": {"kernel": (64, 2, 8)}, "k": {"kernel": (64, 8)},
        "k_norm": {"scale": (8,), "bias": (8,)}, "w": {"kernel": (64, 2)}}


@pytest.fixture(scope="module")
def losses(seeded):
    cfg, model, params, aux, batch = seeded
    with jax.default_matmul_precision("highest"):
        total, (ce, align, state) = jax.jit(
            lambda p: program_losses(model, p, aux, batch))(params)
    their_ce, aligns, counts = jax.jit(
        lambda p: reference.losses_fn(p, batch, cfg))(params)
    return ({"language": ce, "alignment": align, "total": total},
            {"language": their_ce, "alignment": sum(aligns),
             "total": their_ce + cfg["align_loss_weight"] * sum(aligns)},
            counts, state)


@pytest.mark.parametrize("which", ["language", "alignment", "total"])
def test_each_loss_matches_reference(losses, which):
    got, want, counts, _ = losses
    assert float(want[which]) > 0.01
    assert float(got[which]) == pytest.approx(float(want[which]), rel=1e-5)
    # Every query selected min(t + 1, topk) keys.
    for count in counts:
        assert float(count) == pytest.approx(
            dsa.selected_pairs(SEQ, TOPK) / SEQ)


@pytest.fixture(scope="module")
def grad_fns(seeded):
    """The program's gradient (of parameters and the alignment loss's
    weight) and the reference's, each traced once."""
    cfg, model, params, aux, batch = seeded
    return (jax.jit(jax.grad(lambda p, weight: program_losses(
        model, p, aux, batch, weight)[0])),
        jax.jit(jax.grad(
            lambda p: reference.loss_fn(p, aux, batch, cfg)[0])))


@pytest.fixture(scope="module")
def gradients(seeded, grad_fns):
    """Every leaf's first gradient: the program's under the whole loss
    and under the language model's alone, the reference's under the
    whole loss; by leaf name."""
    params = seeded[2]
    program, theirs = grad_fns
    with jax.default_matmul_precision("highest"):
        whole, language = program(params, 1.0), program(params, 0.0)
    theirs = theirs(params)
    return {name: leaves for name, *leaves in zip(
        common.leaf_names(params),
        *map(jax.tree.leaves, (whole, language, theirs)))}


LEAVES = {"indexer": "['indexer']", "attention": "['attn']",
          "experts": "['moe']", "norms": "['ln",
          "embedding": "['tok_embed']", "head": "['lm_head']"}


@pytest.mark.parametrize("group", sorted(LEAVES))
def test_every_gradient_leaf_matches_reference(gradients, group):
    """And the indexer's leaves get theirs from the alignment loss
    alone, while nothing else's moves with its weight."""
    mine = {name: g for name, g in gradients.items()
            if LEAVES[group] in name
            and (group == "indexer" or not is_indexer(name))}
    assert mine
    for name, (whole, language, theirs) in mine.items():
        assert float(jnp.max(jnp.abs(whole))) > 0, name
        assert worst(whole, theirs) < 2e-4, name
        if group == "indexer":
            assert float(jnp.max(jnp.abs(language))) == 0, name
        else:
            np.testing.assert_array_equal(
                np.asarray(whole), np.asarray(language), err_msg=name)
    assert sum(len(m) for m in (
        [n for n in gradients if LEAVES[g] in n] for g in LEAVES)) >= len(
            gradients)      # every leaf is in some group


def test_three_adamw_steps_match_reference(seeded, grad_fns):
    cfg, model, params, aux, batch = seeded
    opt = dict(cfg["optimizer"], learning_rate=1e-3)
    tx = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                     eps=opt["eps"], weight_decay=opt["weight_decay"])
    ours, state = params, tx.init(params)
    theirs, their_state = params, common.adamw_init(params)
    grad, ref_grad = grad_fns
    for _ in range(3):
        with jax.default_matmul_precision("highest"):
            updates, state = tx.update(grad(ours, 1.0), state, ours)
        ours = optax.apply_updates(ours, updates)
        theirs, their_state = common.adamw_update(
            theirs, their_state, ref_grad(theirs), opt)
    for name, a, b, start in zip(common.leaf_names(params),
                                 *map(jax.tree.leaves,
                                      (ours, theirs, params))):
        a, b = jnp.linalg.norm(a - start), jnp.linalg.norm(b - start)
        assert float(b) > 0 and float(abs(a - b) / b) < 1e-3, name


def test_the_references_blocks_change_no_number(seeded, monkeypatch):
    cfg, model, params, aux, batch = seeded
    def loss():
        return jax.jit(lambda p: reference.loss_fn(p, aux, batch, cfg)[0])(
            params)

    blocked = loss()
    monkeypatch.setattr(reference, "QUERY_BLOCK", SEQ)
    monkeypatch.setattr(reference, "LOGIT_BLOCK", 12)
    whole = loss()
    assert float(blocked) == pytest.approx(float(whole), rel=1e-6)


# ---- positions ----------------------------------------------------------

def test_a_rows_positions_follow_its_text_and_its_image():
    cfg = small_cfg()
    table = transformer.mrope_positions(reference.layout(cfg))
    np.testing.assert_array_equal(table, reference.positions(cfg))
    assert table.shape == (SEQ, 3)
    np.testing.assert_array_equal(table[:16], np.arange(16)[:, None]
                                  * np.ones((1, 3), int))
    # The image starts at 16: row r, column c at (16, 16 + r, 16 + c).
    assert table[16 + 4 * 2 + 3].tolist() == [16, 18, 19]
    # The text after it resumes at 16 + max(4, 4).
    assert table[32].tolist() == [20, 20, 20]
    with pytest.raises(ValueError, match="text or image"):
        transformer.mrope_positions((("audio", 3),))
    # The cell's row: 4 x (3072 text + 32 x 32 image), 16,384 tokens, a
    # quarter of them image tokens; text resumes 32 after an image.
    with open(os.path.join(REPO, "benchmark", "configs",
                           "keyevl30b.json")) as f:
        whole = reference.positions(json.load(f))
    assert whole.shape == (16384, 3)
    assert whole[3072 + 32 * 5 + 7].tolist() == [3072, 3077, 3079]
    assert whole[4096].tolist() == [3104] * 3
    assert int((whole[:, 0] != whole[:, 1]).sum()
               + (whole[:, 0] == whole[:, 1]).sum()) == 16384


@pytest.mark.parametrize("theta", [1e4, 1e7])
def test_rope_over_text_only_streams_is_bit_equal_to_plain_rope(theta):
    q = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 2, 16))
    plain = transformer._rope(q, k, theta)
    text = transformer.mrope_positions((("text", 24),))
    for got in (transformer._rope(q, k, theta, text, (2, 3, 3)),
                transformer._rope(q, k, theta, text),
                transformer._rope(q, k, theta, np.arange(24))):
        for a, b in zip(got, plain):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_an_images_tokens_turn_by_their_row_and_column(seeded):
    cfg = seeded[0]
    q = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, SEQ, 2, 16))
    table = transformer.mrope_positions(reference.layout(cfg))
    got = transformer._rope(q, k, 1e4, table, (2, 3, 3))
    np.testing.assert_allclose(
        got[0], reference.mrope(q, dict(cfg, rope_theta=1e4)), atol=1e-6)
    plain = transformer._rope(q, k, 1e4)
    # The leading text is plain rope; an image token is not.
    np.testing.assert_array_equal(np.asarray(got[0][:, :16]),
                                  np.asarray(plain[0][:, :16]))
    assert worst(got[0][:, 20], plain[0][:, 20]) > 0.01
    with pytest.raises(ValueError, match="sections"):
        transformer._rope(q, k, 1e4, table, (2, 3, 4))


# ---- selection ----------------------------------------------------------

def reference_set(scores, topk):
    """[queries, keys] by the reference's ``lax.top_k``."""
    return np.asarray(reference.select(
        scores[None], jnp.arange(scores.shape[0]), topk)[0])


@pytest.mark.parametrize("case", ["random", "tied", "all_equal", "signed"])
def test_the_selected_set_is_the_references_on_every_query(case):
    seq = 64
    scores = jax.random.normal(jax.random.PRNGKey(5), (seq, seq))
    if case == "tied":
        # A few levels only: nearly every row ties at its 8th largest.
        scores = jnp.round(scores * 1.5)
    elif case == "all_equal":
        scores = jnp.zeros((seq, seq))
    elif case == "signed":
        scores = scores.at[:, ::3].set(-0.0).at[:, 1::3].set(0.0) * 1e-30
    want = reference_set(scores, TOPK)
    got = np.concatenate([
        np.asarray(dsa.select_t(scores[first:first + 16].T, first, TOPK))
        for first in range(0, seq, 16)], axis=1).T.astype(bool)
    if case != "signed":     # -0.0 sorts below 0.0 here, beside it there
        np.testing.assert_array_equal(got, want)
    rows = np.arange(seq)
    np.testing.assert_array_equal(got.sum(1), np.minimum(rows + 1, TOPK))
    assert not got[rows[:, None] < rows[None, :]].any()      # causal
    # Whole for t < topk.
    for t in range(TOPK):
        assert got[t, :t + 1].all()
    if case in ("tied", "all_equal"):
        # Ties went to the smaller key: in a row of equal scores, the
        # first keys.
        row = got[40]
        level = np.asarray(scores)[40, :41]
        lowest = level[row[:41]].min()
        at = np.flatnonzero(level == lowest)
        taken = row[at]
        assert taken[:taken.sum()].all()


def test_the_models_selected_sets_are_the_references(seeded):
    cfg, model, params, aux, batch = seeded
    p = params["params"]["backbone"]["block_0"]
    x = params["params"]["backbone"]["tok_embed"]["embedding"][batch[0]]
    h = reference._rms_norm(x, p["ln1"], cfg["rms_norm_eps"])
    q_i, k_i, w = reference.indexer(h, p["attn"]["indexer"], cfg)
    scores = reference.index_scores(q_i, k_i, w)
    select = jax.jit(lambda *a: dsa.index_select(*a, TOPK))
    for b in range(2):
        got = np.asarray(select(q_i[b], k_i[b], w[b])[0]).T
        np.testing.assert_array_equal(got.astype(bool),
                                      reference_set(scores[b], TOPK))
    # The module computes what the reference's indexer computes.
    module = transformer.Indexer(model.cfg)
    with jax.default_matmul_precision("highest"):
        mine = jax.jit(lambda h: module.apply(
            {"params": p["attn"]["indexer"]}, h,
            transformer.mrope_positions(reference.layout(cfg))))(h)
    for a, b in zip(mine, (q_i, k_i, w)):
        assert worst(a, b) < 1e-5


def test_counts_from_shapes():
    assert dsa.selected_pairs(16384, 2048) == 31_458_304
    assert dsa.selected_pairs(SEQ, TOPK) == 36 + 40 * 8
    assert dsa.selected_pairs(5, 8) == 15
    assert round(dsa.kept_share(16384, 2048), 4) == 0.2344
    assert dsa._extents(16384, 512) == [
        (0, 4096, 4096), (4096, 8192, 8192), (8192, 12288, 12288),
        (12288, 16384, 16384)]
    assert dsa._extents(48, 16) == [(0, 16, 16), (16, 32, 32), (32, 48, 48)]
    assert dsa._extents(48, 48) == [(0, 48, 48)]
    with pytest.raises(ValueError, match="whole blocks"):
        dsa._block_size(50, 16)


# ---- the kernels under a mask -------------------------------------------

def masked_case(case):
    b, h, g, s, d = 1, 4, 2, 64, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (b, h, s, d))
    k = jax.random.normal(keys[1], (b, g, s, d))
    v = jax.random.normal(keys[2], (b, g, s, d))
    at = jnp.arange(s)
    causal = at[:, None] <= at[None, :]               # [keys, queries]
    if case == "random":
        keep = jax.random.bernoulli(keys[3], 0.4, (b, s, s)) | jnp.eye(
            s, dtype=bool)
    elif case == "late_keys":
        # A query sees nothing of the first blocks of keys: its row of
        # running maxima stays empty through whole tiles.
        keep = jnp.broadcast_to((at[None, :] - at[:, None] < 20)
                                | (at[None, :] < 4), (b, s, s))
    else:
        keep = jnp.ones((b, s, s), bool)
    return q, k, v, (keep & causal).astype(jnp.int8)


@pytest.mark.parametrize("blocks", [(16, 16), (64, 64)])
@pytest.mark.parametrize("case", ["random", "late_keys", "all"])
def test_flash_kernels_under_a_mask_match_the_einsum_form(case, blocks):
    q, k, v, mask = masked_case(case)
    weigh = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)

    def run(fn, **kw):
        def loss(q, k, v):
            out, lse = fn(q, k, v, causal=True, mask=mask, with_lse=True,
                          **kw)
            return jnp.sum(out * weigh) + 0.3 * jnp.sum(lse), (out, lse)
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    (_, (out, lse)), grads = run(fa.flash_attention, block_q=blocks[0],
                                 block_k=blocks[1])
    (_, (want, want_lse)), want_grads = run(fa.reference_attention)
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, atol=2e-4)
    if case == "all":
        # A mask that keeps every causal pair is the call without one.
        plain = fa.flash_attention(q, k, v, causal=True, block_q=blocks[0],
                                   block_k=blocks[1])
        np.testing.assert_allclose(out, plain, atol=1e-6)


def test_a_mask_goes_with_neither_dropout_nor_a_window_nor_another_shape():
    q, k, v, mask = masked_case("all")
    with pytest.raises(NotImplementedError, match="mask"):
        fa.flash_attention(q, k, v, causal=True, mask=mask, window=8)
    with pytest.raises(NotImplementedError, match="mask"):
        fa.flash_attention(q, k, v, causal=True, mask=mask[:, :8])
    with pytest.raises(NotImplementedError, match="mask"):
        fa.flash_attention(q, k, v, causal=True, mask=mask,
                           dropout_mask=jnp.ones((1, 4, 64, 64)),
                           dropout_rate=0.1)


def test_without_a_mask_the_kernels_trace_what_they_did():
    """``mask=None`` is the call every other model makes: the same two
    kernels on the same grids, no operand more."""
    q, k, v, mask = masked_case("all")

    def calls(**kw):
        jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(fa.flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16, **kw))))(q)
        found = {}

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    found[eqn.params["name"]] = (
                        tuple(eqn.params["grid_mapping"].grid),
                        len(eqn.invars))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        return found

    plain, masked = calls(), calls(mask=mask)
    assert set(plain) == set(masked) == {fa.KERNEL_FWD, fa.KERNEL_BWD_DKDV}
    for name in plain:
        assert plain[name][0] == masked[name][0] == (4, 10)  # live tiles
        assert masked[name][1] == plain[name][1] + 1         # the mask


# ---- the alignment pass -------------------------------------------------

def dense_sparse_attention(q, k, v, q_i, k_i, w, topk):
    """One row, every pair at once: (out, L_I)."""
    seq, heads, d = q.shape
    scores = jnp.einsum("tj,tjs->ts", w, jax.nn.relu(
        jnp.einsum("tjd,sd->tjs", q_i, k_i)))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    _, chosen = lax.top_k(lax.stop_gradient(
        jnp.where(causal, scores, -jnp.inf)), min(topk, seq))
    taken = jnp.zeros((seq, seq), bool).at[
        jnp.arange(seq)[:, None], chosen].set(True) & causal
    group = heads // k.shape[1]
    logits = jnp.einsum("thd,shd->hts", q, jnp.repeat(k, group, 1)) / np.sqrt(
        d)
    probs = jax.nn.softmax(jnp.where(taken, logits, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, jnp.repeat(v, group, 1))
    p = lax.stop_gradient(probs.mean(0))
    log_pi = jax.nn.log_softmax(jnp.where(taken, scores, -jnp.inf), axis=-1)
    kl = jnp.where(taken & (p > 0), p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                         - jnp.where(taken, log_pi, 0.0)), 0.0)
    return out, kl.sum(-1).mean()


def test_sparse_attention_and_its_alignment_pass_match_autodiff():
    b, seq, heads, groups, d, j, di = 2, 64, 4, 2, 16, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    q = jax.random.normal(keys[0], (b, seq, heads, d))
    k = jax.random.normal(keys[1], (b, seq, groups, d))
    v = jax.random.normal(keys[2], (b, seq, groups, d))
    q_i = jax.random.normal(keys[3], (b, seq, j, di))
    k_i = jax.random.normal(keys[4], (b, seq, di))
    w = 0.3 * jax.random.normal(keys[5], (b, seq, j))

    def ours(*args):
        out, align, count = dsa.sparse_attention(*args, TOPK)
        return jnp.sum(jnp.sin(out)) + 2.0 * align, (out, align, count)

    def dense(*args):
        rows = [dense_sparse_attention(*(x[i] for x in args), TOPK)
                for i in range(b)]
        out = jnp.stack([r[0] for r in rows])
        align = sum(r[1] for r in rows) / b
        return jnp.sum(jnp.sin(out)) + 2.0 * align, (out, align)

    args = (q, k, v, q_i, k_i, w)
    (_, (out, align, count)), grads = jax.jit(jax.value_and_grad(
        ours, argnums=tuple(range(6)), has_aux=True))(*args)
    (_, (want, want_align)), want_grads = jax.jit(jax.value_and_grad(
        dense, argnums=tuple(range(6)), has_aux=True))(*args)
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert float(align) == pytest.approx(float(want_align), rel=1e-5)
    assert float(count) == dsa.selected_pairs(seq, TOPK) / seq
    for name, a, c in zip("q k v q_i k_i w".split(), grads, want_grads):
        assert float(jnp.abs(c).max()) > 0, name
        np.testing.assert_allclose(a, c, atol=2e-5, err_msg=name)
    # Without the alignment pass: the same output, no loss.
    out2, none, _ = jax.jit(lambda *a: dsa.sparse_attention(
        *a, TOPK, with_align=False))(*args)
    assert none is None
    np.testing.assert_allclose(out2, out, atol=1e-6)


ALIGN_CASES = {
    # Rows of 64 in tiles of 16 queries by 8 keys: four query blocks,
    # each with the tiles the diagonal crosses.
    "several_blocks": {},
    "kv_groups_of_1": {"heads": 4, "groups": 4},
    "kv_groups_of_8": {"heads": 8, "groups": 1},
    "row_shorter_than_topk": {"seq": 8, "topk": 16},
    "tied_index_scores": {"levels": 2.0},
    "p_underflows_on_a_kept_key": {"sharp": 300.0},
    "without_grads": {},
    "two_rows": {"rows": 2},
}


def align_inputs(rows=1, seq=64, heads=4, groups=2, topk=TOPK, levels=None,
                 sharp=None):
    d, j, di = 16, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    q = jax.random.normal(keys[0], (rows, seq, heads, d))
    k = jax.random.normal(keys[1], (rows, seq, groups, d))
    v = jax.random.normal(keys[2], (rows, seq, groups, d))
    q_i = jax.random.normal(keys[3], (rows, seq, j, di))
    k_i = jax.random.normal(keys[4], (rows, seq, di))
    w = 0.3 * jax.random.normal(keys[5], (rows, seq, j))
    if levels:
        # A few levels an entry: products, and so scores, that tie.
        q_i, k_i = jnp.round(q_i * levels) / levels, jnp.round(k_i)
        w = jnp.round(w * 4) / 4
    if sharp:
        # Every head of query 40 sees one key and underflows on the
        # rest, the kept ones too: p is 0 where the set is not.
        q = q.at[:, 40].multiply(sharp)
    return (q, k, v, q_i, k_i, w), topk


def align_operands(q, k, v, q_i, k_i, w, topk, attend=fa.flash_attention):
    """What ``sparse_attention`` hands ``align_loss`` beside the
    indexer's operands, of one row: q and k head-major, the attention's
    log-sum-exp under the selected set, the set and the log-sum-exp of
    the index scores over it."""
    mask_t, lse_i = dsa.index_select(q_i, k_i, w, topk)
    qh, kh = q.swapaxes(0, 1), k.swapaxes(0, 1)
    _, lse = attend(qh[None], kh[None], v.swapaxes(0, 1)[None], causal=True,
                    sm_scale=q.shape[-1] ** -0.5, mask=mask_t[None],
                    with_lse=True)
    return qh, kh, lse[0], mask_t, lse_i


@pytest.mark.parametrize("case", sorted(ALIGN_CASES))
def test_the_alignment_kernel_matches_autodiff_of_the_dense_loss(case):
    """``align_loss`` in Pallas's interpreter, its loss and its three
    gradients in closed form, against autodiff of
    ``dense_sparse_attention``'s KL at float32."""
    (q, k, v, q_i, k_i, w), topk = align_inputs(**ALIGN_CASES[case])
    rows = q.shape[0]
    sm_scale = q.shape[-1] ** -0.5

    def parts(b):
        return align_operands(q[b], k[b], v[b], q_i[b], k_i[b], w[b], topk)

    def ours(q_i, k_i, w):
        return sum(dsa.align_loss(*parts(b)[:4], q_i[b], k_i[b], w[b],
                                  parts(b)[4], sm_scale)
                   for b in range(rows)) / rows

    def dense(q_i, k_i, w):
        return sum(dense_sparse_attention(
            q[b], k[b], v[b], q_i[b], k_i[b], w[b], topk)[1]
            for b in range(rows)) / rows

    want, want_grads = jax.jit(jax.value_and_grad(dense, argnums=(0, 1, 2)))(
        q_i, k_i, w)
    if case == "without_grads":
        # The primal alone: the same kernel body, the gradient terms
        # left out; the same loss to the last bit.
        alone = jax.jit(ours)(q_i, k_i, w)
        assert float(alone) == pytest.approx(float(want), rel=1e-5)
        assert float(alone) == float(jax.jit(jax.value_and_grad(ours))(
            q_i, k_i, w)[0])
        return
    got, grads = jax.jit(jax.value_and_grad(ours, argnums=(0, 1, 2)))(
        q_i, k_i, w)
    assert float(want) > 1e-3
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, a, c in zip(("q_i", "k_i", "w"), grads, want_grads):
        assert a.shape == c.shape and a.dtype == c.dtype
        assert float(jnp.abs(c).max()) > 0, name
        np.testing.assert_allclose(a, c, atol=2e-6, rtol=2e-5, err_msg=name)
    if case == "p_underflows_on_a_kept_key":
        mask_t = parts(0)[3]
        logits = jnp.einsum("hd,shd->hs", q[0, 40], jnp.repeat(
            k[0], q.shape[2] // k.shape[2], 1)) * sm_scale
        p = jax.nn.softmax(jnp.where(mask_t[:, 40] != 0, logits, -jnp.inf),
                           axis=-1).mean(0)
        assert int(jnp.sum((mask_t[:, 40] != 0) & (p == 0))) > 0
    if case == "tied_index_scores":
        scores = jnp.einsum("tj,tjs->ts", w[0], jax.nn.relu(
            jnp.einsum("tjd,sd->tjs", q_i[0], k_i[0])))
        assert len(np.unique(np.asarray(scores[40, :41]))) < 30


def test_inside_shard_map_off_the_tpu_the_row_goes_through_every_pair():
    """Pallas's interpreter refuses device-varying operands, so there,
    as ``flash_attention`` does, the pass steps aside: the same
    numbers from every pair at once, and the same through a
    ``shard_map`` as outside it."""
    from jax.sharding import Mesh, PartitionSpec as P
    (q, k, v, q_i, k_i, w), topk = align_inputs(rows=2, seq=32)
    sm_scale = q.shape[-1] ** -0.5

    def operands(b, q_i=q_i, k_i=k_i, w=w):
        qh, kh, lse, mask_t, lse_i = align_operands(
            q[b], k[b], v[b], q_i[b], k_i[b], w[b], topk,
            fa.reference_attention)
        return qh, kh, lse, mask_t, q_i[b], k_i[b], w[b], lse_i

    tiles = jax.jit(lambda: dsa._by_tiles(*operands(0), sm_scale, True))()
    pairs = jax.jit(lambda: dsa._every_pair(*operands(0), sm_scale))()
    for a, b in zip(tiles, pairs):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=2e-5)

    def loss(q_i, k_i, w, b):
        return dsa.align_loss(*operands(b, q_i, k_i, w), sm_scale)

    mesh = Mesh(np.array(jax.devices()[:2]), ("hvd",))

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("hvd"),
                       out_specs=P("hvd"))
    def sharded(q, k, v, q_i, k_i, w):
        # A row a device; what varies over the mesh reaches the pass.
        def one(q_i, k_i, w):
            qh, kh, lse, mask_t, lse_i = align_operands(
                q[0], k[0], v[0], q_i[0], k_i[0], w[0], topk,
                fa.reference_attention)
            return dsa.align_loss(qh, kh, lse, mask_t, q_i[0], k_i[0], w[0],
                                  lse_i, sm_scale)
        value, grads = jax.value_and_grad(one, argnums=(0, 1, 2))(q_i, k_i, w)
        return value[None], grads

    values, grads = sharded(q, k, v, q_i, k_i, w)
    for b in range(2):
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda *a: loss(*a, b), argnums=(0, 1, 2)))(q_i, k_i, w)
        assert float(values[b]) == pytest.approx(float(want), rel=1e-5)
        for a, c in zip(grads, want_grads):
            np.testing.assert_allclose(a[b], c[b], atol=1e-6, rtol=2e-5)


@pytest.mark.parametrize("seq,block_q,block_k,run", [
    (16384, 1024, 1024, 136), (16384, 512, 256, 1056), (64, 16, 8, 20),
    (48, 16, 8, 12), (8, 8, 8, 1)])
def test_the_alignment_kernels_steps_are_the_tiles_holding_a_causal_pair(
        seq, block_q, block_k, run, telemetry_plane):
    """From shapes: a step a (query block, key block) tile with a key
    not after one of its queries, and no other; a query block's one
    after the other, first and last flagged."""
    n_q, n_k = seq // block_q, seq // block_k
    want = [(qb, kb) for qb in range(n_q) for kb in range(n_k)
            if kb * block_k <= qb * block_q + block_q - 1]
    steps = dsa._align_steps(seq, block_q, block_k).reshape(4, -1)
    assert list(zip(steps[fa._ROW], steps[fa._INNER])) == want
    assert (steps[fa._FETCH] == steps[fa._INNER]).all()
    assert len(want) == run
    firsts = [i for i, (qb, kb) in enumerate(want) if kb == 0]
    lasts = [i - 1 for i in firsts[1:]] + [len(want) - 1]
    assert list(np.flatnonzero(steps[fa._FLAGS] & fa._ROW_FIRST)) == firsts
    assert list(np.flatnonzero(steps[fa._FLAGS] & fa._ROW_LAST)) == lasts
    assert dsa.align_tiles(seq, block_q, block_k) == {
        "run": run, "rectangle": n_q * n_k}
    # The gauge, set while tracing (docs/metrics.md).
    dsa._publish_tiles(seq, block_q, block_k)
    tiles = {s["labels"]["kind"]: s["value"] for s in telemetry_plane.snapshot()[
        "families"]["hvd_dsa_align_tiles"]["samples"]}
    assert tiles == {"run": float(run), "rectangle": float(n_q * n_k)}


def test_a_call_that_keeps_no_state_runs_no_alignment_pass(seeded):
    cfg, model, params, aux, batch = seeded
    quiet = jax.jit(lambda p: model.apply({**p, **aux}, batch[0]))
    both = jax.jit(lambda p: model.apply({**p, **aux}, batch[0],
                                         mutable=list(aux)))
    logits, state = both(params)
    np.testing.assert_allclose(quiet(params), logits, atol=1e-6)
    assert float(transformer.dsa_align_loss(state)) > 0
    text = quiet.lower(params).as_text(debug_info=True)
    assert "hvd_dsa/select" in text and "hvd_dsa/align" not in text
    assert "hvd_dsa/align" in both.lower(params).as_text(debug_info=True)


def test_a_sparse_layer_refuses_what_it_cannot_run(seeded):
    cfg, model, params, aux, batch = seeded
    with pytest.raises(ValueError, match="indexer"):
        make_model(cfg, indexer=None).init(jax.random.PRNGKey(0), batch[0])
    with pytest.raises(ValueError, match="padding mask"):
        model.apply({**params, **aux}, batch[0],
                    mask=jnp.ones(batch[0].shape, bool))


# ---- the shares ---------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_reference_layer(seeded):
    """A block's output over the 4 shares of its 8 experts (2 each; no
    shared expert), attention, which every chip computes alike, counted
    once: ``x1 + sum of the shares' routed sums`` is what the uncut
    reference gives for the whole block."""
    cfg = seeded[0]
    uncut_cfg = dict(cfg, experts_held=[0, 8])
    whole = reference.init_params(uncut_cfg, jax.random.PRNGKey(4))
    p = whole["params"]["backbone"]["block_0"]
    x = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, 64))
    eps = cfg["rms_norm_eps"]
    want = jax.jit(lambda x: reference._block(x, p, uncut_cfg,
                                              "float32")[0])(x)

    block_cfg = make_model(cfg).cfg
    x1 = x + jax.jit(lambda x: reference.attention(
        reference._rms_norm(x, p["ln1"], eps), p["attn"], cfg)[0])(x)
    u = reference._rms_norm(x1, p["ln2"], eps).reshape(-1, 64)
    # The program's own attention half, counted once.
    module = transformer.Attention(block_cfg, kind="sparse_rope")
    with jax.default_matmul_precision("highest"):
        shares = [jax.jit(lambda u, first=first: moe.moe_apply(
            u, {"router": p["moe"]["router"],
                **{n: p["moe"][n][first:first + 2]
                   for n in ("w_gate", "w_up", "w_down")}},
            jnp.zeros((8,)), k=2, first_held=first, scoring="softmax")[0])(u)
            for first in range(0, 8, 2)]
        mine = jax.jit(lambda x: module.apply(
            {"params": p["attn"]}, reference._rms_norm(x, p["ln1"], eps),
            mutable=["dsa_state"])[0])(x)
    np.testing.assert_allclose(x + mine, x1, atol=2e-5)
    got = x + mine + sum(shares).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=2e-5)
    assert worst(x1 + shares[0].reshape(x.shape), want) > 0.02


# ---- the step, its scopes, its gauges -----------------------------------

def test_compiled_step_names_the_parts_of_sparse_attention(seeded):
    """The scopes this model adds (docs/tracing.md), as the benchmark's
    readers look for them, and a step that trains: the indexer's leaves
    move by the alignment loss, whose value the state carries."""
    import horovod_tpu.jax as hvd_jax
    from jax.sharding import Mesh

    from benchmark import scope_reduce, scope_sum
    cfg, _, params, aux, batch = seeded
    model = TransformerLM(builder.model_config(cfg, {"seq_len": SEQ}))
    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    one = (batch[0][:1], batch[1][:1])

    def loss_fn(p, aux, batch):
        total, (_, _, new_aux) = program_losses(model, p, aux, batch)
        return total, new_aux

    opt = hvd_jax.DistributedOptimizer(optax.adam(1e-2))
    step = hvd_jax.make_train_step(loss_fn, opt, mesh=mesh, has_aux=True,
                                   donate=False)
    lowered = step.lower(params, aux, opt.init(params), one)
    names = re.findall(r'op_name="([^"]+)"', lowered.compile().as_text())
    parts = [scope_reduce._parts(n) for n in names]
    for scopes in (("block_0", "attn", "hvd_dsa", "index", "indexer"),
                   ("block_1", "hvd_dsa", "index"),
                   ("block_1", "hvd_dsa", "select"),
                   ("block_1", "hvd_dsa", "attend"),
                   ("block_1", "hvd_dsa", "align"),
                   ("block_1", "attn", "rope"),
                   ("block_1", "moe", "hvd_moe", "route"),
                   ("block_0", "hvd_moe", "experts")):
        assert [p for p in parts if scope_sum._within(scopes, p)], scopes
    for scope in ("hvd_dsa/attend", "hvd_dsa/align", "hvd_dsa/index"):
        assert any(scope in n and "transpose(" in n for n in names), scope
    assert not any("hvd_dsa/select" in n and "transpose(" in n
                   for n in names)      # no gradient through the selection
    new_params, new_aux, _, loss = step(params, aux, opt.init(params), one)
    state = new_aux["dsa_state"]["backbone"]
    assert float(loss) > float(state["block_0"]["attn"]["align_loss"]) > 0
    assert float(state["block_1"]["attn"]["selected_keys"]) == (
        pytest.approx(dsa.selected_pairs(SEQ, TOPK) / SEQ))
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         new_params, params)
    assert all(x > 0 for x in jax.tree.leaves(moved))


def test_the_models_layers_reach_the_telemetry_plane(seeded, losses,
                                                     telemetry_plane):
    telemetry = telemetry_plane
    model, new_aux = seeded[1], losses[3]
    # What stack ran: ``Backbone`` sets it as it is built.
    jax.eval_shape(model.init, jax.random.PRNGKey(0), seeded[4][0])
    families = telemetry.snapshot()["families"]
    kinds = {s["labels"]["kind"]: s["value"]
             for s in families["hvd_stack_layers"]["samples"]}
    assert set(kinds) == set(transformer.MIXERS)
    assert kinds.pop("sparse_rope") == 2.0 and set(kinds.values()) == {0.0}
    transformer.publish_dsa(new_aux, seq=16384, topk=2048)
    families = telemetry.snapshot()["families"]
    keys = {s["labels"]["layer"]: s["value"]
            for s in families["hvd_dsa_selected_keys"]["samples"]}
    assert keys == {f"dsa_state/backbone/block_{i}/attn": pytest.approx(
        dsa.selected_pairs(SEQ, TOPK) / SEQ) for i in (0, 1)}
    losses = [s["value"] for s in families["hvd_dsa_align_loss"]["samples"]]
    assert len(losses) == 2 and min(losses) > 0
    assert families["hvd_dsa_kept_share"]["samples"][0][
        "value"] == pytest.approx(0.23437, abs=1e-5)
    assert families["hvd_dsa_mask_bytes"]["samples"][0]["value"] == 16384.0 ** 2


def test_publishing_is_a_no_op_with_metrics_off(seeded, monkeypatch):
    from horovod_tpu.telemetry import core as telemetry
    monkeypatch.setattr(telemetry, "_ENABLED", False)
    monkeypatch.setattr(telemetry, "_REGISTRY", telemetry.Registry())
    transformer.publish_dsa(seeded[3], seq=64, topk=8)
    assert telemetry.snapshot()["families"] == {}
