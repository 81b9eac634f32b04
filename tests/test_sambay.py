"""A decoder-hybrid-decoder's mechanisms at a small size on the CPU,
seeded: the program's model (Mamba layers over the selective-scan
kernel, windowed and full differential attention with grouped K/V heads
through the flash kernels, gated memory units, cross-attention over one
shared K/V, a tied head) against the plain reference of
``benchmark/references/sambay.py`` for the loss and every gradient leaf;
the scan kernel against a sequential scan; the flash kernels' window,
grouped heads and wide values against an explicit mask; what the
existing configurations still trace; the tied table's gradient; the
recomputation policies; the vocabulary share. (On the chip the
comparison is the benchmark's ``correct``, at the published widths.)
"""

import dataclasses
import functools
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

from benchmark.builders import sambay as builder  # noqa: E402
from benchmark.references import sambay as reference  # noqa: E402
from horovod_tpu.models import TransformerLM  # noqa: E402
from horovod_tpu.ops import flash_attention as fa  # noqa: E402
from horovod_tpu.ops import selective_scan as ss  # noqa: E402

SEQ = 40
# Six layers of all five kinds, the published indices 14-19, at widths a
# CPU test affords: 4 query and 2 K/V heads of 32, a window of 8, 256
# channels of 4 states.
TINY = dict(
    hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=192, vocab_size=96, num_hidden_layers=6,
    num_hidden_layers_published=32, mb_per_layer=2,
    layer_indices=[14, 15, 16, 17, 18, 19], sliding_window=8,
    layer_norm_eps=1e-5, mlp_bias=False, tie_word_embeddings=True,
    attention_impl="flash", remat=False,
    assumed_sizes=dict(expand=2, d_state=4, d_conv=4, dt_rank=8),
    reference="benchmark/references/sambay.py")


def make_model(cfg=TINY, **overrides):
    """The program's model in float32. Attention by einsum unless a test
    asks for the kernels (interpret mode compiles slowly); the scan is
    the kernel either way."""
    overrides.setdefault("attention_impl", "einsum")
    config = dataclasses.replace(
        builder.model_config(cfg, {"seq_len": SEQ}), dtype=jnp.float32,
        **overrides)
    return TransformerLM(config)


@functools.lru_cache(maxsize=None)
def seeded():
    """The reference's seeded weights with every leaf moved off its
    initial value, so that a bias or a norm left out shows."""
    @jax.jit
    def make(key):
        params = reference.init_params(TINY, key)
        leaves, tree = jax.tree.flatten(params)
        keys = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
        return jax.tree.unflatten(tree, [
            x + 0.05 * jax.random.normal(k, x.shape)
            for x, k in zip(leaves, keys)])

    return make(jax.random.PRNGKey(0))


def batch_of(vocab=96, rows=2, seed=2):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (rows, SEQ + 1),
                                0, vocab)
    return tokens[:, :-1], tokens[:, 1:]


def model_loss(model, params, batch):
    return optax.softmax_cross_entropy_with_integer_labels(
        model.apply(params, batch[0]), batch[1]).mean()


def worst_gap(ours, theirs):
    """Largest difference of a leaf over that leaf's largest entry."""
    named = jax.tree_util.tree_leaves_with_path(ours)
    return max((float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b))
                                                 + 1e-12)),
                jax.tree_util.keystr(path))
               for (path, a), b in zip(named, jax.tree.leaves(theirs)))


def test_the_six_layers_are_of_all_five_kinds():
    assert reference.kinds(TINY) == ["mamba", "window", "mamba",
                                     "attention", "gmu", "cross"]
    # The whole published stack: 9 Mamba, 8 windowed, 1 full, 7 gated
    # memory, 7 cross.
    whole = [reference.mixer_kind(i, TINY) for i in range(32)]
    assert [whole.count(k) for k in reference.KINDS] == [9, 8, 1, 7, 7]
    assert make_model().cfg.mixers == tuple(reference.kinds(TINY))


@pytest.mark.parametrize("impl", ["flash", "einsum"])
def test_loss_and_every_gradient_leaf_match_the_reference(impl):
    model = make_model(attention_impl=impl)
    params, batch = seeded(), batch_of()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), batch[0])
    assert jax.tree.map(lambda x: x.shape, shapes) == jax.tree.map(
        lambda x: x.shape, params)
    with jax.default_matmul_precision("highest"):
        ours = jax.jit(jax.value_and_grad(
            lambda p: model_loss(model, p, batch)))(params)
        theirs = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, {}, batch, TINY)[0]))(params)
    # Both sides float32; they differ in the order of sums (the kernels'
    # online softmax and chunked scan against whole rows and positions
    # one by one): a few units of float32's 6e-8 through six layers.
    assert abs(float(ours[0]) - float(theirs[0])) < 2e-6 * float(theirs[0])
    gap, where = worst_gap(ours[1], theirs[1])
    assert gap < 5e-4, (gap, where)


@pytest.mark.parametrize("seq,chunk", [(37, 16), (64, 64), (5, 8)])
def test_scan_kernel_matches_a_sequential_scan(seq, chunk):
    """Interpret mode, forward and all five gradients, at lengths that
    are and are not multiples of the chunk."""
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    batch, channels, n = 2, 256, 4
    x = jax.random.normal(keys[0], (batch, seq, channels))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, channels)))
    a = -jnp.exp(0.5 * jax.random.normal(keys[2], (channels, n)))
    b, c = (jax.random.normal(k, (batch, seq, n)) for k in keys[3:5])
    w = jax.random.normal(keys[5], (batch, seq, channels))

    def grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda *args: jnp.sum(fn(*args) * w), argnums=(0, 1, 2, 3, 4)))(
                x, dt, a, b, c)

    ours = grads(lambda *args: ss.selective_scan(*args, chunk=chunk))
    theirs = grads(ss.reference_scan)
    # float32 both; the sums over channels (dB, dC) and over positions
    # (dA) are made in another order.
    gap, where = worst_gap(ours, theirs)
    assert gap < 2e-5, (gap, where)
    assert ss.scan_chunks(seq, chunk) == -(-seq // chunk)
    assert ss.state_bytes(batch, seq, channels, n, chunk) == (
        4 * batch * -(-seq // chunk) * channels * n)


def masked_attention(q, k, v, window):
    """Softmax attention with an explicit mask and K, V repeated over
    the group."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    seq = q.shape[2]
    ahead = jnp.arange(seq)[:, None] - jnp.arange(seq)[None, :]
    keep = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# A window shorter than a sub-tile, shorter than, equal to and longer
# than a block (128 here), and one that two block offsets share.
@pytest.mark.parametrize("window", [24, 64, 128, 200, 300])
def test_flash_window_and_grouped_heads_match_an_explicit_mask(
        window, monkeypatch):
    monkeypatch.setattr(fa, "_SUB_TILE", 64)
    monkeypatch.setattr(fa, "_SUB_TILE_BWD", 32)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    seq, d, dv = 512, 32, 64
    q = jax.random.normal(keys[0], (1, 4, seq, d))
    k = jax.random.normal(keys[1], (1, 2, seq, d))
    v = jax.random.normal(keys[2], (1, 2, seq, dv))
    w = jax.random.normal(keys[3], (1, 4, seq, dv))

    def grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2)))(
                q, k, v)

    with jax.default_matmul_precision("highest"):
        ours = grads(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window, block_q=128, block_k=128))
        theirs = grads(lambda q, k, v: masked_attention(q, k, v, window))
    # float32 both: the online softmax sums a row in blocks.
    gap, where = worst_gap(ours, theirs)
    assert gap < 2e-5, (gap, where)


def test_window_counts_at_the_cells_shape():
    """Forward, seq 8192 at 1024 blocks and sub-tiles of 512: of the 136
    sub-tiles on or under the diagonal a 512 window leaves a row's own
    and the one before it, 31, and hides 105; the backward at 128 keeps
    five a strip."""
    fwd = fa.fwd_subtile_counts(8192, 8192, 1024, 1024, True, window=512)
    assert (fwd["interior"], fwd["masked"], fwd["window"],
            fwd["skipped"]) == (0, 31, 105, 120)
    bwd = fa.bwd_subtile_counts(8192, 8192, 1024, 1024, True, window=512)
    n = 64
    assert bwd["interior"] + bwd["masked"] == 5 * n - (4 + 3 + 2 + 1)
    assert bwd["masked"] == n + (n - 4)
    assert bwd["skipped"] == n * (n - 1) // 2
    assert bwd["window"] + bwd["interior"] + bwd["masked"] == n * (n + 1) // 2
    # Either grid runs the 15 live tiles of its 64: a query block sees its
    # own key block and the one before it. Of those steps, the 7 that
    # start a row past the first name the block the step before held.
    for kernel, counts in (("fwd", fwd), ("bwd", bwd)):
        assert fa.grid_steps(kernel, 8192, 8192, 1024, 1024, True,
                             window=512) == {"run": 15, "live": 15}
        assert counts["steps_without_fetch"] == 7


# (batch, heads, seq, head_dim) of the three lm365m shapes.
LM365M = [(2, 16, 8192, 64), (6, 16, 2048, 64), (24, 16, 512, 64)]


@pytest.mark.parametrize("shape", LM365M, ids=["seq8192", "seq2048",
                                               "seq512"])
def test_no_window_traces_what_it_traced(shape):
    """``window=None`` and a window at least the sequence: the same
    program (so bit-equal outputs and gradients) and equal counts."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def traced(window):
        def loss(q, k, v):
            return jnp.sum(fa.flash_attention(
                q, k, v, causal=True, window=window, block_q=1024,
                block_k=1024).astype(jnp.float32))
        return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
            x, x, x))

    seq = shape[2]
    assert traced(None) == traced(seq) == traced(4 * seq)
    assert traced(None) != traced(seq - 1)
    for kernel in ("fwd", "bwd"):
        plain = fa.subtile_counts(kernel, seq, seq, 1024, 1024, True)
        assert set(plain) == {"interior", "masked", "skipped",
                              "steps_without_fetch"}
        assert fa.subtile_counts(kernel, seq, seq, 1024, 1024, True,
                                 window=seq) == plain


def test_a_window_that_hides_nothing_is_bit_equal():
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(key, (1, 2, 256, 32)) for key in keys)

    def grads(window):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fa.flash_attention(
                q, k, v, causal=True, window=window, block_q=128,
                block_k=128) ** 2), argnums=(0, 1, 2)))(q, k, v)

    for a, b in zip(jax.tree.leaves(grads(None)),
                    jax.tree.leaves(grads(256))):
        assert jnp.array_equal(a, b)


def _only(params, shapes):
    """``params`` cut to the leaves ``shapes`` has."""
    if not isinstance(shapes, dict):
        return params
    return {k: _only(params[k], shapes[k]) for k in shapes}


def test_tied_table_gradient_is_the_sum_of_its_two_uses():
    """Against the untied twin given the table twice (no projection has
    a bias in either, so that the twin's head has none): the tied
    leaf's gradient is the embedding's plus the head's."""
    tied = make_model(bias=False)
    untied = make_model(bias=False, tie_embeddings=False)
    batch = batch_of()

    def leaves_of(model, params):
        return _only(params, jax.eval_shape(
            model.init, jax.random.PRNGKey(0), batch[0]))

    params = seeded()
    table = params["params"]["backbone"]["tok_embed"]["embedding"]
    g_tied = jax.jit(jax.grad(lambda p: model_loss(tied, p, batch)))(
        leaves_of(tied, params))
    apart = {"params": {**params["params"], "lm_head": {"kernel": table.T}}}
    g_apart = jax.jit(jax.grad(lambda p: model_loss(untied, p, batch)))(
        leaves_of(untied, apart))
    gather = g_apart["params"]["backbone"]["tok_embed"]["embedding"]
    product = g_apart["params"]["lm_head"]["kernel"].T
    assert float(jnp.max(jnp.abs(gather))) > 0
    assert float(jnp.max(jnp.abs(product))) > 0
    np.testing.assert_allclose(
        g_tied["params"]["backbone"]["tok_embed"]["embedding"],
        gather + product, rtol=1e-5, atol=1e-7)


@functools.lru_cache(maxsize=None)
def _grads(remat):
    model, batch = make_model(remat=remat), batch_of()
    return jax.jit(jax.value_and_grad(
        lambda p: model_loss(model, p, batch)))(seeded())


@pytest.mark.parametrize("remat", ["dots", "flash", True])
def test_recomputation_leaves_loss_and_gradients_as_they_are(remat):
    plain, again = _grads(False), _grads(remat)
    # The same operations made twice: equal but for how XLA fuses them.
    gap, where = worst_gap(again, plain)
    assert gap < 1e-5, (gap, where)


def test_flash_policy_runs_neither_kernel_again():
    """Under ``remat="flash"`` the backward pass holds what both kernel
    pairs' forward calls made: the step's program has one forward call a
    kernel a layer, where ``"dots"`` makes each again."""
    params, batch = seeded(), batch_of()

    def calls(remat):
        model = make_model(remat=remat, attention_impl="flash")
        text = str(jax.make_jaxpr(jax.grad(
            lambda p: model_loss(model, p, batch)))(params))
        # The calls go through jax.jit, by the functions' names: the
        # scan's forward and backward, the flash kernels'.
        return tuple(text.count(f"name={name}\n") + text.count(
            f"name={name} ") for name in (
                "_fwd_call", "_bwd_call", "_fwd_jit", "_bwd_chunk"))

    assert calls(False) == calls("flash") == (2, 2, 6, 6)
    assert calls("dots") == (4, 2, 12, 6)


def test_the_vocabulary_share_is_the_uncut_heads_first_rows():
    """With the same weights, the logits over the rows a chip holds of
    an uncut tied table equal the slice's own."""
    held = 12
    whole, share = make_model(), make_model(dict(TINY, vocab_size=held))
    params = seeded()
    table = params["params"]["backbone"]["tok_embed"]["embedding"]
    cut = jax.tree.map(lambda x: x, params)
    cut["params"]["backbone"]["tok_embed"] = {"embedding": table[:held]}
    tokens = batch_of(vocab=held)[0]
    slice_logits = jax.jit(share.apply)(cut, tokens)
    np.testing.assert_allclose(
        jax.jit(whole.apply)(params, tokens)[..., :held], slice_logits,
        rtol=1e-6, atol=1e-6)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            reference.logits_fn(cut, tokens, dict(TINY, vocab_size=held)),
            jax.jit(share.apply)(cut, tokens), rtol=2e-4, atol=2e-4)


def test_scopes_and_kernels_are_named_in_the_compiled_step():
    model = make_model(attention_impl="flash")
    params, batch = seeded(), batch_of()
    text = jax.jit(jax.grad(lambda p: model_loss(model, p, batch))).lower(
        params).compile().as_text()
    for scope in ("hvd_ssm/", "hvd_ssm/scan", "hvd_gmu/", "hvd_diff/",
                  "hvd_flash/"):
        assert scope in text, scope
    # The backward kernel's rule is traced outside the call's scopes and
    # names them itself.
    assert "transpose(jvp(hvd_ssm))/scan" in text or (
        "hvd_ssm/scan" in text.split("hvd_ssm_bwd")[0][-2000:])


def test_gauges_are_set_when_metrics_are_on(monkeypatch):
    from horovod_tpu import telemetry
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    telemetry.reset()
    try:
        x = jnp.ones((2, 100, 128))
        ss.selective_scan(x, x, -jnp.ones((128, 4)), jnp.ones((2, 100, 4)),
                          jnp.ones((2, 100, 4)), chunk=32)
        families = telemetry.registry().families()

        def value(name):
            return [s["value"] for s in families[name].samples()]
        assert value("hvd_ssm_chunks") == [4.0]
        assert value("hvd_ssm_state_bytes") == [4.0 * 2 * 4 * 128 * 4]
        q = jnp.ones((1, 2, 512, 32))
        fa.flash_attention(q, q[:, :1], q[:, :1], causal=True, window=128,
                           block_q=128, block_k=128)
        kinds = {s["labels"]["kind"]: s["value"] for s in
                 telemetry.registry().families()[
                     "hvd_flash_fwd_subtiles"].samples()}
        assert kinds["window"] == 3.0 and kinds["skipped"] == 6.0
    finally:
        monkeypatch.delenv("HOROVOD_TPU_METRICS", raising=False)
        telemetry.reset()
