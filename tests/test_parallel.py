"""Parallelism-strategy tests on the 8-device virtual CPU mesh.

Each strategy is validated against a single-device oracle: ring/Ulysses
attention vs full flash/einsum attention, pipeline vs sequential stage
application, the dropless expert layer vs a dense-mask oracle, GSPMD sharding vs
replicated execution.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.ops.flash_attention import reference_attention
from horovod_tpu.parallel import (
    MeshConfig, make_mesh, moe_apply, pipeline_apply, ring_attention,
    ulysses_attention)
from horovod_tpu.parallel import moe as moe_lib
from horovod_tpu.parallel.pipeline import stack_stage_params
from moe_fixtures import poison  # noqa: F401 (a fixture)


def _rand(shape, seed, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32),
                       dtype=dtype)


def _sp_mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


# -- mesh ------------------------------------------------------------------

def test_mesh_config_resolve():
    cfg = MeshConfig(dp=-1, tp=2, pp=2).resolve(8)
    assert cfg.shape == (2, 1, 2, 1, 2)
    mesh = make_mesh(MeshConfig(dp=-1, tp=2))
    assert mesh.shape["tp"] == 2 and mesh.shape["dp"] == 4
    with pytest.raises(ValueError):
        MeshConfig(dp=3, tp=2).resolve(8)


# -- ring attention --------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("impl", ["flash", "einsum"])
def test_ring_attention_matches_full(causal, impl):
    n = 4
    mesh = _sp_mesh(n)
    b, h, s, d = 1, 2, 256, 32
    q, k, v = (_rand((b, h, s, d), i) for i in range(3))

    def body(q, k, v):
        return ring_attention(q, k, v, "sp", causal=causal, impl=impl)

    out = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=P(None, None, "sp", None),
        out_specs=P(None, None, "sp", None)))(q, k, v)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_ring_attention_gradients():
    n = 4
    mesh = _sp_mesh(n)
    q, k, v = (_rand((1, 2, 256, 32), i) for i in range(3))

    def ring_loss(q, k, v):
        def body(q, k, v):
            o = ring_attention(q, k, v, "sp", causal=True)
            return jnp.sum(o ** 2)
        losses = shard_map(
            lambda q, k, v: jnp.array([body(q, k, v)]),
            mesh=mesh,
            in_specs=P(None, None, "sp", None), out_specs=P("sp"))(q, k, v)
        return jnp.sum(losses)

    def ref_loss(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g1 = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


# -- ulysses ---------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_full(causal):
    n = 4
    mesh = _sp_mesh(n)
    b, h, s, d = 1, 4, 256, 32
    q, k, v = (_rand((b, h, s, d), i) for i in range(3))

    def body(q, k, v):
        return ulysses_attention(q, k, v, "sp", causal=causal)

    out = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=P(None, None, "sp", None),
        out_specs=P(None, None, "sp", None)))(q, k, v)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_ulysses_rejects_indivisible_heads():
    mesh = _sp_mesh(4)
    q = _rand((1, 2, 64, 32), 0)  # 2 heads, 4-way axis

    def body(q):
        return ulysses_attention(q, q, q, "sp")

    with pytest.raises(ValueError, match="divisible"):
        jax.jit(shard_map(
            body, mesh=mesh, in_specs=P(None, None, "sp", None),
            out_specs=P(None, None, "sp", None)))(q)


# -- pipeline --------------------------------------------------------------

def test_pipeline_matches_sequential():
    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("pp",))
    d = 16
    m, mb = 8, 4

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    stages = [_rand((d, d), 10 + i) for i in range(n)]
    stacked = stack_stage_params(stages)
    x = _rand((m, mb, d), 0)

    # Inputs are sharded over pp (batch m lives on rank m // (M/n)) and
    # stream to stage 0 through the feed register — nothing replicated.
    out = jax.jit(shard_map(
        lambda w, x: pipeline_apply(stage_fn, w, x, "pp"),
        mesh=mesh, in_specs=(P("pp"), P("pp")), out_specs=P()))(
            stacked, x)

    ref = x
    for w in stages:
        ref = stage_fn(w, ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_gradients_match_sequential():
    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("pp",))
    d, m, mb = 8, 4, 2

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    stages = [_rand((d, d), 20 + i) for i in range(n)]
    stacked = stack_stage_params(stages)
    x = _rand((m, mb, d), 1)

    def pipe_loss(stacked_w, x):
        def body(w, x):
            y = pipeline_apply(stage_fn, w, x, "pp")
            return jnp.sum(y ** 2)
        return shard_map(
            body, mesh=mesh, in_specs=(P("pp"), P("pp")),
            out_specs=P())(stacked_w, x)

    def ref_loss(stacked_w, x):
        y = x
        for i in range(n):
            y = stage_fn(stacked_w[i], y)
        return jnp.sum(y ** 2)

    g1 = jax.jit(jax.grad(pipe_loss))(stacked, x)
    g2 = jax.grad(ref_loss)(stacked, x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=1e-4, rtol=1e-4)


def test_pipeline_transformer_stages_with_hetero_ends():
    """2-transformer-blocks-per-stage pipeline with an embedding entry
    (tokens -> hidden, first_fn) and an LM-head exit (hidden -> logits,
    last_fn), matching sequential execution — the round-4 realism
    contract: per-stage param trees, shape-changing ends, stage-0-only
    input consumption."""
    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("pp",))
    vocab, d, f = 32, 16, 32
    m, mb, seq = 8, 2, 6

    def block(w, h):
        # pre-LN MLP block with residual
        mu = h.mean(-1, keepdims=True)
        hn = (h - mu) / jnp.sqrt(h.var(-1, keepdims=True) + 1e-5)
        return h + jax.nn.gelu(hn @ w["w1"]) @ w["w2"]

    def stage_fn(wstack, h):
        # a stage = 2 blocks, parameters stacked along axis 0
        for i in range(2):
            h = block(jax.tree.map(lambda a: a[i], wstack), h)
        return h

    def first_fn(emb, tokens):
        return emb[tokens]

    def last_fn(head, h):
        return h @ head

    stages = [{"w1": _rand((2, d, f), 30 + i) * 0.3,
               "w2": _rand((2, f, d), 40 + i) * 0.3} for i in range(n)]
    stacked = stack_stage_params(stages)
    emb = _rand((vocab, d), 5)
    head = _rand((d, vocab), 6) * 0.3
    tokens = jnp.asarray(
        np.random.RandomState(7).randint(0, vocab, size=(m, mb, seq)))

    out = jax.jit(shard_map(
        lambda w, e, hd, t: pipeline_apply(
            stage_fn, w, t, "pp", first_fn=first_fn, first_params=e,
            last_fn=last_fn, last_params=hd),
        mesh=mesh, in_specs=(P("pp"), P(), P(), P("pp")),
        out_specs=P()))(stacked, emb, head, tokens)

    ref = emb[tokens]
    for s in stages:
        ref = stage_fn(s, ref)
    ref = ref @ head
    assert out.shape == (m, mb, seq, vocab)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pipeline_rounds_interleaved_placement():
    """rounds=2 on 4 ranks = 8 logical stages (stage ro*n+j at rank j,
    slot ro); output and gradients must match the 8-deep sequential
    model."""
    n, rounds = 4, 2
    mesh = Mesh(np.array(jax.devices()[:n]), ("pp",))
    d, m, mb = 8, 8, 2

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    stages = [_rand((d, d), 50 + i) for i in range(n * rounds)]
    stacked = stack_stage_params(stages, n_ranks=n)
    x = _rand((m, mb, d), 2)

    def pipe_loss(w, x):
        def body(w, x):
            y = pipeline_apply(stage_fn, w, x, "pp", rounds=rounds)
            return jnp.sum(y ** 2)
        return shard_map(
            body, mesh=mesh, in_specs=(P("pp"), P("pp")),
            out_specs=P())(w, x)

    def ref_loss(w_seq, x):
        y = x
        for i in range(n * rounds):
            y = stage_fn(w_seq[i], y)
        return jnp.sum(y ** 2)

    w_seq = jnp.stack(stages)
    np.testing.assert_allclose(
        float(jax.jit(pipe_loss)(stacked, x)), float(ref_loss(w_seq, x)),
        rtol=1e-5)
    g1 = jax.jit(jax.grad(pipe_loss))(stacked, x)
    g2 = jax.grad(ref_loss)(w_seq, x)
    # Undo the interleaved placement to compare per-stage grads.
    order = [ro * n + j for j in range(n) for ro in range(rounds)]
    np.testing.assert_allclose(np.asarray(g1),
                               np.asarray(g2)[np.array(order)],
                               atol=1e-4, rtol=1e-4)


# -- MoE -------------------------------------------------------------------

def _moe_layer(tokens=64, d=16, f=32, e=8):
    params = {"router": _rand((d, e), 1) / 4.0,
              "w_gate": _rand((e, d, f), 2) / 4.0,
              "w_up": _rand((e, d, f), 3) / 4.0,
              "w_down": _rand((e, f, d), 4) / 4.0}
    bias = 0.05 * _rand((e,), 5)
    return _rand((tokens, d), 0), params, bias


def _moe_dense_oracle(x, params, bias, k, scale, first_held=0,
                      scoring="sigmoid", gate="silu"):
    """Every expert held on every token, weighted by the token's weight
    for it or by 0: no sort, no grouped product."""
    scores = x @ params["router"]
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(scores)
    _, chosen = jax.lax.top_k(scores + bias, k)
    mask = jax.nn.one_hot(chosen, scores.shape[-1]).sum(-2)
    picked = (scores if scoring == "sigmoid" else jnp.exp(scores)) * mask
    weights = scale * picked / picked.sum(-1, keepdims=True)
    weights = weights[:, first_held:first_held + params["w_gate"].shape[0]]
    h = moe_lib.GATES[gate](jnp.einsum("td,edf->etf", x, params["w_gate"]))
    h = h * jnp.einsum("td,edf->etf", x, params["w_up"])
    return jnp.einsum("etf,efd,te->td", h, params["w_down"], weights)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_moe_dropless_matches_dense_oracle(k):
    x, params, bias = _moe_layer()
    y, drawn = moe_apply(x, params, bias, k=k, scale=1.8)
    assert float(drawn.sum()) == k * x.shape[0]
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_moe_dense_oracle(x, params, bias, k, 1.8)),
        atol=2e-5, rtol=2e-4)


def test_moe_gradients_match_dense_oracle():
    """Tokens, router and all three expert matrices: the sort, the
    permutations' transposes and the grouped products' both gradients
    against plain einsums."""
    x, params, bias = _moe_layer()

    def loss(fn, x, params):
        return jnp.sum(fn(x, params) ** 2)

    got = jax.grad(lambda x, p: loss(
        lambda x, p: moe_apply(x, p, bias, k=2, scale=1.8)[0], x, p),
        argnums=(0, 1))(x, params)
    want = jax.grad(lambda x, p: loss(
        lambda x, p: _moe_dense_oracle(x, p, bias, 2, 1.8), x, p),
        argnums=(0, 1))(x, params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("tokens", [64, 512], ids=["full", "sized"])
def test_moe_shares_over_a_mesh_add_up(tokens):
    """Four chips hold two experts each and see the same tokens: every
    chip routes over all eight, computes its own experts' part, and the
    parts sum (psum) to the single-program layer, gradients included.
    At 512 tokens each chip's buffers are sized to its draw and the
    choice of the path varies with the chip (``first_held`` is traced).
    No token exchange: that layout (each chip its own tokens, an
    all-to-all both ways) is ROADMAP B3."""
    n = 4
    mesh = Mesh(np.array(jax.devices()[:n]), ("ep",))
    x, params, bias = _moe_layer(tokens)
    assert (moe_lib.sized_rows(tokens * 2, 2, 8) < tokens * 2) == (
        tokens == 512)

    def share(x, params):
        first = jax.lax.axis_index("ep") * params["w_gate"].shape[0]
        y, _ = moe_apply(x, params, bias, k=2, scale=1.8, first_held=first)
        return jax.lax.psum(y, "ep")

    specs = {"router": P(), "w_gate": P("ep"), "w_up": P("ep"),
             "w_down": P("ep")}
    sharded = shard_map(share, mesh=mesh, in_specs=(P(), specs),
                        out_specs=P())

    def whole(x, params):
        return moe_apply(x, params, bias, k=2, scale=1.8)[0]

    np.testing.assert_allclose(
        np.asarray(jax.jit(sharded)(x, params)),
        np.asarray(whole(x, params)), atol=2e-5, rtol=2e-4)
    got = jax.jit(jax.grad(lambda x, p: jnp.sum(sharded(x, p) ** 2),
                           argnums=(0, 1)))(x, params)
    want = jax.grad(lambda x, p: jnp.sum(whole(x, p) ** 2),
                    argnums=(0, 1))(x, params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)


def test_moe_uneven_routing_drops_nothing():
    # A bias that sends every token to experts 0 and 1: two groups of
    # 64 rows and six empty ones.
    x, params, bias = _moe_layer()
    bias = bias.at[:2].add(10.0)
    y, drawn = moe_apply(x, params, bias, k=2, scale=1.0)
    np.testing.assert_array_equal(np.asarray(drawn),
                                  [64, 64, 0, 0, 0, 0, 0, 0])
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_moe_dense_oracle(x, params, bias, 2, 1.0)),
        atol=2e-5, rtol=2e-4)


# The routed part on buffers sized to the draw (held experts 2 and 3 of
# 8, 512 tokens, k = 2: 512 rows for 1024 pairs) and its fallback.

_HELD = 2       # first_held; two experts held


def _moe_share(tokens=512):
    x, params, bias = _moe_layer(tokens)
    share = {name: w if name == "router" else w[_HELD:_HELD + 2]
             for name, w in params.items()}
    return x, share, bias


def _whole_sized(rows, *routed, gate="silu"):
    """The sized path from its two halves, as ``_either`` puts them
    together when the draw fits."""
    x, w_gate, w_up, _, chosen, _, drawn, first_held = routed
    return moe_lib._sized(rows, moe_lib._sized_rows(
        rows, x, w_gate, w_up, chosen, drawn, first_held), *routed,
        gate=gate)


def _share_apply(x, share, bias, **kinds):
    return moe_apply(x, share, bias, k=2, scale=1.8, first_held=_HELD,
                     **kinds)


def test_moe_sized_path_matches_dense_oracle(poison):
    x, share, bias = _moe_share()
    poison("_routed")
    y, drawn = _share_apply(x, share, bias)
    assert moe_lib.sized_rows(1024, 2, 8) == 512
    assert moe_lib.took_sized_path(np.asarray(drawn), _HELD, _HELD + 2)
    np.testing.assert_allclose(
        np.asarray(y),
        np.asarray(_moe_dense_oracle(x, share, bias, 2, 1.8, _HELD)),
        atol=2e-5, rtol=2e-4)


_KINDS = [("silu", "sigmoid"), ("relu", "softmax"), ("silu", "softmax"),
          ("relu", "sigmoid")]


def _assert_trees_close(got, want, atol=2e-4, rtol=2e-3):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol,
                                   rtol=rtol)


@pytest.mark.parametrize("gate,scoring", _KINDS)
@pytest.mark.parametrize("against", ["oracle", "routed", "sized"])
def test_moe_sized_path_gradients(against, gate, scoring):
    """Tokens, router (through the weights) and the three expert
    matrices through the sized path, whose backward pass is written by
    hand over what its forward pass kept: against the dense oracle,
    against ``_routed`` on a row for every pair, and against
    ``jax.grad`` through the sized path's own two halves."""
    x, share, bias = _moe_share()
    kinds = dict(gate=gate, scoring=scoring)

    def plain(path):
        def of(x, p):
            chosen, weights, drawn = moe_lib.route(
                x, p["router"], bias, k=2, scale=1.8, scoring=scoring)
            return path(x, p["w_gate"], p["w_up"], p["w_down"], chosen,
                        weights, drawn, _HELD, gate=gate)
        return of

    other = {
        "oracle": lambda x, p: _moe_dense_oracle(x, p, bias, 2, 1.8, _HELD,
                                                 **kinds),
        "routed": plain(moe_lib._routed),
        "sized": plain(functools.partial(_whole_sized, 512))}[against]
    np.testing.assert_allclose(
        np.asarray(_share_apply(x, share, bias, **kinds)[0]),
        np.asarray(other(x, share)), atol=2e-5, rtol=2e-4)
    got = jax.grad(
        lambda x, p: jnp.sum(_share_apply(x, p, bias, **kinds)[0] ** 2),
        argnums=(0, 1))(x, share)
    want = jax.grad(lambda x, p: jnp.sum(other(x, p) ** 2),
                    argnums=(0, 1))(x, share)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(want))
    _assert_trees_close(got, want)


def _oracle_gradients(x, share, bias):
    return jax.grad(lambda x, p: jnp.sum(
        _moe_dense_oracle(x, p, bias, 2, 1.8, _HELD) ** 2),
        argnums=(0, 1))(x, share)


def _share_gradients(x, share, bias):
    return jax.grad(lambda x, p: jnp.sum(_share_apply(x, p, bias)[0] ** 2),
                    argnums=(0, 1))(x, share)


@pytest.mark.parametrize("way", ["forward", "backward"])
def test_moe_draw_over_the_sized_rows_takes_the_fallback(poison, way):
    # A bias that sends every token to the two held experts: 1024 pairs
    # for 512 rows. The full-size program runs, forward and backward,
    # drops nothing, and keeps nothing of its own: what its backward
    # pass is handed has the sized shapes, no row for every pair.
    x, share, bias = _moe_share()
    bias = bias.at[_HELD:_HELD + 2].add(10.0)
    poison("_sized")
    if way == "backward":
        _assert_trees_close(_share_gradients(x, share, bias),
                            _oracle_gradients(x, share, bias))
        chosen, weights, drawn = moe_lib.route(x, share["router"], bias,
                                               k=2, scale=1.8)
        routed = (x, share["w_gate"], share["w_up"], share["w_down"],
                  chosen, weights, drawn, _HELD)
        _, pull = jax.vjp(
            lambda *trained: moe_lib._sized_or_routed(
                512, "silu", *trained[:4], chosen, trained[4], drawn,
                _HELD), *routed[:4], weights)
        kept_shapes = {leaf.shape for leaf in jax.tree.leaves(pull)}
        assert (512, 32) in kept_shapes
        assert not {(1024, 16), (1024, 32)} & kept_shapes
        return
    y, drawn = _share_apply(x, share, bias)
    np.testing.assert_array_equal(np.asarray(drawn),
                                  [0, 0, 512, 512, 0, 0, 0, 0])
    assert not moe_lib.took_sized_path(np.asarray(drawn), _HELD, _HELD + 2)
    np.testing.assert_allclose(
        np.asarray(y),
        np.asarray(_moe_dense_oracle(x, share, bias, 2, 1.8, _HELD)),
        atol=2e-5, rtol=2e-4)
    assert not np.any(np.all(np.asarray(y) == 0.0, axis=-1))


@pytest.mark.parametrize("extra,path", [(0, "_sized"), (1, "_routed")],
                         ids=["draw_equals_rows", "one_pair_over"])
def test_moe_sized_path_boundary(poison, extra, path):
    """Every token picks expert 2 (held) and expert 0 (held elsewhere):
    a draw of exactly the 512 rows, which fits; with one token that
    picks expert 3 in place of 0 the draw is 513 and does not."""
    x, share, bias = _moe_share()
    x = x.at[:, 0].set(1.0).at[:, 1].set(0.0).at[0, 1].set(float(extra))
    router = share["router"].at[:2].set(0.0)
    router = router.at[0, 2].set(10.0).at[0, 0].set(5.0)
    router = router.at[0, 3].set(-5.0).at[1, 3].set(20.0)
    share = {**share, "router": router}
    bias = jnp.zeros_like(bias)
    poison({"_sized": "_routed", "_routed": "_sized"}[path])
    y, drawn = _share_apply(x, share, bias)
    assert float(drawn[_HELD:_HELD + 2].sum()) == 512 + extra
    assert moe_lib.took_sized_path(np.asarray(drawn), _HELD,
                                   _HELD + 2) == (path == "_sized")
    np.testing.assert_allclose(
        np.asarray(y),
        np.asarray(_moe_dense_oracle(x, share, bias, 2, 1.8, _HELD)),
        atol=2e-5, rtol=2e-4)
    # The backward pass chooses again by the same test: the other way
    # back is poisoned too.
    _assert_trees_close(_share_gradients(x, share, bias),
                        _oracle_gradients(x, share, bias))


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


def _all_shapes(jaxpr):
    for eqn in _all_eqns(jaxpr):
        for var in eqn.outvars:
            yield tuple(getattr(var.aval, "shape", ()))


def test_moe_every_expert_held_traces_no_conditional():
    x, params, bias = _moe_layer(512)
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, p: jnp.sum(moe_apply(x, p, bias, k=2)[0])))(x, params))
    assert "cond[" not in text
    x, share, bias = _moe_share()
    text = str(jax.make_jaxpr(
        lambda x, p: _share_apply(x, p, bias)[0])(x, share))
    assert text.count("cond[") == 1


# (tokens, per token, hidden, width, held, experts, gate) of the two
# expert cells.
_CELL_SHAPES = {"glm47flash": (8192, 4, 2048, 1536, 8, 64, "silu"),
                "smallthinker21b": (16384, 6, 2560, 768, 16, 64, "relu")}


def _routed_shapes(tokens, k, d, f, held, experts):
    bf16, f32 = jnp.bfloat16, jnp.float32
    return (jax.ShapeDtypeStruct((tokens, d), bf16),
            jax.ShapeDtypeStruct((held, d, f), f32),
            jax.ShapeDtypeStruct((held, d, f), f32),
            jax.ShapeDtypeStruct((held, f, d), f32),
            jax.ShapeDtypeStruct((tokens, k), jnp.int32),
            jax.ShapeDtypeStruct((tokens, k), f32),
            jax.ShapeDtypeStruct((experts,), f32))


@pytest.mark.parametrize("cell", list(_CELL_SHAPES))
def test_moe_sized_branch_holds_no_row_for_every_pair(cell):
    """At the shapes of ``glm47flash-seq4096-1chip`` (8192 tokens, k 4,
    8 of 64 experts, hidden 2048, expert width 1536: 8192 of 32768
    rows) and of ``smallthinker21b-seq16384-1chip`` (49152 of 98304)
    nothing in the sized branch, forward or backward, has a row for
    every pair at either width."""
    tokens, k, d, f, held, experts, gate = _CELL_SHAPES[cell]
    rows = moe_lib.sized_rows(tokens * k, held, experts)
    assert rows == {"glm47flash": 8192, "smallthinker21b": 49152}[cell]

    def loss(x, w_gate, w_up, w_down, chosen, weights, drawn):
        return jnp.sum(_whole_sized(
            rows, x, w_gate, w_up, w_down, chosen, weights, drawn, 0,
            gate=gate).astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 5)))(
        *_routed_shapes(tokens, k, d, f, held, experts))
    seen = set(_all_shapes(jaxpr.jaxpr))
    assert (rows, d) in seen and (rows, f) in seen
    assert not {(tokens * k, d), (tokens * k, f)} & seen


@pytest.mark.parametrize("cell", list(_CELL_SHAPES))
def test_moe_sized_way_back_makes_no_forward_again(cell):
    """The backward pass's sized branch at the two cells' shapes: no
    sort, the tokens' and the cotangent's rows gathered, the tokens'
    gradient gathered back a choice at a time, and six grouped
    products, three to the rows and three to the weights, where making
    the forward again had three more; what it reads was kept on
    ``rows`` rows, ``kept_bytes`` of them, and nothing it makes has a
    row for every pair."""
    tokens, k, d, f, held, experts, gate = _CELL_SHAPES[cell]
    rows = moe_lib.sized_rows(tokens * k, held, experts)
    routed = (*_routed_shapes(tokens, k, d, f, held, experts), 0)
    x, w_gate, w_up, _, chosen, _, drawn, first_held = routed
    kept = jax.eval_shape(
        lambda *args: moe_lib._sized_rows(rows, *args, first_held),
        x, w_gate, w_up, chosen, drawn)
    assert [a.shape for a in kept] == [(rows, f), (rows, f), (rows,)]
    assert sum(a.size * a.dtype.itemsize for a in kept) == (
        moe_lib.kept_bytes(rows, f)) == {
        "glm47flash": 50_364_416, "smallthinker21b": 151_191_552}[cell]

    jaxpr = jax.make_jaxpr(
        lambda g, kept, *routed: moe_lib._sized_back(
            rows, gate, g, kept, *routed))(x, kept, *routed)
    eqns = list(_all_eqns(jaxpr.jaxpr))
    names = [eqn.primitive.name for eqn in eqns]
    assert "sort" not in names and "argsort" not in names
    products = [eqn.outvars[0].aval.shape for eqn in eqns
                if eqn.primitive.name == "ragged_dot_general"]
    assert sorted(products) == sorted(
        [(rows, f), (rows, d), (rows, d),
         (held, d, f), (held, d, f), (held, f, d)])
    # Rows move by gathers alone, both ways: the tokens' and the
    # cotangent's into the buffers, and the buffers' back into token
    # order a choice at a time (``glm47flash`` has as many rows as
    # tokens: tell them apart by what is read). Nothing scatters a row.
    moved = [(eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape)
             for name, eqn in zip(names, eqns) if name == "gather"
             and eqn.outvars[0].aval.shape[-1:] == (d,)]
    assert moved.count(((tokens, d), (rows, d))) == 2 + k * (tokens == rows)
    assert moved.count(((rows, d), (tokens, d))) == k + 2 * (tokens == rows)
    assert len(moved) == 2 + k
    assert not [eqn for name, eqn in zip(names, eqns)
                if name.startswith("scatter")
                and eqn.invars[2].aval.shape[-1:] == (d,)]
    seen = set(_all_shapes(jaxpr.jaxpr))
    assert not {(tokens * k, d), (tokens * k, f)} & seen


@pytest.mark.parametrize("remat", [False, True, "dots", "flash"])
def test_moe_gradients_under_remat_are_the_layers_own(remat):
    """A block whose expert layer holds 2 of 8 experts, under each
    ``TransformerConfig.remat``: recomputation makes the layer's
    forward pass again, what it keeps included, and changes no
    gradient."""
    from horovod_tpu.models import TransformerConfig, TransformerLM
    from horovod_tpu.parallel.moe import MoEConfig

    def grads(remat):
        cfg = TransformerConfig(
            vocab_size=64, hidden=16, layers=2, heads=2, max_len=256,
            norm="rmsnorm", bias=False, mlp="swiglu", remat=remat,
            moe=MoEConfig(experts=8, per_token=2, width=32,
                          held=(_HELD, _HELD + 2), scale=1.8))
        model = TransformerLM(cfg)
        ids = jax.random.randint(jax.random.PRNGKey(3), (2, 256), 0, 64)
        variables = model.init(jax.random.PRNGKey(4), ids)

        def loss(params):
            logits, _ = model.apply(
                {**variables, "params": params}, ids,
                mutable=[moe_lib.STATE])
            return jnp.mean(logits.astype(jnp.float32) ** 2)
        return jax.grad(loss)(variables["params"])

    want = grads(False)
    assert any("w_gate" in jax.tree_util.keystr(path)
               and float(jnp.abs(leaf).max()) > 0
               for path, leaf in jax.tree_util.tree_leaves_with_path(want))
    if remat:
        _assert_trees_close(grads(remat), want, atol=1e-6, rtol=1e-5)


# -- GSPMD sharding rules --------------------------------------------------

def test_param_specs_shard_qkv_and_tolerate_missing_axes():
    from jax.sharding import Mesh
    from horovod_tpu.parallel.sharding import make_param_specs

    mesh = make_mesh(MeshConfig(dp=-1, tp=2))
    params = {
        "block_0": {"attn": {"qkv": {"kernel": jnp.zeros((64, 3, 4, 16)),
                                     "bias": jnp.zeros((3, 4, 16))},
                             "proj": {"kernel": jnp.zeros((4, 16, 64))}},
                    "mlp_in": {"kernel": jnp.zeros((64, 256))}},
        "odd": {"weird": jnp.zeros((7, 5))},
    }
    specs = make_param_specs(params, mesh)
    assert specs["block_0"]["attn"]["qkv"]["kernel"] == P(None, None, "tp",
                                                          None)
    assert specs["block_0"]["attn"]["proj"]["kernel"] == P("tp", None, None)
    assert specs["block_0"]["mlp_in"]["kernel"] == P(None, "tp")
    assert specs["odd"]["weird"] == P()

    # A mesh without the axes named in the moe rules must not crash.
    small = Mesh(np.array(jax.devices()[:2]), ("fsdp", ))
    specs2 = make_param_specs({"moe": {"w_up": jnp.zeros((8, 16, 32))}},
                              small)
    assert specs2["moe"]["w_up"] == P()


def test_gspmd_sharded_matmul_matches_replicated():
    from horovod_tpu.parallel.sharding import shard_params

    mesh = make_mesh(MeshConfig(dp=-1, tp=2))
    params = {"mlp_in": {"kernel": _rand((32, 64), 0)},
              "mlp_out": {"kernel": _rand((64, 32), 1)}}
    x = _rand((16, 32), 2)

    def f(p, x):
        return jnp.tanh(x @ p["mlp_in"]["kernel"]) @ p["mlp_out"]["kernel"]

    sharded = shard_params(params, mesh)
    out = jax.jit(f)(sharded, x)
    ref = f(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_fsdp_training_matches_replicated():
    """ZeRO-3/FSDP end to end: parameters stored SHARDED along the fsdp
    axis (transformer_param_rules fsdp_axis), the jitted train step
    all-gathers them at use and reduce-scatters gradients — XLA inserts
    the collectives from the shardings (the scaling-book recipe). Oracle:
    the same steps on replicated params must give identical losses and
    parameters."""
    import optax
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                TransformerLM)
    from horovod_tpu.parallel.sharding import (batch_spec,
                                               make_param_specs)

    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    cfg = TransformerConfig(vocab_size=128, hidden=32, layers=2, heads=2,
                            max_len=16, dtype=jnp.float32, causal=True,
                            use_rope=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 16), jnp.int32))
    specs = make_param_specs(params, mesh)
    # The point of the test is SHARDED storage: at least one big kernel
    # must actually carry the fsdp axis.
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    assert any("fsdp" in str(s) for s in flat_specs), flat_specs

    opt = optax.adamw(1e-2)

    def loss_fn(p, batch):
        x, y = batch
        logits = model.apply(p, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    def step(p, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        updates, opt_state = opt.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, loss

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randint(0, 128, size=(8, 16)))
    y = jnp.asarray(rng.randint(0, 128, size=(8, 16)))

    # Sharded run: params placed per spec, batch split over dp x fsdp.
    p_shard = jax.tree.map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
        params, specs)
    opt_state = opt.init(p_shard)
    bspec = NamedSharding(mesh, batch_spec(extra_dims=1))
    xb = jax.device_put(x, bspec)
    yb = jax.device_put(y, bspec)
    jstep = jax.jit(step)
    losses = []
    for _ in range(3):
        p_shard, opt_state, loss = jstep(p_shard, opt_state, (xb, yb))
        losses.append(float(loss))

    # Replicated oracle on one device.
    p_ref, s_ref = params, opt.init(params)
    ref_losses = []
    for _ in range(3):
        p_ref, s_ref, loss = step(p_ref, s_ref, (x, y))
        ref_losses.append(float(loss))

    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(p_shard), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
