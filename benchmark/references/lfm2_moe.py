"""Plain reference of the ``lfm2_moe`` family (LFM2-24B-A2B): a causal
decoder of pre-RMSNorm blocks in which three token mixers in four are
gated short convolutions and the fourth is grouped-head softmax
attention with a norm on every head's q and k; a bias-free SwiGLU FFN in
the leading layers and a sigmoid-routed expert FFN without a shared
expert in the others; a head that is the embedding's transpose. It reads
the parameter tree the program's ``TransformerLM`` reads, and shares no
code with it: no kernel, no sort, no grouped product, no flax.

Published description: the model's ``config.json`` (the configuration
file's ``source``, ``model_type`` ``lfm2_moe``). What the source does
not state is listed in the configuration file under ``assumed``. The
equations, ``l`` a layer's index in the published model, ``x`` its input
``[T, d]``:

    h = RMSNorm_op(x)
    layer_types[l] == "conv":
        B, C, z = thirds of (h W_in)                  # W_in: d x 3d
        u   = B * z
        c_t = sum_{j < taps} w_j * u_{t - (taps - 1) + j}
                                                      # one filter a channel, zeros before
                                                      # the row's start, the last tap meets t
        a   = (C * c) W_out                           # no activation anywhere
    layer_types[l] == "full_attention":
        q, k, v = h W_q, h W_k, h W_v                 # 32, 8, 8 heads of 64
        q, k = RMSNorm_q(q), RMSNorm_k(k)             # over a head's lanes, one gain each
        q, k = rope(q, k; theta, all lanes)
        a   = softmax_{j <= i}(q_i . k_j / sqrt(64)) v W_o
                                                      # query head n reads K/V head n // 4
    x1 = x + a;  f = RMSNorm_ffn(x1)
    l < num_dense_layers:  y = W_2 (silu(W_1 f) * (W_3 f))
    else:  s = sigmoid(f W_r)                          # [T, 64], float32
           S = top-4 of (s + b)                        # b selects only
           w_e = s_e / (sum_{c in S} s_c + 1e-6), e in S
           y = sum_{e in S} w_e W_2,e (silu(W_1,e f) * (W_3,e f))
    out = x1 + y
    loss: logits = RMSNorm_f(x) E^T, float32; mean cross-entropy.

The reference is one chip's share of a deployment, as the program is:
it runs the published layers ``layers_held``, routes over all
``num_experts_published`` experts and computes the experts
``experts_held`` only, each applied densely to every token and weighted
by the token's weight for it, or by 0; what the absent experts would add
is left out in both.

Departures, all of them about memory and none about a number: the score
matrix is made ``QUERY_BLOCK`` query rows at a time against every key
(the ones past the diagonal masked) and the logits ``LOGIT_BLOCK``
positions at a time, each block made again on the way back; each half of
a block (mixer, FFN) and each held expert are made again on the way back
too (``jax.checkpoint``), so that 8,192 float32 positions of five layers
fit beside the reference's own AdamW state. The convolution's taps are
element-wise work, as the gates are, and stay in float32 under every
control precision: a control lowers the operands of the matrix products.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references import common

BIAS_SEED = 20260928    # the selection bias: fixed, not the run's seed
QUERY_BLOCK = 1024      # rows of the score matrix held at a time
LOGIT_BLOCK = 4096      # positions whose logits are held at a time


def _dims(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return h, heads, cfg["num_key_value_heads"], h // heads


def _held(cfg):
    first, end = cfg["experts_held"]
    return end - first


def layers(cfg):
    """Indices in the published model of the layers that are run."""
    first, end = cfg["layers_held"]
    assert end - first == cfg["num_hidden_layers"], cfg["layers_held"]
    return list(range(first, end))


def kinds(cfg):
    """The program's kind of every layer run (``models/transformer.py:
    MIXERS``), from the published ``layer_types`` at ``layers_held``."""
    names = {"conv": "conv", "full_attention": "full_rope"}
    return [names[cfg["layer_types"][l]] for l in layers(cfg)]


def is_expert(cfg, i):
    """Whether block ``i`` of the stack that is run has the expert
    layer: every one past the leading ``num_dense_layers`` held here."""
    return i >= cfg["num_dense_layers"]


def init_params(cfg, key):
    """The weights, made from ``key`` in one traced call: kernels normal
    with variance 1/fan_in (a filter's fan-in is its taps), norms 1,
    embedding rows normal with variance 1 / ``embedding_fan_in`` of the
    configuration file (a lookup has no fan-in of its own;
    ``assumed.initializer`` says why that value)."""
    h, heads, kv, hd = _dims(cfg)
    inter, width = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    experts, held = cfg["num_experts_published"], _held(cfg)
    taps, vocab = cfg["conv_L_cache"], cfg["vocab_size"]
    keys = iter(jax.random.split(key, 16 * cfg["num_hidden_layers"] + 8))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(
            fan_in)

    def ones(n=h):
        return {"scale": jnp.ones((n,))}

    backbone = {"tok_embed": {"embedding": normal(
        (vocab, h), cfg["embedding_fan_in"])}, "ln_f": ones()}
    for i, kind in enumerate(kinds(cfg)):
        block = {"ln1": ones(), "ln2": ones()}
        if kind == "conv":
            block["conv"] = {
                "in_proj": {"kernel": normal((h, 3 * h), h)},   # B, C, z
                "conv_kernel": normal((taps, h), taps),
                "out_proj": {"kernel": normal((h, h), h)}}
        else:
            block["attn"] = {
                # q's heads, then k's, then v's, from one product.
                "qkv": {"kernel": normal((h, heads + 2 * kv, hd), h)},
                "q_norm": ones(hd), "k_norm": ones(hd),
                "proj": {"kernel": normal((heads, hd, h), heads * hd)}}
        if is_expert(cfg, i):
            block["moe"] = {"router": normal((h, experts), h),
                            "w_gate": normal((held, h, width), h),
                            "w_up": normal((held, h, width), h),
                            "w_down": normal((held, width, h), width)}
        else:
            block.update(mlp_gate={"kernel": normal((h, inter), h)},
                         mlp_in={"kernel": normal((h, inter), h)},
                         mlp_out={"kernel": normal((inter, h), inter)})
        backbone[f"block_{i}"] = block
    return {"params": {"backbone": backbone}}


def init_aux(cfg):
    """The non-trained state: each expert layer's selection bias (small,
    fixed: ``assumed`` in the configuration file) and the tokens each
    expert drew in the last step, which the program fills in and nothing
    here reads."""
    experts = cfg["num_experts_published"]
    state = {}
    for i in range(cfg["num_hidden_layers"]):
        if is_expert(cfg, i):
            state[f"block_{i}"] = {"moe": {
                "bias": cfg["router_bias_scale"] * jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(BIAS_SEED), i),
                    (experts,), jnp.float32),
                "expert_tokens": jnp.zeros((experts,), jnp.float32)}}
    return {"moe_state": {"backbone": state}}


def _rms_norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * p["scale"]


def _rope(x, theta):
    """x: [b, s, n, d]. Rotate-half rotary embedding over all of d."""
    seq, half = x.shape[1], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(half) / half))
    angles = jnp.asarray(np.arange(seq)[:, None] * freqs[None, :],
                         jnp.float32)[None, :, None, :]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def shifts(u, kernel):
    """``c_t = sum_j kernel[j] u_{t - (taps - 1) + j}``: a depthwise
    causal convolution over positions as one shifted multiply-add a tap.
    u: [b, s, channels]; kernel: [taps, channels]."""
    taps, seq = kernel.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j] * padded[:, j:j + seq] for j in range(taps))


def short_conv(h, p, precision="float32"):
    """A conv layer's mixer on its normed input ``h`` [b, s, d]."""
    bcz = common.einsum("bsh,hi->bsi", h, p["in_proj"]["kernel"], precision)
    b, c, z = jnp.split(bcz, 3, axis=-1)
    return common.einsum("bsi,ih->bsh", c * shifts(b * z, p["conv_kernel"]),
                         p["out_proj"]["kernel"], precision)


def _attention(q, k, v, precision):
    """Causal softmax attention, a block of query rows at a time (one
    ``lax.scan`` over the blocks) against every key, the ones past the
    diagonal masked. q: [b, s, heads, d]; k, v: [b, s, kv, d], read by a
    group of ``heads / kv`` query heads each."""
    seq, heads, d = q.shape[1], q.shape[2], q.shape[-1]
    group = heads // k.shape[2]
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))

    @jax.checkpoint
    def rows(_, start):
        qi = lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = common.einsum("bqnd,bknd->bnqk", qi, k, precision)
        scores = scores / math.sqrt(d)
        keep = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)[None]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return None, common.einsum("bnqk,bknd->bqnd", probs, v, precision)

    out = lax.scan(rows, None, jnp.arange(0, seq, block))[1]
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def qkv(h, p, cfg, precision="float32"):
    """q, k, v of a full-attention layer as attention takes them: q and
    k normed over each head's lanes, then rotated; v as the product
    gives it."""
    _, heads, kv, _ = _dims(cfg)
    eps, theta = cfg["norm_eps"], cfg["rope_parameters"]["rope_theta"]
    out = common.einsum("bsh,hnd->bsnd", h, p["qkv"]["kernel"], precision)
    q, k, v = (out[:, :, :heads], out[:, :, heads:heads + kv],
               out[:, :, heads + kv:])
    return (_rope(_rms_norm(q, p["q_norm"], eps), theta),
            _rope(_rms_norm(k, p["k_norm"], eps), theta), v)


def attention(h, p, cfg, precision="float32"):
    """A full-attention layer's mixer on its normed input ``h``."""
    a = _attention(*qkv(h, p, cfg, precision), precision)
    return common.einsum("bsnd,ndh->bsh", a, p["proj"]["kernel"], precision)


def _swiglu(x, gate, up, down, precision):
    h = jax.nn.silu(common.einsum("bsh,hi->bsi", x, gate, precision))
    h = h * common.einsum("bsh,hi->bsi", x, up, precision)
    return common.einsum("bsi,ih->bsh", h, down, precision)


def route(scores, bias, cfg):
    """[.., experts] weights from the router's sigmoid ``scores``: the
    ``num_experts_per_tok`` largest of ``scores + bias`` keep their own
    score over the chosen scores' sum (``norm_topk_prob``), the others
    get 0."""
    assert cfg["norm_topk_prob"] and cfg["use_expert_bias"]
    _, chosen = lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    picked = scores * jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]),
                              axis=-2)
    return cfg["routed_scaling_factor"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-6)


def expert_ffn(f, p, bias, cfg, precision="float32"):
    """The expert layer's share on its input ``f``: routing over all the
    model's experts, the held experts' part of the sum. No shared
    expert."""
    first = cfg["experts_held"][0]
    weights = route(jax.nn.sigmoid(jnp.einsum(
        "bsh,he->bse", f, p["router"], precision=lax.Precision.HIGHEST)),
        bias, cfg)

    @jax.checkpoint
    def term(w_gate, w_up, w_down, weight):
        return weight[..., None] * _swiglu(f, w_gate, w_up, w_down,
                                           precision)

    # The running sum is outside what is made again, so that the way
    # back keeps no copy of it a step.
    held = p["w_gate"].shape[0]
    mine = jnp.moveaxis(weights[..., first:first + held], -1, 0)
    return lax.scan(lambda y, expert: (y + term(*expert), None),
                    jnp.zeros_like(f),
                    (p["w_gate"], p["w_up"], p["w_down"], mine))[0]


def _block(x, p, bias, cfg, precision):
    """One pre-norm block. Each half is made again on the way back, so
    that the float32 activations of the mixer are not held through the
    FFN's backward pass."""
    eps = cfg["norm_eps"]

    @jax.checkpoint
    def mix(x, p):
        h = _rms_norm(x, p["ln1"], eps)
        if "conv" in p:
            return x + short_conv(h, p["conv"], precision)
        return x + attention(h, p["attn"], cfg, precision)

    @jax.checkpoint
    def ffn(x, p, bias):
        f = _rms_norm(x, p["ln2"], eps)
        if "moe" in p:
            return x + expert_ffn(f, p["moe"], bias, cfg, precision)
        return x + _swiglu(f, p["mlp_gate"]["kernel"], p["mlp_in"]["kernel"],
                           p["mlp_out"]["kernel"], precision)

    return ffn(mix(x, p), p, bias)


def hidden_fn(params, aux, tokens, cfg, precision="float32"):
    bb = params["params"]["backbone"]
    biases = aux["moe_state"]["backbone"]
    x = bb["tok_embed"]["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        name = f"block_{i}"
        bias = biases[name]["moe"]["bias"] if name in biases else None
        x = _block(x, bb[name], bias, cfg, precision)
    return _rms_norm(x, bb["ln_f"], cfg["norm_eps"])


def logits_fn(params, aux, tokens, cfg, precision="float32"):
    """Float32 logits under the tied head: the embedding's transpose."""
    return common.einsum(
        "bsh,vh->bsv", hidden_fn(params, aux, tokens, cfg, precision),
        params["params"]["backbone"]["tok_embed"]["embedding"], precision)


def loss_fn(params, aux, batch, cfg, precision="float32"):
    """Mean next-token cross-entropy over the vocabulary slice, the
    logits of ``LOGIT_BLOCK`` positions at a time (one ``lax.scan``),
    each block's made again on the way back."""
    tokens, targets = batch
    table = params["params"]["backbone"]["tok_embed"]["embedding"]
    h = hidden_fn(params, aux, tokens, cfg, precision)
    block = min(LOGIT_BLOCK, h.shape[1])
    assert h.shape[1] % block == 0, (h.shape, block)

    def blocks(x):      # [b, s, ...] -> [s / block, b, block, ...]
        return jnp.moveaxis(x.reshape(x.shape[0], -1, block, *x.shape[2:]),
                            1, 0)

    @jax.checkpoint
    def xent(total, at):
        h, targets = at
        return total + common.softmax_xent_mean(
            common.einsum("bsh,vh->bsv", h, table, precision), targets), None

    total = lax.scan(xent, jnp.zeros(()), (blocks(h), blocks(targets)))[0]
    return total / (h.shape[1] // block), aux


# ---- what the mathematics requires, for ``mfu`` and the rooflines --------

def attention_layers(cfg):
    return kinds(cfg).count("full_rope")


def conv_layers(cfg):
    return kinds(cfg).count("conv")


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def expert_params(cfg):
    """Matrix parameters a token meets in one expert layer's products:
    (routed, shared). Routed is an expectation: four choices, each held
    here with probability held / published under uniform routing; the
    program computes the real draw. There is no shared expert."""
    one = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return (cfg["num_experts_per_tok"] * _held(cfg)
            / cfg["num_experts_published"] * one, 0)


def attention_work(cfg, traffic):
    """(operations, bytes) one row's attention requires, forward and
    backward, over the full-attention layers: a product of q with the
    keys at or before it and one of the weights with their values, a
    head wide, for each of the query heads, and twice that again on the
    way back. q, k, v, the output and their gradients cross HBM once, in
    the activations' two bytes: q, k, v in and o out forward; q, k, v, o,
    do in and dq, dk, dv out backward."""
    _, heads, kv, hd = _dims(cfg)
    seq = traffic["seq_len"]
    seen = seq * (seq + 1) // 2
    q, k_and_v = heads * hd, 2 * kv * hd
    moved = 2 * seq * ((2 * q + k_and_v) + (3 * q + k_and_v)
                       + (q + k_and_v))
    n = attention_layers(cfg)
    return n * 3 * 2 * 2 * heads * hd * seen, n * moved


def conv_work(cfg, traffic):
    """(operations, bytes) one row requires of the conv mixers, forward
    and backward, over the conv layers: the two products (hidden to
    three times it, hidden to hidden) three times over; the gates and
    the taps are element-wise and count no operation. The mixer's input,
    ``[B, C, z]``, ``C * c`` and the output cross HBM once each with
    their gradients, in the activations' two bytes."""
    h, seq = cfg["hidden_size"], traffic["seq_len"]
    products = h * 3 * h + h * h
    moved = 2 * 2 * seq * (h + 3 * h + h + h)
    n = conv_layers(cfg)
    return n * 6 * seq * products, n * moved


def flops_per_row(cfg, traffic):
    """FLOPs one row (a sequence) requires, forward and backward. One
    multiply-add is 2 FLOPs, a step is the forward product and two
    backward (x 3); the embedding's gather counts nothing and the tied
    table counts once, as the head's product; attention as
    ``attention_work`` (the causal half); the routed experts by
    expectation (``expert_params``); no recomputation, no optimizer, no
    element-wise work."""
    h, heads, kv, hd = _dims(cfg)
    attention = h * (heads + 2 * kv) * hd + heads * hd * h
    dense = 3 * h * cfg["intermediate_size"]
    expert = h * cfg["num_experts_published"] + sum(expert_params(cfg))
    products = (attention_layers(cfg) * attention
                + cfg["num_dense_layers"] * dense
                + expert_layers(cfg) * expert + h * cfg["vocab_size"])
    return (6 * traffic["seq_len"] * products + conv_work(cfg, traffic)[0]
            + attention_work(cfg, traffic)[0])


def expert_products(cfg, traffic):
    """(FLOPs, bytes) a step on one chip requires of the products under
    scope ``hvd_moe/experts``: the held experts' grouped products,
    forward and backward, over every expert layer. Bytes: each weight
    read once forward and once backward and its gradient written once,
    as float32; the tokens in and out as bfloat16, forward and
    backward."""
    tokens = traffic["rows_per_chip"] * traffic["seq_len"]
    n = expert_layers(cfg)
    weights = _held(cfg) * 3 * cfg["hidden_size"] * cfg[
        "moe_intermediate_size"]
    moved = 3 * 4 * weights + 4 * 2 * tokens * cfg["hidden_size"]
    return (n * 6 * tokens * sum(expert_params(cfg)), n * moved)
