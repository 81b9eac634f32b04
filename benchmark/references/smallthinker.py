"""Plain reference of the ``smallthinker`` family (SmallThinker-21BA3B-
Instruct): a causal decoder of pre-RMSNorm blocks, every one with an
expert FFN, whose attention layers differ by a published pattern: one
layer in four sees every key before the query and has no positions, the
others see a window of keys and rotate q and k. Grouped K/V heads, a
head dimension that is not ``hidden / heads``, a softmax router that
reads the attention's input, ReGLU experts, no shared expert, an untied
head. It reads the parameter tree the program's ``TransformerLM`` reads,
and shares no code with it: no kernel, no sort, no grouped product, no
flax.

Published description: the model's ``config.json`` (the configuration
file's ``source``); Song et al., "SmallThinker: A Family of Efficient
Large Language Models Natively Trained for Local Deployment",
arXiv:2507.20984. What the source does not state is listed in the
configuration file under ``assumed``. The equations, ``l`` a layer's
index in the published model, ``x`` its input ``[T, d]``:

    h = RMSNorm_1(x);  r = h W_r                      # [T, 64]
    q, k, v = h W_q, h W_k, h W_v                      # 28, 4, 4 heads of 128
    rope(q, k; theta, all 128 lanes) where rope_layout[l] == 1
    s_ij = q_i . k_j / sqrt(128), kept where j <= i and
           (sliding_window_layout[l] == 0 or i - j < sliding_window_size);
           query head n reads K/V head n // 7
    x1 = x + concat_heads(softmax_j(s) v) W_o
    u = RMSNorm_2(x1);  C = top-6 of r
    w_e = exp(r_e) / sum_{c in C} exp(r_c), e in C     # softmax over all 64,
                                                       # renormalised over C
    out = x1 + sum_{e in C} w_e W_down,e (relu(W_gate,e u) * (W_up,e u))
    loss: logits = RMSNorm_f(x) W_head, float32; mean cross-entropy.

The reference is one chip's share of a deployment, as the program is:
it routes over all ``moe_num_primary_experts_published`` experts and
computes the experts ``experts_held`` only, each applied densely to
every token and weighted by the token's weight for it, or by 0; what the
absent experts would add is left out in both.

Departures, all of them about memory and none about a number: the score
matrix is made ``QUERY_BLOCK`` query rows at a time against the keys
those rows can see (in the full layer against every key, the ones past
the diagonal masked), and the logits ``LOGIT_BLOCK`` positions at a
time, each block made again on the way back; each half of a block
(attention, experts) and each held expert are made again on the way
back too (``jax.checkpoint``), so that 16,384 float32 positions of four
layers fit beside the reference's own AdamW state. The blocks are the
iterations of a ``lax.scan``, so that the program is small to compile.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references import common

QUERY_BLOCK = 1024      # rows of the score matrix held at a time
LOGIT_BLOCK = 4096      # positions whose logits are held at a time


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def _held(cfg):
    first, end = cfg["experts_held"]
    return end - first


def windowed(cfg, i):
    """Whether layer ``i`` of the stack that is run (the published
    model's first ``num_hidden_layers``) sees a window of keys only."""
    return bool(cfg["sliding_window_layout"][i])


def rotates(cfg, i):
    return bool(cfg["rope_layout"][i])


def kinds(cfg):
    """The program's kind of every layer run (``models/transformer.py:
    PLAIN``), from the two published layouts."""
    return [("sliding" if windowed(cfg, i) else "full")
            + ("_rope" if rotates(cfg, i) else "")
            for i in range(cfg["num_hidden_layers"])]


def init_params(cfg, key):
    """The weights, made from ``key`` in one traced call: kernels normal
    with variance 1/fan_in, RMSNorm scales 1, embedding rows normal with
    variance 1 (a lookup has no fan-in; the configuration file's
    ``assumed`` says what rows of norm 1 did to the routing)."""
    h, heads, kv, hd = _dims(cfg)
    width, experts = cfg["moe_ffn_hidden_size"], cfg[
        "moe_num_primary_experts_published"]
    held, vocab = _held(cfg), cfg["vocab_size"]
    keys = iter(jax.random.split(key, 64 * cfg["num_hidden_layers"] + 8))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(
            fan_in)

    def ones():
        return {"scale": jnp.ones((h,))}

    backbone = {"tok_embed": {"embedding": normal((vocab, h), 1)},
                "ln_f": ones()}
    for i in range(cfg["num_hidden_layers"]):
        backbone[f"block_{i}"] = {
            "ln1": ones(), "ln2": ones(),
            "attn": {
                # q's heads, then k's, then v's, from one product.
                "qkv": {"kernel": normal((h, heads + 2 * kv, hd), h)},
                "proj": {"kernel": normal((heads, hd, h), heads * hd)}},
            "moe": {"router": normal((h, experts), h),
                    "w_gate": normal((held, h, width), h),
                    "w_up": normal((held, h, width), h),
                    "w_down": normal((held, width, h), width)}}
    return {"params": {"backbone": backbone,
                       "lm_head": {"kernel": normal((h, vocab), h)}}}


def init_aux(cfg):
    """The non-trained state of the program's expert layer: a selection
    bias, which this family has none of (zeros, and nothing here reads
    it), and the tokens each expert drew in the last step, which the
    program fills in."""
    experts = cfg["moe_num_primary_experts_published"]
    return {"moe_state": {"backbone": {
        f"block_{i}": {"moe": {
            "bias": jnp.zeros((experts,), jnp.float32),
            "expert_tokens": jnp.zeros((experts,), jnp.float32)}}
        for i in range(cfg["num_hidden_layers"])}}}


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


def keep_mask(rows, keys, window):
    """[rows, keys] of booleans: query at position ``rows[i]`` sees the
    key at position ``keys[j]``: ``j <= i`` and, under a window, ``i - j
    < window`` (the query itself is the window's last key)."""
    ahead = rows[:, None] - keys[None, :]
    keep = ahead >= 0
    if window is not None:
        keep = jnp.logical_and(keep, ahead < window)
    return keep


def _attention(q, k, v, window, precision):
    """Causal softmax attention, a block of query rows at a time (one
    ``lax.scan`` over the blocks) against the keys the block can see:
    every key where there is no window, masked past the diagonal; under
    a window the ``window - 1 + block`` keys that end with the block's
    last row. q: [b, s, heads, d]; k, v: [b, s, kv, d], read by a group
    of ``heads / kv`` query heads each."""
    seq, heads, d = q.shape[1], q.shape[2], q.shape[-1]
    group = heads // k.shape[2]
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)
    span = seq if window is None else min(seq, window - 1 + block)

    @jax.checkpoint
    def rows(_, start):
        first = jnp.clip(start + block - span, 0, seq - span)
        qi = lax.dynamic_slice_in_dim(q, start, block, axis=1)
        ki, vi = (jnp.repeat(lax.dynamic_slice_in_dim(x, first, span, axis=1),
                             group, axis=2) for x in (k, v))
        scores = common.einsum("bqnd,bknd->bnqk", qi, ki, precision)
        scores = scores / math.sqrt(d)
        keep = keep_mask(start + jnp.arange(block), first + jnp.arange(span),
                         window)
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return None, common.einsum("bnqk,bknd->bqnd", probs, vi, precision)

    out = lax.scan(rows, None, jnp.arange(0, seq, block))[1]
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def attention(h, p, cfg, i, precision="float32"):
    """Layer ``i``'s attention on its normed input ``h`` [b, s, d]."""
    _, heads, kv, _ = _dims(cfg)
    qkv = common.einsum("bsh,hnd->bsnd", h, p["qkv"]["kernel"], precision)
    q, k, v = (qkv[:, :, :heads], qkv[:, :, heads:heads + kv],
               qkv[:, :, heads + kv:])
    if rotates(cfg, i):
        q, k = (_rope(x, cfg["rope_theta"]) for x in (q, k))
    window = cfg["sliding_window_size"] if windowed(cfg, i) else None
    a = _attention(q, k, v, window, precision)
    return common.einsum("bsnd,ndh->bsh", a, p["proj"]["kernel"], precision)


def _reglu(x, gate, up, down, precision):
    h = jax.nn.relu(common.einsum("bsh,hi->bsi", x, gate, precision))
    h = h * common.einsum("bsh,hi->bsi", x, up, precision)
    return common.einsum("bsi,ih->bsh", h, down, precision)


def route(r, cfg):
    """[.., experts] weights from router logits ``r``: the softmax over
    the chosen ``moe_num_active_primary_experts``, 0 for the others."""
    _, chosen = lax.top_k(r, cfg["moe_num_active_primary_experts"])
    is_chosen = jnp.sum(jax.nn.one_hot(chosen, r.shape[-1]), axis=-2) > 0
    return jax.nn.softmax(jnp.where(is_chosen, r, -jnp.inf), axis=-1)


def expert_ffn(u, h, p, cfg, precision="float32"):
    """The expert layer's share on its input ``u``, routed by what
    attention read (``h``): routing over all the model's experts, the
    held experts' part of the sum."""
    first = cfg["experts_held"][0]
    weights = route(jnp.einsum("bsh,he->bse", h, p["router"],
                               precision=lax.Precision.HIGHEST), cfg)

    @jax.checkpoint
    def term(w_gate, w_up, w_down, weight):
        return weight[..., None] * _reglu(u, w_gate, w_up, w_down, precision)

    # The running sum is outside what is made again, so that the way
    # back keeps no copy of it a step.
    held = p["w_gate"].shape[0]
    mine = jnp.moveaxis(weights[..., first:first + held], -1, 0)
    return lax.scan(lambda y, expert: (y + term(*expert), None),
                    jnp.zeros_like(u),
                    (p["w_gate"], p["w_up"], p["w_down"], mine))[0]


def _block(x, p, cfg, i, precision):
    """One pre-norm block. Each half is made again on the way back, so
    that the float32 activations of attention are not held through the
    experts' backward pass."""
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def mix(x, p):
        h = _rms_norm(x, p["ln1"], eps)
        return x + attention(h, p["attn"], cfg, i, precision)

    @jax.checkpoint
    def ffn(x1, x, p):
        # The router reads the attention's input, the experts the FFN's.
        return x1 + expert_ffn(_rms_norm(x1, p["ln2"], eps),
                               _rms_norm(x, p["ln1"], eps), p["moe"], cfg,
                               precision)

    return ffn(mix(x, p), x, p)


def hidden_fn(params, tokens, cfg, precision="float32"):
    bb = params["params"]["backbone"]
    x = bb["tok_embed"]["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, bb[f"block_{i}"], cfg, i, precision)
    return _rms_norm(x, bb["ln_f"], cfg["rms_norm_eps"])


def logits_fn(params, tokens, cfg, precision="float32"):
    return common.einsum("bsh,hv->bsv",
                         hidden_fn(params, tokens, cfg, precision),
                         params["params"]["lm_head"]["kernel"], precision)


def loss_fn(params, aux, batch, cfg, precision="float32"):
    """Mean next-token cross-entropy over the vocabulary slice, the
    logits of ``LOGIT_BLOCK`` positions at a time (one ``lax.scan``),
    each block's made again on the way back."""
    tokens, targets = batch
    kernel = params["params"]["lm_head"]["kernel"]
    h = hidden_fn(params, tokens, cfg, precision)
    block = min(LOGIT_BLOCK, h.shape[1])
    assert h.shape[1] % block == 0, (h.shape, block)

    def blocks(x):      # [b, s, ...] -> [s / block, b, block, ...]
        return jnp.moveaxis(x.reshape(x.shape[0], -1, block, *x.shape[2:]),
                            1, 0)

    @jax.checkpoint
    def xent(total, at):
        h, targets = at
        return total + common.softmax_xent_mean(
            common.einsum("bsh,hv->bsv", h, kernel, precision), targets), None

    total = lax.scan(xent, jnp.zeros(()), (blocks(h), blocks(targets)))[0]
    return total / (h.shape[1] // block), aux


# ---- what the mathematics requires, for ``mfu`` and the rooflines --------

def attention_layers(cfg):
    return cfg["num_hidden_layers"]


def expert_params(cfg):
    """Matrix parameters a token meets in one expert layer's products:
    (routed, shared). Routed is an expectation: six choices, each held
    here with probability held / published under uniform routing; the
    program computes the real draw. There is no shared expert."""
    one = 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]
    return (cfg["moe_num_active_primary_experts"] * _held(cfg)
            / cfg["moe_num_primary_experts_published"] * one, 0)


def keys_seen(seq, window):
    """Sum over a sequence's queries of the keys each sees."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_work(cfg, traffic):
    """(operations, bytes) one row's attention requires, forward and
    backward, over the layers: a product of q with the keys a query sees
    and one of the weights with their values, ``head_dim`` wide, for
    each of the query heads, and twice that again on the way back; a
    windowed layer at its window's keys only. q, k, v, the output and
    their gradients cross HBM once, in the activations' two bytes: q,
    k, v in and o out forward; q, k, v, o, do in and dq, dk, dv out
    backward."""
    _, heads, kv, hd = _dims(cfg)
    seq = traffic["seq_len"]
    operations = moved = 0
    for i in range(cfg["num_hidden_layers"]):
        window = cfg["sliding_window_size"] if windowed(cfg, i) else None
        operations += 3 * 2 * 2 * heads * hd * keys_seen(seq, window)
        q, k_and_v = heads * hd, 2 * kv * hd
        moved += 2 * seq * ((2 * q + k_and_v) + (3 * q + k_and_v)
                            + (q + k_and_v))
    return operations, moved


def block_params(cfg):
    """Matrix parameters a token meets in one block's products outside
    attention's scores: q, k, v and the output projection, the router,
    the experts by expectation."""
    h, heads, kv, hd = _dims(cfg)
    return (h * (heads + 2 * kv) * hd + heads * hd * h
            + h * cfg["moe_num_primary_experts_published"]
            + sum(expert_params(cfg)))


def flops_per_row(cfg, traffic):
    """FLOPs one row (a sequence) requires, forward and backward. One
    multiply-add is 2 FLOPs, a step is the forward product and two
    backward (x 3); the embedding is a gather and counts nothing;
    attention as ``attention_work`` (the causal half, the window's keys
    only); the routed experts by expectation (``expert_params``); no
    recomputation, no optimizer, no element-wise work."""
    products = (cfg["num_hidden_layers"] * block_params(cfg)
                + cfg["hidden_size"] * cfg["vocab_size"])
    return (6 * traffic["seq_len"] * products
            + attention_work(cfg, traffic)[0])


def expert_products(cfg, traffic):
    """(FLOPs, bytes) a step on one chip requires of the products under
    scope ``hvd_moe/experts``: the held experts' grouped products,
    forward and backward, over every layer. Bytes: each weight read once
    forward and once backward and its gradient written once, as float32;
    the tokens in and out as bfloat16, forward and backward."""
    tokens = traffic["rows_per_chip"] * traffic["seq_len"]
    layers = cfg["num_hidden_layers"]
    weights = _held(cfg) * 3 * cfg["hidden_size"] * cfg[
        "moe_ffn_hidden_size"]
    moved = 3 * 4 * weights + 4 * 2 * tokens * cfg["hidden_size"]
    return (layers * 6 * tokens * sum(expert_params(cfg)), layers * moved)
