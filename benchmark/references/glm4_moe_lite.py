"""Plain reference of the ``glm4_moe_lite`` family (GLM-4.7-Flash): a
causal decoder of pre-RMSNorm blocks with multi-head latent attention, a
bias-free SwiGLU FFN in the leading layers and a sigmoid-routed expert
FFN with a shared expert in the others, an untied head, and
multi-token-prediction modules that share the embedding and the head.
It reads the parameter tree the program's ``TransformerLM`` reads, and
shares no code with it: no kernel, no sort, no grouped product.

Published description: the model's ``config.json`` (the configuration
file's ``source``); DeepSeek-V2, arXiv:2405.04434 (latent attention);
DeepSeek-V3, arXiv:2412.19437 (sigmoid routing with a selection bias,
section 2.1.2; multi-token prediction, section 2.2, eq. 21-25), which
``glm4_moe_lite`` follows. What the source does not state is listed in
the configuration file under ``assumed``.

The reference is one chip's share of a deployment, as the program is:
it routes over all ``n_routed_experts_published`` experts and computes
the experts ``experts_held`` only (by a dense mask: every held expert
on every token, weighted by the token's weight for it or by 0), so what
the absent experts would add is left out in both.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import flops
from benchmark.references import common

BIAS_SEED = 20260928    # the selection bias: fixed, not the run's seed
QUERY_BLOCK = 1024      # rows of the score matrix held at a time


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def _expert_layers(cfg):
    """Names of the blocks whose FFN is the expert layer, main model
    first, then the MTP modules' (paths under ``backbone``)."""
    main = [(f"block_{i}",) for i in range(cfg["first_k_dense_replace"],
                                           cfg["num_hidden_layers"])]
    return main + [(f"mtp_{i}", "block")
                   for i in range(cfg["num_nextn_predict_layers"])]


def init_params(cfg, key):
    """The weights, made from ``key`` in one traced call: kernels normal
    with variance 1/fan_in, RMSNorm scales 1."""
    h, heads, nope, rope, vd = _dims(cfg)
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    inter, width = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    experts = cfg["n_routed_experts_published"]
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    shared, vocab = cfg["n_shared_experts"] * width, cfg["vocab_size"]
    keys = iter(jax.random.split(key, 4096))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(
            fan_in)

    def ones(n):
        return {"scale": jnp.ones((n,))}

    def block(expert):
        out = {
            "ln1": ones(h), "ln2": ones(h),
            "attn": {
                "q_a": {"kernel": normal((h, q_rank), h)},
                "q_norm": ones(q_rank),
                "q_b": {"kernel": normal((q_rank, heads, nope + rope),
                                         q_rank)},
                "kv_a": {"kernel": normal((h, kv_rank + rope), h)},
                "kv_norm": ones(kv_rank),
                "kv_b": {"kernel": normal((kv_rank, heads, nope + vd),
                                          kv_rank)},
                "proj": {"kernel": normal((heads, vd, h), heads * vd)}}}
        if expert:
            out["moe"] = {
                "router": normal((h, experts), h),
                "w_gate": normal((held, h, width), h),
                "w_up": normal((held, h, width), h),
                "w_down": normal((held, width, h), width),
                "shared_gate": normal((h, shared), h),
                "shared_up": normal((h, shared), h),
                "shared_down": normal((shared, h), shared)}
        else:
            out.update(mlp_gate={"kernel": normal((h, inter), h)},
                       mlp_in={"kernel": normal((h, inter), h)},
                       mlp_out={"kernel": normal((inter, h), inter)})
        return out

    backbone = {"tok_embed": {"embedding": normal((vocab, h), h)},
                "ln_f": ones(h)}
    for i in range(cfg["num_hidden_layers"]):
        backbone[f"block_{i}"] = block(i >= cfg["first_k_dense_replace"])
    for i in range(cfg["num_nextn_predict_layers"]):
        backbone[f"mtp_{i}"] = {
            "embed_norm": ones(h), "hidden_norm": ones(h),
            "proj": {"kernel": normal((2 * h, h), 2 * h)},
            "block": block(True), "ln_f": ones(h)}
    return {"params": {"backbone": backbone,
                       "lm_head": {"kernel": normal((h, vocab), h)}}}


def init_aux(cfg):
    """The non-trained state: each expert layer's selection bias (small,
    fixed: ``assumed`` in the configuration file) and the tokens each
    expert drew in the last step, which the program fills in and nothing
    here reads."""
    experts = cfg["n_routed_experts_published"]
    state = {}
    for n, path in enumerate(_expert_layers(cfg)):
        node = state
        for name in path + ("moe",):
            node = node.setdefault(name, {})
        node["bias"] = cfg["router_bias_scale"] * jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(BIAS_SEED), n),
            (experts,), jnp.float32)
        node["expert_tokens"] = jnp.zeros((experts,), jnp.float32)
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


def _attention(q, k, v, precision):
    """Causal softmax attention, a block of query rows at a time against
    the keys at or before it. q, k, v: [b, s, n, d]. Each block is made
    again on the way back from the whole of k and v, so that neither its
    scores nor its slices of k and v are kept."""
    seq, d = q.shape[1], q.shape[-1]
    block = min(QUERY_BLOCK, seq)

    def rows(start, qi, k, v):
        ki, vi = k[:, :start + block], v[:, :start + block]
        scores = common.einsum("bqnd,bknd->bnqk", qi, ki, precision)
        scores = scores / math.sqrt(d)
        keep = (start + jnp.arange(qi.shape[1]))[:, None] >= jnp.arange(
            ki.shape[1])[None, :]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return common.einsum("bnqk,bknd->bqnd", probs, vi, precision)

    out = [jax.checkpoint(functools.partial(rows, s))(q[:, s:s + block], k, v)
           for s in range(0, seq, block)]
    return jnp.concatenate(out, axis=1)


def _latent_attention(x, p, cfg, precision):
    _, heads, nope, rope, _ = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    kv_rank = cfg["kv_lora_rank"]
    c_q = _rms_norm(common.einsum("bsh,hr->bsr", x, p["q_a"]["kernel"],
                                  precision), p["q_norm"], eps)
    q = common.einsum("bsr,rnd->bsnd", c_q, p["q_b"]["kernel"], precision)
    kv = common.einsum("bsh,hr->bsr", x, p["kv_a"]["kernel"], precision)
    c_kv = _rms_norm(kv[..., :kv_rank], p["kv_norm"], eps)
    k_rope = _rope(kv[..., None, kv_rank:], theta)
    kv = common.einsum("bsr,rnd->bsnd", c_kv, p["kv_b"]["kernel"],
                       precision)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope, k_rope.shape[:2] + (heads, rope))], -1)
    # The softmax scale is that of the whole query width, nope + rope.
    a = _attention(q, k, kv[..., nope:], precision)
    return common.einsum("bsnd,ndh->bsh", a, p["proj"]["kernel"], precision)


def _swiglu(x, gate, up, down, precision):
    h = jax.nn.silu(common.einsum("bsh,hi->bsi", x, gate, precision))
    h = h * common.einsum("bsh,hi->bsi", x, up, precision)
    return common.einsum("bsi,ih->bsh", h, down, precision)


def expert_ffn(x, p, bias, cfg, precision="float32"):
    """The expert layer's share: routing over all the model's experts,
    the held experts' part of the sum, and the shared expert."""
    k, first = cfg["num_experts_per_tok"], cfg["experts_held"][0]
    scores = jax.nn.sigmoid(jnp.einsum(
        "bsh,he->bse", x, p["router"], precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(scores + bias, k)
    is_chosen = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]), axis=-2)
    picked = scores * is_chosen
    weights = cfg["routed_scaling_factor"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-20)

    @jax.checkpoint
    def one(y, expert):
        w_gate, w_up, w_down, weight = expert
        return y + weight[..., None] * _swiglu(x, w_gate, w_up, w_down,
                                               precision), None

    held = p["w_gate"].shape[0]
    mine = jnp.moveaxis(weights[..., first:first + held], -1, 0)
    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (p["w_gate"], p["w_up"], p["w_down"], mine))
    return y + _swiglu(x, p["shared_gate"], p["shared_up"],
                       p["shared_down"], precision)


def _block(x, p, bias, cfg, precision):
    """One pre-norm block. Each half is made again on the way back, so
    that the float32 activations of attention are not held through the
    FFN's backward pass."""
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def attention(x, p):
        return x + _latent_attention(_rms_norm(x, p["ln1"], eps), p["attn"],
                                     cfg, precision)

    @jax.checkpoint
    def ffn(x, p, bias):
        h = _rms_norm(x, p["ln2"], eps)
        if "moe" in p:
            return x + expert_ffn(h, p["moe"], bias, cfg, precision)
        return x + _swiglu(h, p["mlp_gate"]["kernel"], p["mlp_in"]["kernel"],
                           p["mlp_out"]["kernel"], precision)

    return ffn(attention(x, p), p, bias)


def hidden_fn(params, aux, tokens, next_tokens, cfg, precision="float32"):
    """The final hidden states, normed: the main model's, then each MTP
    module's (position ``i`` of module ``d`` is for token ``i + d + 2``)."""
    bb = params["params"]["backbone"]
    biases = aux["moe_state"]["backbone"]
    eps = cfg["rms_norm_eps"]
    embedding = bb["tok_embed"]["embedding"]
    def block(x, p, bias):
        return _block(x, p, bias, cfg, precision)

    x = embedding[tokens]
    for i in range(cfg["num_hidden_layers"]):
        name = f"block_{i}"
        bias = biases[name]["moe"]["bias"] if name in biases else None
        x = block(x, bb[name], bias)
    out = [_rms_norm(x, bb["ln_f"], eps)]
    for i in range(cfg["num_nextn_predict_layers"]):
        m = bb[f"mtp_{i}"]
        joined = jnp.concatenate(
            [_rms_norm(embedding[jnp.roll(next_tokens, -i, axis=1)],
                       m["embed_norm"], eps),
             _rms_norm(x, m["hidden_norm"], eps)], axis=-1)
        x = common.einsum("bsh,hd->bsd", joined, m["proj"]["kernel"],
                          precision)
        x = block(x, m["block"], biases[f"mtp_{i}"]["block"]["moe"]["bias"])
        out.append(_rms_norm(x, m["ln_f"], eps))
    return out


def logits_fn(params, aux, tokens, next_tokens, cfg, precision="float32"):
    """Float32 logits of the main model, then of each MTP module."""
    kernel = params["params"]["lm_head"]["kernel"]
    return [common.einsum("bsh,hv->bsv", h, kernel, precision)
            for h in hidden_fn(params, aux, tokens, next_tokens, cfg,
                               precision)]


def loss_terms(params, aux, batch, cfg, precision="float32"):
    """(main loss, [each MTP module's loss]): mean next-token
    cross-entropy; module ``d`` over the ``seq_len - d - 1`` positions
    that have a target. The logits are made again on the way back, so
    that two float32 sets of them need not be kept."""
    tokens, targets = batch
    kernel = params["params"]["lm_head"]["kernel"]

    @jax.checkpoint
    def xent(h, kernel, targets):
        return common.softmax_xent_mean(
            common.einsum("bsh,hv->bsv", h, kernel, precision), targets)

    main, *extra = hidden_fn(params, aux, tokens, targets, cfg, precision)
    return xent(main, kernel, targets), [
        xent(h[:, :-(d + 1)], kernel, targets[:, d + 1:])
        for d, h in enumerate(extra)]


def loss_fn(params, aux, batch, cfg, precision="float32"):
    """``L_main + mtp_loss_weight x mean of the MTP modules' losses``."""
    main, mtp = loss_terms(params, aux, batch, cfg, precision)
    if mtp:
        main = main + cfg["mtp_loss_weight"] * sum(mtp) / len(mtp)
    return main, aux


# ---- what the mathematics requires, for ``mfu`` and the rooflines --------

def attention_layers(cfg):
    """Attention layers a step runs: the main model's and the MTP
    modules'."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def expert_params(cfg):
    """Matrix parameters a token meets in one expert layer's products:
    (routed, shared). Routed is an expectation: ``num_experts_per_tok``
    choices, each held here with probability held / published under
    uniform routing (what seeded random weights give within a few
    percent); the program computes the real draw."""
    one = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    return (cfg["num_experts_per_tok"] * held
            / cfg["n_routed_experts_published"] * one,
            cfg["n_shared_experts"] * one)


def flops_per_row(cfg, traffic):
    """FLOPs one row (a sequence) requires, forward and backward. One
    multiply-add is 2 FLOPs, a step is the forward product and two
    backward (x 3); the embedding is a gather and counts nothing, causal
    attention counts the half of the scores the mask keeps; the routed
    experts count by expectation (``expert_params``); an MTP module
    counts at every position but the last; no recomputation, no
    optimizer, no element-wise work."""
    h, heads, nope, rope, vd = _dims(cfg)
    seq = traffic["seq_len"]
    mla = (h * cfg["q_lora_rank"]
           + cfg["q_lora_rank"] * heads * (nope + rope)
           + h * (cfg["kv_lora_rank"] + rope)
           + cfg["kv_lora_rank"] * heads * (nope + vd) + heads * vd * h)
    expert = mla + h * cfg["n_routed_experts_published"] + sum(
        expert_params(cfg))
    dense = mla + 3 * h * cfg["intermediate_size"]
    first = cfg["first_k_dense_replace"]
    head = h * cfg["vocab_size"]
    main = (first * dense + (cfg["num_hidden_layers"] - first) * expert
            + head)
    mtp = cfg["num_nextn_predict_layers"] * (2 * h * h + expert + head)
    attention = sum(flops.attention_flops(*attention_shape(
        cfg, {"rows_per_chip": 1, "seq_len": seq}), causal=True))
    return (6 * (seq * main + (seq - 1) * mtp)
            + attention_layers(cfg) * attention)


def attention_shape(cfg, traffic):
    """(batch, heads, seq, head_dim) of one layer's attention on one
    chip: q and k are nope + rope wide, v as wide."""
    _, heads, nope, rope, vd = _dims(cfg)
    assert nope + rope == vd
    return (traffic["rows_per_chip"], heads, traffic["seq_len"], vd)


def expert_products(cfg, traffic):
    """(FLOPs, bytes) a step on one chip requires of the products under
    scope ``hvd_moe/experts``: the held experts' grouped products and the
    shared expert's, forward and backward, over every expert layer.
    Bytes: each weight read once forward and once backward and its
    gradient written once, as float32; the tokens in and out as
    bfloat16, forward and backward."""
    tokens = traffic["rows_per_chip"] * traffic["seq_len"]
    layers = len(_expert_layers(cfg))
    routed, shared = expert_params(cfg)
    one = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    weights = (held + cfg["n_shared_experts"]) * one
    moved = 3 * 4 * weights + 4 * 2 * tokens * cfg["hidden_size"]
    return (layers * 6 * tokens * (routed + shared), layers * moved)
