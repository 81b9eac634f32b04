"""Plain reference of the ``transformer_lm`` family: a dense causal
decoder with rotary positions, pre-LayerNorm blocks, a tanh-GELU MLP
and an untied output head. It reads the parameter tree the program's
``TransformerLM`` reads, and shares no code with it.

Published description: Vaswani et al. 2017 (blocks), Su et al. 2021
(RoPE, rotate-half form), widths of BERT-large. Departures from
BERT-large are listed in the configuration file.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import flops
from benchmark.references import common

QUERY_BLOCK = 1024      # rows of the score matrix held at a time


def init_params(cfg, key):
    """The weights, made from ``key`` in one traced call: kernels normal
    with variance 1/fan_in, biases 0, LayerNorm scales 1."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    heads, inter = cfg["num_attention_heads"], cfg["intermediate_size"]
    hd, vocab = h // heads, cfg["vocab_size"]
    keys = iter(jax.random.split(key, 4 * layers + 2))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(
            fan_in)

    def ln():
        return {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))}

    backbone = {"tok_embed": {"embedding": normal((vocab, h), h)},
                "ln_f": ln()}
    for i in range(layers):
        backbone[f"block_{i}"] = {
            "ln1": ln(), "ln2": ln(),
            "attn": {
                "qkv": {"kernel": normal((h, 3, heads, hd), h),
                        "bias": jnp.zeros((3, heads, hd))},
                "proj": {"kernel": normal((heads, hd, h), h),
                         "bias": jnp.zeros((h,))}},
            "mlp_in": {"kernel": normal((h, inter), h),
                       "bias": jnp.zeros((inter,))},
            "mlp_out": {"kernel": normal((inter, h), inter),
                        "bias": jnp.zeros((h,))},
        }
    return {"params": {
        "backbone": backbone,
        "lm_head": {"kernel": normal((h, vocab), h),
                    "bias": jnp.zeros((vocab,))}}}


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x):
    """x: [b, s, n, d]. Rotate-half rotary embedding, base 10000."""
    seq, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = 1.0 / (10000.0 ** (np.arange(half) / half))
    angles = jnp.asarray(np.arange(seq)[:, None] * freqs[None, :],
                         jnp.float32)[None, :, None, :]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, precision):
    """Causal softmax attention, a block of query rows at a time against
    the keys at or before it. q, k, v: [b, s, n, d]."""
    seq, d = q.shape[1], q.shape[-1]
    block = min(QUERY_BLOCK, seq)

    @jax.checkpoint
    def rows(qi, ki, vi, start):
        scores = common.einsum("bqnd,bknd->bnqk", qi, ki, precision)
        scores = scores / math.sqrt(d)
        keep = (start + jnp.arange(qi.shape[1]))[:, None] >= jnp.arange(
            ki.shape[1])[None, :]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return common.einsum("bnqk,bknd->bqnd", probs, vi, precision)

    out = [rows(q[:, s:s + block], k[:, :s + block], v[:, :s + block], s)
           for s in range(0, seq, block)]
    return jnp.concatenate(out, axis=1)


def _block(x, p, cfg, precision):
    eps = cfg["layer_norm_eps"]
    h = _layer_norm(x, p["ln1"], eps)
    qkv = common.einsum("bsh,hcnd->bscnd", h, p["attn"]["qkv"]["kernel"],
                        precision) + p["attn"]["qkv"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    a = _attention(_rope(q), _rope(k), v, precision)
    x = x + common.einsum("bsnd,ndh->bsh", a, p["attn"]["proj"]["kernel"],
                          precision) + p["attn"]["proj"]["bias"]
    h = _layer_norm(x, p["ln2"], eps)
    h = common.einsum("bsh,hi->bsi", h, p["mlp_in"]["kernel"],
                      precision) + p["mlp_in"]["bias"]
    h = jax.nn.gelu(h, approximate=True)
    h = common.einsum("bsi,ih->bsh", h, p["mlp_out"]["kernel"],
                      precision) + p["mlp_out"]["bias"]
    return x + h


def logits_fn(params, tokens, cfg, precision="float32"):
    p = params["params"]
    bb = p["backbone"]
    x = bb["tok_embed"]["embedding"][tokens]
    blocks = [bb[f"block_{i}"] for i in range(cfg["num_hidden_layers"])]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)

    @jax.checkpoint
    def layer(x, lp):
        return _block(x, lp, cfg, precision), None

    x, _ = lax.scan(layer, x, stacked)
    x = _layer_norm(x, bb["ln_f"], cfg["layer_norm_eps"])
    return common.einsum("bsh,hv->bsv", x, p["lm_head"]["kernel"],
                         precision) + p["lm_head"]["bias"]


def loss_fn(params, aux, batch, cfg, precision="float32"):
    """Mean next-token cross-entropy of a block of rows. ``aux`` is the
    family's non-trained state: none here."""
    tokens, targets = batch
    logits = logits_fn(params, tokens, cfg, precision)
    return common.softmax_xent_mean(logits, targets), aux


def init_aux(cfg):
    return {}


# ---- what the mathematics requires, for ``mfu`` and the roofline ---------

def flops_per_token(cfg, seq_len):
    """Forward + backward FLOPs per token, counted from shapes:
    72 L h^2 + 6 h V + 6 L s h at BERT-large's 4h MLP. One multiply-add
    is 2 FLOPs, a step is the forward product and two backward (x 3);
    the embedding is a gather and counts nothing, causal attention
    counts the half of the scores the mask keeps; no recomputation, no
    optimizer, no element-wise work."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense_params = layers * (4 * h * h + 2 * h * cfg["intermediate_size"])
    dense_params += h * cfg["vocab_size"]          # lm_head, untied
    attention = flops.attention_flops(*attention_shape(
        cfg, {"rows_per_chip": 1, "seq_len": seq_len}), causal=True)
    return 6 * dense_params + layers * sum(attention) / seq_len


def flops_per_row(cfg, traffic):
    """FLOPs one row of the batch (a sequence) requires."""
    return traffic["seq_len"] * flops_per_token(cfg, traffic["seq_len"])


def attention_shape(cfg, traffic):
    """(batch, heads, seq, head_dim) of one layer's attention on one
    chip, as the kernel's roofline takes it."""
    heads = cfg["num_attention_heads"]
    return (traffic["rows_per_chip"], heads, traffic["seq_len"],
            cfg["hidden_size"] // heads)
