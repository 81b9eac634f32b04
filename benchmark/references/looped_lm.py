"""Plain reference of the ``looped_lm`` family (Ouro): a causal decoder
whose stack of sandwich-normed blocks and final norm run ``total_ut_steps``
times with the same weights, an exit gate on every pass's state, one
untied head applied to each, and the loss that mixes the passes'
cross-entropies by the exit distribution less an entropy term. It reads
the parameter tree the program's ``TransformerLM`` reads, and shares no
code with it: no kernel, no flax, no lifted scan.

Published description: the model's ``config.json`` (the configuration
file's ``source``) and Zhu et al., "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741. What the source does not state is
listed in the configuration file under ``assumed``. The equations:

    N(x) = x / sqrt(mean(x^2) + eps) * w
    block: a = Attn(N1(x)); x = x + N2(a); m = MLP(N3(x)); x = x + N4(m)
    loop:  h_0 = Emb(tokens); h_t = N_f(Block_L(... Block_1(h_{t-1})))
    gate:  lambda_t = sigmoid(w_g . h_t + b_g)
    exit:  p_t = lambda_t prod_{j<t} (1 - lambda_j), p_T what is left
    loss:  mean_i [sum_t p_t,i l_t,i - beta H(p_.,i)]

A block at a time is made again on the way back and one head at a time,
so that the float32 activations fit beside the reference's own AdamW
state.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark import flops
from benchmark.references import common

QUERY_BLOCK = 1024      # rows of the score matrix held at a time
NORMS = ("ln1", "ln1_out", "ln2", "ln2_out")


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"])


def init_params(cfg, key):
    """The weights, made from ``key`` in one traced call: kernels normal
    with variance 1/fan_in, RMSNorm scales 1, the exit gate 0 (every
    pass then leaves half of what reaches it)."""
    h, heads, hd, inter, vocab = _dims(cfg)
    assert heads * hd == h and cfg["num_key_value_heads"] == heads
    keys = iter(jax.random.split(key, 5 * cfg["num_hidden_layers"] + 2))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(
            fan_in)

    def ones():
        return {"scale": jnp.ones((h,))}

    backbone = {"tok_embed": {"embedding": normal((vocab, h), h)},
                "ln_f": ones()}
    for i in range(cfg["num_hidden_layers"]):
        backbone[f"block_{i}"] = {
            **{name: ones() for name in NORMS},
            "attn": {"qkv": {"kernel": normal((h, 3, heads, hd), h)},
                     "proj": {"kernel": normal((heads, hd, h), h)}},
            "mlp_gate": {"kernel": normal((h, inter), h)},
            "mlp_in": {"kernel": normal((h, inter), h)},
            "mlp_out": {"kernel": normal((inter, h), inter)}}
    return {"params": {
        "backbone": backbone,
        "lm_head": {"kernel": normal((h, vocab), h)},
        "exit_gate": {"kernel": jnp.zeros((h, 1)), "bias": jnp.zeros((1,))}}}


def init_aux(cfg):
    """The non-trained state: the mean share of positions that leave
    after each pass, which the step fills in and nothing reads."""
    return {"loop_state": {"exit_share": jnp.zeros((cfg["total_ut_steps"],),
                                                   jnp.float32)}}


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
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms_norm(x, p["ln1"], eps)
    qkv = common.einsum("bsh,hcnd->bscnd", h, p["attn"]["qkv"]["kernel"],
                        precision)
    a = _attention(_rope(qkv[:, :, 0], theta), _rope(qkv[:, :, 1], theta),
                   qkv[:, :, 2], precision)
    a = common.einsum("bsnd,ndh->bsh", a, p["attn"]["proj"]["kernel"],
                      precision)
    x = x + _rms_norm(a, p["ln1_out"], eps)
    h = _rms_norm(x, p["ln2"], eps)
    m = jax.nn.silu(common.einsum("bsh,hi->bsi", h, p["mlp_gate"]["kernel"],
                                  precision))
    m = m * common.einsum("bsh,hi->bsi", h, p["mlp_in"]["kernel"], precision)
    m = common.einsum("bsi,ih->bsh", m, p["mlp_out"]["kernel"], precision)
    return x + _rms_norm(m, p["ln2_out"], eps)


def states_fn(params, tokens, cfg, precision="float32"):
    """Every pass's exit state, ``[passes, batch, seq, hidden]``."""
    bb = params["params"]["backbone"]
    blocks = [bb[f"block_{i}"] for i in range(cfg["num_hidden_layers"])]

    @jax.checkpoint
    def block(x, p):
        return _block(x, p, cfg, precision)

    def one_pass(x, _):
        for p in blocks:
            x = block(x, p)
        x = _rms_norm(x, bb["ln_f"], cfg["rms_norm_eps"])
        return x, x

    return lax.scan(one_pass, bb["tok_embed"]["embedding"][tokens], None,
                    length=cfg["total_ut_steps"])[1]


def gate_fn(params, h):
    """The exit gate's logit at every position of ``h``: float32 at the
    highest precision whatever the products' (the configuration states
    the gate and the exit distribution in float32)."""
    gate = params["params"]["exit_gate"]
    return jnp.einsum("...h,ho->...o", h, gate["kernel"],
                      precision=lax.Precision.HIGHEST)[..., 0] + gate["bias"]


def exit_log_probs(gate_logits):
    """``log p_t`` of the exit distribution from the gates' logits
    (``[passes, ...]``): the last pass takes what is left."""
    passes = gate_logits.shape[0]
    out, reached = [], jnp.zeros_like(gate_logits[0])
    for t in range(passes - 1):
        out.append(reached + jax.nn.log_sigmoid(gate_logits[t]))
        reached = reached + jax.nn.log_sigmoid(-gate_logits[t])
    return jnp.stack(out + [reached])


def loss_terms(params, batch, cfg, precision="float32"):
    """(cross-entropy, gate logit) of every pass at every position, each
    ``[passes, batch, seq]``. One pass's logits at a time, made again on
    the way back."""
    tokens, targets = batch
    kernel = params["params"]["lm_head"]["kernel"]

    @jax.checkpoint
    def exit_of(h, kernel):
        logits = common.einsum("bsh,hv->bsv", h, kernel, precision)
        picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    states = states_fn(params, tokens, cfg, precision)
    xent = lax.map(lambda h: exit_of(h, kernel), states)
    return xent, gate_fn(params, states)


def loss_fn(params, aux, batch, cfg, precision="float32"):
    """``mean_i [sum_t p_t l_t - beta H(p)]`` over a block of rows, and
    the mean exit share of each pass as the new non-trained state."""
    xent, gate_logits = loss_terms(params, batch, cfg, precision)
    log_p = exit_log_probs(gate_logits)
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)
    loss = jnp.mean(jnp.sum(p * xent, axis=0) - cfg["exit_entropy_beta"]
                    * entropy)
    share = lax.stop_gradient(jnp.mean(p, axis=tuple(range(1, p.ndim))))
    return loss, {"loop_state": {"exit_share": share}}


# ---- what the mathematics requires, for ``mfu`` and the roofline ---------

def attention_layers(cfg):
    """Attention calls a step runs: every layer once a pass."""
    return cfg["num_hidden_layers"] * cfg["total_ut_steps"]


def block_params(cfg):
    """Matrix parameters a token meets in one application of a block:
    q, k, v, o and the three of the SwiGLU."""
    h, _, _, inter, _ = _dims(cfg)
    return 4 * h * h + 3 * h * inter


def flops_per_row(cfg, traffic):
    """FLOPs one row (a sequence) requires, forward and backward. One
    multiply-add is 2 FLOPs, a step is the forward product and two
    backward (x 3); every block counts once a pass and so does the head;
    the embedding is a gather and counts nothing, causal attention
    counts the half of the scores the mask keeps; the gate (hidden -> 1)
    counts nothing; no recomputation, no optimizer, no element-wise
    work."""
    h, _, _, _, vocab = _dims(cfg)
    seq, passes = traffic["seq_len"], cfg["total_ut_steps"]
    products = attention_layers(cfg) * block_params(cfg) + passes * h * vocab
    attention = sum(flops.attention_flops(*attention_shape(
        cfg, {"rows_per_chip": 1, "seq_len": seq}), causal=True))
    return 6 * seq * products + attention_layers(cfg) * attention


def attention_shape(cfg, traffic):
    """(batch, heads, seq, head_dim) of one attention call on one chip."""
    return (traffic["rows_per_chip"], cfg["num_attention_heads"],
            traffic["seq_len"], cfg["head_dim"])
