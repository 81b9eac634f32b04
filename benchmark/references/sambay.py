"""Plain reference of the ``sambay`` family (Phi-4-mini-flash-reasoning):
a decoder-hybrid-decoder whose layers differ in their token mixer. A
self-decoder alternates Mamba layers with differential attention under a
sliding window; one full-attention layer closes it; a cross-decoder
alternates gated memory units, which gate the last Mamba layer's scan
output, with cross-attention over the full layer's one K and V. Tied
embedding and head, LayerNorm, bias-free SwiGLU, no positional encoding.
It reads the parameter tree the program's ``TransformerLM`` reads, and
shares no code with it: no kernel, no flax.

Published description: the model's ``config.json`` (the configuration
file's ``source``); Ren et al., "Decoder-Hybrid-Decoder Architecture for
Efficient Reasoning with Long Generation", arXiv:2507.06607 (SambaY);
Gu & Dao, arXiv:2312.00752 (Mamba-1); Ye et al., arXiv:2410.05258
(differential attention). What the source does not state is listed in
the configuration file under ``assumed``. The equations, ``i`` a layer's
index in the published model (``layer_indices``):

    block:   x = x + Mixer_i(LN(x)); x = x + W_2 (silu(W_g h) * W_u h)
    mixer:   even i <= 16 Mamba; odd i < 16 windowed attention; i = 17
             full attention; even i >= 18 gated memory; odd i >= 19 cross
    Mamba:   [x, z] = W_in h; x = silu(conv4(x) + b_c);
             [r, B_t, C_t] = W_x x; dt = softplus(W_dt r + b_dt);
             s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) (x) B_t, A = -exp(A_log);
             y_t = s_t . C_t + D x_t; out = W_out (y silu(z)); memory = y
    attn:    pair p of 20: a_c = softmax(q_c k_c^T / 8 + M) [v_1, v_2],
             c = 1, 2, the K/V pair p // 2 of 10; lambda = exp(lq1 . lk1)
             - exp(lq2 . lk2) + lambda_init(i), lambda_init = 0.8 - 0.6
             exp(-0.3 i); o_p = RMSNorm_128(a_1 - lambda a_2) (1 -
             lambda_init); out = W_o [o_p] + b_o. M is causal, in the
             windowed layers also -inf where t - s >= sliding_window.
    cross:   q = W_q h + b only; k, v are the full layer's.
    memory:  W_2 (memory * silu(W_1 h))
    loss:    logits = LN_f(x) E^T, float32; mean cross-entropy.

A block at a time is made again on the way back; the recurrence is a
sequential ``lax.scan`` over positions, ``SCAN_BLOCK`` of them made
again at a time, so that the float32 activations fit beside the
reference's own AdamW state.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.references import common

QUERY_BLOCK = 1024      # rows of the score matrix held at a time
SCAN_BLOCK = 128        # positions of the recurrence kept at a time
KINDS = ("mamba", "window", "attention", "gmu", "cross")


def mixer_kind(i, cfg):
    """The mixer of layer ``i`` of the published model (0-based):
    ``mb_per_layer`` 2 in a model of ``num_hidden_layers_published``,
    the modelling file's rule."""
    half = cfg["num_hidden_layers_published"] // 2
    period = cfg["mb_per_layer"]
    if i <= half:
        return "mamba" if i % period == 0 else "window"
    if i == half + 1:
        return "attention"
    return "gmu" if i % period == 0 else "cross"


def kinds(cfg):
    return [mixer_kind(i, cfg) for i in cfg["layer_indices"]]


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def _dims(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    m = cfg["assumed_sizes"]
    return dict(h=h, heads=heads, kv=cfg["num_key_value_heads"],
                hd=h // heads, inter=cfg["intermediate_size"],
                vocab=cfg["vocab_size"], di=m["expand"] * h,
                n=m["d_state"], conv=m["d_conv"], rank=m["dt_rank"])


def init_params(cfg, key):
    """The weights, made from ``key`` in one traced call: kernels normal
    with variance 1/fan_in, biases 0, norms 1 / 0, ``A_log`` log(1..N),
    ``D`` 1, the step size's bias the inverse softplus of a log-uniform
    draw in [0.001, 0.1], the lambda vectors normal of deviation 0.1."""
    d = _dims(cfg)
    h, hd, di, n = d["h"], d["hd"], d["di"], d["n"]
    keys = iter(jax.random.split(key, 16 * len(cfg["layer_indices"]) + 1))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(
            fan_in)

    def dense(shape, fan_in, bias=None):
        out = {"kernel": normal(shape, fan_in)}
        if bias is not None:
            out["bias"] = jnp.zeros(bias)
        return out

    def ln():
        return {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))}

    def mamba():
        step = jnp.exp(jax.random.uniform(
            next(keys), (di,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            "in_proj": dense((h, 2 * di), h),
            "conv_kernel": normal((d["conv"], di), d["conv"]),
            "conv_bias": jnp.zeros((di,)),
            "x_proj": dense((di, d["rank"] + 2 * n), di),
            "dt_proj": {"kernel": normal((d["rank"], di), d["rank"]),
                        "bias": step + jnp.log(-jnp.expm1(-step))},
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (di, n)),
            "D": jnp.ones((di,)),
            "out_proj": dense((di, h), di)}

    def attention(cross):
        heads, kv = d["heads"], d["kv"]
        out = {f"lambda_{name}": 0.1 * jax.random.normal(
                   next(keys), (hd,), jnp.float32)
               for name in ("q1", "k1", "q2", "k2")}
        out["subln"] = {"scale": jnp.ones((2 * hd,))}
        out["proj"] = dense((h, h), h, (h,))
        if cross:
            out["q"] = dense((h, heads, hd), h, (heads, hd))
        else:
            out["qkv"] = dense((h, heads + 2 * kv, hd), h,
                               (heads + 2 * kv, hd))
        return out

    backbone = {"tok_embed": {"embedding": normal((d["vocab"], h), h)},
                "ln_f": ln()}
    for i, kind in enumerate(kinds(cfg)):
        block = {"ln1": ln(), "ln2": ln(),
                 "mlp_gate": dense((h, d["inter"]), h),
                 "mlp_in": dense((h, d["inter"]), h),
                 "mlp_out": dense((d["inter"], h), d["inter"])}
        if kind == "mamba":
            block["mamba"] = mamba()
        elif kind == "gmu":
            block["gmu"] = {"in_proj": dense((h, di), h),
                            "out_proj": dense((di, h), di)}
        else:
            block["attn"] = attention(kind == "cross")
        backbone[f"block_{i}"] = block
    return {"params": {"backbone": backbone}}


def init_aux(cfg):
    """The family has no non-trained state."""
    return {}


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _scan(x, dt, a, b, c):
    """``y_t = s_t . C_t`` of the recurrence, position by position.
    x, dt: [batch, seq, channels]; b, c: [batch, seq, N]; a: [channels,
    N]. ``SCAN_BLOCK`` positions' states live at a time."""
    batch, seq, channels = x.shape
    block = min(SCAN_BLOCK, seq)
    pad = (-seq) % block

    def blocks(z):      # [blocks, block, batch, .]; dt = 0 passes a state on
        z = jnp.pad(z, ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(z, 1, 0).reshape(-1, block, batch, z.shape[-1])

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        s = (jnp.exp(dt_t[..., None] * a) * s
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def one_block(s, at):
        return lax.scan(step, s, at)

    s0 = jnp.zeros((batch, channels, a.shape[1]), jnp.float32)
    y = lax.scan(one_block, s0, tuple(map(blocks, (x, dt, b, c))))[1]
    return jnp.moveaxis(y.reshape(-1, batch, channels), 0, 1)[:, :seq]


def _mamba(h, p, cfg, precision):
    d = _dims(cfg)
    di, n, rank = d["di"], d["n"], d["rank"]
    xz = common.einsum("bsh,hc->bsc", h, p["in_proj"]["kernel"], precision)
    x, z = xz[..., :di], xz[..., di:]
    taps = p["conv_kernel"].shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    x = p["conv_bias"] + sum(padded[:, i:i + x.shape[1]]
                             * p["conv_kernel"][i] for i in range(taps))
    x = jax.nn.silu(x)
    rbc = common.einsum("bsc,cr->bsr", x, p["x_proj"]["kernel"], precision)
    dt = jax.nn.softplus(common.einsum(
        "bsr,rc->bsc", rbc[..., :rank], p["dt_proj"]["kernel"], precision)
        + p["dt_proj"]["bias"])
    y = _scan(x, dt, -jnp.exp(p["A_log"]), rbc[..., rank:rank + n],
              rbc[..., rank + n:]) + p["D"] * x
    out = common.einsum("bsc,ch->bsh", y * jax.nn.silu(z),
                        p["out_proj"]["kernel"], precision)
    return out, y


def _softmax_rows(q, k, v, window, precision):
    """Causal softmax attention, a block of query rows at a time against
    the keys at or before it, the window as a mask. q: [b, s, n, d]; k:
    [b, s, n, d]; v: [b, s, n, dv]."""
    seq, d = q.shape[1], q.shape[-1]
    block = min(QUERY_BLOCK, seq)

    @jax.checkpoint
    def rows(qi, ki, vi, start):
        scores = common.einsum("bqnd,bknd->bnqk", qi, ki, precision)
        scores = scores / math.sqrt(d)
        ahead = (start + jnp.arange(qi.shape[1]))[:, None] - jnp.arange(
            ki.shape[1])[None, :]
        keep = ahead >= 0
        if window is not None:
            keep = jnp.logical_and(keep, ahead < window)
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return common.einsum("bnqk,bknd->bqnd", probs, vi, precision)

    out = [rows(q[:, s:s + block], k[:, :s + block], v[:, :s + block], s)
           for s in range(0, seq, block)]
    return jnp.concatenate(out, axis=1)


def _diff_attention(h, p, cfg, depth, window, shared, precision):
    """``(out, (k, v))``; with ``shared`` the layer's own q over the
    full layer's k and v."""
    d = _dims(cfg)
    heads, kv, hd = d["heads"], d["kv"], d["hd"]
    if shared is None:
        qkv = common.einsum("bsh,hnd->bsnd", h, p["qkv"]["kernel"],
                            precision) + p["qkv"]["bias"]
        q, k, v = (qkv[:, :, :heads], qkv[:, :, heads:heads + kv],
                   qkv[:, :, heads + kv:])
    else:
        q = common.einsum("bsh,hnd->bsnd", h, p["q"]["kernel"],
                          precision) + p["q"]["bias"]
        k, v = shared
    group = heads // kv
    # K and V repeated over the group, pair by pair: query pair p reads
    # K/V pair p // group.
    values = jnp.repeat(v.reshape(*v.shape[:2], kv // 2, 2 * hd), group,
                        axis=2)
    maps = [_softmax_rows(q[:, :, c::2],
                          jnp.repeat(k[:, :, c::2], group, axis=2), values,
                          window, precision) for c in (0, 1)]
    init = lambda_init(depth)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init)
    mixed = maps[0] - lam * maps[1]
    mixed = mixed * lax.rsqrt(jnp.mean(jnp.square(mixed), -1, keepdims=True)
                              + cfg["layer_norm_eps"]) * p["subln"]["scale"]
    mixed = (mixed * (1.0 - init)).reshape(*h.shape[:2], -1)
    out = common.einsum("bsc,ch->bsh", mixed, p["proj"]["kernel"],
                        precision) + p["proj"]["bias"]
    return out, (k, v)


def _block(x, p, cfg, kind, depth, memory, shared, precision):
    """``(x, made)``: what the mixer made for the layers after it."""
    eps = cfg["layer_norm_eps"]
    h = _layer_norm(x, p["ln1"], eps)
    made = None
    if kind == "mamba":
        a, made = _mamba(h, p["mamba"], cfg, precision)
    elif kind == "gmu":
        gate = jax.nn.silu(common.einsum(
            "bsh,hc->bsc", h, p["gmu"]["in_proj"]["kernel"], precision))
        a = common.einsum("bsc,ch->bsh", memory * gate,
                          p["gmu"]["out_proj"]["kernel"], precision)
    else:
        a, kv = _diff_attention(
            h, p["attn"], cfg, depth,
            cfg["sliding_window"] if kind == "window" else None,
            shared if kind == "cross" else None, precision)
        made = kv if kind == "attention" else None
    x = x + a
    h = _layer_norm(x, p["ln2"], eps)
    m = jax.nn.silu(common.einsum("bsh,hi->bsi", h, p["mlp_gate"]["kernel"],
                                  precision))
    m = m * common.einsum("bsh,hi->bsi", h, p["mlp_in"]["kernel"], precision)
    return x + common.einsum("bsi,ih->bsh", m, p["mlp_out"]["kernel"],
                             precision), made


def hidden_fn(params, tokens, cfg, precision="float32"):
    """The final normed states, [batch, seq, hidden]."""
    bb = params["params"]["backbone"]
    x = bb["tok_embed"]["embedding"][tokens]
    made = {"mamba": None, "attention": None}
    for i, (kind, depth) in enumerate(zip(kinds(cfg), cfg["layer_indices"])):
        block = jax.checkpoint(
            lambda x, p, memory, shared, kind=kind, depth=depth: _block(
                x, p, cfg, kind, depth, memory, shared, precision))
        x, new = block(x, bb[f"block_{i}"], made["mamba"], made["attention"])
        if kind in made:
            made[kind] = new
    return _layer_norm(x, bb["ln_f"], cfg["layer_norm_eps"])


def logits_fn(params, tokens, cfg, precision="float32"):
    table = params["params"]["backbone"]["tok_embed"]["embedding"]
    return common.einsum("bsh,vh->bsv",
                         hidden_fn(params, tokens, cfg, precision), table,
                         precision)


def loss_fn(params, aux, batch, cfg, precision="float32"):
    tokens, targets = batch
    return common.softmax_xent_mean(
        logits_fn(params, tokens, cfg, precision), targets), aux


# ---- what the mathematics requires, for ``mfu`` and the rooflines --------

def mixer_params(cfg, kind):
    """Matrix parameters a token meets in one mixer of ``kind``."""
    d = _dims(cfg)
    h, di = d["h"], d["di"]
    if kind == "mamba":
        return (h * 2 * di + di * (d["rank"] + 2 * d["n"]) + d["rank"] * di
                + di * h)
    if kind == "gmu":
        return 2 * h * di
    if kind == "cross":
        return 2 * h * h
    return h * (h + 2 * d["kv"] * d["hd"]) + h * h


def block_params(cfg):
    """Matrix parameters a token meets on its way up the stack: every
    layer's mixer and its SwiGLU (the convolution, the recurrence, the
    norms and the lambda mix are element-wise and count nothing)."""
    d = _dims(cfg)
    return sum(mixer_params(cfg, kind) + 3 * d["h"] * d["inter"]
               for kind in kinds(cfg))


def attention_layers(cfg):
    """Layers that call the attention kernels (twice each: a pair's two
    score maps)."""
    return sum(kind in ("window", "attention", "cross") for kind in kinds(cfg))


def _keys_seen(seq, window):
    """Sum over a sequence's queries of the keys each sees."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_work(cfg, traffic):
    """(operations, bytes) one row's attention requires, forward and
    backward, over the attention layers: each of a pair's two score maps
    is a product over the keys a query sees (head_dim wide) and a
    product with the pair's value (2 head_dim wide), twice that again on
    the way back; the windowed layer at its window's keys only. q, k, v,
    the two maps' outputs and their gradients cross HBM once, in the
    activations' two bytes."""
    d = _dims(cfg)
    seq, hd = traffic["seq_len"], d["hd"]
    operations = moved = 0
    for kind in kinds(cfg):
        if kind not in ("window", "attention", "cross"):
            continue
        window = cfg["sliding_window"] if kind == "window" else None
        pairs = _keys_seen(seq, window)
        operations += 3 * 2 * d["heads"] * pairs * (hd + 2 * hd)
        q, kv, out = d["heads"] * hd, 2 * d["kv"] * hd, 2 * d["heads"] * hd
        # Forward: q, k, v in, the maps out. Backward: those and the
        # maps' gradients in, dq, dk, dv out.
        moved += 2 * seq * ((q + kv + out) + (q + kv + 2 * out) + (q + kv))
    return operations, moved


def scan_work(cfg, traffic):
    """(operations, bytes) one row's selective scans require, forward
    and backward: per position, channel and state index one exponential
    and three multiply-adds forward and about three times that on the
    way back, none of it the MXU's; x, dt, B, C in and y out forward,
    those and dy in and dx, ddt, dB, dC out backward, float32, once
    across HBM. The bytes bound it by far."""
    d = _dims(cfg)
    seq, di, n = traffic["seq_len"], d["di"], d["n"]
    layers = sum(kind == "mamba" for kind in kinds(cfg))
    wide, narrow = 4 * seq * di, 4 * seq * n
    moved = (2 * wide + 2 * narrow + wide) + (3 * wide + 2 * narrow
                                              + 2 * wide + 2 * narrow)
    return layers * 24 * seq * di * n, layers * moved


def flops_per_row(cfg, traffic):
    """FLOPs one row (a sequence) requires, forward and backward. One
    multiply-add is 2 FLOPs, a step is the forward product and two
    backward (x 3); the tied table counts once, as the head's product
    (the embedding is a gather); attention as ``attention_work``; the
    recurrence, the convolution and every other element-wise pass count
    nothing; no recomputation, no optimizer."""
    d = _dims(cfg)
    products = block_params(cfg) + d["h"] * d["vocab"]
    return (6 * traffic["seq_len"] * products
            + attention_work(cfg, traffic)[0])
