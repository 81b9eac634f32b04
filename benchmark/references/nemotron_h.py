"""Plain reference of the ``nemotron_h`` family (NVIDIA-Nemotron-3-Nano-
30B-A3B): a causal decoder whose every layer is ONE sub-layer under one
pre-RMSNorm and one residual add, by the published
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``E`` a
sigmoid-routed expert FFN of ungated squared-ReLU experts with a shared
one, ``*`` grouped-head softmax attention without positions; an untied
head. It reads the parameter tree the program's ``TransformerLM`` reads,
and shares no code with it: no chunked form, no kernel, no sort, no
grouped product, no flax.

Published description: the model's ``config.json`` (the configuration
file's ``source``, ``model_type`` ``nemotron_h``); Nemotron-H,
arXiv:2504.03624; Mamba-2, arXiv:2405.21060. What the source does not
state is listed in the configuration file under ``assumed``. The
equations, ``x`` a layer's input ``[T, d]``, ``k`` its kind:

    x <- x + F_k(RMSNorm(x))                          # eps, one gain of d
    k == "M" (64 heads of 64, state N = 128, 8 groups of B / C):
        z, xBC, dt = split(h W_in)                    # d -> 4096 + 6144 + 64
        xBC = silu(conv(xBC) + b_c)                   # depthwise causal, 4 taps, the
                                                      # last tap on the position itself
        u, B, C = split(xBC)                          # [T,64,64], [T,8,128], [T,8,128]
        dt  = softplus(dt + dt_bias);  A_h = -exp(A_log_h)
        S_t = exp(dt_t A_h) S_{t-1} + (dt_t u_t) (x) B_t,   S_0 = 0
        y_t = S_t C_t + D_h u_t                       # head h reads group h // 8
        g   = RMSNorm_groups(y * silu(z))             # each 512 lanes, one gain of 4096
        F   = g W_out
    k == "E":
        s   = sigmoid(h W_r)                          # [T, 128], float32
        S   = top-6 of (s + b)                        # b selects only
        w_e = 2.5 s_e / (sum_{c in S} s_c + 1e-20), e in S
        F   = sum_{e in S} w_e W_down,e relu(W_up,e h)^2 + W_down,s relu(W_up,s h)^2
    k == "*":
        q, k, v = h W_q, h W_k, h W_v                 # 32, 2, 2 heads of 128; no positions
        F   = softmax_{j <= i}(q_i . k_j / sqrt(128)) v W_o
                                                      # query head n reads K/V head n // 16
    loss: logits = RMSNorm_f(x) W_head, float32; mean cross-entropy.

The reference is one chip's share of a deployment, as the program is:
it runs the published layers ``layers_held``, routes over all
``n_routed_experts_published`` experts and computes the experts
``experts_held`` only, each applied densely to every token and weighted
by the token's weight for it, or by 0; what the absent experts would add
is left out in both.

The recurrence is computed as the recurrence, position by position
(``lax.scan``), not by its chunked dual. Departures, all of them about
memory and none about a number: that scan is nested in a scan over
chunks of ``STATE_BLOCK`` positions under ``jax.checkpoint``, so that the
way back keeps a state a chunk and a chunk's own, not 16,384 (the
arithmetic is the sequential one); a Mamba-2 mixer as a whole walks the
row ``ROW_BLOCK`` positions at a time, the state and the convolution's
last three inputs carried from block to block, so that its wide
tensors (10,304 float32 numbers a token) exist a block at a time; the
score matrix is made ``QUERY_BLOCK`` query rows at a time and the logits
``LOGIT_BLOCK`` positions at a time, each block made again on the way
back; each layer and each held expert are made again on the way back
too. The
convolution's taps, the recurrence and the gates are element-wise work
(the recurrence's ``S_t C_t`` a sum over 128 numbers) and stay in
float32 under every control precision: a control lowers the operands of
the matrix products.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.references import common

BIAS_SEED = 20261004    # the selection bias: fixed, not the run's seed
QUERY_BLOCK = 128       # rows of the score matrix held at a time
LOGIT_BLOCK = 4096      # positions whose logits are held at a time
STATE_BLOCK = 128       # positions between the states the way back keeps
ROW_BLOCK = 2048        # positions of a Mamba-2 mixer held at a time

KINDS = {"M": ("mamba2", "none"), "E": ("none", "expert"),
         "*": ("full", "none")}


def _held(cfg):
    first, end = cfg["experts_held"]
    return end - first


def layers(cfg):
    """Indices in the published model of the layers that are run."""
    first, end = cfg["layers_held"]
    assert end - first == cfg["num_hidden_layers"], cfg["layers_held"]
    return list(range(first, end))


def pattern(cfg):
    """The published pattern at ``layers_held``: a letter a layer."""
    return "".join(cfg["hybrid_override_pattern"][l] for l in layers(cfg))


def kinds(cfg):
    """The program's mixer of every layer run (``models/transformer.py:
    MIXERS``); an ``E`` layer has none."""
    return [KINDS[k][0] for k in pattern(cfg)]


def ffns(cfg):
    """The program's FFN of every layer run (``FFNS``): the expert layer
    in an ``E`` layer, none elsewhere."""
    return [KINDS[k][1] for k in pattern(cfg)]


def mamba_dims(cfg):
    """(heads, a head's width, groups, state, channels the convolution
    runs over, width of the input product)."""
    heads, width = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    conv = heads * width + 2 * groups * n
    return heads, width, groups, n, conv, heads * width + conv + heads


def init_params(cfg, key):
    """The weights, made from ``key`` in one traced call
    (``assumed.initializer`` of the configuration file): kernels normal
    with variance 1/fan_in (a filter's fan-in is its taps, its bias
    uniform within 1/sqrt(taps)), those that write into the residual
    stream (a mixer's and attention's output product, an expert's down
    product) divided by the square root of the published depth where
    ``rescale_prenorm_residual``, norms 1, embedding rows normal with
    variance 1 / ``embedding_fan_in``; ``A = -exp(A_log)`` uniform in
    [1, 16], ``dt_bias`` the inverse softplus of a step log-uniform in
    [``time_step_min``, ``time_step_max``] and no smaller than
    ``time_step_floor``, ``D`` 1."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"]
    m_heads, m_width, _, _, conv, m_in = mamba_dims(cfg)
    inner, taps = m_heads * m_width, cfg["conv_kernel"]
    width = cfg["moe_intermediate_size"]
    shared = cfg["moe_shared_expert_intermediate_size"]
    experts, held = cfg["n_routed_experts_published"], _held(cfg)
    keys = iter(jax.random.split(key, 16 * cfg["num_hidden_layers"] + 8))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(
            fan_in)

    def residual(shape, fan_in):
        depth = cfg["num_hidden_layers_published"]
        return normal(shape, fan_in * (
            depth if cfg["rescale_prenorm_residual"] else 1))

    def uniform(shape, low, high):
        return jax.random.uniform(next(keys), shape, jnp.float32, low, high)

    def ones(n=h):
        return {"scale": jnp.ones((n,))}

    backbone = {"tok_embed": {"embedding": normal(
        (vocab, h), cfg["embedding_fan_in"])}, "ln_f": ones()}
    for i, kind in enumerate(pattern(cfg)):
        if kind == "M":
            step = jnp.maximum(jnp.exp(uniform(
                (m_heads,), math.log(cfg["time_step_min"]),
                math.log(cfg["time_step_max"]))), cfg["time_step_floor"])
            block = {"ln1": ones(), "mamba2": {
                "in_proj": {"kernel": normal((h, m_in), h)},    # z, xBC, dt
                "conv_kernel": normal((taps, conv), taps),
                "conv_bias": uniform((conv,), -taps ** -0.5, taps ** -0.5),
                # softplus(dt_bias) = step
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(uniform((m_heads,), 1.0, 16.0)),
                "D": jnp.ones((m_heads,)), "norm": jnp.ones((inner,)),
                "out_proj": {"kernel": residual((inner, h), inner)}}}
        elif kind == "E":
            block = {"ln2": ones(), "moe": {
                "router": normal((h, experts), h),
                "w_up": normal((held, h, width), h),
                "w_down": residual((held, width, h), width),
                "shared_up": normal((h, shared), h),
                "shared_down": residual((shared, h), shared)}}
        else:
            block = {"ln1": ones(), "attn": {
                # q's heads, then k's, then v's, from one product.
                "qkv": {"kernel": normal((h, heads + 2 * kv, hd), h)},
                "proj": {"kernel": residual((heads, hd, h), heads * hd)}}}
        backbone[f"block_{i}"] = block
    return {"params": {"backbone": backbone,
                       "lm_head": {"kernel": normal((h, vocab), h)}}}


def init_aux(cfg):
    """The non-trained state: each expert layer's selection bias (small,
    fixed: ``assumed`` in the configuration file) and the tokens each
    expert drew in the last step, which the program fills in and nothing
    here reads."""
    experts = cfg["n_routed_experts_published"]
    state = {}
    for i, kind in enumerate(pattern(cfg)):
        if kind == "E":
            state[f"block_{i}"] = {"moe": {
                "bias": cfg["router_bias_scale"] * jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(BIAS_SEED), i),
                    (experts,), jnp.float32),
                "expert_tokens": jnp.zeros((experts,), jnp.float32)}}
    return {"moe_state": {"backbone": state}}


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * scale


def shifts(u, kernel, before):
    """``c_t = sum_j kernel[j] u_{t - (taps - 1) + j}``: a depthwise
    causal convolution over positions as one shifted multiply-add a tap.
    u: [b, s, channels]; kernel: [taps, channels]; ``before``: the
    ``taps - 1`` positions that precede ``u`` (zeros at a row's start).
    Returns ``c`` and the last ``taps - 1`` positions of the input."""
    taps, seq = kernel.shape[0], u.shape[1]
    padded = jnp.concatenate([before, u], axis=1)
    return (sum(kernel[j] * padded[:, j:j + seq] for j in range(taps)),
            padded[:, seq:])


def recurrence(s, u, dt, a, b, c):
    """``y_t = S_t C_t`` of ``S_t = exp(dt_t A) S_{t-1} + (dt_t u_t)
    (x) B_t`` from the state ``s`` [b, heads, width, N], position by
    position, and the state after the last. u: [b, s, heads, width];
    dt: [b, s, heads]; a: [heads]; b, c: [b, s, groups, N]."""
    batch, seq, heads, width = u.shape
    each = heads // b.shape[2]
    block = min(STATE_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    def position(s, at):
        u_t, dt_t, b_t, c_t = at
        b_t, c_t = (jnp.repeat(x, each, axis=1) for x in (b_t, c_t))
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * u_t)[..., None] * b_t[:, :, None, :])
        return s, jnp.sum(s * c_t[:, :, None, :], axis=-1)

    @jax.checkpoint
    def chunk(s, at):
        return lax.scan(position, s, at)

    def blocks(x):      # [b, s, ...] -> [s / block, block, b, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(seq // block, block, *x.shape[1:])

    s, y = lax.scan(chunk, s, tuple(blocks(x) for x in (u, dt, b, c)))
    return jnp.moveaxis(y.reshape(seq, batch, heads, width), 0, 1), s


def mamba2(h, p, cfg, precision="float32"):
    """An ``M`` layer's mixer on its normed input ``h`` [b, s, d], the
    row ``ROW_BLOCK`` positions at a time."""
    heads, width, groups, n, conv, _ = mamba_dims(cfg)
    inner, bc, taps = heads * width, groups * n, cfg["conv_kernel"]
    batch, seq, _ = h.shape
    block = min(ROW_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    @jax.checkpoint
    def rows(carry, h):
        state, before = carry
        zxd = common.einsum("bsh,hi->bsi", h, p["in_proj"]["kernel"],
                            precision)
        z, xbc, dt = jnp.split(zxd, (inner, 2 * inner + 2 * bc), axis=-1)
        xbc, before = shifts(xbc, p["conv_kernel"], before)
        xbc = jax.nn.silu(xbc + p["conv_bias"])
        u, b, c = jnp.split(xbc, (inner, inner + bc), axis=-1)
        u = u.reshape(batch, block, heads, width)
        y, state = recurrence(
            state, u, jax.nn.softplus(dt + p["dt_bias"]),
            -jnp.exp(p["A_log"]), b.reshape(batch, block, groups, n),
            c.reshape(batch, block, groups, n))
        y = (y + p["D"][:, None] * u).reshape(z.shape)
        g = (y * jax.nn.silu(z)).reshape(batch, block, groups, -1)
        g = g * lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
        return (state, before), common.einsum(
            "bsi,ih->bsh", g.reshape(z.shape) * p["norm"],
            p["out_proj"]["kernel"], precision)

    first = (jnp.zeros((batch, heads, width, n), jnp.float32),
             jnp.zeros((batch, taps - 1, conv), jnp.float32))
    out = lax.scan(rows, first, jnp.moveaxis(
        h.reshape(batch, seq // block, block, -1), 1, 0))[1]
    return jnp.moveaxis(out, 0, 1).reshape(h.shape)


def _attention(q, k, v, precision):
    """Causal softmax attention, a block of query rows at a time (one
    ``lax.scan`` over the blocks) against every key, the ones past the
    diagonal masked. q: [b, s, heads, d]; k, v: [b, s, kv, d], read by a
    group of ``heads / kv`` query heads each."""
    batch, seq, heads, d = q.shape
    kv = k.shape[2]
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)
    q = q.reshape(batch, seq, kv, heads // kv, d)   # a group a K/V head

    @jax.checkpoint
    def rows(_, start):
        qi = lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = common.einsum("bqngd,bknd->bngqk", qi, k, precision)
        scores = scores / math.sqrt(d)
        keep = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)[None]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return None, common.einsum("bngqk,bknd->bqngd", probs, v, precision)

    out = lax.scan(rows, None, jnp.arange(0, seq, block))[1]
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, d)


def attention(h, p, cfg, precision="float32"):
    """A ``*`` layer's mixer on its normed input ``h``: no positions."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = common.einsum("bsh,hnd->bsnd", h, p["qkv"]["kernel"], precision)
    a = _attention(out[:, :, :heads], out[:, :, heads:heads + kv],
                   out[:, :, heads + kv:], precision)
    return common.einsum("bsnd,ndh->bsh", a, p["proj"]["kernel"], precision)


def _relu2_ffn(x, up, down, precision):
    h = jnp.square(jax.nn.relu(common.einsum("bsh,hi->bsi", x, up,
                                             precision)))
    return common.einsum("bsi,ih->bsh", h, down, precision)


def route(scores, bias, cfg):
    """[.., experts] weights from the router's sigmoid ``scores``: the
    ``num_experts_per_tok`` largest of ``scores + bias`` keep their own
    score over the chosen scores' sum (``norm_topk_prob``) times
    ``routed_scaling_factor``, the others get 0. ``n_group`` and
    ``topk_group`` are 1: no group limits the choice."""
    assert cfg["norm_topk_prob"] and cfg["n_group"] == cfg["topk_group"] == 1
    _, chosen = lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    picked = scores * jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]),
                              axis=-2)
    return cfg["routed_scaling_factor"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-20)


def router_weights(f, p, bias, cfg):
    return route(jax.nn.sigmoid(jnp.einsum(
        "bsh,he->bse", f, p["router"], precision=lax.Precision.HIGHEST)),
        bias, cfg)


def expert_ffn(f, p, bias, cfg, precision="float32"):
    """An ``E`` layer's share on its normed input ``f``: routing over all
    the model's experts, the held experts' part of the sum, and the
    shared expert."""
    first = cfg["experts_held"][0]
    weights = router_weights(f, p, bias, cfg)

    @jax.checkpoint
    def term(w_up, w_down, weight):
        return weight[..., None] * _relu2_ffn(f, w_up, w_down, precision)

    # The running sum is outside what is made again, so that the way
    # back keeps no copy of it a step.
    held = p["w_up"].shape[0]
    mine = jnp.moveaxis(weights[..., first:first + held], -1, 0)
    routed = lax.scan(lambda y, expert: (y + term(*expert), None),
                      jnp.zeros_like(f), (p["w_up"], p["w_down"], mine))[0]
    return routed + _relu2_ffn(f, p["shared_up"], p["shared_down"],
                               precision)


def _layer(x, p, bias, cfg, precision):
    """One layer: one sub-layer under its norm and one residual add,
    made again on the way back."""
    eps = cfg["layer_norm_epsilon"]

    @jax.checkpoint
    def run(x, p, bias):
        if "mamba2" in p:
            return x + mamba2(_rms_norm(x, p["ln1"]["scale"], eps),
                              p["mamba2"], cfg, precision)
        if "attn" in p:
            return x + attention(_rms_norm(x, p["ln1"]["scale"], eps),
                                 p["attn"], cfg, precision)
        return x + expert_ffn(_rms_norm(x, p["ln2"]["scale"], eps),
                              p["moe"], bias, cfg, precision)

    return run(x, p, bias)


def hidden_fn(params, aux, tokens, cfg, precision="float32"):
    bb = params["params"]["backbone"]
    biases = aux["moe_state"]["backbone"]
    x = bb["tok_embed"]["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        name = f"block_{i}"
        bias = biases[name]["moe"]["bias"] if name in biases else None
        x = _layer(x, bb[name], bias, cfg, precision)
    return _rms_norm(x, bb["ln_f"]["scale"], cfg["layer_norm_epsilon"])


def logits_fn(params, aux, tokens, cfg, precision="float32"):
    """Float32 logits under the untied head."""
    return common.einsum(
        "bsh,hv->bsv", hidden_fn(params, aux, tokens, cfg, precision),
        params["params"]["lm_head"]["kernel"], precision)


def loss_fn(params, aux, batch, cfg, precision="float32"):
    """Mean next-token cross-entropy over the vocabulary slice, the
    logits of ``LOGIT_BLOCK`` positions at a time (one ``lax.scan``),
    each block's made again on the way back. No auxiliary loss."""
    tokens, targets = batch
    head = params["params"]["lm_head"]["kernel"]
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
            common.einsum("bsh,hv->bsv", h, head, precision), targets), None

    total = lax.scan(xent, jnp.zeros(()), (blocks(h), blocks(targets)))[0]
    return total / (h.shape[1] // block), aux


# ---- what the mathematics requires, for ``mfu`` and the rooflines --------

def count(cfg, kind):
    return pattern(cfg).count(kind)


def expert_params(cfg):
    """Matrix parameters a token meets in one expert layer's products:
    (routed, shared). Routed is an expectation: six choices, each held
    here with probability held / published under uniform routing; the
    program computes the real draw. Two matrices an expert."""
    h = cfg["hidden_size"]
    return (cfg["num_experts_per_tok"] * _held(cfg)
            / cfg["n_routed_experts_published"]
            * 2 * h * cfg["moe_intermediate_size"],
            2 * h * cfg["moe_shared_expert_intermediate_size"])


def attention_work(cfg, traffic):
    """(operations, bytes) one row's attention requires, forward and
    backward, over the ``*`` layers: a product of q with the keys at or
    before it and one of the weights with their values, a head wide, for
    each of the query heads, and twice that again on the way back. q, k,
    v, the output and their gradients cross HBM once, in the
    activations' two bytes: q, k, v in and o out forward; q, k, v, o, do
    in and dq, dk, dv out backward."""
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    seq = traffic["seq_len"]
    seen = seq * (seq + 1) // 2
    q, k_and_v = heads * hd, 2 * kv * hd
    moved = 2 * seq * ((2 * q + k_and_v) + (3 * q + k_and_v)
                       + (q + k_and_v))
    n = count(cfg, "*")
    return n * 3 * 2 * 2 * heads * hd * seen, n * moved


def ssd_work(cfg, traffic):
    """(operations, bytes) one row requires of the Mamba-2 recurrence,
    forward and backward, over the ``M`` layers, whatever form computes
    it: the recurrence itself, a multiply-add of every state number to
    decay and feed it and one to read it (``4 x heads x width x N``
    FLOPs a token a layer), three times over; ``u``, ``dt``, ``B``,
    ``C``, ``y`` and their gradients across HBM once, in the dtypes the
    program holds them in (bfloat16; ``dt`` float32): 10,304 numbers a
    token a layer each way."""
    heads, width, groups, n, _, _ = mamba_dims(cfg)
    seq, layers_ = traffic["seq_len"], count(cfg, "M")
    numbers = 2 * heads * width + 2 * groups * n        # u, y, B, C
    moved = 2 * seq * (2 * numbers + 4 * heads)
    return layers_ * 3 * 4 * heads * width * n * seq, layers_ * moved


def flops_per_row(cfg, traffic):
    """FLOPs one row (a sequence) requires, forward and backward. One
    multiply-add is 2 FLOPs, a step is the forward product and two
    backward (x 3); the embedding's gather counts nothing; the
    recurrence as the recurrence (``ssd_work``), whatever form computes
    it; attention as ``attention_work`` (the causal half); the routed
    experts by expectation (``expert_params``); no recomputation, no
    optimizer, no element-wise work (the convolution's taps among
    it)."""
    h = cfg["hidden_size"]
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    m_heads, m_width, _, _, _, m_in = mamba_dims(cfg)
    mixer = h * m_in + m_heads * m_width * h
    attention = h * (heads + 2 * kv) * hd + heads * hd * h
    expert = h * cfg["n_routed_experts_published"] + sum(expert_params(cfg))
    products = (count(cfg, "M") * mixer + count(cfg, "*") * attention
                + count(cfg, "E") * expert + h * cfg["vocab_size"])
    return (6 * traffic["seq_len"] * products + ssd_work(cfg, traffic)[0]
            + attention_work(cfg, traffic)[0])


def expert_products(cfg, traffic):
    """(FLOPs, bytes) a step on one chip requires of the held experts'
    grouped products (two an expert, by the expected draw), forward and
    backward, over every ``E`` layer: what ``moe_experts_roofline`` holds
    against the time under scope ``hvd_moe/experts``. Bytes: each held
    weight read once forward and once backward and its gradient written
    once, as float32; the drawn rows in and out as bfloat16, forward and
    backward. FLOP-bound.

    **The shared expert's two products are not counted**, though the
    scope holds what a trace files of them: XLA fuses their gradients to
    the weights into AdamW's update and one product each way into the
    neighbouring norm, whose roots a trace files under other scopes, so
    of the 39.9 ms those products need at the chip's peak 19 to 27 are
    under the scope. Counted, the share read 112% once the grouped
    kernels took 16 ms of a step and not 90 (my chip run, PR 48): the
    time left out part of the work. Without them the share is a floor:
    it reads low by the shared expert's time that does lie under the
    scope, never high."""
    tokens = traffic["rows_per_chip"] * traffic["seq_len"]
    h, n = cfg["hidden_size"], count(cfg, "E")
    routed, _ = expert_params(cfg)
    weights = 2 * h * _held(cfg) * cfg["moe_intermediate_size"]
    drawn = tokens * routed / (2 * h * cfg["moe_intermediate_size"])
    moved = 3 * 4 * weights + 4 * 2 * drawn * h
    return n * 6 * tokens * routed, n * moved
