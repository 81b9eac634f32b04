"""Plain reference of the ``KeyeVL2`` family's language model
(Keye-VL-2.0-30B-A3B): a causal decoder of pre-RMSNorm blocks, every
one with grouped-head attention over the keys a learned indexer picks
for each query and an expert FFN. A norm on every head's q and k, rope
whose frequency pairs take their angle from three position streams
(time, height, width), a softmax router over the chosen experts, SwiGLU
experts, no shared expert, an untied head, and beside the language
model's loss the indexer's alignment loss. It reads the parameter tree
the program's ``TransformerLM`` reads, and shares no code with it: no
kernel, no threshold search, no grouped product, no flax.

Published description: the model's ``config.json`` (the configuration
file's ``source``); the attention is DeepSeek-V3.2-Exp's sparse
attention (DeepSeek-AI, "DeepSeek-V3.2-Exp: Boosting Long-Context
Efficiency with DeepSeek Sparse Attention", 2025), whose sparse
training stage this is; the positions are Qwen2-VL's (Wang et al.,
arXiv:2409.12191). What the source does not state is listed in the
configuration file under ``assumed``. The equations, ``x`` a layer's
input ``[T, d]``, ``pos`` ``[T, 3]`` the row's positions, ``sg`` a
stop-gradient:

    h = RMSNorm_1(x)
    q, k, v = h W_q, h W_k, h W_v            # 32, 4, 4 heads of 128
    q, k = RMSNorm over each head's lanes (one gain for q, one for k)
    M-RoPE: pair i of 64 (lane i with lane i + 64) turns by
            pos[t, s(i)] theta^(-i/64), s(i) the run of mrope_section
            [16, 24, 24] that i lies in
    qI = sg(h) W_qI (16 heads of 64);  kI = LayerNorm(sg(h) W_kI)
    plain rope on qI, kI over their 32 pairs by pos[:, 0]
    w = sg(h) W_w / sqrt(16) / sqrt(64)
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t
    S_t = the min(t + 1, 2048) keys s <= t of largest I[t, s]
    A[n, t, .] = softmax over S_t of q[n, t] . k[n // 8, s] / sqrt(128)
    x1 = x + concat_heads(A v) W_o
    p[t, s] = mean_n sg(A[n, t, s]);  pi[t, .] = softmax over S_t of I
    L_I = mean_t sum_{s in S_t} p log(p / pi)
    u = RMSNorm_2(x1);  r = u W_r;  C = top-8 of r
    w_e = exp(r_e) / sum_{c in C} exp(r_c), e in C
    out = x1 + sum_{e in C} w_e W_down,e (silu(W_gate,e u) * (W_up,e u))
    loss = CE(RMSNorm_f(x) W_head, targets) + lambda sum_layers L_I

The language model's loss reaches every parameter but the indexer's,
and ``L_I`` the indexer's alone: the indexer reads ``sg(h)``, the
selection passes no gradient, and ``p`` is a constant of ``L_I``.

The reference is one chip's share of a deployment, as the program is:
it routes over all ``num_experts_published`` experts and computes the
experts ``experts_held`` only, each applied densely to every token and
weighted by the token's weight for it, or by 0; what the absent experts
would add is left out in both.

Departures, all of them about memory and none about a number: index
scores, selection (``lax.top_k`` over the masked scores of a row),
attention and the alignment term are made ``QUERY_BLOCK`` query rows at
a time against every key, and the logits ``LOGIT_BLOCK`` positions at a
time, each block made again on the way back; each half of a block
(attention, experts) and each held expert are made again on the way
back too (``jax.checkpoint``). The blocks are the iterations of a
``lax.scan``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.references import common

QUERY_BLOCK = 256       # rows of the score matrices held at a time
LOGIT_BLOCK = 4096      # positions whose logits are held at a time


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def _indexer(cfg):
    sa = cfg["sa_config"]
    assert sa["indexer_num_kv_heads"] == 1, sa
    return sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]


def _held(cfg):
    first, end = cfg["experts_held"]
    return end - first


def kinds(cfg):
    """The program's kind of every layer run (``models/transformer.py:
    SPARSE``): the stack is uniform."""
    return ["sparse_rope"] * cfg["num_hidden_layers"]


def layout(cfg):
    """The row's spans as the program's ``rope_layout`` takes them."""
    return tuple(tuple(span) for span in cfg["row_layout"])


def positions(cfg):
    """``[T, 3]`` (time, height, width) of the row ``row_layout``
    describes: a text token's three are the running position; an image
    of ``rows x columns`` patches starting at ``p0`` puts patch (r, c)
    at ``(p0, p0 + r, p0 + c)`` and the text after it at ``p0 +
    max(rows, columns)``."""
    table, p = [], 0
    for kind, *size in cfg["row_layout"]:
        if kind == "text":
            table += [(p + i,) * 3 for i in range(size[0])]
            p += size[0]
        else:
            assert kind == "image", kind
            table += [(p, p + r, p + c) for r in range(size[0])
                      for c in range(size[1])]
            p += max(size)
    return np.asarray(table)


def init_params(cfg, key):
    """The weights, made from ``key`` in one traced call: kernels normal
    with variance 1/fan_in, norms' gains 1 and the LayerNorm's bias 0,
    embedding rows normal with variance 1 / ``embedding_fan_in`` of the
    configuration file (``assumed.initializer`` says why that value)."""
    h, heads, kv, hd = _dims(cfg)
    ih, id_, _ = _indexer(cfg)
    width, experts = cfg["moe_intermediate_size"], cfg[
        "num_experts_published"]
    held, vocab = _held(cfg), cfg["vocab_size"]
    keys = iter(jax.random.split(key, 16 * cfg["num_hidden_layers"] + 8))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(
            fan_in)

    def ones(n=h):
        return {"scale": jnp.ones((n,))}

    backbone = {"tok_embed": {"embedding": normal(
        (vocab, h), cfg["embedding_fan_in"])}, "ln_f": ones()}
    for i in range(cfg["num_hidden_layers"]):
        backbone[f"block_{i}"] = {
            "ln1": ones(), "ln2": ones(),
            "attn": {
                # q's heads, then k's, then v's, from one product.
                "qkv": {"kernel": normal((h, heads + 2 * kv, hd), h)},
                "q_norm": ones(hd), "k_norm": ones(hd),
                "proj": {"kernel": normal((heads, hd, h), heads * hd)},
                "indexer": {
                    "q": {"kernel": normal((h, ih, id_), h)},
                    "k": {"kernel": normal((h, id_), h)},
                    "k_norm": {"scale": jnp.ones((id_,)),
                               "bias": jnp.zeros((id_,))},
                    "w": {"kernel": normal((h, ih), h)}}},
            "moe": {"router": normal((h, experts), h),
                    "w_gate": normal((held, h, width), h),
                    "w_up": normal((held, h, width), h),
                    "w_down": normal((held, width, h), width)}}
    return {"params": {"backbone": backbone,
                       "lm_head": {"kernel": normal((h, vocab), h)}}}


def init_aux(cfg):
    """The non-trained state of the program: each expert layer's
    selection bias, which this family has none of (zeros, and nothing
    here reads it), the tokens each expert drew in the last step, and
    each attention layer's last alignment loss and mean number of keys
    selected, all of which the program fills in."""
    experts = cfg["num_experts_published"]
    blocks = [f"block_{i}" for i in range(cfg["num_hidden_layers"])]
    return {
        "moe_state": {"backbone": {b: {"moe": {
            "bias": jnp.zeros((experts,), jnp.float32),
            "expert_tokens": jnp.zeros((experts,), jnp.float32)}}
            for b in blocks}},
        "dsa_state": {"backbone": {b: {"attn": {
            "align_loss": jnp.zeros((), jnp.float32),
            "selected_keys": jnp.zeros((), jnp.float32)}}
            for b in blocks}}}


def _rms_norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * p["scale"]


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, theta, at):
    """x: [b, s, n, d]; ``at`` [s, d / 2]: the position each frequency
    pair of each token turns by. Rotate-half over all of d."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(half) / half))
    angles = jnp.asarray(at * freqs[None, :], jnp.float32)[None, :, None, :]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mrope(x, cfg):
    """Rope by three streams: pair ``i`` reads the stream of the run of
    ``mrope_section`` it lies in."""
    stream = np.repeat(np.arange(3), cfg["rope_scaling"]["mrope_section"])
    return _rope(x, cfg["rope_theta"], positions(cfg)[:, stream])


def rope_first_stream(x, cfg):
    """Plain rope over all of x's pairs by the first position stream."""
    half = x.shape[-1] // 2
    return _rope(x, cfg["rope_theta"],
                 np.repeat(positions(cfg)[:, :1], half, axis=1))


def qkv(h, p, cfg, precision="float32"):
    """q, k, v as attention takes them: q and k normed over each head's
    lanes, then rotated; v as the product gives it."""
    _, heads, kv, _ = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    out = common.einsum("bsh,hnd->bsnd", h, p["qkv"]["kernel"], precision)
    q, k, v = (out[:, :, :heads], out[:, :, heads:heads + kv],
               out[:, :, heads + kv:])
    return (mrope(_rms_norm(q, p["q_norm"], eps), cfg),
            mrope(_rms_norm(k, p["k_norm"], eps), cfg), v)


def indexer(h, p, cfg, precision="float32"):
    """``(qI, kI, w)`` of the layer's normed input, detached."""
    heads, dim, _ = _indexer(cfg)
    h = lax.stop_gradient(h)
    q_i = common.einsum("bsh,hjd->bsjd", h, p["q"]["kernel"], precision)
    k_i = _layer_norm(
        common.einsum("bsh,hd->bsd", h, p["k"]["kernel"], precision),
        p["k_norm"], cfg["rms_norm_eps"])
    w = common.einsum("bsh,hj->bsj", h, p["w"]["kernel"], precision)
    return (rope_first_stream(q_i, cfg),
            rope_first_stream(k_i[:, :, None], cfg)[:, :, 0],
            w / math.sqrt(heads) / math.sqrt(dim))


def index_scores(q_i, k_i, w, precision="float32"):
    """``I[b, t, s]`` for queries ``q_i`` [b, q, j, d], ``w`` [b, q, j]
    against every key ``k_i`` [b, s, d]."""
    r = common.einsum("bqjd,bkd->bqjk", q_i, k_i, precision)
    return jnp.sum(w[..., None] * jax.nn.relu(r), axis=2)


def select(scores, rows, topk):
    """[b, q, s] of booleans: the set ``S_t`` of every query of a block
    at positions ``rows``: the ``min(t + 1, topk)`` largest ``scores``
    over ``s <= t``, ties to the smaller ``s`` (``lax.top_k`` keeps the
    order of equal entries)."""
    b, n, seq = scores.shape
    causal = rows[:, None] >= jnp.arange(seq)[None, :]
    masked = jnp.where(causal, lax.stop_gradient(scores), -jnp.inf)
    _, chosen = lax.top_k(masked, min(topk, seq))
    taken = jnp.zeros((b, n, seq), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(n)[None, :, None],
        chosen].set(True)
    return jnp.logical_and(taken, causal)


def sparse_attention(q, k, v, q_i, k_i, w, cfg, precision="float32"):
    """``(out, L_I, selected)``: attention of every query over its
    selected set, the alignment loss (the mean over rows and queries)
    and the mean number of keys a query selected; a block of query rows
    at a time (one ``lax.scan``) against every key."""
    seq, heads, d = q.shape[1], q.shape[2], q.shape[-1]
    group = heads // k.shape[2]
    topk = _indexer(cfg)[2]
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))

    @jax.checkpoint
    def rows(_, start):
        def cut(x):
            return lax.dynamic_slice_in_dim(x, start, block, axis=1)

        scores = index_scores(cut(q_i), k_i, cut(w), precision)
        taken = select(scores, start + jnp.arange(block), topk)
        logits = common.einsum("bqnd,bknd->bnqk", cut(q), k, precision)
        logits = logits / math.sqrt(d)
        probs = jax.nn.softmax(
            jnp.where(taken[:, None], logits, -jnp.inf), axis=-1)
        out = common.einsum("bnqk,bknd->bqnd", probs, v, precision)
        p = lax.stop_gradient(jnp.mean(probs, axis=1))
        log_pi = jax.nn.log_softmax(jnp.where(taken, scores, -jnp.inf),
                                    axis=-1)
        live = jnp.logical_and(taken, p > 0)
        kl = jnp.sum(jnp.where(
            live, p * (jnp.log(jnp.where(live, p, 1.0))
                       - jnp.where(live, log_pi, 0.0)), 0.0), axis=-1)
        return None, (out, jnp.sum(kl), jnp.sum(taken))

    out, kl, count = lax.scan(rows, None, jnp.arange(0, seq, block))[1]
    tokens = q.shape[0] * seq
    return (jnp.moveaxis(out, 0, 1).reshape(q.shape), jnp.sum(kl) / tokens,
            jnp.sum(count) / tokens)


def attention(h, p, cfg, precision="float32"):
    """A layer's attention on its normed input ``h`` [b, s, d]:
    ``(output, L_I, mean keys selected)``."""
    a, align, count = sparse_attention(
        *qkv(h, p, cfg, precision), *indexer(h, p["indexer"], cfg, precision),
        cfg, precision)
    return common.einsum("bsnd,ndh->bsh", a, p["proj"]["kernel"],
                         precision), align, count


def _swiglu(x, gate, up, down, precision):
    h = jax.nn.silu(common.einsum("bsh,hi->bsi", x, gate, precision))
    h = h * common.einsum("bsh,hi->bsi", x, up, precision)
    return common.einsum("bsi,ih->bsh", h, down, precision)


def route(r, cfg):
    """[.., experts] weights from router logits ``r``: the softmax over
    the chosen ``num_experts_per_tok`` (``norm_topk_prob``), 0 for the
    others."""
    assert cfg["norm_topk_prob"]
    _, chosen = lax.top_k(r, cfg["num_experts_per_tok"])
    is_chosen = jnp.sum(jax.nn.one_hot(chosen, r.shape[-1]), axis=-2) > 0
    return jax.nn.softmax(jnp.where(is_chosen, r, -jnp.inf), axis=-1)


def expert_ffn(u, p, cfg, precision="float32"):
    """The expert layer's share on its input ``u``: routing over all the
    model's experts, the held experts' part of the sum."""
    first = cfg["experts_held"][0]
    weights = route(jnp.einsum("bsh,he->bse", u, p["router"],
                               precision=lax.Precision.HIGHEST), cfg)

    @jax.checkpoint
    def term(w_gate, w_up, w_down, weight):
        return weight[..., None] * _swiglu(u, w_gate, w_up, w_down,
                                           precision)

    # The running sum is outside what is made again, so that the way
    # back keeps no copy of it a step.
    held = p["w_gate"].shape[0]
    mine = jnp.moveaxis(weights[..., first:first + held], -1, 0)
    return lax.scan(lambda y, expert: (y + term(*expert), None),
                    jnp.zeros_like(u),
                    (p["w_gate"], p["w_up"], p["w_down"], mine))[0]


def _block(x, p, cfg, precision):
    """One pre-norm block: ``(output, L_I, mean keys selected)``. Each
    half is made again on the way back, so that the float32 activations
    of attention are not held through the experts' backward pass."""
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def mix(x, p):
        a, align, count = attention(_rms_norm(x, p["ln1"], eps), p["attn"],
                                    cfg, precision)
        return x + a, align, count

    @jax.checkpoint
    def ffn(x, p):
        return x + expert_ffn(_rms_norm(x, p["ln2"], eps), p["moe"], cfg,
                              precision)

    x, align, count = mix(x, p)
    return ffn(x, p), align, count


def hidden_fn(params, tokens, cfg, precision="float32"):
    """``(final hidden states, [L_I a layer], [mean keys selected a
    layer])``."""
    bb = params["params"]["backbone"]
    x = bb["tok_embed"]["embedding"][tokens]
    aligns, counts = [], []
    for i in range(cfg["num_hidden_layers"]):
        x, align, count = _block(x, bb[f"block_{i}"], cfg, precision)
        aligns.append(align)
        counts.append(count)
    return _rms_norm(x, bb["ln_f"], cfg["rms_norm_eps"]), aligns, counts


def losses_fn(params, batch, cfg, precision="float32"):
    """``(CE, [L_I a layer], [mean keys selected a layer])``: the mean
    next-token cross-entropy over the vocabulary slice, the logits of
    ``LOGIT_BLOCK`` positions at a time (one ``lax.scan``), each
    block's made again on the way back; and what the layers hand out."""
    tokens, targets = batch
    kernel = params["params"]["lm_head"]["kernel"]
    h, aligns, counts = hidden_fn(params, tokens, cfg, precision)
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
    return total / (h.shape[1] // block), aligns, counts


def loss_fn(params, aux, batch, cfg, precision="float32"):
    """``CE + align_loss_weight * sum_layers L_I``."""
    ce, aligns, _ = losses_fn(params, batch, cfg, precision)
    return ce + cfg["align_loss_weight"] * sum(aligns), aux


# ---- what the mathematics requires, for ``mfu`` and the rooflines --------

def attention_layers(cfg):
    return cfg["num_hidden_layers"]


def expert_params(cfg):
    """Matrix parameters a token meets in one expert layer's products:
    (routed, shared). Routed is an expectation: eight choices, each held
    here with probability held / published under uniform routing; the
    program computes the real draw. There is no shared expert."""
    one = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return (cfg["num_experts_per_tok"] * _held(cfg)
            / cfg["num_experts_published"] * one, 0)


def causal_pairs(traffic):
    """(query, key) pairs of a row with the key at or before the query:
    what the indexer scores."""
    seq = traffic["seq_len"]
    return seq * (seq + 1) // 2


def selected_pairs(cfg, traffic):
    """``sum_t min(t + 1, topk)``: the pairs of a row, a layer, that
    attention and the alignment term are over."""
    seq, topk = traffic["seq_len"], _indexer(cfg)[2]
    short = min(seq, topk)
    return short * (short + 1) // 2 + (seq - short) * topk


def attention_work(cfg, traffic):
    """(operations, bytes) one row's attention over the selected keys
    requires, forward and backward, over the layers: a product of q
    with a query's selected keys and one of the weights with their
    values, ``head_dim`` wide, for each of the query heads, and twice
    that again on the way back. The count is of the selected pairs
    whatever computes them: a kernel that runs every causal tile under a
    mask does 4.27 times the pairs at 16,384 positions, and is judged
    by this. Bytes: q, k, v, the output and their gradients cross HBM
    once, in the activations' two bytes."""
    _, heads, kv, hd = _dims(cfg)
    seq, layers = traffic["seq_len"], cfg["num_hidden_layers"]
    operations = layers * 3 * 2 * 2 * heads * hd * selected_pairs(
        cfg, traffic)
    q, k_and_v = heads * hd, 2 * kv * hd
    moved = layers * 2 * seq * ((2 * q + k_and_v) + (3 * q + k_and_v)
                                + (q + k_and_v))
    return operations, moved


def index_work(cfg, traffic):
    """(operations, bytes) one row's index scores require over the
    layers: a product ``indexer_head_dim`` wide for each indexer head
    over every causal pair, counted three times over as every other
    product is (the alignment term takes its gradient). Bytes: qI, kI
    and w and their gradients once, in two bytes."""
    heads, dim, _ = _indexer(cfg)
    seq, layers = traffic["seq_len"], cfg["num_hidden_layers"]
    operations = layers * 3 * 2 * heads * dim * causal_pairs(traffic)
    moved = layers * 2 * 2 * seq * (heads * dim + dim + heads)
    return operations, moved


def block_params(cfg):
    """Matrix parameters a token meets in one block's products outside
    the scores: q, k, v and the output projection, the indexer's three
    projections, the router, the experts by expectation."""
    h, heads, kv, hd = _dims(cfg)
    ih, id_, _ = _indexer(cfg)
    return (h * (heads + 2 * kv) * hd + heads * hd * h
            + h * (ih * id_ + id_ + ih)
            + h * cfg["num_experts_published"] + sum(expert_params(cfg)))


def flops_per_row(cfg, traffic):
    """FLOPs one row (a sequence) requires, forward and backward. One
    multiply-add is 2 FLOPs, a step is the forward product and two
    backward (x 3); the embedding is a gather and counts nothing;
    attention over the selected pairs (``attention_work``), the index
    scores over the causal pairs (``index_work``); the routed experts
    by expectation (``expert_params``); no recomputation (the alignment
    term's second pass over the heads' scores among it), no optimizer,
    no element-wise work."""
    products = (cfg["num_hidden_layers"] * block_params(cfg)
                + cfg["hidden_size"] * cfg["vocab_size"])
    return (6 * traffic["seq_len"] * products
            + attention_work(cfg, traffic)[0] + index_work(cfg, traffic)[0])


def expert_products(cfg, traffic):
    """(FLOPs, bytes) a step on one chip requires of the products under
    scope ``hvd_moe/experts``: the held experts' grouped products,
    forward and backward, over every layer. Bytes: each weight read once
    forward and once backward and its gradient written once, as float32;
    the tokens in and out as bfloat16, forward and backward."""
    tokens = traffic["rows_per_chip"] * traffic["seq_len"]
    layers = cfg["num_hidden_layers"]
    weights = _held(cfg) * 3 * cfg["hidden_size"] * cfg[
        "moe_intermediate_size"]
    moved = 3 * 4 * weights + 4 * 2 * tokens * cfg["hidden_size"]
    return (layers * 6 * tokens * sum(expert_params(cfg)), layers * moved)
