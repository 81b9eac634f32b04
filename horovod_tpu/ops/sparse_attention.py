"""Attention over keys that a learned indexer picks for every query
(DeepSeek-V3.2-Exp's sparse attention, the training stage in which the
main model attends over the selected set and the indexer learns from
the main model's attention over it).

Four parts, each under a scope of its own inside ``SCOPE``
(docs/tracing.md), for a row of ``T`` positions:

- ``index``: the index score of every causal pair, ``I[t, s] = sum_j
  w[t, j] relu(qI[t, j] . kI[s])``, in float32 (the products take the
  activations' dtype and accumulate in float32).
- ``select``: for every query the ``min(t + 1, topk)`` keys ``s <= t``
  of largest score, exactly, ties to the smaller ``s``: the score's
  bits in an order-keeping integer form, the ``topk``-th largest of a
  row found bit by bit (32 counts over the row, no sort), ties at it
  taken in key order. The set is a mask, ``[T keys, T queries]`` int8,
  key-major as the flash kernels' tiles are; it is kept for the way
  back and never made a second time.
- ``attend``: the flash kernels of ``ops/flash_attention.py`` with that
  mask as an operand (``mask=``), which run the causal tiles as without
  it; they also hand out the log-sum-exp.
- ``align``: ``L_I = mean_t KL(p[t, .] || softmax_{S_t} I[t, .])`` with
  ``p`` the main attention's probabilities averaged over the heads,
  made again from q, k and the log-sum-exp; one pass gives the loss and
  its gradient to ``qI``, ``kI`` and ``w`` (``p`` is a constant of it,
  and so are q and k).

Every pass is blocked over queries (``lax.scan``) against the keys the
block's group can see (``_extents``: four groups of query blocks, each
against the keys up to its own end, 62.5% of the square where the
causal half is 50%), so that no ``[heads, T, T]`` array lives at once.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils.jax_compat import pvary
from .flash_attention import flash_attention

# Names in a device trace (docs/tracing.md); readers match the literals.
SCOPE = "hvd_dsa"
SCOPE_INDEX = "index"
SCOPE_SELECT = "select"
SCOPE_ATTEND = "attend"
SCOPE_ALIGN = "align"

SELECT_BLOCK = 512      # queries whose scores and counts live at a time
ALIGN_BLOCK = 128       # queries whose 32-head probabilities do
GROUPS = 4              # groups of query blocks by the keys they can see
FLASH_BLOCK = 1024      # the kernels' tiles, as every flash configuration's


def kept_share(seq, topk):
    """Selected pairs over causal pairs of a row of ``seq`` positions."""
    return selected_pairs(seq, topk) / (seq * (seq + 1) // 2)


def selected_pairs(seq, topk):
    """``sum_t min(t + 1, topk)``."""
    short = min(seq, topk)
    return short * (short + 1) // 2 + (seq - short) * topk


def _extents(seq, block):
    """``[(first query, end query, keys)]``: the row's query blocks in
    at most ``GROUPS`` groups, each seeing the keys before its end."""
    blocks = seq // block
    groups = next(g for g in range(min(GROUPS, blocks), 0, -1)
                  if blocks % g == 0)
    per = blocks // groups * block
    return [(g * per, (g + 1) * per, (g + 1) * per) for g in range(groups)]


def _block_size(seq, block):
    block = min(block, seq)
    if seq % block:
        raise ValueError(f"a row of {seq} positions is not whole blocks "
                         f"of {block}")
    return block


def _zeros(shape, dtype, *like):
    """Zeros that vary over the mesh axes ``like`` vary over (inside
    ``shard_map``): what a loop's carry starts as."""
    axes = frozenset().union(*(jax.typeof(x).vma for x in like))
    return functools.reduce(pvary, sorted(axes), jnp.zeros(shape, dtype))


def _scores_t(k_i, q_i, w):
    """``I^T`` [keys, queries] in float32 for queries ``q_i`` [B, J, D],
    ``w`` [B, J] against keys ``k_i`` [n, D]."""
    r = jnp.einsum("kd,qjd->jkq", k_i, q_i,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(r) * w.astype(jnp.float32).T[:, None, :],
                   axis=0)


def _ordered(scores):
    """float32 scores as uint32 in the same order (-0.0 below 0.0)."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    signed = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return lax.bitcast_convert_type(signed, jnp.uint32) ^ jnp.uint32(
        0x80000000)


def select_t(scores_t, first_query, topk):
    """The selected set of a block of queries as int8 [keys, B], 1 where
    query ``first_query + b`` takes the key: its ``min(t + 1, topk)``
    largest ``scores_t[s, b]`` over ``s <= t``, ties to the smaller
    ``s``. Exact: the ``k``-th largest of a column is built from its
    top bit down, a count of the column a bit."""
    n, b = scores_t.shape
    t = first_query + jnp.arange(b)
    causal = jnp.arange(n)[:, None] <= t[None, :]
    # A pair past the diagonal is 0, below every score's form.
    u = jnp.where(causal, _ordered(scores_t), jnp.uint32(0))
    k = jnp.minimum(t + 1, topk)

    def bit(i, theta):
        cand = theta | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        enough = jnp.sum(u >= cand[None, :], axis=0, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, theta)

    theta = lax.fori_loop(0, 32, bit, _zeros((b,), jnp.uint32, scores_t))
    above = u > theta[None, :]
    level = u == theta[None, :]
    room = k - jnp.sum(above, axis=0, dtype=jnp.int32)
    ties = jnp.sum(level, axis=0, dtype=jnp.int32)

    def in_key_order(level):
        return level & (jnp.cumsum(level, axis=0, dtype=jnp.int32)
                        <= room[None, :])

    level = lax.cond(jnp.any(ties > room), in_key_order, lambda x: x, level)
    return (above | level).astype(jnp.int8)


def index_select(q_i, k_i, w, topk):
    """The selected sets of one row: int8 [T keys, T queries] from the
    indexer's ``q_i`` [T, J, D], ``k_i`` [T, D], ``w`` [T, J]. No
    gradient passes through it."""
    q_i, k_i, w = map(lax.stop_gradient, (q_i, k_i, w))
    seq = q_i.shape[0]
    block = _block_size(seq, SELECT_BLOCK)
    parts = []
    for first, end, keys in _extents(seq, block):
        def one(_, at, keys=keys):
            start, q_b, w_b = at
            with jax.named_scope(SCOPE_INDEX):
                scores = _scores_t(k_i[:keys], q_b, w_b)
            with jax.named_scope(SCOPE_SELECT):
                return None, select_t(scores, start, topk)

        n = (end - first) // block
        masks = lax.scan(one, None, (
            first + block * jnp.arange(n),
            q_i[first:end].reshape(n, block, *q_i.shape[1:]),
            w[first:end].reshape(n, block, -1)))[1]      # [n, keys, B]
        with jax.named_scope(SCOPE_SELECT):
            part = jnp.moveaxis(masks, 0, 1).reshape(keys, end - first)
            parts.append(jnp.pad(part, ((0, seq - keys), (0, 0))))
    with jax.named_scope(SCOPE_SELECT):
        return jnp.concatenate(parts, axis=1)


def _head_mean_t(q, k, lse, sm_scale):
    """The probabilities of q [B, H, D] over keys k [n, G, D], averaged
    over the heads, key-major [n, B] float32; ``lse`` [H, B] is the
    attention's own log-sum-exp over the selected set."""
    b, heads, d = q.shape
    groups = k.shape[1]
    s = jnp.einsum("kgd,qgrd->grkq", k,
                   q.reshape(b, groups, heads // groups, d),
                   preferred_element_type=jnp.float32)
    p = jnp.exp(s * sm_scale - lse.reshape(groups, -1, 1, b))
    return jnp.mean(p, axis=(0, 1))


def _kl_sum(p, keep, k_i, q_i, w):
    """``sum_t KL(p[., t] || softmax over the kept of I[., t])`` over a
    block's queries; ``p`` [n, B] is a constant of it."""
    scores = jnp.where(keep, _scores_t(k_i, q_i, w), -jnp.inf)
    log_pi = scores - jax.nn.logsumexp(scores, axis=0, keepdims=True)
    live = keep & (p > 0)
    return jnp.sum(jnp.where(
        live, p * (jnp.log(jnp.where(live, p, 1.0))
                   - jnp.where(live, log_pi, 0.0)), 0.0))


def _align(q, k, lse, mask_t, q_i, k_i, w, sm_scale, with_grads):
    """``(L_I, (dq_i, dk_i, dw))`` of one row, the gradients None
    without ``with_grads``: one pass over the query blocks."""
    seq = q.shape[0]
    block = _block_size(seq, ALIGN_BLOCK)
    varying = (q, k, lse, mask_t, q_i, k_i, w)
    total = _zeros((), jnp.float32, *varying)
    dk_i = _zeros(k_i.shape, jnp.float32, *varying)
    dq_parts, dw_parts = [], []
    for first, end, keys in _extents(seq, block):
        def one(carry, at, keys=keys):
            total, dk_i = carry
            q_b, lse_b, keep_b, qi_b, w_b = at
            keep = keep_b != 0
            p = jnp.where(keep, _head_mean_t(q_b, k[:keys], lse_b, sm_scale),
                          0.0)
            loss = functools.partial(_kl_sum, p, keep)
            if not with_grads:
                return (total + loss(k_i[:keys], qi_b, w_b), dk_i), None
            value, (dk_b, dq_b, dw_b) = jax.value_and_grad(
                loss, argnums=(0, 1, 2))(
                    k_i[:keys].astype(jnp.float32),
                    qi_b.astype(jnp.float32), w_b.astype(jnp.float32))
            return (total + value, dk_i.at[:keys].add(dk_b)), (dq_b, dw_b)

        n = (end - first) // block
        rows = slice(first, end)

        def blocks(x, axis=0):      # [.., end - first, ..] -> [n, .., B, ..]
            x = x.reshape(*x.shape[:axis], n, block, *x.shape[axis + 1:])
            return jnp.moveaxis(x, axis, 0)

        (total, dk_i), grads = lax.scan(one, (total, dk_i), (
            blocks(q[rows]), blocks(lse[:, rows], 1),
            blocks(mask_t[:keys, rows], 1), blocks(q_i[rows]),
            blocks(w[rows])))
        if with_grads:
            dq_parts.append(grads[0].reshape(end - first, *q_i.shape[1:]))
            dw_parts.append(grads[1].reshape(end - first, -1))
    if not with_grads:
        return total / seq, None
    return total / seq, tuple(
        (g / seq).astype(like.dtype) for g, like in (
            (jnp.concatenate(dq_parts), q_i), (dk_i, k_i),
            (jnp.concatenate(dw_parts), w)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def align_loss(q, k, lse, mask_t, q_i, k_i, w, sm_scale):
    """``L_I`` of one row: the mean over its queries of ``KL(p || pi)``
    on the selected set, ``p`` the attention's probabilities (of q
    [T, H, D], k [T, G, D] and the attention's log-sum-exp [H, T])
    averaged over the heads, ``pi`` the softmax of the index scores of
    ``q_i``, ``k_i``, ``w`` over the set ``mask_t`` [T keys, T queries].
    Differentiable in ``q_i``, ``k_i`` and ``w`` alone: ``p`` is a
    constant of the loss."""
    return _align(q, k, lse, mask_t, q_i, k_i, w, sm_scale, False)[0]


def _align_fwd(q, k, lse, mask_t, q_i, k_i, w, sm_scale):
    loss, grads = _align(q, k, lse, mask_t, q_i, k_i, w, sm_scale, True)
    return loss, (grads, q, k, lse, mask_t)


def _align_bwd(sm_scale, res, g):
    (dq_i, dk_i, dw), q, k, lse, mask_t = res
    return (jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(lse),
            np.zeros(mask_t.shape, jax.dtypes.float0),
            (g * dq_i).astype(dq_i.dtype), (g * dk_i).astype(dk_i.dtype),
            (g * dw).astype(dw.dtype))


align_loss.defvjp(_align_fwd, _align_bwd)


def sparse_attention(q, k, v, q_i, k_i, w, topk, *, with_align=True):
    """Attention of q [B, T, H, D] over k, v [B, T, G, D] in which query
    ``t`` sees the ``min(t + 1, topk)`` keys ``s <= t`` that the
    indexer (``q_i`` [B, T, J, Di], ``k_i`` [B, T, Di], ``w`` [B, T, J])
    scores highest. Returns ``(out, align, selected)``: the output
    [B, T, H, D]; the alignment loss, the mean over the batch's rows
    (None without ``with_align``); and the mean number of keys a query
    selected, counted from the mask. The selection carries no gradient;
    ``out``'s reaches q, k and v, the loss's ``q_i``, ``k_i``, ``w``."""
    sm_scale = 1.0 / np.sqrt(q.shape[-1])
    with jax.named_scope(SCOPE):
        mask_t = jnp.stack([index_select(q_i[b], k_i[b], w[b], topk)
                            for b in range(q.shape[0])])
        with jax.named_scope(SCOPE_SELECT):
            selected = jnp.sum(mask_t, dtype=jnp.float32) / (
                q.shape[0] * q.shape[1])
        with jax.named_scope(SCOPE_ATTEND):
            out, lse = flash_attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                causal=True, sm_scale=sm_scale, block_q=FLASH_BLOCK,
                block_k=FLASH_BLOCK, mask=mask_t, with_lse=True)
        align = None
        if with_align:
            with jax.named_scope(SCOPE_ALIGN):
                qs, ks, lses = map(lax.stop_gradient, (q, k, lse))
                align = sum(align_loss(qs[b], ks[b], lses[b], mask_t[b],
                                       q_i[b], k_i[b], w[b], sm_scale)
                            for b in range(q.shape[0])) / q.shape[0]
        return out.swapaxes(1, 2), align, lax.stop_gradient(selected)
