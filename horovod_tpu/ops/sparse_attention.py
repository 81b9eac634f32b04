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
  made again from q, k and the log-sum-exp. One Mosaic kernel
  (``KERNEL_ALIGN``) gives the loss and its gradient to ``qI``, ``kI``
  and ``w`` in one sweep (``p`` is a constant of the loss, and so are q
  and k): a grid step is one (key block, query block) tile that holds
  a causal pair, from a table made while tracing, as the flash
  kernels' steps are; inside it the heads' products, ``exp`` and
  their sum, the indexer heads' products, the loss's terms, and the
  gradient in closed form, ``dL/dI = (pi - p) / T``, through the same
  products transposed. No tile of scores or probabilities is written.
  The log-sum-exp of ``I`` over a query's set, which ``pi`` needs
  before the sweep, comes from ``select``, where a block's scores and
  set are at hand.

``index`` and ``select`` are blocked over queries (``lax.scan``) against
the keys the block's group can see (``_extents``: four groups of query
blocks, each against the keys up to its own end, 62.5% of the square
where the causal half is 50%); ``attend`` and ``align`` run the causal
tiles. No ``[heads, T, T]`` array lives at once.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.jax_compat import pvary
from . import flash_attention as fa

# Names in a device trace (docs/tracing.md); readers match the literals.
SCOPE = "hvd_dsa"
SCOPE_INDEX = "index"
SCOPE_SELECT = "select"
SCOPE_ATTEND = "attend"
SCOPE_ALIGN = "align"
KERNEL_ALIGN = "hvd_dsa_align"      # the Mosaic call under SCOPE_ALIGN

SELECT_BLOCK = 512      # queries whose scores and counts live at a time
GROUPS = 4              # groups of query blocks by the keys they can see
FLASH_BLOCK = 1024      # the kernels' tiles, as every flash configuration's
# The alignment kernel's tile: queries (lanes) by keys.
ALIGN_TILE_Q = 256
ALIGN_TILE_K = 256


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
    """The selected sets of one row, int8 [T keys, T queries], from the
    indexer's ``q_i`` [T, J, D], ``k_i`` [T, D], ``w`` [T, J], and the
    log-sum-exp of every query's index scores over its set, float32
    [T]. No gradient passes through it."""
    q_i, k_i, w = map(lax.stop_gradient, (q_i, k_i, w))
    seq = q_i.shape[0]
    block = _block_size(seq, SELECT_BLOCK)
    parts, lses = [], []
    for first, end, keys in _extents(seq, block):
        def one(_, at, keys=keys):
            start, q_b, w_b = at
            with jax.named_scope(SCOPE_INDEX):
                scores = _scores_t(k_i[:keys], q_b, w_b)
            with jax.named_scope(SCOPE_SELECT):
                taken = select_t(scores, start, topk)
                return None, (taken, jax.nn.logsumexp(
                    jnp.where(taken != 0, scores, -jnp.inf), axis=0))

        n = (end - first) // block
        masks, lse = lax.scan(one, None, (
            first + block * jnp.arange(n),
            q_i[first:end].reshape(n, block, *q_i.shape[1:]),
            w[first:end].reshape(n, block, -1)))[1]      # [n, keys, B]
        with jax.named_scope(SCOPE_SELECT):
            part = jnp.moveaxis(masks, 0, 1).reshape(keys, end - first)
            parts.append(jnp.pad(part, ((0, seq - keys), (0, 0))))
            lses.append(lse.reshape(-1))
    with jax.named_scope(SCOPE_SELECT):
        return jnp.concatenate(parts, axis=1), jnp.concatenate(lses)


def align_tiles(seq, block_q=None, block_k=None):
    """The alignment kernel's grid over a row of ``seq`` positions:
    ``run``, the (query block, key block) tiles it has, which are the
    ones that hold a causal pair, and ``rectangle``, every tile of the
    square. From shapes alone."""
    block_q = _block_size(seq, block_q or ALIGN_TILE_Q)
    block_k = _block_size(seq, block_k or ALIGN_TILE_K)
    n_q, n_k = seq // block_q, seq // block_k
    steps = _align_steps(seq, block_q, block_k)
    return {"run": steps.shape[0] // 4, "rectangle": n_q * n_k}


def _align_steps(seq, block_q, block_k):
    """The kernel's step table: the flash forward's (``_step_table``), a
    query block's key blocks up to the diagonal one after the other."""
    return fa._step_table(False, (0, 0, seq), seq // block_q,
                          seq // block_k, block_q, block_k, True)


def _publish_tiles(seq, block_q, block_k):
    """Set ``hvd_dsa_align_tiles{kind}`` from ``align_tiles`` of the
    call being traced (docs/metrics.md). A no-op with metrics off."""
    from ..telemetry import core as telemetry
    if not telemetry.enabled():
        return
    gauge = telemetry.gauge(
        "hvd_dsa_align_tiles",
        "Grid steps of the alignment kernel of the call last traced, a "
        "step a (query block, key block) tile: run, the tiles that hold "
        "a causal pair, and rectangle, every tile of the square",
        ("kind",))
    for kind, n in align_tiles(seq, block_q, block_k).items():
        gauge.labels(kind=kind).set(float(n))


def _lane_pack(n_j, d_i):
    """How many of the indexer's ``n_j`` heads of ``d_i`` lie side by
    side in the lanes of one block: the most that fill a lane tile and
    divide the heads (2 of 64 in 128 lanes). The kernel takes ``q_i``
    as it is stored, ``[T, n_j * d_i]``, and ``k_i`` once a slot,
    ``[pack, T, pack * d_i]`` with zeros beside it, so a product of a
    lane tile of ``q_i`` with slot ``c`` of ``k_i`` is head ``c`` of the
    tile's alone, and no 64-wide array is padded to the lanes in HBM or
    transposed on its way in or out (a contraction half zeros costs the
    kernel 0.9 ms a layer over the 64-wide one: PERF.md section 6,
    PR 46)."""
    return max(p for p in range(1, n_j + 1)
               if n_j % p == 0 and p * d_i <= max(fa._LANE, d_i))


def _align_kernel(steps_ref, q_ref, k_ref, lse_ref, mask_ref, qi_ref, ki_ref,
                  w_ref, lsei_ref, loss_ref, *rest, sm_scale, seq, block_k,
                  with_grads):
    """One (key block, query block) tile of the alignment pass,
    key-major as the flash kernels' tiles: ``p`` from the heads'
    products, the index scores from the indexer's, the loss's terms
    and, ``with_grads``, what the tile adds to the three gradients,
    which leave divided by the row's length. ``rest`` is then
    ``dqi_ref, dki_ref, dw_ref``, ``dq_i``'s float32 accumulator and
    the scratch that holds the indexer heads' products, past ``relu``,
    between their two uses."""
    step = pl.program_id(0)
    kb = fa._column(steps_ref, fa._INNER, step)
    flags = fa._column(steps_ref, fa._FLAGS, step)
    heads, groups = q_ref.shape[0], k_ref.shape[0]
    n_j, (pack, _, width) = w_ref.shape[0], ki_ref.shape
    nt = (((1,), (1,)), ((), ()))
    f32 = jnp.float32

    def packed(j):
        """Head ``j`` of the indexer: its lane tile of ``q_i`` and of
        ``dq_i``, and its slot of ``k_i`` (_lane_pack)."""
        tile, slot = divmod(j, pack)
        return slice(tile * width, (tile + 1) * width), slot

    per_block = (loss_ref,)
    if with_grads:
        dqi_ref, dki_ref, dw_ref, dq_scr, r_scr = rest
        per_block = (loss_ref, dq_scr, dw_ref)

        @pl.when(step == 0)
        def _():
            dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when((flags & fa._ROW_FIRST) != 0)
    def _():
        for ref in per_block:
            ref[...] = jnp.zeros_like(ref)

    # The heads' probabilities, summed: a K/V head's keys against each
    # of its query heads, never written.
    p = None
    for h in range(heads):
        st = lax.dot_general(k_ref[h // (heads // groups)], q_ref[h], nt,
                             preferred_element_type=f32)
        e = jnp.exp(st * sm_scale - lse_ref[h:h + 1, :])
        p = e if p is None else p + e
    keep = fa._mask_tile(mask_ref, slice(None), slice(None))
    p = jnp.where(keep, p * (1.0 / heads), 0.0)

    scores = None
    for j in range(n_j):
        lanes, slot = packed(j)
        r = jnp.maximum(lax.dot_general(
            ki_ref[slot], qi_ref[:, lanes], nt, preferred_element_type=f32),
            0.0)
        if with_grads:
            r_scr[j] = r
        term = w_ref[j:j + 1, :] * r
        scores = term if scores is None else scores + term
    log_pi = scores - lsei_ref[...]
    live = keep & (p > 0)
    loss_ref[...] += jnp.sum(jnp.where(
        live, p * (jnp.log(jnp.where(live, p, 1.0)) - log_pi), 0.0),
        axis=0, keepdims=True)
    if not with_grads:
        return

    # dL/dI times the row's length, with sum_s p[s, t] taken as 1: the
    # log-sum-exp is the attention's own over the same set.
    d = jnp.where(keep, jnp.exp(log_pi), 0.0) - p
    dk = [None] * pack
    for j in range(n_j):
        lanes, slot = packed(j)
        r = r_scr[j]
        g = jnp.where(r > 0, d * w_ref[j:j + 1, :], 0.0).astype(
            qi_ref.dtype)
        # Into head j's lanes of the tile: the slot's zeros keep the
        # other heads' lanes as they are.
        dq_scr[:, lanes] += lax.dot_general(
            g, ki_ref[slot], (((0,), (0,)), ((), ())),
            preferred_element_type=f32)
        # Head j's lanes hold its share of dk_i; the others are dropped
        # below.
        part = jnp.dot(g, qi_ref[:, lanes], preferred_element_type=f32)
        dk[slot] = part if dk[slot] is None else dk[slot] + part
        dw_ref[j:j + 1, :] += jnp.sum(d * r, axis=0, keepdims=True)
    slot_of = lax.broadcasted_iota(jnp.int32, dk[0].shape, 1) // (
        width // pack)
    rows = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
    dki_ref[rows, :] += sum(jnp.where(slot_of == slot, x, 0.0)
                            for slot, x in enumerate(dk))

    @pl.when((flags & fa._ROW_LAST) != 0)
    def _():
        dqi_ref[...] = (dq_scr[...] * (1.0 / seq)).astype(dqi_ref.dtype)
        dw_ref[...] *= 1.0 / seq

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        dki_ref[...] *= 1.0 / seq


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_q", "block_k", "with_grads", "interpret"))
def _align_call(q, k, lse, mask_t, q_i, k_i, w, lse_i, steps, *, sm_scale,
                block_q, block_k, with_grads, interpret):
    """The kernel over one row: q [H, T, D], k [G, T, D], lse [H, T],
    mask_t [1, T keys, T queries], q_i [T, J * Di], k_i [pack, T,
    pack * Di] (_lane_pack), w [J, T] float32, lse_i [1, T]. Grid
    ``(steps,)``, a step a tile of ``steps`` (``_align_steps``).
    Returns the loss's terms summed over the keys, [1, T], and
    ``with_grads`` the gradients over T: ``dq_i`` as ``q_i``, ``dk_i``
    [T, pack * Di] float32, a head's share in its slot's lanes, ``dw``
    [J, T] float32. What a query block's tiles add to it stays in VMEM
    until the block ends; ``dk_i`` stays for the whole call. Through
    ``jax.jit`` as the flash calls are: the layers share one
    lowering."""
    seq, n_j = q.shape[1], w.shape[0]
    pack, _, width = k_i.shape
    operands = (q, k, lse, mask_t, q_i, k_i, w, lse_i)

    def qb(s, steps):
        return fa._column(steps, fa._ROW, s)

    def kb(s, steps):
        return fa._column(steps, fa._FETCH, s)

    def heads_of(x, block, at):       # [heads, T, d]: a block of T
        return pl.BlockSpec((x.shape[0], block, x.shape[2]),
                            lambda s, steps: (0, at(s, steps), 0))

    def queries(rows):                # [rows, T]: a block of T
        return pl.BlockSpec((rows, block_q),
                            lambda s, steps: (0, qb(s, steps)))

    qi_spec = pl.BlockSpec((block_q, q_i.shape[1]),
                           lambda s, steps: (qb(s, steps), 0))
    in_specs = [
        heads_of(q, block_q, qb), heads_of(k, block_k, kb),
        queries(lse.shape[0]),
        pl.BlockSpec((1, block_k, block_q),
                     lambda s, steps: (0, kb(s, steps), qb(s, steps))),
        qi_spec, heads_of(k_i, block_k, kb), queries(n_j), queries(1),
    ]
    out_specs = [queries(1)]
    out_shape = [fa._struct((1, seq), jnp.float32, *operands)]
    scratch = []
    if with_grads:
        out_specs += [qi_spec,
                      pl.BlockSpec((seq, width), lambda s, steps: (0, 0)),
                      queries(n_j)]
        out_shape += [fa._struct(q_i.shape, q_i.dtype, *operands),
                      fa._struct((seq, width), jnp.float32, *operands),
                      fa._struct(w.shape, jnp.float32, *operands)]
        scratch = [pltpu.VMEM((block_q, q_i.shape[1]), jnp.float32),
                   pltpu.VMEM((n_j, block_k, block_q), jnp.float32)]
    # Scoped VMEM: the blocks, double-buffered, the scratch, a dozen
    # tiles of float32 for what a step makes, and room.
    vmem = (sum(2 * math.prod(spec.block_shape) * x.dtype.itemsize
                for spec, x in zip(in_specs + out_specs,
                                   operands + tuple(out_shape)))
            + sum(math.prod(x.shape) * x.dtype.itemsize for x in scratch)
            + 12 * block_k * block_q * 4 + 4 * 2 ** 20)
    return pl.pallas_call(
        functools.partial(_align_kernel, sm_scale=sm_scale, seq=seq,
                          block_k=block_k, with_grads=with_grads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps.shape[0] // 4,),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name=KERNEL_ALIGN,
    )(steps, *operands)


def _by_tiles(q, k, lse, mask_t, q_i, k_i, w, lse_i, sm_scale, with_grads):
    """``(sum of the loss's terms, dq_i / T, dk_i / T, dw / T)`` of one
    row from the kernel, the operands laid out for it and the gradients
    brought back to theirs."""
    seq, n_j, d_i = q_i.shape
    block_q = _block_size(seq, ALIGN_TILE_Q)
    block_k = _block_size(seq, ALIGN_TILE_K)
    _publish_tiles(seq, block_q, block_k)
    pack = _lane_pack(n_j, d_i)
    slots = jnp.stack([jnp.pad(k_i, ((0, 0), (c * d_i, (pack - 1 - c) * d_i)))
                       for c in range(pack)])
    loss, *grads = _align_call(
        q, k, lse, mask_t[None], q_i.reshape(seq, -1), slots,
        w.astype(jnp.float32).T, lse_i[None, :],
        _align_steps(seq, block_q, block_k), sm_scale=float(sm_scale),
        block_q=block_q, block_k=block_k, with_grads=with_grads,
        interpret=fa._interpret())
    if not with_grads:
        return (jnp.sum(loss),)
    dq_i, dk_i, dw = grads
    return (jnp.sum(loss), dq_i.reshape(q_i.shape),
            dk_i.reshape(seq, pack, d_i).sum(axis=1), dw.T)


def _every_pair(q, k, lse, mask_t, q_i, k_i, w, lse_i, sm_scale):
    """What ``_by_tiles`` returns, from every pair of the row at once:
    the kernel's arithmetic in as many lines, for the one place the
    kernel cannot run (``_loss_and_grads``)."""
    f32, seq = jnp.float32, q.shape[1]

    def product(spec, a, b):
        # The operands' values as they are, summed in float32 (the
        # CPU's dot takes no bfloat16 pair into float32 in every form).
        return jnp.einsum(spec, a.astype(f32), b.astype(f32))

    keep = mask_t != 0
    s = product("gsd,grtd->grst", k, q.reshape(k.shape[0], -1, *q.shape[1:]))
    p = jnp.exp(s * sm_scale - lse.reshape(*s.shape[:2], 1, -1))
    p = jnp.where(keep, jnp.mean(p, axis=(0, 1)), 0.0)
    r = jnp.maximum(product("sd,tjd->jst", k_i, q_i), 0.0)
    w_t = w.astype(f32).T[:, None, :]
    log_pi = jnp.sum(w_t * r, axis=0) - lse_i
    live = keep & (p > 0)
    loss = jnp.sum(jnp.where(
        live, p * (jnp.log(jnp.where(live, p, 1.0)) - log_pi), 0.0))
    d = (jnp.where(keep, jnp.exp(log_pi), 0.0) - p) / seq
    g = jnp.where(r > 0, d * w_t, 0.0).astype(k_i.dtype)
    return (loss, product("jst,sd->tjd", g, k_i),
            product("jst,tjd->sd", g, q_i), jnp.sum(d * r, axis=1).T)


def _loss_and_grads(q, k, lse, mask_t, q_i, k_i, w, lse_i, sm_scale,
                    with_grads):
    """``(L_I, (dq_i, dk_i, dw))`` of one row, the gradients None
    without ``with_grads``: one call of the kernel. Off the TPU it runs
    in Pallas's interpreter, which refuses device-varying operands
    (inside ``shard_map``, as for the flash kernels, whose rule this
    is: ``flash_attention`` steps aside to ``reference_attention``
    there); the row then goes through ``_every_pair``."""
    operands = (q, k, lse, mask_t, q_i, k_i, w, lse_i)
    if fa._interpret() and fa._varying(*operands):
        loss, *grads = _every_pair(*operands, sm_scale)
    else:
        loss, *grads = _by_tiles(*operands, sm_scale, with_grads)
    loss = loss / q.shape[1]
    if not with_grads:
        return loss, None
    return loss, tuple(g.astype(like.dtype)
                       for g, like in zip(grads, (q_i, k_i, w)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def align_loss(q, k, lse, mask_t, q_i, k_i, w, lse_i, sm_scale):
    """``L_I`` of one row: the mean over its queries of ``KL(p || pi)``
    on the selected set, ``p`` the attention's probabilities (of q
    [H, T, D], k [G, T, D], head-major as the flash kernels take them,
    and the attention's log-sum-exp [H, T]) averaged over the heads,
    ``pi`` the softmax of the index scores of ``q_i``, ``k_i``, ``w``
    over the set ``mask_t`` [T keys, T queries], whose log-sum-exp over
    a query's set is ``lse_i`` [T] (``index_select`` gives both).
    Differentiable in ``q_i``, ``k_i`` and ``w`` alone: ``p`` is a
    constant of the loss, and so is ``lse_i``, whose part of the
    gradient the closed form holds."""
    return _loss_and_grads(q, k, lse, mask_t, q_i, k_i, w, lse_i, sm_scale,
                           False)[0]


def _align_fwd(q, k, lse, mask_t, q_i, k_i, w, lse_i, sm_scale):
    loss, grads = _loss_and_grads(q, k, lse, mask_t, q_i, k_i, w, lse_i,
                                  sm_scale, True)
    return loss, (grads, q, k, lse, mask_t, lse_i)


def _align_bwd(sm_scale, res, g):
    (dq_i, dk_i, dw), q, k, lse, mask_t, lse_i = res
    return (jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(lse),
            np.zeros(mask_t.shape, jax.dtypes.float0),
            (g * dq_i).astype(dq_i.dtype), (g * dk_i).astype(dk_i.dtype),
            (g * dw).astype(dw.dtype), jnp.zeros_like(lse_i))


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
    rows = range(q.shape[0])
    with jax.named_scope(SCOPE):
        mask_t, lse_i = map(jnp.stack, zip(*(
            index_select(q_i[b], k_i[b], w[b], topk) for b in rows)))
        with jax.named_scope(SCOPE_SELECT):
            selected = jnp.sum(mask_t, dtype=jnp.float32) / (
                q.shape[0] * q.shape[1])
        with jax.named_scope(SCOPE_ATTEND):
            out, lse = fa.flash_attention(
                q, k, v, causal=True, sm_scale=sm_scale,
                block_q=FLASH_BLOCK, block_k=FLASH_BLOCK, mask=mask_t,
                with_lse=True, layout="bshd")
        align = None
        if with_align:
            with jax.named_scope(SCOPE_ALIGN):
                # The alignment kernel's rows are heads: its copies of q
                # and k are the pass's own, and carry no gradient.
                qs, ks, lses = map(lax.stop_gradient, (
                    q.swapaxes(1, 2), k.swapaxes(1, 2), lse))
                align = sum(align_loss(qs[b], ks[b], lses[b], mask_t[b],
                                       q_i[b], k_i[b], w[b], lse_i[b],
                                       sm_scale)
                            for b in rows) / q.shape[0]
        return out, align, lax.stop_gradient(selected)
