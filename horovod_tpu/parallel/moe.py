"""Sparse experts: a dropless expert layer, routed by a sigmoid or a
softmax, that is told which experts it holds.

An expert, routed or shared, has one of two forms (``MoEConfig.gate``):

    gated     W_down (act(W_gate x) * W_up x)    "silu" (SwiGLU), "relu" (ReGLU)
    ungated   W_down act(W_up x)                 "relu2": act(v) = relu(v)^2

The gated form has three matrices an expert (``w_gate``, ``w_up``,
``w_down``; ``shared_gate``, ``shared_up``, ``shared_down``), the
ungated form two: it has no ``w_gate`` and no ``shared_gate`` leaf,
keeps one product of the sized rows where the gated form keeps two, and
pulls a gradient back through four grouped products, not six.

The layer routes every token over all the experts the model has (the
router keeps its published width and its experts per token) and computes
the part of the result that the experts held here give, plus the shared
expert, which every chip computes alike. That is one chip's share of an
expert-parallel layer (``first_held`` says where its experts start; with
all of them held it is the whole layer). The shares of all the chips,
the shared expert counted once, add up to the whole layer's output
(``tests/test_glm4_moe_lite.py``). Exchanging tokens between chips
(the all-to-all of a layout in which each chip sees its own tokens only)
is not here: ROADMAP B3.

How it computes, and why (PERF.md section 3, "expert layer"):

- Routing (scope ``hvd_moe/route``, which also holds the sort, the
  gathers, the weighing and the way back into token order below):
  scores in float32 at ``highest`` precision, ``sigmoid(r W_r)`` or the
  logits ``r W_r`` themselves (``MoEConfig.scoring``), the chosen set
  the top-k of ``scores + bias`` (the bias selects and takes no part in
  the weights nor any gradient). Weights over all k chosen experts,
  held here or not: ``scale * s_i / (sum of the chosen s + 1e-20)``
  under ``"sigmoid"``, ``scale * softmax over the chosen logits`` under
  ``"softmax"`` (a softmax over all the experts renormalised over the
  chosen is the same numbers). ``r`` is the experts' input ``x``, or
  another tensor of the same tokens that the caller hands in
  (``scores_from``: a router placed before attention reads the
  attention's input).
- Dispatch is dropless: the ``(token, choice)`` pairs are sorted by
  expert, pairs of experts held elsewhere last; no capacity, no token
  dropped. A chip that holds ``held`` of ``experts`` experts draws about
  ``held / experts`` of the pairs, so the routed part works on buffers
  of ``sized_rows`` rows, twice that expectation (a static shape, from
  shapes alone), whenever the step's own draw fits in them: only the
  first rows of the sorted order are gathered, multiplied, weighed and
  summed into their tokens (``_sized_rows``, ``_sized``). A draw
  that does not fit takes ``_routed``, the same computation on a row
  for every pair, chosen on the device by ``lax.cond`` on the count the
  router already makes; with every expert held there is one path and no
  conditional. The grouped product skips the tiles of rows past the
  held pairs; what it leaves in such rows is not specified, so they are
  zeroed wherever they could reach a result.
- The experts' products (scope ``hvd_moe/experts``) are
  ``jax.lax.ragged_dot`` over the groups: XLA's own grouped Mosaic
  kernel on the TPU, with both gradients. That kernel tiles the two
  widths by what divides them; where one is not whole lane tiles (an
  expert 1856 wide) it runs at a ninth of its bound and its time follows
  the groups' sizes more than their sum, so the sized rows' products
  (``_grouped``, ``_grouped_weights``) go through the kernels of
  ``ops/grouped_product.py`` there, on the TPU, from shapes alone; the
  full-size path, which JAX differentiates, keeps ``ragged_dot``.
- What is kept for the way back. Where there are two sizes
  (``_sized_or_routed``) the sized path keeps its gate and up products
  before the activation and its rows' places in pair order
  (``kept_bytes``: from ``sized_rows`` and the experts' width alone),
  and its backward pass is written by hand over them (``_sized_back``):
  the sort and those two products are made once a step, the down
  product's result is neither kept nor made again, and the tokens' rows,
  the widest of what the forward pass made, are gathered again. The
  kept arrays are made before the conditional (``_sized_rows``), not
  inside its branch: what a conditional returns XLA holds twice. The
  full-size path keeps nothing of its own: differentiating the
  conditional, or one ``jax.checkpoint`` round it, would keep the union
  of its branches' residuals, the buffers with a row for every pair
  among them, so the backward pass is a conditional of its own on the
  same test, whose full-size branch makes ``_routed`` again and pulls
  the gradient back through it. A draw over the rows costs that step
  its time, never the memory. With every expert held there is one path,
  ``jax.checkpoint(_routed)``: buffers with a row for every pair are
  what recomputation is for. A model that has to save more says so with
  ``TransformerConfig.remat``: under ``nn.remat`` the block's forward
  pass, this layer's included, runs again on the way back, and what is
  kept here (named ``hvd_moe_kept`` for ``jax.checkpoint`` policies;
  ``"dots"`` and ``"flash"`` do not keep it) lives only there. Forward
  and backward each go through ``jax.jit``, so a model's layers share
  one trace and one lowering of the two sizes.
- The sized rows come back into token order without a row being
  scattered, forward (each token's rows weighed and summed) and, by
  hand, backward (the tokens' gradient): ``_to_tokens``. XLA's
  scatter-add costs 110-124 ns a row at every shape the benchmark has,
  live or dead; a gather by token 5.5 ns a row out of a buffer of 34 MB
  and 40 ns out of one of 252 MB (49,152 rows of 2560). The pairs
  are sorted by expert with a stable sort, so one expert's rows are in
  token order and the rows of one block of tokens and one expert are
  consecutive: on the TPU, for bfloat16 rows in whole tiles
  (``rows_to_tokens.tiling``, from shapes alone), a Mosaic kernel copies
  that run for each expert held and sums the block's rows with one
  product against a 0/1 matrix made from the rows' token ids
  (``ops/rows_to_tokens.py``). Everywhere else (``_into_tokens``) a
  gather of T rows for each of a token's k choices, selected where the
  pair has a row of the buffers (``_places``), summed in float32 and
  rounded once. Rows past the groups are selected away before either
  sum, never multiplied by 0. PR 37's reading, that the scatter-add beat
  the un-gather by token, was of a form that went through a buffer with
  a row for every pair (PERF.md section 6, PR 37 and 41). In ``_routed``
  gather and un-gather are a permutation and its inverse, each the
  other's transpose, so neither direction scatters a row for every pair.
"""

import dataclasses
import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops import grouped_product, rows_to_tokens
from ..utils.jax_compat import pvary

# Names in a device trace (docs/tracing.md); readers match the literals.
SCOPE = "hvd_moe"
SCOPE_ROUTE = "route"
SCOPE_EXPERTS = "experts"
STATE = "moe_state"     # flax collection: selection bias, tokens drawn


SCORINGS = ("sigmoid", "softmax")
# What the router reads: the experts' own input, or the block's normed
# input before attention, which the block hands in (``scores_from``).
ROUTER_READS = ("ffn", "attention")
# An expert's activation. "silu" and "relu" act on a gate product of
# their own, ``W_down(act(W_gate x) * W_up x)``: SwiGLU and ReGLU. The
# values of UNGATED act on the one product there is, ``W_down act(W_up
# x)``, and the expert has no gate matrix.
GATES = {"silu": nn.silu, "relu": nn.relu,
         "relu2": lambda v: jnp.square(nn.relu(v))}
UNGATED = ("relu2",)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    experts: int                    # routed experts of the model
    per_token: int                  # chosen per token
    width: int                      # an expert's hidden width
    held: Tuple[int, int] = None    # [first, end) held here; None: all
    shared: int = 1                 # shared experts (one gated FFN, wider)
    scale: float = 1.0              # routed_scaling_factor
    first_dense: int = 1            # leading layers with a dense FFN
    scoring: str = "sigmoid"        # of SCORINGS: the weights' form
    # Of GATES, every expert's activation: "silu" and "relu" with a gate
    # matrix, "relu2" (squared ReLU) without one.
    gate: str = "silu"
    router_reads: str = "ffn"       # of ROUTER_READS

    @property
    def span(self):
        return self.held or (0, self.experts)


def _hidden(gate, product, x, w_gate, w_up):
    """An expert's hidden rows from ``product(x, w)``: gated, or with no
    ``w_gate`` the activation of the one product."""
    if w_gate is None:
        return GATES[gate](product(x, w_up))
    return GATES[gate](product(x, w_gate)) * product(x, w_up)


def swiglu(x, w_gate, w_up, w_down, gate="silu"):
    """``W_down(gate(W_gate x) * W_up x)``: a dense gated FFN, the shared
    expert's form and every routed expert's; with ``w_gate`` None the
    ungated form, ``W_down gate(W_up x)``."""
    h = _hidden(gate, lambda a, w: jnp.dot(a, w.astype(a.dtype)), x, w_gate,
                w_up)
    return jnp.dot(h, w_down.astype(x.dtype))


def route(x, w_router, bias, *, k, scale, scoring="sigmoid"):
    """(chosen (T, k) int32, weights (T, k) float32, drawn (E,) float32:
    the tokens each expert drew)."""
    scores = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(scores)
    _, chosen = lax.top_k(scores + lax.stop_gradient(bias), k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if scoring == "sigmoid":
        weights = scale * picked / (jnp.sum(picked, -1, keepdims=True)
                                    + 1e-20)
    else:
        weights = scale * jax.nn.softmax(picked, axis=-1)
    drawn = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1],
                                   dtype=jnp.float32), axis=(0, 1))
    return chosen, weights, drawn


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather(x, order, inverse, k):
    """Rows of ``x`` (T, d) in pair order: row ``i`` is token
    ``order[i] // k``. Its transpose un-permutes and sums each token's
    k rows: a gather too."""
    return x[order // k]


def _gather_bwd(k, res, g):
    order, inverse = res
    return (g[inverse].reshape(-1, k, g.shape[-1]).sum(1), None, None)


_gather.defvjp(lambda x, order, inverse, k: (x[order // k],
                                             (order, inverse)),
               _gather_bwd)


@jax.custom_vjp
def _ungather(y, order, inverse):
    """Rows of ``y`` (T*k, d) back in (token, choice) order."""
    return y[inverse]


_ungather.defvjp(lambda y, order, inverse: (y[inverse], (order, inverse)),
                 lambda res, g: (g[res[0]], None, None))


def _vary_like(x, like):
    """``x`` marked varying over the mesh axes ``like`` varies over
    (inside ``shard_map``): the cast's transpose is the psum that a
    hand-written transpose such as ``_gather``'s cannot add itself."""
    for axis in jax.typeof(like).vma - jax.typeof(x).vma:
        x = pvary(x, axis)
    return x


def _routed(x, w_gate, w_up, w_down, chosen, weights, drawn, first_held,
            gate="silu"):
    """The held experts' part of the layer's output for tokens ``x``
    (T, d): sort, grouped products, un-sort, weigh."""
    tokens, k = chosen.shape
    held = w_up.shape[0]
    with jax.named_scope(SCOPE_ROUTE):
        local = chosen.reshape(-1) - first_held
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)
        # argsort sorts (key, iota) and types the sorted iota as its
        # input was: mark it as varying as the key it was sorted by.
        order = _vary_like(jnp.argsort(key, stable=True), key)
        inverse = _vary_like(jnp.argsort(order), key)
        sizes = lax.dynamic_slice(drawn, (first_held,), (held,)).astype(
            jnp.int32)
        # Zero the rows of pairs held elsewhere, and with them their
        # cotangents: what a grouped product leaves in rows past its
        # groups is not specified.
        rows = (jnp.arange(tokens * k) < jnp.sum(sizes))[:, None]
        xs = jnp.where(
            rows, _gather(_vary_like(x, order), order, inverse, k), 0)
    with jax.named_scope(SCOPE_EXPERTS):
        def product(a, w):
            return lax.ragged_dot(a, w.astype(a.dtype), sizes)
        ys = product(_hidden(gate, product, xs, w_gate, w_up), w_down)
    with jax.named_scope(SCOPE_ROUTE):
        ys = _ungather(jnp.where(rows, ys, 0), order, inverse)
        weights = jnp.where(mine.reshape(tokens, k), weights, 0.0)
        return jnp.einsum("tkd,tk->td", ys.reshape(tokens, k, -1),
                          weights.astype(ys.dtype))


# Rows of the routed part's buffers, in expected draws of the experts
# held. One layer's draw swings widely from step to step: 0.43 to 1.90
# of the expectation over 760 readings in ``glm47flash-seq4096-1chip``
# (PERF.md section 7). A draw over the rows costs that layer the
# full-size program for that step, never a token; 1.5 would send one
# step in five there, and every row costs time whether it is drawn or
# not.
_ROWS_PER_EXPECTED = 2
_ROW_TILE = 512     # rows are a multiple of the grouped kernel's tile


def sized_rows(pairs, held, experts):
    """Rows the routed part's buffers get for ``pairs`` (token, choice)
    pairs where ``held`` of the router's ``experts`` are held: from
    shapes alone. ``pairs`` itself when that is no fewer."""
    rows = -(-_ROWS_PER_EXPECTED * pairs * held // experts)
    return min(pairs, -(-rows // _ROW_TILE) * _ROW_TILE)


def took_sized_path(drawn, first, end):
    """Whether a layer whose experts drew ``drawn`` tokens (E,), of
    which ``[first, end)`` are held, ran on ``sized_rows`` rows: the
    test ``moe_apply`` makes on the device, from the counts a step
    returns."""
    pairs = round(float(drawn.sum()))       # every token draws k experts
    rows = sized_rows(pairs, end - first, len(drawn))
    return rows < pairs and float(drawn[first:end].sum()) <= rows


def _held_sizes(w_up, drawn, first_held):
    """The held experts' draws, (held,) int32."""
    return lax.dynamic_slice(drawn, (first_held,), (w_up.shape[0],)
                             ).astype(jnp.int32)


def _fits(rows, routed):
    _, _, w_up, _, _, _, drawn, first_held = routed
    return jnp.sum(_held_sizes(w_up, drawn, first_held)) <= rows


def _held_draw(rows, w_up, drawn, first_held):
    """(the grouped products' group sizes: the held experts' draws;
    which of ``rows`` sorted pairs are pairs of theirs, (rows, 1)).
    A draw over ``rows`` reads as no draw at all: that step's result
    comes from ``_routed``."""
    sizes = _held_sizes(w_up, drawn, first_held)
    sizes = jnp.where(jnp.sum(sizes) <= rows, sizes, 0)
    return sizes, (jnp.arange(rows) < jnp.sum(sizes))[:, None]


def _held_key(chosen, first_held, held):
    """Each (token, choice) pair's expert among the ``held`` held here,
    or ``held`` for a pair of an expert held elsewhere: what the pairs
    are sorted by."""
    local = chosen.reshape(-1) - first_held
    return jnp.where((local >= 0) & (local < held), local, held)


def _grouped(a, w, sizes, transposed=False):
    """The sized rows ``a`` (rows, k), each group's against its expert's
    matrix of ``w`` (held, k, n), or against its transpose where
    ``transposed`` (``w`` (held, n, k)): XLA's grouped kernel, or for
    widths that are not whole lane tiles, which it tiles badly, the
    Pallas kernels of ``ops/grouped_product.py`` (from shapes alone;
    never off the TPU). Neither reads a row past the groups, and what
    either leaves in such rows is not specified."""
    if grouped_product.takes(a.shape[0], *w.shape[1:], a.dtype):
        return grouped_product.rows_by_group(a, w, sizes, transposed)
    return lax.ragged_dot(a, jnp.swapaxes(w, 1, 2) if transposed else w,
                          sizes)


# A grouped product's gradient to its weights: each group's rows of the
# left operand against the same rows of the cotangent (what JAX's own
# transpose of ``lax.ragged_dot`` makes).
_BY_GROUP = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _grouped_weights(a, ct, sizes):
    """``_grouped``'s gradient to its matrices, (held, k, n): each
    group's rows of ``a`` (rows, k) against the same rows of ``ct``
    (rows, n), by the kernels that ``_grouped`` takes at these widths
    (theirs sums and returns float32)."""
    if grouped_product.takes(a.shape[0], a.shape[1], ct.shape[1], a.dtype):
        return grouped_product.weights_by_group(a, ct, sizes)
    return lax.ragged_dot_general(a, ct, sizes, _BY_GROUP)


def _sized_rows(rows, x, w_gate, w_up, chosen, drawn, first_held):
    """The sized path as far as the experts' activation: the first
    ``rows`` pairs of the sorted order hold every pair of the experts
    held, so only their tokens' rows are gathered and multiplied.
    Returns the gate and up products (the gate's None where the experts
    have no gate matrix) and the rows' places in pair order: what
    ``_sized`` goes on from and ``_sized_back`` reads in place of making
    it again (``kept_bytes``)."""
    held = w_up.shape[0]
    with jax.named_scope(SCOPE_ROUTE):
        key = _held_key(chosen, first_held, held)
        order = _vary_like(jnp.argsort(key, stable=True), key)[:rows]
        sizes, _ = _held_draw(rows, w_up, drawn, first_held)
        # Rows past the held pairs are other tokens' own, left as they
        # are: a grouped product reads no row past its groups, and the
        # way back is ``_sized_back``, which zeroes what it must.
        xs = x[order // chosen.shape[1]]
    with jax.named_scope(SCOPE_EXPERTS):
        gated, up = (None if w is None else
                     _grouped(xs, w.astype(xs.dtype), sizes)
                     for w in (w_gate, w_up))
    return gated, up, order


def _places(order, chosen, live):
    """``(place, has)``, both (T, k): the row of the sized buffers that
    holds pair ``(t, j)`` and whether there is one. ``order`` (rows,) is
    the first rows of the sorted order, of which the first ``live`` are
    pairs of the experts held; every other pair reads ``has`` false and
    a ``place`` that is some row's all the same, for a gather to read
    and a select to throw away. A scatter of ``rows`` int32 to places no
    two of which are alike: the sort is not made again."""
    rows = order.shape[0]
    place = _vary_like(jnp.full((chosen.size,), rows, jnp.int32), order).at[
        order].set(jnp.arange(rows, dtype=jnp.int32), unique_indices=True)
    place = place.reshape(chosen.shape)
    return jnp.minimum(place, rows - 1), place < live


def _into_tokens(buffer, place, has, weights=None):
    """Rows of a sized buffer (rows, d) back in token order, (T, d):
    ``sum_j buffer[place[t, j]] * weights[t, j]`` over the choices of
    token ``t`` that have a row (``_places``). A gather of T rows a
    choice, summed in float32 and rounded once; no buffer with a row
    for every pair, and no row scattered: the transpose of
    ``x[order // k]`` by hand. Select after the gather, never multiply
    by 0: what a grouped product leaves in rows past its groups is not
    specified."""
    total = 0.0
    for j in range(place.shape[1]):
        mine = jnp.where(has[:, j, None], buffer[place[:, j]], 0).astype(
            jnp.float32)
        total = total + (mine if weights is None else
                         mine * weights[:, j, None].astype(jnp.float32))
    return total.astype(buffer.dtype)


def return_rows(tokens, per_token, held, tiles):
    """Rows the sized path reads to bring its rows back into token
    order, each way: under the kernel (``tiles``: its block and chunk)
    a chunk for every token block and expert held, where no run is
    longer than a chunk; under the gathers a row for every pair."""
    if tiles is None:
        return tokens * per_token
    block, chunk = tiles
    return tokens // block * held * chunk


def _publish_return_rows(tokens, per_token, held, tiles):
    from ..telemetry import core as telemetry
    if telemetry.enabled():
        telemetry.gauge(
            "hvd_moe_return_rows",
            "Rows the expert layer last traced reads to bring its sized "
            "rows back into token order, each way (return_rows: from "
            "shapes alone; the kernel's chunks, or a row for every pair "
            "under the gathers)").set(
                float(return_rows(tokens, per_token, held, tiles)))


def _to_tokens(buffer, order, sizes, chosen, first_held, experts,
               weights=None):
    """A sized buffer's rows (rows, d), each weighed where there are
    ``weights`` (T, k), summed into their tokens, (T, d): the way back
    from sorted order, forward and, by hand, backward. On the TPU, for
    bfloat16 rows in whole tiles, through the MXU
    (``ops/rows_to_tokens.py``); else by gathers (``_into_tokens``).
    Neither scatters a row, and each sums in float32 and rounds once."""
    tokens, k = chosen.shape
    tiles = None if rows_to_tokens._interpret() else rows_to_tokens.tiling(
        tokens, k, experts, sizes.shape[0], *buffer.shape, buffer.dtype)
    _publish_return_rows(tokens, k, sizes.shape[0], tiles)
    if tiles is None:
        place, has = _places(order, chosen, jnp.sum(sizes))
        return _into_tokens(buffer, place, has, weights)
    return rows_to_tokens.rows_to_tokens(
        buffer, order, _held_key(chosen, first_held, sizes.shape[0]), sizes,
        k, tiles, None if weights is None else weights.reshape(-1))


def _activated(gate, gated, up):
    """The hidden rows from the kept products (``_sized_rows``)."""
    return GATES[gate](up) if gated is None else GATES[gate](gated) * up


def _sized(rows, kept, x, w_gate, w_up, w_down, chosen, weights, drawn,
           first_held, gate="silu"):
    """``_routed`` for a draw of at most ``rows`` pairs, from
    ``_sized_rows``: the activation, the down product, and each token's
    rows weighed and summed (``_into_tokens``)."""
    gated, up, order = kept
    sizes, _ = _held_draw(rows, w_up, drawn, first_held)
    with jax.named_scope(SCOPE_EXPERTS):
        ys = _grouped(_activated(gate, gated, up), w_down.astype(up.dtype),
                      sizes)
    with jax.named_scope(SCOPE_ROUTE):
        return _to_tokens(ys, order, sizes, chosen, first_held,
                          drawn.shape[0], weights)


def _sized_back(rows, gate, g, kept, x, w_gate, w_up, w_down, chosen,
                weights, drawn, first_held):
    """``g`` (T, d) pulled back through ``_sized_rows`` and ``_sized``
    to the routed part's trained arguments, from what the first kept:
    no sort and no forward product is made again; the tokens' rows are
    gathered again (``rows x d`` a layer: PERF.md section 6, PR 39).
    The down product's result ``ys`` is neither kept nor made again:
    with ``u = g_rows W_d^T``, which the gradient of the gated rows
    ``h`` needs anyway, ``<h W_d, g_rows> = <h, u>`` is the weights'
    gradient and ``u * weight`` is ``h``'s. What a grouped product
    leaves in rows past its groups is not specified: such rows are
    selected away wherever they reach a result (the tokens' gradient,
    the weights'), never multiplied by 0. Experts without a gate matrix
    kept one product and pull back through four grouped products where
    the gated form's six are; ``w_gate``'s place in what is returned
    holds None."""
    gated, up, order = kept
    with jax.named_scope(SCOPE_ROUTE):
        sizes, live = _held_draw(rows, w_up, drawn, first_held)
        token = order // chosen.shape[1]
        # Not zeroed past the held pairs: those rows are other tokens'
        # own, and a grouped product reads no row past its groups.
        xs, gs = x[token], g[token]
        weight = weights.reshape(-1)[order].astype(g.dtype)[:, None]
    with jax.named_scope(SCOPE_EXPERTS):
        def to_rows(ct, w):
            return _grouped(ct, w.astype(ct.dtype), sizes, transposed=True)

        def to_weights(a, ct, w):
            return _grouped_weights(a, ct, sizes).astype(w.dtype)
        h, back = jax.vjp(functools.partial(_activated, gate), gated, up)
        u = to_rows(gs, w_down)
        # A kept product's cotangent beside the matrix it is of; without
        # a gate matrix both are None.
        kept_of = list(zip(back(u * weight), (w_gate, w_up)))
        d_xs = [to_rows(d, w) for d, w in kept_of if w is not None]
        d_w = ([None if w is None else to_weights(xs, d, w)
                for d, w in kept_of] + [to_weights(h * weight, gs, w_down)])
        d_weight = jnp.sum(h.astype(weights.dtype) * u.astype(weights.dtype),
                           -1, keepdims=True)
    with jax.named_scope(SCOPE_ROUTE):
        d_weights = jnp.zeros_like(weights.reshape(-1)).at[order].add(
            jnp.where(live, d_weight, 0)[:, 0])
        # The sum of the two products is the way back's first pass, not
        # a product's: a reader of ``hvd_moe/experts`` does not meet it.
        return (_to_tokens(sum(d_xs[1:], d_xs[0]), order, sizes, chosen,
                           first_held, drawn.shape[0]), *d_w,
                d_weights.reshape(weights.shape))


_TRAINED = (0, 1, 2, 3, 5)      # of ``routed``: x, the three w, weights


def _routed_back(gate, g, kept, *routed):
    """``g`` pulled back through ``_routed`` to its trained arguments
    with nothing of its own kept: ``_routed`` is made again here.
    ``kept`` is ``_sized_rows``'s, of a draw that did not fit: unread."""
    def of(*trained):
        args = list(routed)
        for i, a in zip(_TRAINED, trained):
            args[i] = a
        return _routed(*args, gate=gate)
    return jax.vjp(of, *(routed[i] for i in _TRAINED))[1](g)


def kept_bytes(rows, width, gate="silu"):
    """Bytes an expert layer on ``rows`` sized rows of experts ``width``
    wide keeps from its forward pass for its backward pass
    (``_sized_rows``): the gate and up products in bfloat16 (the one
    product of experts without a gate matrix), the rows' places in
    int32."""
    return rows * ((1 if gate in UNGATED else 2) * width * 2 + 4)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _either(rows, gate, *routed):
    x, w_gate, w_up, _, chosen, _, drawn, first_held = routed
    with jax.named_scope(SCOPE):
        kept = _sized_rows(rows, x, w_gate, w_up, chosen, drawn, first_held)
        return lax.cond(
            _fits(rows, routed), functools.partial(_sized, rows, gate=gate),
            lambda kept, *routed: _routed(*routed, gate=gate),
            kept, *routed), kept


@functools.partial(jax.jit, static_argnums=(0, 1))
def _either_back(rows, gate, g, kept, *routed):
    with jax.named_scope(SCOPE):
        return lax.cond(_fits(rows, routed),
                        functools.partial(_sized_back, rows, gate),
                        functools.partial(_routed_back, gate),
                        g, kept, *routed)


# What the sized path keeps for the way back, as ``jax.checkpoint``
# policies may name it: ``"dots"`` and ``"flash"`` do not, so under
# them (and under whole-block ``remat``) the layer is made again.
KEPT_NAME = "hvd_moe_kept"


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sized_or_routed(rows, gate, x, w_gate, w_up, w_down, chosen, weights,
                     drawn, first_held):
    """``_sized`` where the held experts' draw fits in ``rows`` rows,
    else ``_routed``, with a backward pass of its own that chooses
    again by the same test. What ``_sized_rows`` made for the sized
    branch (the gate and up products, the rows' places: ``kept_bytes``,
    shapes from ``rows`` alone) is kept, and ``_sized_back`` pulls the
    gradient back from it: the sort and those products are made once a
    step. It is made before the conditional, whichever branch then
    runs (XLA holds what a conditional returns twice), so a draw over
    the rows pays for a gather it does not use. The full-size branch
    keeps nothing of its own: differentiating the conditional itself
    would keep the union of its branches' residuals, the buffers with a
    row for every pair among them, so that step makes ``_routed`` again
    on the way back and pays in time, never in memory. A model that has
    to save more says so with ``TransformerConfig.remat``: under
    ``nn.remat`` the block's forward pass, this layer's included, runs
    again on the way back and what is kept here lives only there.

    Both ways go through ``jax.jit``, so the layers of a model share one
    trace and one lowering of each, under each layer's own names.
    They open ``hvd_moe`` themselves and are called outside it: XLA
    names the grouped kernels of a jitted function for its call site
    alone, and a reader of a trace (``benchmark/scope_sum.py``) files a
    grouped kernel under ``hvd_moe/experts`` when its name holds no
    scope of this module, as it did before there was a ``jax.jit``."""
    return _either(rows, gate, x, w_gate, w_up, w_down, chosen, weights,
                   drawn, first_held)[0]


def _sized_or_routed_fwd(rows, gate, *routed):
    y, kept = _either(rows, gate, *routed)
    return y, (tuple(None if r is None else checkpoint_name(r, KEPT_NAME)
                     for r in kept), routed)


def _sized_or_routed_bwd(rows, gate, res, g):
    kept, routed = res
    pulled = dict(zip(_TRAINED, _either_back(rows, gate, g, kept, *routed)))
    return tuple(pulled.get(i) for i in range(len(routed)))


_sized_or_routed.defvjp(_sized_or_routed_fwd, _sized_or_routed_bwd)


def _vary_together(*xs):
    """``xs``, each marked varying over every mesh axis that any of
    them varies over (inside ``shard_map``): a hand-written backward
    pass hands each its cotangent as varying as the result."""
    axes = frozenset().union(*(jax.typeof(x).vma for x in xs
                               if x is not None))
    return [x if x is None else
            functools.reduce(pvary, sorted(axes - jax.typeof(x).vma), x)
            for x in xs]


def moe_apply(x, params, bias, *, k, scale=1.0, first_held=0,
              scoring="sigmoid", gate="silu", scores_from=None):
    """The expert layer on tokens ``x`` (T, d). Returns ``(y, drawn)``:
    this share of the layer's output and the tokens each of the model's
    experts drew (float32, (E,)). ``scoring`` and ``gate`` as
    ``MoEConfig``'s; ``scores_from`` (T, d): what the router reads where
    that is not ``x``.

    ``params``: ``router`` (d, E) over all E experts; ``w_gate``,
    ``w_up`` (held, d, f) and ``w_down`` (held, f, d) of the experts
    held, which are experts ``first_held`` and on (``first_held`` may be
    traced, e.g. from ``lax.axis_index``); optionally ``shared_gate``,
    ``shared_up`` (d, fs), ``shared_down`` (fs, d). Under a ``gate`` of
    ``UNGATED`` there is neither ``w_gate`` nor ``shared_gate``.
    ``bias`` (E,): the selection bias, a buffer."""
    pairs = x.shape[0] * k
    rows = sized_rows(pairs, params["w_up"].shape[0],
                      params["router"].shape[1])
    with jax.named_scope(SCOPE), jax.named_scope(SCOPE_ROUTE):
        chosen, weights, drawn = route(
            x if scores_from is None else scores_from, params["router"],
            bias, k=k, scale=scale, scoring=scoring)
    routed = (x, params.get("w_gate"), params["w_up"], params["w_down"],
              chosen, weights, drawn, first_held)
    if rows == pairs:
        with jax.named_scope(SCOPE):
            y = jax.checkpoint(functools.partial(_routed, gate=gate))(
                *routed)
    else:
        y = _sized_or_routed(rows, gate, *_vary_together(*routed))
    if "shared_up" in params:
        with jax.named_scope(SCOPE), jax.named_scope(SCOPE_EXPERTS):
            y = y + swiglu(x, params.get("shared_gate"), params["shared_up"],
                           params["shared_down"], gate)
    return y, drawn


class MoELayer(nn.Module):
    """The expert FFN of a transformer block. Parameter names match the
    ``moe/`` rules of ``parallel/sharding.py``; the selection bias and
    the tokens each expert drew live in collection ``moe_state``."""

    cfg: MoEConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, scores_from=None):
        """``scores_from``: what the router reads in place of ``x``
        (``cfg.router_reads`` says which tensor of the block that is;
        the block hands it in)."""
        cfg = self.cfg
        if (cfg.scoring not in SCORINGS or cfg.gate not in GATES
                or cfg.router_reads not in ROUTER_READS):
            raise ValueError(
                f"MoEConfig: scoring {cfg.scoring!r} of {SCORINGS}, gate "
                f"{cfg.gate!r} of {tuple(GATES)}, router_reads "
                f"{cfg.router_reads!r} of {ROUTER_READS}")
        d = x.shape[-1]
        first, end = cfg.span
        init = nn.initializers.lecun_normal()
        batched = nn.initializers.lecun_normal(batch_axis=(0,))
        wide = cfg.shared * cfg.width
        # An ungated expert has no gate leaf, routed or shared.
        shapes = {"router": (init, (d, cfg.experts)),
                  "w_gate": (batched, (end - first, d, cfg.width)),
                  "w_up": (batched, (end - first, d, cfg.width)),
                  "w_down": (batched, (end - first, cfg.width, d)),
                  "shared_gate": (init, (d, wide)),
                  "shared_up": (init, (d, wide)),
                  "shared_down": (init, (wide, d))}
        params = {name: self.param(name, *shape)
                  for name, shape in shapes.items()
                  if (cfg.shared or not name.startswith("shared"))
                  and not (cfg.gate in UNGATED and name.endswith("gate"))}
        bias = self.variable(STATE, "bias", jnp.zeros, (cfg.experts,))
        tokens = self.variable(STATE, "expert_tokens", jnp.zeros,
                               (cfg.experts,))
        y, drawn = moe_apply(
            x.reshape(-1, d).astype(self.dtype), params, bias.value,
            k=cfg.per_token, scale=cfg.scale, first_held=first,
            scoring=cfg.scoring, gate=cfg.gate,
            scores_from=None if scores_from is None
            else scores_from.reshape(-1, d))
        if self.is_mutable_collection(STATE):
            tokens.value = drawn
        return y.reshape(x.shape)


def publish_expert_tokens(state, held=None, width=None, gate="silu"):
    """Set ``hvd_moe_expert_tokens{layer,expert}``,
    ``hvd_moe_held_share``, ``hvd_moe_buffer_rows{layer}``,
    ``hvd_moe_sized_layers`` and, given the experts' ``width`` (and
    their ``gate``, where they have no gate matrix),
    ``hvd_moe_kept_bytes{layer}`` from the ``moe_state`` collection a
    train step returned. Call it outside the step; it fetches the
    arrays. A no-op when ``HOROVOD_TPU_METRICS`` is off."""
    from ..telemetry import core as telemetry
    from .sharding import _path_str
    if not telemetry.enabled():
        return
    tokens = telemetry.gauge(
        "hvd_moe_expert_tokens",
        "Tokens the expert drew in the last step (mean over replicas)",
        ("layer", "expert"))
    share = telemetry.gauge(
        "hvd_moe_held_share",
        "Share of the last step's (token, choice) pairs that went to "
        "experts held on this chip")
    buffer_rows = telemetry.gauge(
        "hvd_moe_buffer_rows",
        "Rows of the routed part's buffers when the held draw fits in "
        "them (sized_rows: from shapes alone)", ("layer",))
    sized = telemetry.gauge(
        "hvd_moe_sized_layers",
        "Expert layers whose held draw of the last step fitted in "
        "hvd_moe_buffer_rows rows, fewer than a row for every pair: "
        "they ran on buffers of that size")
    kept = telemetry.gauge(
        "hvd_moe_kept_bytes",
        "Bytes the layer's forward pass keeps for its backward pass on "
        "hvd_moe_buffer_rows rows (kept_bytes: from shapes alone, "
        "bfloat16 activations); 0 where every expert is held and the "
        "routed part is made again", ("layer",))
    mine = total = 0.0
    fitted = 0
    for path, drawn in jax.tree_util.tree_leaves_with_path(state):
        if getattr(path[-1], "key", None) != "expert_tokens":
            continue
        layer = _path_str(path[:-1])
        drawn = jax.device_get(drawn)
        for expert, n in enumerate(drawn):
            tokens.labels(layer=layer, expert=expert).set(float(n))
        first, end = held or (0, len(drawn))
        pairs = round(float(drawn.sum()))
        rows = sized_rows(pairs, end - first, len(drawn))
        buffer_rows.labels(layer=layer).set(rows)
        if width:
            kept.labels(layer=layer).set(
                kept_bytes(rows, width, gate) if rows < pairs else 0)
        fitted += took_sized_path(drawn, first, end)
        mine += float(drawn[first:end].sum())
        total += float(drawn.sum())
    if total:
        share.set(mine / total)
        sized.set(fitted)
