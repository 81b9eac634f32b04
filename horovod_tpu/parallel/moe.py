"""Sparse experts: a dropless, sigmoid-routed expert layer that is told
which experts it holds.

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
  gathers and the weighted sum below): scores ``sigmoid(x W_r)`` in
  float32 at ``highest`` precision, the chosen set the top-k of
  ``scores + bias`` (the bias selects and takes no part in the weights
  nor any gradient), weights ``scale * s_i / (sum of the chosen s +
  1e-20)`` over all k chosen experts, held here or not.
- Dispatch is dropless: the ``(token, choice)`` pairs are sorted by
  expert, pairs of experts held elsewhere last; no capacity, no token
  dropped. The buffer has a row for every pair (a static shape must
  cover every token choosing experts held here); rows past the held
  pairs are zero and the grouped product skips their tiles.
- The experts' products (scope ``hvd_moe/experts``) are
  ``jax.lax.ragged_dot`` over the groups: XLA's own grouped Mosaic
  kernel on the TPU, with both gradients. The buffers are worst-case
  sized and seven eighths empty at 8 of 64 experts, so they are not
  kept for the backward pass: the routed part is recomputed there
  (``jax.checkpoint``; 1.3% of the step's required FLOPs).
- Gather and un-gather are a permutation and its inverse, each the
  other's transpose, so neither direction scatters.
"""

import dataclasses
import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..utils.jax_compat import pvary

# Names in a device trace (docs/tracing.md); readers match the literals.
SCOPE = "hvd_moe"
SCOPE_ROUTE = "route"
SCOPE_EXPERTS = "experts"
STATE = "moe_state"     # flax collection: selection bias, tokens drawn


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    experts: int                    # routed experts of the model
    per_token: int                  # chosen per token
    width: int                      # an expert's hidden width
    held: Tuple[int, int] = None    # [first, end) held here; None: all
    shared: int = 1                 # shared experts (one SwiGLU, wider)
    scale: float = 1.0              # routed_scaling_factor
    first_dense: int = 1            # leading layers with a dense FFN

    @property
    def span(self):
        return self.held or (0, self.experts)


def swiglu(x, w_gate, w_up, w_down):
    """``W_down(silu(W_gate x) * W_up x)``: a dense gated FFN, the shared
    expert's form and every routed expert's."""
    h = nn.silu(jnp.dot(x, w_gate.astype(x.dtype))) * jnp.dot(
        x, w_up.astype(x.dtype))
    return jnp.dot(h, w_down.astype(x.dtype))


def route(x, w_router, bias, *, k, scale):
    """(chosen (T, k) int32, weights (T, k) float32, drawn (E,) float32:
    the tokens each expert drew)."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(scores + lax.stop_gradient(bias), k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = scale * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
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


def _routed(x, w_gate, w_up, w_down, chosen, weights, drawn, first_held):
    """The held experts' part of the layer's output for tokens ``x``
    (T, d): sort, grouped products, un-sort, weigh."""
    tokens, k = chosen.shape
    held = w_gate.shape[0]
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
        ys = product(nn.silu(product(xs, w_gate)) * product(xs, w_up),
                     w_down)
    with jax.named_scope(SCOPE_ROUTE):
        ys = _ungather(jnp.where(rows, ys, 0), order, inverse)
        weights = jnp.where(mine.reshape(tokens, k), weights, 0.0)
        return jnp.einsum("tkd,tk->td", ys.reshape(tokens, k, -1),
                          weights.astype(ys.dtype))


def moe_apply(x, params, bias, *, k, scale=1.0, first_held=0):
    """The expert layer on tokens ``x`` (T, d). Returns ``(y, drawn)``:
    this share of the layer's output and the tokens each of the model's
    experts drew (float32, (E,)).

    ``params``: ``router`` (d, E) over all E experts; ``w_gate``,
    ``w_up`` (held, d, f) and ``w_down`` (held, f, d) of the experts
    held, which are experts ``first_held`` and on (``first_held`` may be
    traced, e.g. from ``lax.axis_index``); optionally ``shared_gate``,
    ``shared_up`` (d, fs), ``shared_down`` (fs, d). ``bias`` (E,): the
    selection bias, a buffer."""
    with jax.named_scope(SCOPE):
        with jax.named_scope(SCOPE_ROUTE):
            chosen, weights, drawn = route(x, params["router"], bias,
                                           k=k, scale=scale)
        y = jax.checkpoint(_routed)(
            x, params["w_gate"], params["w_up"], params["w_down"], chosen,
            weights, drawn, first_held)
        if "shared_gate" in params:
            with jax.named_scope(SCOPE_EXPERTS):
                y = y + swiglu(x, params["shared_gate"],
                               params["shared_up"], params["shared_down"])
        return y, drawn


class MoELayer(nn.Module):
    """The expert FFN of a transformer block. Parameter names match the
    ``moe/`` rules of ``parallel/sharding.py``; the selection bias and
    the tokens each expert drew live in collection ``moe_state``."""

    cfg: MoEConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d = x.shape[-1]
        first, end = cfg.span
        init = nn.initializers.lecun_normal()
        batched = nn.initializers.lecun_normal(batch_axis=(0,))
        params = {
            "router": self.param("router", init, (d, cfg.experts)),
            "w_gate": self.param("w_gate", batched,
                                 (end - first, d, cfg.width)),
            "w_up": self.param("w_up", batched,
                               (end - first, d, cfg.width)),
            "w_down": self.param("w_down", batched,
                                 (end - first, cfg.width, d)),
        }
        if cfg.shared:
            wide = cfg.shared * cfg.width
            params.update(
                shared_gate=self.param("shared_gate", init, (d, wide)),
                shared_up=self.param("shared_up", init, (d, wide)),
                shared_down=self.param("shared_down", init, (wide, d)))
        bias = self.variable(STATE, "bias", jnp.zeros, (cfg.experts,))
        tokens = self.variable(STATE, "expert_tokens", jnp.zeros,
                               (cfg.experts,))
        y, drawn = moe_apply(
            x.reshape(-1, d).astype(self.dtype), params, bias.value,
            k=cfg.per_token, scale=cfg.scale, first_held=first)
        if self.is_mutable_collection(STATE):
            tokens.value = drawn
        return y.reshape(x.shape)


def publish_expert_tokens(state, held=None):
    """Set ``hvd_moe_expert_tokens{layer,expert}`` and
    ``hvd_moe_held_share`` from the ``moe_state`` collection a train
    step returned. Call it outside the step; it fetches the arrays. A
    no-op when ``HOROVOD_TPU_METRICS`` is off."""
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
    mine = total = 0.0
    for path, drawn in jax.tree_util.tree_leaves_with_path(state):
        if getattr(path[-1], "key", None) != "expert_tokens":
            continue
        layer = _path_str(path[:-1])
        drawn = jax.device_get(drawn)
        for expert, n in enumerate(drawn):
            tokens.labels(layer=layer, expert=expert).set(float(n))
        first, end = held or (0, len(drawn))
        mine += float(drawn[first:end].sum())
        total += float(drawn.sum())
    if total:
        share.set(mine / total)
