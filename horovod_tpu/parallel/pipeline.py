"""Pipeline parallelism: microbatch streaming over the 'pp' mesh axis.

GPipe-style schedule expressed as a differentiable lax.scan inside
shard_map: each pp rank holds its stages' parameters; every tick each rank
applies a stage and ppermutes the activation to the next rank, so after
the n_pp-1 warm-up ticks every stage is busy. Reverse-mode autodiff of the
scan yields the mirrored backward schedule (1F1B-shaped in steady state)
without any hand-written backward plumbing.

Round-4 realism upgrades over the original GPipe toy:

- **Input lives with its owner, not replicated.** ``microbatches`` is the
  rank-local shard of the global microbatch queue (batch m on rank
  m // per_rank). A one-hop-per-tick ppermute *shift register* streams
  each batch so it arrives at stage 0 exactly on its tick — comm cost one
  microbatch per tick, same order as the activation hop; no rank ever
  holds the full queue.
- **Heterogeneous ends.** ``first_fn`` (e.g. token embedding) runs where
  the queue feeds stage 0 and may change shape/dtype (tokens → hidden);
  ``last_fn`` (e.g. LM head) runs on the last stage's output (hidden →
  logits). The ring itself still carries one fixed hidden shape — that is
  what a static ppermute requires.
- **More stages than ranks** via ``rounds``: rank j holds ``rounds``
  stage-parameter slots; each circuit applies slot ro on every rank, so
  logical stage ro*n + j lives at rank j, slot ro — the interleaved
  placement. Circuits run back-to-back with a drain between them (outputs
  of circuit ro are re-sharded into circuit ro+1's queue), so the bubble
  is rounds*(n_pp-1) ticks; the schedule is circular-GPipe, not
  interleaved-1F1B (a 1F1B interleave cannot be expressed as one
  homogeneous scan — documented limitation). The drain between circuits
  replicates the (M, mb, hidden) outputs with a psum before each rank
  slices its block — ~n x the bytes a true scatter would move, but
  bounded at ~2 circuits' worth of activation-ppermute traffic per
  drain; acceptable until a last-rank scatter primitive exists.

Bubble fraction per circuit is (n_pp-1)/(M+n_pp-1) for M microbatches —
choose M >= 4x the stage count for >80% utilization.
"""

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.jax_compat import pvary as _pvary


def _circuit(stage_fn, params_ro, queue, axis_name, *, first=None,
             last=None, hidden_struct):
    """One full pass of every microbatch through the n ranks.

    queue: (per_rank, ...) rank-local input shard, batch m on rank
      m // per_rank.
    Returns (M, ...) per-tick outputs ys[n-1:] (meaningful on the last
    rank; caller masks/replicates).
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    per = queue.shape[0]
    m = per * n
    ticks = m + n - 1
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]
    perm_feed = [(i, (i - 1) % n) for i in range(n)]

    def tick(carry, s):
        state, feed = carry
        # Shift-register slot invariant: at tick s, rank j's slot holds
        # global batch s+j. A batch is loaded from the local queue at its
        # origin rank (tgt // per == idx) and then rides the feed permute
        # one hop per tick, arriving at rank 0 exactly at its tick.
        tgt = s + idx
        load = (tgt // per == idx) & (tgt < m)
        li = jnp.clip(tgt - idx * per, 0, per - 1)
        q = lax.dynamic_index_in_dim(queue, li, 0, keepdims=False)
        feed = jax.tree.map(
            lambda f, qq: jnp.where(load, qq, f), feed, q)
        x0 = first(feed) if first is not None else feed
        xin = jnp.where(idx == 0, x0, state)
        y = stage_fn(params_ro, xin)
        out = last(y) if last is not None else y
        nxt = lax.ppermute(y, axis_name, perm_fwd)
        feed_next = lax.ppermute(feed, axis_name, perm_feed)
        return (nxt, feed_next), out

    state0 = _pvary(jnp.zeros(hidden_struct.shape, hidden_struct.dtype),
                    axis_name)
    feed0 = _pvary(jnp.zeros_like(queue[0]), axis_name)
    (_, _), ys = lax.scan(tick, (state0, feed0), jnp.arange(ticks))
    # On the last rank, tick t produced microbatch t-(n-1); slice the
    # steady-state window. (On other ranks this window is their stage's
    # intermediate activations — discarded.)
    return ys[n - 1:]


def _replicate_from_last(outputs, axis_name):
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    return lax.psum(
        jnp.where(idx == n - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)


def pipeline_apply(stage_fn, stage_params, microbatches, axis_name="pp", *,
                   first_fn=None, first_params=None,
                   last_fn=None, last_params=None,
                   rounds=1, replicate_out=True):
    """Run microbatches through the pipeline (inside shard_map over
    ``axis_name``).

    Args:
      stage_fn: ``stage_fn(stage_params, x) -> y`` with y.shape == x.shape
        (a transformer stage: hidden states in, hidden states out).
      stage_params: THIS rank's stage-parameter block with a leading
        ``rounds`` axis — build the stacked global tree with
        :func:`stack_stage_params` (which applies the interleaved
        placement) and shard its axis 0 over ``axis_name`` in the
        shard_map in_specs.
      microbatches: (M/n, mb, ...) rank-local input shard (global batch m
        on rank m // (M/n)); shard the global (M, mb, ...) queue's axis 0
        over ``axis_name``. Only stage 0 consumes values — they stream
        there through the feed register.
      first_fn / first_params: optional entry adapter applied where the
        queue feeds stage 0 (``first_fn(first_params, batch) -> hidden``,
        e.g. embedding). May change shape/dtype. Pass first_params
        replicated (P()).
      last_fn / last_params: optional exit adapter applied to the last
        stage's output (e.g. LM head).
      rounds: circuits around the ring; total logical stages =
        rounds * n_pp, stage ro*n+j living at rank j slot ro.
      replicate_out: psum the final outputs so every pp rank returns the
        full (M, mb, ...) result (needed when loss is computed under
        further dp reduction); if False, only the last rank's values are
        meaningful.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    per = microbatches.shape[0]

    leaves = jax.tree.leaves(stage_params)
    if leaves and any(leaf.shape[0] != rounds for leaf in leaves):
        raise ValueError(
            f"stage_params leaves must carry a leading rounds={rounds} "
            f"axis (local slot block); got shapes "
            f"{[leaf.shape for leaf in leaves]}. Build the global tree "
            "with stack_stage_params(stages, n_ranks) and shard axis 0.")

    first = (lambda x: first_fn(first_params, x)) \
        if first_fn is not None else None
    last_wrapped = (lambda y: last_fn(last_params, y)) \
        if last_fn is not None else None

    queue = microbatches
    for ro in range(rounds):
        params_ro = jax.tree.map(lambda a: a[ro], stage_params)
        probe = queue[0]
        if first is not None and ro == 0:
            hidden_struct = jax.eval_shape(first, probe)
        else:
            hidden_struct = jax.eval_shape(lambda x: x, probe)
        outputs = _circuit(
            stage_fn, params_ro, queue, axis_name,
            first=first if ro == 0 else None,
            last=last_wrapped if ro == rounds - 1 else None,
            hidden_struct=hidden_struct)
        if ro < rounds - 1:
            # Drain: replicate the circuit's outputs, then each rank
            # slices its block as the next circuit's queue.
            full = _replicate_from_last(outputs, axis_name)
            queue = lax.dynamic_slice_in_dim(full, idx * per, per, 0)

    if replicate_out:
        outputs = _replicate_from_last(outputs, axis_name)
    return outputs


def stack_stage_params(per_stage_params, n_ranks=None):
    """Stack per-stage param trees (sequential order) into the pipeline's
    global layout.

    With ``n_ranks=None`` (or len(stages) == n_ranks): plain stacking —
    axis 0 index j = stage j; shard over 'pp'.

    With rounds = len(stages) / n_ranks > 1: interleaved placement —
    logical stage ro*n + j must land at rank j, slot ro, so axis 0 index
    j*rounds + ro holds stage ro*n + j. Shard axis 0 over 'pp' (giving
    each rank a contiguous (rounds, ...) block) and pass rounds= to
    :func:`pipeline_apply`.
    """
    stages = list(per_stage_params)
    if n_ranks is None or len(stages) == n_ranks:
        return jax.tree.map(lambda *xs: jnp.stack(xs), *stages)
    if len(stages) % n_ranks:
        raise ValueError(
            f"{len(stages)} stages not divisible by n_ranks={n_ranks}")
    rounds = len(stages) // n_ranks
    order = [ro * n_ranks + j
             for j in range(n_ranks) for ro in range(rounds)]
    arranged = [stages[i] for i in order]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *arranged)
