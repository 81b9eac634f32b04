"""Rows of an expert layer's sized buffers back into token order, on
the MXU: a Mosaic kernel that scatters nothing and gathers no row.

``parallel/moe.py`` sorts the (token, choice) pairs by expert with a
stable sort, so the buffer rows of one expert are in token order, and
the rows that belong to one block of tokens and one expert are a run of
consecutive rows. For a block of ``block`` tokens the kernel copies, for
each expert held, one aligned chunk of ``chunk`` rows that covers that
run (one DMA an expert, rows by the dozen, where a gather by token moves
a row a pair), builds from the rows' token ids the 0/1 matrix that says
which row is which token's (times the pair's weight, where there is
one), and multiplies: ``out[block] = S^T rows``, summed over the experts
in float32 inside the product and rounded once. A run longer than a
chunk takes another round of the same; no pair is dropped whatever the
draw.

What the product may not meet is a row past the experts' groups: what a
grouped product leaves there is not specified, and ``0 x`` it is not 0.
A chunk that reaches past the live rows has them selected away first;
the scratch is zeroed once, so what the product meets beside the run is
other tokens' own rows under a 0.

XLA's own ways, measured at the benchmark's three expert shapes
(PERF.md section 6, PR 41): a scatter-add costs 110-124 ns a row, live
or dead, at every shape; a gather by token 5.5 ns a row out of a buffer
of 34 MB (8,192 rows of 2048) and 40 ns out of one of 252 MB (49,152 of
2560). ``parallel/moe.py`` keeps the gathers (``_into_tokens``) for what
this kernel does not take: off the TPU, activations that are not
bfloat16, shapes that are not whole tiles.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention

# The kernel's name in a device trace (docs/tracing.md), under the
# caller's scope ``hvd_moe/route``: readers match the literal.
KERNEL = "hvd_moe_rows"

_ALIGN = 16         # a chunk starts on a whole bfloat16 tile of rows
_LANES = 128
_BLOCKS = (256, 128)    # tokens a grid step, the first that divides
_VMEM_LIMIT = 64 * 2 ** 20


def _interpret():
    """Compiled wherever the flash kernels are: a test that steers the
    step onto the TPU's path steers all of it."""
    return flash_attention._interpret()


def tiling(tokens, per_token, experts, held, rows, width, dtype):
    """``(block, chunk)``: tokens a grid step and rows a chunk, from
    shapes alone; None where the kernel does not take the shape. A
    chunk is twice the rows one (block, expert) expects and a tile for
    the alignment: a run that is longer costs that block a round."""
    if jnp.dtype(dtype) != jnp.bfloat16 or width % _LANES:
        return None
    for block in _BLOCKS:
        expected = -(-block * per_token // experts)
        chunk = -(-(2 * expected + _ALIGN) // _ALIGN) * _ALIGN
        # Two slots of chunks (rows, and ids and weights along the
        # lanes), the 0/1 matrix, the float32 sum and the output twice.
        scratch = held * chunk * (2 * (2 * width + 8 * _LANES) + 2 * block
                                  ) + block * width * (4 + 2 * 2)
        if (tokens % block == 0 and chunk <= rows and rows % _ALIGN == 0
                and scratch <= _VMEM_LIMIT // 2):
            return block, chunk
    return None


def plan(key, sizes, block_pairs, chunk):
    """Where the rows of each (token block, expert) lie. ``key``
    (pairs,): each pair's expert among the ``held`` held, or ``held``;
    ``sizes`` (held,): the held experts' draws; ``block_pairs``: pairs
    of one token block. Returns ``starts`` and ``counts``
    (blocks * held,) and ``rounds`` (blocks,): the chunks the longest
    run of a block spans."""
    held = sizes.shape[0]
    counts = jnp.sum(jax.nn.one_hot(key.reshape(-1, block_pairs), held,
                                    dtype=jnp.int32), axis=1)
    starts = (jnp.cumsum(sizes) - sizes)[None, :] + (
        jnp.cumsum(counts, axis=0) - counts)
    rounds = jnp.max(-(-(starts % _ALIGN + counts) // chunk), axis=1)
    return starts.reshape(-1), counts.reshape(-1), rounds


def _kernel(starts, counts, rounds, live, ids_hbm, rows_hbm, out_ref, buf,
            ids, select, total, sem, *, held, chunk, block, weighted):
    step, steps = pl.program_id(0), pl.num_programs(0)
    rows = rows_hbm.shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1)
    down = lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 0)

    def span(of, turn, expert):
        """(the chunk's first row, the part of the run of token block
        ``of`` in ``expert``'s group that lies inside it)."""
        start = starts[of * held + expert]
        first = start // _ALIGN * _ALIGN + turn * chunk
        return (pl.multiple_of(jnp.minimum(first, rows - chunk), _ALIGN),
                jnp.maximum(start, first),
                jnp.minimum(start + counts[of * held + expert],
                            first + chunk))

    def copy(of, turn, slot, wait):
        """Start, or wait for, the chunks of block ``of``'s round
        ``turn`` into ``slot``: one of rows and one of ids an expert
        that has a run there."""
        for expert in range(held):
            first, lo, hi = span(of, turn, expert)
            at = pl.ds(expert * chunk, chunk)

            @pl.when(hi > lo)
            def _():
                for source, to, kind in ((rows_hbm, buf, 0),
                                         (ids_hbm, ids, 1)):
                    moved = pltpu.make_async_copy(
                        source.at[pl.ds(first, chunk)], to.at[slot, at],
                        sem.at[slot, kind])
                    moved.wait() if wait else moved.start()

    def add(turn, slot):
        """This block's round ``turn``, its chunks in ``slot``: the 0/1
        matrix (times the weights) and the product."""
        for expert in range(held):
            first, lo, hi = span(step, turn, expert)
            at = pl.ds(expert * chunk, chunk)

            @pl.when((hi > lo) & (first + chunk > live[0]))
            def _():
                alive = first + down[:, :1] < live[0]
                buf[slot, at, :] = jnp.where(alive, buf[slot, at, :],
                                             jnp.zeros((), buf.dtype))
            mine = (first + down >= lo) & (first + down < hi)
            token = ids[slot, at, :_LANES]
            weight = lax.bitcast_convert_type(
                ids[slot, at, _LANES:], jnp.float32) if weighted else 1.0
            for part in range(block // _LANES):
                hit = mine & (token == step * block + part * _LANES + lane)
                select[at, part * _LANES:(part + 1) * _LANES] = jnp.where(
                    hit, weight, 0.0).astype(select.dtype)
        total[...] += lax.dot_general(
            select[...], buf[slot], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # A block's first round is on its way while the block before it is
    # summed: two slots, this block's and the next one's.
    slot = step % 2

    @pl.when(step == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)
        copy(0, 0, 0, wait=False)
    copy(step, 0, slot, wait=True)

    @pl.when(step + 1 < steps)
    def _():
        copy(step + 1, 0, 1 - slot, wait=False)
    total[...] = jnp.zeros_like(total)
    add(0, slot)

    def further(turn, _):       # a run longer than a chunk: rare
        copy(step, turn, slot, wait=False)
        copy(step, turn, slot, wait=True)
        add(turn, slot)
    lax.fori_loop(1, rounds[step], further, None)
    out_ref[...] = total[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tokens", "block", "chunk",
                                             "interpret"))
def _call(rows, token_of_row, weight_of_row, starts, counts, rounds, live, *,
          tokens, block, chunk, interpret):
    held = starts.shape[0] // (tokens // block)
    # A row's token id, and its weight's bits, along the lanes: the
    # kernel compares them with an iota of the block's tokens.
    ids = [token_of_row] if weight_of_row is None else [
        token_of_row, lax.bitcast_convert_type(
            weight_of_row.astype(jnp.float32), jnp.int32)]
    ids = jnp.concatenate([jnp.broadcast_to(
        column[:, None], (rows.shape[0], _LANES)) for column in ids], axis=1)
    return pl.pallas_call(
        functools.partial(_kernel, held=held, chunk=chunk, block=block,
                          weighted=weight_of_row is not None),
        out_shape=flash_attention._struct(
            (tokens, rows.shape[1]), rows.dtype, rows, token_of_row, starts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(tokens // block,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, rows.shape[1]),
                                   lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, held * chunk, rows.shape[1]), rows.dtype),
                pltpu.VMEM((2, held * chunk, ids.shape[1]), jnp.int32),
                pltpu.VMEM((held * chunk, block), rows.dtype),
                pltpu.VMEM((block, rows.shape[1]), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=KERNEL)(
            starts, counts, rounds, live.reshape(1).astype(jnp.int32), ids,
            rows)


def rows_to_tokens(rows, order, key, sizes, per_token, tiles, weights=None):
    """``out[t] = sum of rows[i] (* weights[order[i]])`` over the live
    rows ``i`` whose pair ``order[i]`` is one of token ``t``'s: (T, d)
    in ``rows``' dtype. ``rows`` (n, d) bfloat16 in sorted order, of
    which the first ``sum(sizes)`` are live; ``order`` (n,): the pair of
    each; ``key`` (pairs,): each pair's held expert, or ``len(sizes)``;
    ``tiles``: ``tiling``'s, not None; ``weights`` (pairs,) or None. A
    weight is rounded to bfloat16 as it enters the product."""
    block, chunk = tiles
    starts, counts, rounds = plan(key, sizes, block * per_token, chunk)
    return _call(
        rows, order // per_token,
        None if weights is None else weights[order], starts, counts, rounds,
        jnp.sum(sizes), tokens=key.shape[0] // per_token, block=block,
        chunk=chunk, interpret=_interpret())
