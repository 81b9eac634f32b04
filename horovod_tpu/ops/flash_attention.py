"""Pallas TPU flash attention with log-sum-exp outputs.

This is the hot-op kernel of the framework's model zoo and the inner step of
ring attention (horovod_tpu.parallel.ring_attention). The reference framework
has no attention kernels at all (it is a communication layer; SURVEY.md §2.6)
— this kernel exists because the TPU rebuild's flagship models are
transformers and attention is where HBM bandwidth goes.

Design (MXU/VMEM-first):
- Online-softmax tiling: grid (batch*heads, steps). A step is one (query
  block, key block) tile, named by a table that is handed in as scalar
  prefetch (``_step_table``): a query block's key blocks one after the
  other, with fp32 running max / denominator / accumulator in VMEM
  scratch that persists across them. Where the call's offsets are known
  while tracing (every call of a model) the table lists the tiles that
  do something and no other, so no grid step is idle; where they are
  traced (a ring step) it lists every tile and the body guards them.
- Two sizes. A **block** (the callers' ``block_q``, ``block_k``; 1024 in
  the model zoo) is what one grid step's DMA brings: a step's fixed cost
  and the rescaling of the running statistics are paid once a block, so
  the largest that VMEM holds is fastest. A **sub-tile** (``_sub_tile``,
  a side for each kernel) is what the causal mask is resolved at: both
  kernels walk the block the diagonal crosses in strips of query rows,
  each against the keys it can see, build the mask for the one sub-tile
  on the diagonal and run nothing for those beyond it. Blocks past a
  query block's last visible one are no steps of the grid (with traced
  offsets: neither computed nor fetched, the table names a block
  already held).
- The forward is key-major, like the backward: s^T = k q^T is (keys,
  queries), so max and sum over keys add vregs to each other and the
  running statistics are rows along lanes. Query-major, every 8 rows of
  every tile paid two reductions across lanes, and those, not the
  products or the exponential, set the kernel's time (PERF.md section 6,
  PR 29).
- Logits and accumulation in fp32 on the MXU (``preferred_element_type``),
  inputs bf16 or fp32. A power-of-two ``sm_scale`` is folded into q
  (exact), any other multiplies s.
- Global-position masking: query/key chunk offsets arrive as dynamic scalars
  (scalar-prefetch), so the same compiled kernel serves local attention and
  every step of a ring schedule (offsets are device-varying under shard_map).
- A causal ``window`` is a second edge of the same mask: the blocks
  whose keys lie a window or more before their queries are left out
  like those past the diagonal, and the one or two block
  offsets the window's edge passes through are walked in the same
  sub-tiles, each strip from its first visible key (``_aligned_offsets``,
  ``_strip_span``). ``k`` and ``v`` may hold fewer heads than ``q`` (the
  index maps name the shared row; nothing is repeated in HBM) and ``v``
  may be wider than ``q`` and ``k`` (differential attention's pair of
  values).
- A ``mask`` of data (int8, (batch, keys, queries), one for a batch
  element's heads: the keys a learned indexer selected for each query,
  ``ops/sparse_attention.py``) is one more operand of both kernels, a
  block of it fetched with every tile; the grids stay the causal
  mask's, since no table made from shapes knows which tiles such a
  mask empties. A call without it traces what it always did.
- Two ways a head's blocks lie (``addressing``), chosen from the call's
  layout and widths. Head-major, ``[batch x heads, seq, width]``: what
  ``layout="bhsd"`` operands are, and what XLA makes of a projection's
  output at no cost where the width fills lane tiles. Sequence-minor,
  ``[batch x heads, width, seq]``: how XLA holds a 64-wide q, k, v,
  output and gradients, which head-major kernels had it copy in and out
  (eight copies a layer, into arrays half padding); a ``layout="bshd"``
  call at that width reads and writes them where they lie. A tile of
  scores is (keys, queries) either way: the forward's two products
  change their dimension numbers (its accumulator was ``[dv, block_q]``
  already), the backward turns its blocks into scratch as they arrive
  and its gradients back as they leave, and runs the body it had.
- Returns (out, lse); lse makes partial results mergeable (ring attention)
  and feeds the backward pass.
- Custom VJP with one backward kernel. Two Mosaic calls a layer:
  ``hvd_flash_fwd`` writes the output and the log-sum-exp;
  ``hvd_flash_bwd_dkdv`` writes dq, dk and dv from one pass over the
  tiles (s, one exp, dp and ds once a tile: five matrix products). Its
  grid is (batch*heads, steps) too, a key block's query blocks one after
  the other: dk and dv accumulate in
  per-key-block scratch, dq in a float32 VMEM accumulator over the whole
  query range of the (batch, head). It keeps the name of the dk/dv
  kernel it grew from, which the readers of a trace match.

On non-TPU backends the kernels run in Pallas interpret mode, so the full
test suite exercises the exact kernel logic on the CPU mesh.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import ad_checkpoint, lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import envparse

_bridge_fallback_noted = set()


def note_flash_fallback(reason):
    """One-shot warning that a bridge attention site stayed on its
    einsum lowering. Shared by the torch and TF bridges so the wording
    and dedup behavior cannot diverge."""
    if reason in _bridge_fallback_noted:
        return
    _bridge_fallback_noted.add(reason)
    import warnings
    warnings.warn(
        f"tpu_compile: attention falls back to the einsum lowering "
        f"({reason}); the Pallas flash path needs 4-D rank-consistent "
        f"q/k/v with equal head dims and a mask that is all-keep or "
        f"causal at compile time", stacklevel=3)


def bridge_flash_enabled():
    """Should the torch/TF bridges route attention through this kernel?
    auto = only when the math actually runs on a TPU (in interpret mode
    the kernel is a python-level grid loop — correct but slow, so the
    CPU test suite keeps the einsum lowerings unless it opts in via
    HVDTPU_BRIDGE_FLASH=always)."""
    mode = envparse.get_str(envparse.BRIDGE_FLASH, "auto").lower()
    if mode == "always":
        return True
    if mode == "never":
        return False
    return jax.default_backend() == "tpu"

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_LANE = 128          # TPU lane width: scratch vectors are (block, _LANE)
_NEG_INF = -1e30

# Names in a device trace (docs/tracing.md): each Mosaic call carries
# its kernel's name (``pallas_call(name=)`` names the HLO instruction and
# pushes a scope of the same name), inside ``SCOPE`` together with the
# XLA work the kernel drags along (pads, ``delta``, layout copies).
# Readers of a trace match these literals.
SCOPE = "hvd_flash"
KERNEL_FWD = "hvd_flash_fwd"
KERNEL_BWD_DKDV = "hvd_flash_bwd_dkdv"


def _interpret():
    return jax.default_backend() != "tpu"


def _struct(shape, dtype, *like):
    """ShapeDtypeStruct carrying the union of the inputs' varying-mesh-axes
    type so pallas_call type-checks inside shard_map (check_vma)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _pad_to(x, multiple, axis):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _lane_tiles(d):
    return -(-d // _LANE)


# ---------------------------------------------------------------------------
# Which way a head's blocks lie
# ---------------------------------------------------------------------------

# How the kernels of a call address a head (``addressing``). Either way
# a grid row is one (batch, head) and a tile of scores is (keys,
# queries); what differs is which axis of q's, k's, v's, o's and the
# gradients' blocks is the head's width.
# ``HEAD_MAJOR``: ``[batch x heads, seq, width]``, positions along
# sublanes and the width along lanes. Where the width fills whole lane
# tiles that is how XLA holds q, k, v around the kernels anyway (a
# product writes ``[batch, heads, seq, width]`` as readily as any other
# order), and nothing is copied.
# ``SEQ_MINOR``: ``[batch x heads, width, seq]``, the width along
# sublanes and the positions along lanes. A 64-wide array XLA never
# holds width-minor, where every (8, 128) tile would be half padding:
# the projections, rope and the output product read and write
# ``[batch, heads, width, seq]``, and a kernel that wants the head-major
# form has XLA copy q, k, v in, the output out, and the four back again
# in the gradient, into arrays that are half padding. Read this way the
# blocks are whole tiles and nothing is copied; the 64-wide blocks are
# turned inside the kernels, where that is a few vector registers a
# tile and not a pass over HBM.
HEAD_MAJOR, SEQ_MINOR = "head_major", "seq_minor"
_SEQ_MINOR_WIDTH = 64


def addressing(head_dim, v_dim=None, layout="bshd", dropout=False):
    """How the kernels of a call address a head, ``"seq_minor"`` or
    ``"head_major"`` (above): a function of the call's layout and widths
    alone. ``"bhsd"`` operands are head-major as they come. Of ``"bshd"``
    ones, q and k 64 wide and v a multiple of that (64, or differential
    attention's 128) are read sequence-minor: the widths measured on
    the chip (PERF.md section 6, PR 47). Every other call is
    head-major, and so is one with dropout, whose mask is laid out by
    query rows."""
    v_dim = head_dim if v_dim is None else v_dim
    if (layout == "bshd" and not dropout and head_dim == _SEQ_MINOR_WIDTH
            and v_dim % _SEQ_MINOR_WIDTH == 0):
        return SEQ_MINOR
    return HEAD_MAJOR


def _laid(seq, width, seq_minor):
    """``(seq, width)`` as a head's block or array has them, the width
    first where the call is sequence-minor; its own inverse."""
    return (width, seq) if seq_minor else (seq, width)


def _block(ref, positions, seq_minor):
    """``positions`` of the block a q, k or v ref of the forward holds:
    ``[positions, width]``, or with ``seq_minor`` ``[width,
    positions]``."""
    return ref[(0, *_laid(positions, slice(None), seq_minor))]


def _over(lhs, rhs):
    """``dot_general`` dimension numbers contracting axis ``lhs`` of the
    left operand with axis ``rhs`` of the right one."""
    return (((lhs,), (rhs,)), ((), ()))


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _block_skip(causal, q_start, k_start, kv_len, qb, kb, block_q,
                block_k, window=None):
    """True when the (qb, kb) tile contributes nothing: every key col is
    padding, or (causal) the whole tile lies above the diagonal, or
    (``window``) every key of it lies ``window`` or more positions
    before every query. Skipped
    tiles are mathematically identity updates (p==0 everywhere), so
    guarding them with pl.when drops ~half the FLOPs of a causal kernel
    without changing results. Traced scalars, Python integers (a Python
    bool out) and numpy arrays alike."""
    skip = kb * block_k >= kv_len
    if causal:
        max_row = q_start + qb * block_q + block_q - 1
        min_col = k_start + kb * block_k
        skip = skip | (max_row < min_col)
    if window is not None:
        min_row = q_start + qb * block_q
        max_col = k_start + kb * block_k + block_k - 1
        skip = skip | (min_row - max_col >= window)
    return skip


def _tile_interior(causal, q_start, k_start, kv_len, qb, kb, block_q,
                   block_k, window=None):
    """True when NO element of the (qb, kb) tile is masked: every key
    col is valid, (causal) the whole tile lies on/below the
    diagonal and (``window``) its first key is inside its last query's
    window. Such tiles skip the iota/compare/where mask construction
    — per-element VPU work comparable to the exp itself, and at long
    context most tiles are interior."""
    inside = (kb + 1) * block_k <= kv_len
    if causal:
        min_row = q_start + qb * block_q
        max_col = k_start + kb * block_k + block_k - 1
        inside = inside & (max_col <= min_row)
    if window is not None:
        max_row = q_start + qb * block_q + block_q - 1
        min_col = k_start + kb * block_k
        inside = inside & (max_row - min_col < window)
    return inside


def _keep_scale(dm, dropout_rate):
    """fp32 dropout multiplier for a tile of the keep-mask: rescaled by
    1/(1-rate). One definition keeps the fwd and bwd use sites in
    exact sync (a fwd/bwd mismatch would be a silent gradient bug)."""
    return dm.astype(jnp.float32) * (1.0 / (1.0 - dropout_rate))


def _seeded_keep_scale(lens_ref, qb, kb, block_q, block_k, dropout_rate):
    """fp32 dropout multiplier drawn from the ON-CHIP prng (TPU only):
    seeded per (batch·head, q-tile, k-tile), so the forward and the
    backward kernel regenerate the exact same keep pattern without a
    single byte of mask leaving VMEM — no bernoulli host program, no
    O(S²) mask residual. The threshold compare gives keep probability
    exact to 2^-32.

    Mosaic accepts at most TWO seed words: the batch·head index folds
    into the user seed via an odd multiplicative hash (a bijection mod
    2^32, so distinct bh stay distinct), and the tile coordinates pack
    into the second word (16 bits each — tile counts beyond 65536 would
    mean a >8M-token sequence)."""
    bh = pl.program_id(0)
    s1 = jnp.bitwise_xor(lens_ref[3], bh * jnp.int32(-1640531527))
    s2 = qb * jnp.int32(65536) + kb
    pltpu.prng_seed(s1, s2)
    bits = pltpu.prng_random_bits((block_q, block_k))
    bits = jax.lax.bitcast_convert_type(bits, jnp.uint32)
    thresh = jnp.uint32(int((1.0 - dropout_rate) * 4294967296.0))
    return (bits < thresh).astype(jnp.float32) * (
        1.0 / (1.0 - dropout_rate))


# Side of the forward's sub-tiles at a head of one lane tile, and of the
# backward's at every head width.
_SUB_TILE = 512
_SUB_TILE_BWD = 128


def _sub_tile(causal, block_q, block_k, d, backward=False):
    """Side of the square sub-tiles a kernel walks a block on the
    diagonal in (the block's own where it is smaller: one sub-tile, and
    still the diagonal's constant mask), or None where it walks none (no
    causal mask, blocks not square). The blocks are what a
    grid step's DMA brings (the callers' to choose, as large as VMEM
    allows: a step's fixed cost and the statistics' rescaling are paid
    once a block); the sub-tile is what the mask is resolved at.
    Measured on ``_fwd_call`` alone (PERF.md section 6, PR 29): 512 at a
    head of one lane tile, 256 at two, where the products are long
    enough to pay for four sub-blocks of rows; never smaller. The
    backward has five products a tile where the forward has two and no
    running statistics to rescale a strip: measured on ``_bwd_call``
    alone (PERF.md section 6, PR 31) the finest side the lanes allow is
    fastest at every head width, or level."""
    if backward:
        side = _SUB_TILE_BWD
    else:
        side = _SUB_TILE // min(2, _lane_tiles(d))
    sub = min(block_q, side)
    if not causal or block_q != block_k or block_q % sub:
        return None
    return sub


def _aligned_offsets(window, block):
    """The block offsets (query block less key block) at which a tile
    with its corner on a block boundary is crossed by the mask: 0, the
    causal diagonal, and the one or two the window's far edge passes
    through. A tile at such an offset has the same mask wherever it
    lies, so its sub-tiles' classes are fixed (_strip_span)."""
    if window is None:
        return (0,)
    return tuple(d for d in range((window + block - 1) // block + 1)
                 if d == 0 or d * block - (block - 1) < window
                 <= d * block + block - 1)


def _aligned(q_start, k_start, kv_len, qb, kb, block, offset=0):
    """True for the block whose queries start ``offset`` blocks after
    its keys, corner on corner, with every key valid; at offset 0 the
    block the diagonal crosses from corner to corner."""
    kb_q = kb if offset == 0 else kb + offset
    return ((q_start + qb * block == k_start + kb_q * block)
            & ((kb + 1) * block <= kv_len))


def _strip_span(i, n_j, sub, rows_after=0, window=None):
    """What sub-block ``i`` of query rows sees of an aligned tile whose
    first query stands ``rows_after`` positions after its first key and
    whose keys are all valid: ``(key0, width, head, tail)``, the first
    key seen, how many are seen, and of those the leading ones that go
    through the mask (the window's edge) and the trailing ones that do
    (the diagonal); None where it sees none. The sub-tiles' classes
    are those of the functions that class a tile, on each sub-tile's
    own corners. Sub-tiles seen that are all masked count as
    trailing."""
    at = [(True, rows_after, 0, n_j * sub, i, j, sub, sub, window)
          for j in range(n_j)]
    seen = [j for j in range(n_j) if not _block_skip(*at[j])]
    if not seen:
        return None
    whole = [j for j in seen if _tile_interior(*at[j])]
    head = whole[0] - seen[0] if whole else 0
    tail = seen[-1] - whole[-1] if whole else len(seen)
    return seen[0] * sub, len(seen) * sub, head * sub, tail * sub


def _span_mask(shape, row0, key0, rows_after, causal, q_pos, k_start, k_pos,
               kv_len, window=None):
    """Which elements of a key-major part ``shape`` = (keys, queries)
    of a tile are visible: its keys from ``key0`` on by its query rows
    from ``row0`` on. The tile's first row stands at ``q_pos`` of the
    sequence; its first key at ``k_pos`` of the key chunk (what
    ``kv_len`` counts), which starts at ``k_start``. ``rows_after`` is
    not None for an aligned tile (_strip_span): its mask is the same
    wherever it lies, and only the edges that can cut this part are
    built."""
    c = key0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    r = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if rows_after is not None:
        # Distances query less key over the part, at their extremes.
        nearest = rows_after + row0 - (key0 + shape[0] - 1)
        farthest = rows_after + row0 + shape[1] - 1 - key0
        mask = None
        if nearest < 0 or window is None:
            mask = r >= c if rows_after == 0 else r + rows_after >= c
        if window is not None and farthest >= window:
            inside = r - c < window - rows_after
            mask = inside if mask is None else jnp.logical_and(mask, inside)
        return mask
    mask = k_pos + c < kv_len                         # key padding
    if causal:
        mask = jnp.logical_and(mask, q_pos + r >= k_start + k_pos + c)
    if window is not None:
        mask = jnp.logical_and(
            mask, q_pos + r - (k_start + k_pos + c) < window)
    return mask


def _masker(width, head, tail, mask_of):
    """``masked(x, fill)`` for (keys, queries) tiles ``x`` of ``width``
    keys: the first ``head`` and the last ``tail`` keys are filled where
    ``mask_of(first key, keys)`` hides them (None: it hides none), the
    ones between are left as they are."""
    head_mask = mask_of(0, head) if head else None
    tail_mask = mask_of(width - tail, tail) if tail else None

    def masked(x, fill):
        if head_mask is None and tail_mask is None:
            return x
        # Made in the order tail, middle, head.
        parts = [x[width - tail:, :]] if tail else []
        if tail_mask is not None:
            parts = [jnp.where(tail_mask, parts[0], fill)]
        if width - head - tail:
            parts.insert(0, x[head:width - tail, :])
        if head:
            part = x[:head, :]
            parts.insert(0, part if head_mask is None
                         else jnp.where(head_mask, part, fill))
        return parts[0] if len(parts) == 1 else jnp.concatenate(
            parts, axis=0)

    return masked


def _last_key_block(i, lens, n_k, block_q, block_k, causal):
    """The last key block the forward's query block ``i`` sees, inside
    the grid; None where every step is visible or nothing would be
    saved. The steps after it are skipped (_block_skip). ``lax.div``,
    not ``//``, which Mosaic lowers as a nested ``pjit`` a map (1.2 s of
    set-up in PR 27)."""
    if not causal or n_k == 1:
        return None
    last = lax.div(lens[0] + i * block_q + (block_q - 1) - lens[1],
                   jnp.int32(block_k))
    last = lax.min(last, lax.div(lens[2] - 1, jnp.int32(block_k)))
    # Truncating division: a negative numerator gives 0 or more where
    # the floor gives -1, and both end at block 0.
    return lax.max(lax.min(last, jnp.int32(n_k - 1)), jnp.int32(0))


def _first_key_block(i, lens, n_k, block_q, block_k, window):
    """The first key block the forward's query block ``i`` sees through
    a window, inside the grid: the steps before it are skipped."""
    first = lax.div(lens[0] + i * block_q - (window - 1) - lens[1],
                    jnp.int32(block_k))
    return lax.max(lax.min(first, jnp.int32(n_k - 1)), jnp.int32(0))


def _kv_block(i, j, lens, n_k, block_q, block_k, causal, window=None,
              n_q=1):
    """The K and V block grid step (i, j) of the forward holds: its own
    while visible, block 0 on the skipped steps after. One fetch, under
    the diagonal tile's products, brings what the next query block
    starts with, and the other skipped steps fetch nothing (measured
    against naming the last visible block, which fetches at the row's
    end: 4.25 -> 4.10 ms a layer at 32 x 8192 x 64, PERF.md, PR 29).
    Under a window the steps before the first visible block name that
    block, and the ones after the last name the first block of the next
    query block (of ``n_q``), which is often the one held."""
    last = _last_key_block(i, lens, n_k, block_q, block_k, causal)
    if last is None:
        return j
    if window is None:
        return lax.select(j <= last, j, jnp.zeros_like(j))
    grid_of = (lens, n_k, block_q, block_k, window)
    first = _first_key_block(i, *grid_of)
    after = _first_key_block(lax.rem(i + 1, jnp.int32(n_q)), *grid_of)
    return lax.select(j < first, first, lax.select(j <= last, j, after))


def _first_query_block(j, lens, n_q, block_q, block_k, qb0):
    # Truncating division: where it differs from the floor the first
    # block is negative and ``i`` wins either way.
    first = lax.div(lens[1] + j * block_k - lens[0],
                    jnp.int32(block_q)) - qb0
    return lax.min(first, jnp.int32(n_q - 1))


def _q_block(j, i, lens, n_q, block_q, block_k, causal, qb0, window=None,
             n_k=1):
    """The query block grid step (j, i) of the backward holds, of a call
    whose tiles are ``qb0`` onward of the sequence. Under a causal mask
    the steps before a key block's first visible query block are skipped
    (_block_skip): they name that first block, so they fetch nothing and
    the block is there when its step comes. Under a window the steps
    after its last visible query block name the first of the next key
    block (of ``n_k``)."""
    if not causal or n_q == 1:
        return i
    grid_of = (lens, n_q, block_q, block_k, qb0)
    first = _first_query_block(j, *grid_of)
    if window is None:
        return lax.max(i, first)
    last = lax.div(lens[1] + j * block_k + (block_k - 1) + (window - 1)
                   - lens[0], jnp.int32(block_q)) - qb0
    after = lax.max(_first_query_block(lax.rem(j + 1, jnp.int32(n_k)),
                                       *grid_of), jnp.int32(0))
    return lax.select(i <= last, lax.max(i, first), after)


# ---------------------------------------------------------------------------
# The grid: one axis over the tiles that do something
# ---------------------------------------------------------------------------

# Columns of a step table (_step_table), and the bits of its flags.
_ROW, _INNER, _FETCH, _FLAGS = range(4)
_ROW_FIRST, _ROW_LAST, _DQ_FIRST, _DQ_LAST = 1, 2, 4, 8


def _column(steps, column, step):
    """Entry ``step`` of a column of a step table (flat, a column after
    the other: a one-dimensional array is what scalar memory holds
    without padding)."""
    return steps[column * (steps.shape[0] // 4) + step]


def _named(column, one_tile):
    """For an index map: ``(step, steps) -> `` the block that ``column``
    of the step table names at a grid step. A call of one tile a
    (batch, head) has block 0 alone and looks nothing up."""
    if one_tile:
        return lambda step, steps: 0
    return lambda step, steps: _column(steps, column, step)


def _this_step(steps_ref, one_tile):
    """``(row, inner, flags)`` of the grid step that is running. A call
    of one tile a (batch, head) looks nothing up: its one step is tile
    (0, 0), every row's first and last, and the tests of its flags are
    Python's, so the body is one straight line (at seq 512, one block a
    head, the table's loads and branches cost 4% of either kernel:
    PERF.md section 6, PR 43)."""
    if one_tile:
        return (jnp.int32(0), jnp.int32(0),
                _ROW_FIRST | _ROW_LAST | _DQ_FIRST | _DQ_LAST)
    return tuple(_column(steps_ref, column, pl.program_id(1))
                 for column in (_ROW, _INNER, _FLAGS))


def _step_table(backward, where, n_q, n_k, block_q, block_k, causal,
                window=None, qb0=0):
    """The steps of one (batch, head) of a kernel's grid ``(bh, steps)``,
    handed to the kernel as scalar prefetch beside ``lens``: int32,
    four columns of ``steps`` entries each (_column). A step is one
    (query block, key block) tile. ``_ROW`` is the block whose
    accumulators the step adds to (the forward's query block, the
    backward's key block) and ``_INNER`` the other, ascending inside a
    row, rows ascending: the order of the rectangular grid this one
    replaced, so every running sum adds the same terms in the same
    order. ``_FETCH`` is the inner block the step's index maps name and
    ``_FLAGS`` says whether the step is its row's first
    (``_ROW_FIRST``: the accumulators start) and last (``_ROW_LAST``:
    they are written out) and, in the backward, whether its query block
    is met for the first time (``_DQ_FIRST``: its rows of dq's
    accumulator start) and for the last (``_DQ_LAST``: they are
    written).

    ``where`` is the call's ``(q_offset, k_offset, kv_len)``. Python
    integers (a tuple; every call of a model): the table is made here,
    in numpy, and lists the tiles that ``_block_skip`` does not skip and
    nothing else, so no grid step is idle; a block of an output that no
    tile is left of keeps one step, skipped in the body, which writes
    its zeros. Traced (the kernels' ``lens``; a ring step, one compiled
    kernel for every position): the live tiles are not known while
    tracing, so the table lists every tile, ``_block_skip`` guards the
    body, and a skipped step names a block already held (_kv_block,
    _q_block) and fetches nothing. One grid, one kernel body; what
    differs is the table. ``qb0``: the backward's query blocks are
    ``qb0`` onward of the sequence (_bwd_call)."""
    qb, kb = np.meshgrid(np.arange(n_q), np.arange(n_k), indexing="ij")
    traced = not isinstance(where, tuple)
    run = np.ones(qb.shape, bool)
    if not traced:
        run &= ~_block_skip(causal, *where, qb0 + qb, kb, block_q, block_k,
                            window)
        if backward:
            run[-1, ~run.any(axis=0)] = True
        run[~run.any(axis=1), 0] = True
    row, inner = np.nonzero(run.T if backward else run)
    turns = row[1:] != row[:-1]
    flags = (_ROW_FIRST * np.r_[True, turns] + _ROW_LAST * np.r_[turns, True])
    if backward:
        flags[np.unique(inner, return_index=True)[1]] |= _DQ_FIRST
        flags[len(inner) - 1
              - np.unique(inner[::-1], return_index=True)[1]] |= _DQ_LAST
    columns = [x.astype(np.int32) for x in (row, inner, inner, flags)]
    if not traced:
        return np.concatenate(columns)
    row, inner = jnp.asarray(columns[_ROW]), jnp.asarray(columns[_INNER])
    if backward:
        columns[_FETCH] = _q_block(row, inner, where, n_q, block_q, block_k,
                                   causal, qb0, window, n_k)
    else:
        columns[_FETCH] = _kv_block(row, inner, where, n_k, block_q,
                                    block_k, causal, window, n_q)
    return jnp.concatenate(columns)


def _fwd_kernel(lens_ref, steps_ref, q_ref, k_ref, v_ref, *rest, sm_scale,
                causal, block_q, block_k, sub, one_tile, dropout_rate=0.0,
                seeded=False, window=None, has_mask=False, seq_minor=False):
    # rest = [dm_ref?], [mask_ref?], o_ref, lse_ref, m_scr, l_scr, acc_scr
    # The axis of a q, k or v block that is the head's width (SEQ_MINOR).
    w = 0 if seq_minor else 1
    dm_ref = mask_ref = None
    if dropout_rate > 0.0 and not seeded:
        dm_ref, *rest = rest
    if has_mask:
        mask_ref, *rest = rest
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    # This grid step's tile, and whether it is its query block's first
    # and last (_step_table).
    qb, kb, flags = _this_step(steps_ref, one_tile)
    q_start = lens_ref[0]
    k_start = lens_ref[1]
    kv_len = lens_ref[2]
    # A power of two: q * scale is exact and so is every product of it,
    # so s needs no multiply of its own.
    fold = math.frexp(sm_scale)[0] == 0.5

    @pl.when((flags & _ROW_FIRST) != 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    mask_of = (causal, q_start, k_start, kv_len)
    skip = _block_skip(*mask_of, qb, kb, block_q, block_k, window)
    interior = _tile_interior(*mask_of, qb, kb, block_q, block_k, window)

    def update(keep, row0, n_rows, key0, width, head, tail,
               rows_after=None):
        """Online-softmax update of the tile's query rows ``row0`` to
        ``row0 + n_rows`` by its ``width`` keys from ``key0`` on. The
        first ``head`` and the last ``tail`` of them go through the
        mask; the ones between are known to be visible. ``rows_after``
        is given for an aligned tile (_strip_span). Key-major: s^T is
        (keys, queries), so the statistics are rows along lanes and a
        reduction over keys adds vregs to each other, nothing across
        lanes."""
        rows = pl.ds(row0, n_rows)
        keys = slice(key0, key0 + width)
        q = _block(q_ref, rows, seq_minor)
        if fold:
            q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
        st = jax.lax.dot_general(
            _block(k_ref, keys, seq_minor), q, _over(w, w),
            preferred_element_type=jnp.float32)      # (width, n_rows)
        if not fold:
            st = st * sm_scale

        masked = _masker(
            width, head, tail, lambda first, n: _span_mask(
                (n, n_rows), row0, key0 + first, rows_after, causal,
                q_start + qb * block_q, k_start, kb * block_k, kv_len,
                window))

        st = masked(st, _NEG_INF)
        if mask_ref is not None:
            st = jnp.where(_mask_tile(mask_ref, keys, rows), st, _NEG_INF)
        m_prev = m_scr[:1, rows]                   # (1, n_rows)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        if mask_ref is not None:
            # A row that has seen no key yet keeps m at _NEG_INF, where
            # exp(st - m) would be 1 for its hidden keys: against a
            # maximum held just above it they are exp(-9e29) = 0, at the
            # cost of a row of maxima and not of a pass over the tile.
            pt = jnp.exp(st - jnp.maximum(m_new, 0.1 * _NEG_INF))
        else:
            pt = jnp.exp(st - m_new)               # (width, n_rows) fp32
        if rows_after != 0 and mask_ref is None:
            # Fully-masked rows: m_new stays _NEG_INF and p would be
            # exp(0)=1 — zero those contributions so l stays 0 for
            # them. (On the diagonal every row sees its own key, so a
            # masked p is exp(-1e30 - m) = 0 already.)
            pt = masked(pt, 0.0)
        l_new = alpha * l_scr[:1, rows] + jnp.sum(pt, axis=0, keepdims=True)
        # Attention dropout (torch semantics: probs are dropped AFTER
        # softmax, so the normalizer l uses the undropped p while the
        # value accumulation uses the dropped/rescaled weights).
        pvt = pt
        if keep is not None:
            pvt = pt * keep[row0:row0 + n_rows, keys].T
        elif dm_ref is not None:
            pvt = pt * _keep_scale(dm_ref[0, rows, keys], dropout_rate).T
        acc_scr[:, rows] = acc_scr[:, rows] * alpha + jax.lax.dot_general(
            _block(v_ref, keys, seq_minor), pvt.astype(v_ref.dtype),
            _over(1 - w, 0), preferred_element_type=jnp.float32)
        m_scr[:, rows] = jnp.broadcast_to(m_new, (m_scr.shape[0], n_rows))
        l_scr[:, rows] = jnp.broadcast_to(l_new, (l_scr.shape[0], n_rows))

    def draw():
        if dropout_rate > 0.0 and seeded:
            return _seeded_keep_scale(lens_ref, qb, kb, block_q, block_k,
                                      dropout_rate)
        return None

    visible = jnp.logical_not(skip)

    @pl.when(jnp.logical_and(visible, interior))
    def _():
        update(draw(), 0, block_q, 0, block_k, 0, 0)

    partial = jnp.logical_and(visible, jnp.logical_not(interior))
    for offset in _aligned_offsets(window, block_q) if sub else ():
        # A sub-block of query rows sees the sub-tiles between the
        # window's edge and the diagonal whole, the ones on either
        # through the mask, and nothing of those beyond.
        aligned = _aligned(q_start, k_start, kv_len, qb, kb, block_q,
                           offset)

        @pl.when(jnp.logical_and(partial, aligned))
        def _(offset=offset):
            keep = draw()
            for i in range(block_q // sub):
                span = _strip_span(i, block_k // sub, sub,
                                   offset * block_q, window)
                if span is not None:
                    update(keep, i * sub, sub, *span,
                           rows_after=offset * block_q)

        partial = jnp.logical_and(partial, jnp.logical_not(aligned))

    @pl.when(partial)
    def _():
        update(draw(), 0, block_q, 0, block_k, 0, block_k)

    @pl.when((flags & _ROW_LAST) != 0)
    def _():
        l = l_scr[:1, :]                           # (1, block_q)
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out = acc_scr[:] / safe_l                  # (dv, block_q)
        o_ref[0] = (out if seq_minor else out.T).astype(o_ref.dtype)
        # lse is laid out (bh, 1, sq): TPU requires the last two block dims
        # to divide (8, 128) or equal the array dims — (1, 1, block_q) does.
        lse_ref[0] = jnp.where(l == 0.0, _NEG_INF,
                               m_scr[:1, :] + jnp.log(safe_l))


def _fwd_call(q, k, v, lens, sm_scale, causal, block_q, block_k,
              dm=None, dropout_rate=0.0, seeded=False, window=None,
              where=None, mask=None, seq_minor=False):
    """One forward kernel call. The layers of a model make the same
    call, so it goes through ``jax.jit``: the kernel is traced and
    lowered once a program, not once a layer, and XLA inlines the calls
    (each keeps its own layer's ``op_name``). What the trace reads of
    the module's state is an argument, so no cached trace outlives it;
    so are the grid's steps (_step_table: a grid step is a tile that
    does something wherever ``where``, the call's offsets and
    ``kv_len`` as Python integers, is given, and any tile where they
    are traced), which the layers' calls hand in alike.

    ``k`` and ``v`` may hold fewer heads than ``q`` (``q``'s rows are
    ``group`` to a row of theirs, adjacent), and ``v`` another width
    than ``q`` and ``k``. ``mask`` (``_mask_tile``) is one more operand
    of the same grid: the table is made from shapes and offsets, which
    a mask of data does not change, so every tile under the diagonal
    runs.

    ``seq_minor``: 0, or the batch size of a call whose operands are
    ``[batch x heads, width, seq]`` (SEQ_MINOR), not ``[batch x heads,
    seq, width]``. Its output is ``[batch, heads, width, seq]``, a
    transpose of what the kernel writes (_o_row) that XLA makes a
    layout and not a copy: the transpose is on this side of the
    ``custom_vjp``, so the output's cotangent need not lie as the
    output does. The log-sum-exp is ``[batch x heads, seq]`` either
    way."""
    seq, width = _laid(1, 2, seq_minor)      # the operands' axes
    steps = _step_table(False, lens if where is None else where,
                        q.shape[seq] // block_q, k.shape[seq] // block_k,
                        block_q, block_k, causal, window)
    args = (q, k, v, lens, dm, steps, sm_scale, causal, block_q, block_k,
            _sub_tile(causal, block_q, block_k, q.shape[width]),
            dropout_rate, seeded, _interpret(), window, seq_minor)
    o, lse = (_fwd_jit(*args) if mask is None
              else _fwd_masked_jit(mask, *args))
    if seq_minor:
        o = o.reshape(-1, seq_minor, *o.shape[1:]).swapaxes(0, 1)
    return o, lse


def _kv_row(b, group):
    """The K/V row of query row ``b`` of the flattened (batch, head)s."""
    return b if group == 1 else lax.div(b, jnp.int32(group))


def _mask_tile(mask_ref, keys, rows):
    """Which (key, query) pairs of a part of a tile the call's ``mask``
    keeps. The mask is int8 ``[batch, keys, queries]``, key-major as
    the kernels' tiles are and shared by a batch element's heads; a
    block of it comes with every tile, beside K and V."""
    return mask_ref[0, keys, rows].astype(jnp.int32) != 0


def _mask_spec(mask, bh, block_q, block_k, qb, kb):
    """The mask's block of a grid step whose query and key blocks
    ``qb(step, steps)`` and ``kb(step, steps)`` name."""
    heads = bh // mask.shape[0]
    return pl.BlockSpec(
        (1, block_k, block_q), lambda b, s, lens, steps: (
            lax.div(b, jnp.int32(heads)), kb(s, steps), qb(s, steps)))


def _mask_vmem_bytes(block_q, block_k):
    """Scoped VMEM a mask adds to a kernel: its int8 block, double-
    buffered, and the 32 bits a tile of it is widened to."""
    return 6 * block_q * block_k


@functools.partial(jax.jit, static_argnums=tuple(range(6, 16)))
def _fwd_jit(q, k, v, lens, dm, steps, sm_scale, causal, block_q, block_k,
             sub, dropout_rate, seeded, interpret, window, seq_minor):
    return _fwd_pallas(None, q, k, v, lens, dm, steps, sm_scale, causal,
                       block_q, block_k, sub, dropout_rate, seeded, interpret,
                       window, seq_minor)


@functools.partial(jax.jit, static_argnums=tuple(range(7, 17)))
def _fwd_masked_jit(mask, *args):
    return _fwd_pallas(mask, *args)


def _tile_spec(block, width, row, named, seq_minor):
    """The ``BlockSpec`` of ``block`` positions by ``width`` of grid row
    ``row(b)``'s head: the block of positions ``named(step, steps)``
    names, laid out as the call's operands are (SEQ_MINOR)."""
    return pl.BlockSpec(
        (1, *_laid(block, width, seq_minor)),
        lambda b, s, lens, steps: (
            row(b), *_laid(named(s, steps), 0, seq_minor)))


def _o_row(bh, seq_minor):
    """For an index map: the row of the forward kernel's output that
    grid row ``b`` of ``bh`` writes. Head-major, and of a batch of one:
    its own. Sequence-minor (``seq_minor`` is the batch size) the
    kernel writes ``[heads x batch, width, seq]``, the heads outermost,
    which is how the output product reads it; the cotangent comes back
    from that product batch outermost, as q, k and v are (_fwd_call)."""
    if seq_minor <= 1:
        return lambda b: b
    heads = bh // seq_minor
    return lambda b: (lax.rem(b, jnp.int32(heads)) * jnp.int32(seq_minor)
                      + lax.div(b, jnp.int32(heads)))


def _fwd_pallas(mask, q, k, v, lens, dm, steps, sm_scale, causal, block_q,
                block_k, sub, dropout_rate, seeded, interpret, window,
                seq_minor):
    bh, (sq, d), (sk, dv) = q.shape[0], *(
        _laid(*x.shape[1:], seq_minor) for x in (q, v))
    group = bh // k.shape[0]
    one_tile = sq == block_q and sk == block_k
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, sub=sub, one_tile=one_tile,
        dropout_rate=dropout_rate, seeded=seeded, window=window,
        has_mask=mask is not None, seq_minor=seq_minor)
    qb, kb = _named(_ROW, one_tile), _named(_FETCH, one_tile)

    def kv_row(b):
        return _kv_row(b, group)

    in_specs = [
        _tile_spec(block_q, d, lambda b: b, qb, seq_minor),
        _tile_spec(block_k, d, kv_row, kb, seq_minor),
        _tile_spec(block_k, dv, kv_row, kb, seq_minor),
    ]
    operands = [q, k, v]
    if dropout_rate > 0.0 and not seeded:
        # Beside the K/V block the step names: the step's own wherever
        # the tile is visible.
        in_specs.append(pl.BlockSpec(
            (1, block_q, block_k), lambda b, s, lens, steps: (
                b, qb(s, steps), kb(s, steps))))
        operands.append(dm)
    limits = {}
    if mask is not None:
        in_specs.append(_mask_spec(mask, bh, block_q, block_k, qb, kb))
        operands.append(mask)
        limits["vmem_limit_bytes"] = _TILE_VMEM_BYTES + _mask_vmem_bytes(
            block_q, block_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, steps.shape[0] // 4),
        in_specs=in_specs,
        out_specs=[
            _tile_spec(block_q, dv, _o_row(bh, seq_minor), qb, seq_minor),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, s, lens, steps: (b, 0, qb(s, steps))),
        ],
        scratch_shapes=[
            pltpu.VMEM((8, block_q), jnp.float32),
            pltpu.VMEM((8, block_q), jnp.float32),
            pltpu.VMEM((dv, block_q), jnp.float32),
        ],
    )
    out_shapes = [
        _struct((bh, *_laid(sq, dv, seq_minor)), q.dtype, q, k, v, lens),
        _struct((bh, 1, sq), jnp.float32, q, k, v, lens),
    ]
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"), **limits)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=compiler_params,
        interpret=interpret,
        name=KERNEL_FWD,
    )(lens, steps, *operands)
    return o, lse[:, 0, :]


# ---------------------------------------------------------------------------
# Backward kernel
# ---------------------------------------------------------------------------

def _bwd_kernel(lens_ref, steps_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, *rest, sm_scale, causal, block_q, block_k, qb0,
                sub, one_tile, dropout_rate=0.0, seeded=False, window=None,
                has_mask=False, seq_minor=False):
    # rest = [dm_ref?], [mask_ref?], dq_ref, dk_ref, dv_ref, dq_scr,
    # dk_scr, dv_scr, [q_scr, k_scr, v_scr, do_scr]
    dm_ref = mask_ref = None
    if dropout_rate > 0.0 and not seeded:
        dm_ref, *rest = rest
    if has_mask:
        mask_ref, *rest = rest
    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *turned = rest
    # Every product below takes its blocks as [positions, width]: p and
    # ds, a tile's 1024 x 1024, then enter dv, dk and dq as the operand
    # that streams, and the 64-wide blocks as the one that stays. A
    # sequence-minor call (SEQ_MINOR) holds [width, positions]: its
    # blocks are turned into scratch as they arrive, K and V once a key
    # block, q and do once a tile, and dq, dk, dv turned back as they
    # are written, so the body is the head-major one. (With the blocks
    # as they lie and the products' dimension numbers changed instead,
    # the tile is the operand that stays: 28% slower at seq 2048 and
    # 55% at seq 512 in the step; PERF.md section 6, PR 47.)
    if seq_minor:
        q_of, k_of, v_of, do_of = (
            lambda at, scr=scr: scr[at, :] for scr in turned)
    else:
        q_of, k_of, v_of, do_of = (
            lambda at, ref=ref: ref[0, at, :]
            for ref in (q_ref, k_ref, v_ref, do_ref))
    # This grid step's tile, whether it is its key block's first and
    # last, and its query block's in the whole call (_step_table).
    kb, qb, flags = _this_step(steps_ref, one_tile)
    # This call's query rows are a chunk of the sequence (see _bwd_call):
    # tile ``qb`` here is tile ``qg`` of the whole, which is what the mask
    # and the dropout seed are drawn from.
    qg = qb0 + qb
    q_start = lens_ref[0]
    k_start = lens_ref[1]
    kv_len = lens_ref[2]
    # A power of two: scaling q for s and dk, and dq once as it is
    # written, is exactly the multiply of s and ds (it commutes with
    # every rounding), without the two passes over a float32 tile.
    fold = math.frexp(sm_scale)[0] == 0.5

    def dq_rows(row0, n_rows):
        return pl.ds(pl.multiple_of(qb * block_q + row0, n_rows), n_rows)

    @pl.when((flags & _ROW_FIRST) != 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        if seq_minor:
            turned[1][:] = k_ref[0].T
            turned[2][:] = v_ref[0].T

    @pl.when((flags & _DQ_FIRST) != 0)
    def _():
        dq_scr[dq_rows(0, block_q), :] = jnp.zeros(
            (block_q, dq_scr.shape[1]), jnp.float32)

    mask_of = (causal, q_start, k_start, kv_len)
    skip = _block_skip(*mask_of, qg, kb, block_q, block_k, window)
    interior = _tile_interior(*mask_of, qg, kb, block_q, block_k, window)

    def tile_update(keep, row0, n_rows, key0, width, head, tail,
                    rows_after=None):
        """Add to dq, dk and dv what the tile's query rows ``row0`` to
        ``row0 + n_rows`` and its ``width`` keys from ``key0`` on give.
        The first ``head`` and the last ``tail`` of them go through the
        mask; the ones between are known to be visible. ``rows_after``
        is given for an aligned tile (_strip_span). Key-major: every
        (keys, queries) tile below
        is the transpose of the forward's. p^T and ds^T then enter dv
        and dk as plain left operands and lse, delta broadcast along
        sublanes as they are stored; only dq contracts over the left
        operand's rows."""
        rows = slice(row0, row0 + n_rows)
        keys = slice(key0, key0 + width)
        q = q_of(rows)                    # (n_rows, d)
        do = do_of(rows)
        k = k_of(keys)                    # (width, d)
        v = v_of(keys)
        lse = lse_ref[0, :, rows]         # (1, n_rows)
        delta = delta_ref[0, :, rows]
        if fold:
            q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)

        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # (width, n_rows)
        if not fold:
            st = st * sm_scale
        pt = jnp.exp(st - lse)                       # (width, n_rows) fp32
        pt = _masker(
            width, head, tail, lambda first, n: _span_mask(
                (n, n_rows), row0, key0 + first, rows_after, causal,
                q_start + qg * block_q, k_start, kb * block_k, kv_len,
                window))(pt, 0.0)
        if mask_ref is not None:
            pt = jnp.where(_mask_tile(mask_ref, keys, rows), pt, 0.0)

        # Dropout backward: o = (P∘M̃)V with M̃ = mask/(1-rate), so
        # dV = (P∘M̃)ᵀdO and dP = (dO Vᵀ)∘M̃; the delta trick survives
        # because Σₖ Pᵢₖ dPᵢₖ = rowsum(dO∘O) = delta exactly as without
        # dropout (O already carries M̃).
        if keep is not None:
            keep = keep[rows, keys].T
        elif dm_ref is not None:
            keep = _keep_scale(dm_ref[0, rows, keys], dropout_rate).T
        pvt = pt if keep is None else pt * keep
        # MXU operands in the input dtype (bf16 in training; identity for
        # fp32 inputs), fp32 accumulation. fp32 operands would run the
        # matmuls at a fraction of MXU rate — the softmax weights and ds
        # are the canonical safe-to-round tensors of the flash backward.
        dv_scr[keys, :] = dv_scr[keys, :] + jax.lax.dot_general(
            pvt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # (width, n_rows)
        if keep is not None:
            dpt = dpt * keep
        dst = pt * (dpt - delta)
        if not fold:
            dst = dst * sm_scale
        dst = dst.astype(q.dtype)
        dk_scr[keys, :] = dk_scr[keys, :] + jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        at = dq_rows(row0, n_rows)
        dq_scr[at, :] = dq_scr[at, :] + jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def draw():
        if dropout_rate > 0.0 and seeded:
            return _seeded_keep_scale(lens_ref, qg, kb, block_q, block_k,
                                      dropout_rate)
        return None

    visible = jnp.logical_not(skip)

    if seq_minor:
        @pl.when(visible)
        def _():
            turned[0][:] = q_ref[0].T
            turned[3][:] = do_ref[0].T

    @pl.when(jnp.logical_and(visible, interior))
    def _():
        tile_update(draw(), 0, block_q, 0, block_k, 0, 0)

    partial = jnp.logical_and(visible, jnp.logical_not(interior))
    for offset in _aligned_offsets(window, block_q) if sub else ():
        # As the forward walks it: a strip of query rows sees the
        # sub-tiles between the window's edge and the diagonal whole,
        # the ones on either through the mask, and nothing of those
        # beyond, whose p is zero.
        aligned = _aligned(q_start, k_start, kv_len, qg, kb, block_q,
                           offset)

        @pl.when(jnp.logical_and(partial, aligned))
        def _(offset=offset):
            keep = draw()
            for i in range(block_q // sub):
                span = _strip_span(i, block_k // sub, sub,
                                   offset * block_q, window)
                if span is not None:
                    tile_update(keep, i * sub, sub, *span,
                                rows_after=offset * block_q)

        partial = jnp.logical_and(partial, jnp.logical_not(aligned))

    @pl.when(partial)
    def _():
        tile_update(draw(), 0, block_q, 0, block_k, 0, block_k)

    @pl.when((flags & _ROW_LAST) != 0)
    def _():
        for ref, scr in ((dk_ref, dk_scr), (dv_ref, dv_scr)):
            ref[0] = (scr[:].T if seq_minor else scr[:]).astype(ref.dtype)

    @pl.when((flags & _DQ_LAST) != 0)
    def _():
        at = dq_rows(0, block_q)
        dq = dq_scr[at, :]
        dq = (dq * sm_scale if fold else dq).astype(dq_ref.dtype)
        if seq_minor:
            dq_ref[0, :, at] = dq.T
        else:
            dq_ref[0, at, :] = dq


# Scoped VMEM of the backward kernel. Its tiles and their temporaries get
# a Mosaic kernel's default on a v5e (16 MiB of 128) for every lane tile
# of the head's width. Compiled alone, 1024 tiles need 12 MiB at a head
# of 64 and 19 at 256; inside a whole step XLA's prefetches of the
# kernel's operands count against the same limit (the GLM step does not
# compile at 19). Not more than that either: XLA keeps buffers of its
# own in the VMEM the kernels leave, and 32 MiB at a head of 64 put 11 to
# 18 MB of them back into HBM. dq's resident bytes come on top.
_TILE_VMEM_BYTES = 16 * 2 ** 20
# Most that dq's float32 accumulator and its double-buffered output block
# may hold; a longer query range goes through in chunks.
_DQ_RESIDENT_BYTES = 32 * 2 ** 20


def _dq_resident_bytes(rows, d, dtype, seq_minor=False):
    """Of dq's float32 accumulator and its double-buffered output block
    over ``rows`` queries. A width along lanes is padded to whole lane
    tiles; the output block of a sequence-minor call has it along
    sublanes, and is not."""
    padded = _lane_tiles(d) * _LANE
    return rows * (4 * padded + 2 * jnp.dtype(dtype).itemsize * (
        d if seq_minor else padded))


@jax.named_scope(SCOPE)
def _bwd_call(q, k, v, o, do, lse, lens, sm_scale, causal, block_q, block_k,
              g_lse=None, dm=None, dropout_rate=0.0, seeded=False,
              window=None, where=None, mask=None, seq_minor=False):
    """dq, dk and dv from one kernel: one pass over the (key, query)
    tiles computes s, exp, dp and ds once and feeds all three gradients.
    Grid (batch*heads, steps), a step a tile (_step_table: the tiles
    that do something wherever ``where`` is given, every tile where the
    offsets are traced), a key block's query blocks one after the other:
    dk and dv accumulate in per-key-block scratch, dq in a float32 VMEM
    accumulator over the whole query range of the (batch, head), its
    output block resident until the (batch, head) is done. A chunk's
    table is made for its own query blocks. Where ``k`` and
    ``v`` hold fewer heads than ``q``, the kernel writes every query
    head's share of dk and dv in float32 and the group's are summed
    here. ``mask`` as in the forward (``_mask_tile``): a chunk takes
    its own queries' columns of it. ``seq_minor`` as in ``_fwd_call``:
    the three gradients are laid out as the operands are; ``o`` and
    ``do`` are ``[batch, heads, width, seq]``."""
    seq, width = _laid(1, 2, seq_minor)      # the operands' axes
    bh, sq, d = q.shape[0], q.shape[seq], q.shape[width]
    group = bh // k.shape[0]
    if seq_minor:
        o, do = (x.reshape(bh, *x.shape[2:]) for x in (o, do))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=width)                     # (bh, sq)
    if g_lse is not None:
        # dlse_i/ds_ij = p_ij, so the lse cotangent enters the shared
        # ds = p*(dp - delta')*scale term as delta' = delta - g_lse.
        delta = delta - g_lse.astype(jnp.float32)
    # 3-D (bh, 1, sq) layout for TPU block-shape rules (see _fwd_kernel).
    lse3 = lse[:, None, :]
    delta3 = delta[:, None, :]
    # Where the accumulator would not fit, the query range goes through
    # the same kernel in chunks and dk, dv are summed in float32.
    n_q = sq // block_q
    per_chunk = max(1, _DQ_RESIDENT_BYTES
                    // _dq_resident_bytes(block_q, d, q.dtype, seq_minor))
    # What the trace reads of the module's state is an argument, as in
    # _fwd_call.
    def chunk(qb0, n_q, *rows, **kv_dtype):
        if mask is not None:
            kv_dtype = dict(kv_dtype, mask=mask[
                :, :, qb0 * block_q:(qb0 + n_q) * block_q])
        return _bwd_chunk(
            *rows, k=k, v=v, lens=lens, qb0=qb0, steps=_step_table(
                True, lens if where is None else where, n_q,
                k.shape[seq] // block_k, block_q, block_k, causal, window,
                qb0),
            sm_scale=sm_scale, causal=causal, block_q=block_q,
            block_k=block_k,
            sub=_sub_tile(causal, block_q, block_k, d, backward=True),
            dropout_rate=dropout_rate, seeded=seeded,
            interpret=_interpret(), window=window, seq_minor=seq_minor,
            **kv_dtype)

    if n_q <= per_chunk and group == 1:
        return chunk(0, n_q, q, do, lse3, delta3, dm)
    dqs, dk, dv = [], 0.0, 0.0
    for qb0 in range(0, n_q, per_chunk):
        rows = slice(qb0 * block_q, (qb0 + per_chunk) * block_q)
        tile = (slice(None), slice(None), rows) if seq_minor else (
            slice(None), rows)
        dq_c, dk_c, dv_c = chunk(
            qb0, min(per_chunk, n_q - qb0),
            q[tile], do[tile], lse3[:, :, rows], delta3[:, :, rows],
            None if dm is None else dm[:, rows], kv_dtype=jnp.float32)
        dqs.append(dq_c)
        dk, dv = dk + dk_c, dv + dv_c
    if group > 1:
        dk, dv = (x.reshape(-1, group, *x.shape[1:]).sum(axis=1)
                  for x in (dk, dv))
    return (jnp.concatenate(dqs, axis=seq), dk.astype(k.dtype),
            dv.astype(v.dtype))


@functools.partial(jax.jit, static_argnames=(
    "qb0", "sm_scale", "causal", "block_q", "block_k", "sub",
    "dropout_rate", "seeded", "interpret", "kv_dtype", "window",
    "seq_minor"))
def _bwd_chunk(q, do, lse3, delta3, dm, *, k, v, lens, steps, qb0,
               sm_scale, causal, block_q, block_k, sub, dropout_rate, seeded,
               interpret, kv_dtype=None, window=None, mask=None,
               seq_minor=False):
    """The backward kernel over the query rows it is given: tiles ``qb0``
    onward of the sequence, in the order ``steps`` gives (_step_table,
    of these rows' query blocks). dk and dv are this chunk's share, a row to
    each of ``q``'s, in
    ``kv_dtype`` (k's and v's own unless the caller sums shares). The
    layers of a model make the same call, so it goes through ``jax.jit``
    like the forward's: traced and lowered once a program."""
    bh, (sq, d), (sk, dv) = q.shape[0], *(
        _laid(*x.shape[1:], seq_minor) for x in (q, v))
    group = bh // k.shape[0]
    one_tile = sq == block_q and sk == block_k
    qi, kb = _named(_FETCH, one_tile), _named(_ROW, one_tile)

    def q_tile(width):
        return _tile_spec(block_q, width, lambda b: b, qi, seq_minor)

    def k_tile(width, row=lambda b: b):
        return _tile_spec(block_k, width, row, kb, seq_minor)

    def kv_row(b):
        return _kv_row(b, group)

    q_row = pl.BlockSpec((1, 1, block_q),
                         lambda b, s, lens, steps: (b, 0, qi(s, steps)))
    in_specs = [q_tile(d), k_tile(d, kv_row), k_tile(dv, kv_row),
                q_tile(dv), q_row, q_row]
    operands = [q, k, v, do, lse3, delta3]
    if dm is not None:
        in_specs.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda b, s, lens, steps: (b, qi(s, steps), kb(s, steps))))
        operands.append(dm)
    if mask is not None:
        in_specs.append(_mask_spec(mask, bh, block_q, block_k, qi, kb))
        operands.append(mask)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, steps.shape[0] // 4),
        in_specs=in_specs,
        out_specs=[
            _tile_spec(sq, d, lambda b: b, lambda s, steps: 0, seq_minor),
            k_tile(d), k_tile(dv),
        ],
        scratch_shapes=[
            pltpu.VMEM((sq, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ] + ([pltpu.VMEM(shape, q.dtype) for shape in (
            (block_q, d), (block_k, d), (block_k, dv), (block_q, dv))]
             if seq_minor else []),
    )
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
            block_k=block_k, qb0=qb0, sub=sub, one_tile=one_tile,
            dropout_rate=dropout_rate, seeded=seeded, window=window,
            has_mask=mask is not None, seq_minor=seq_minor),
        grid_spec=grid_spec,
        out_shape=[
            _struct((bh, *_laid(*shape, seq_minor)), dtype, q, k, v, do, lens)
            for shape, dtype in (((sq, d), q.dtype),
                                 ((sk, d), kv_dtype or k.dtype),
                                 ((sk, dv), kv_dtype or v.dtype))
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # A value wider than the key, and a group's shares of dk
            # and dv written in float32, make the tiles' blocks as large
            # as a head of twice the width does.
            vmem_limit_bytes=_TILE_VMEM_BYTES * _lane_tiles(d) * (
                2 if dv > d or group > 1 else 1)
            + _dq_resident_bytes(sq, d, q.dtype, seq_minor)
            + (_mask_vmem_bytes(block_q, block_k) if mask is not None
               else 0)),
        interpret=interpret,
        name=KERNEL_BWD_DKDV,
    )(lens, steps, *operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Differentiable public entry points
# ---------------------------------------------------------------------------

# What the forward kernel hands the backward one, as ``jax.checkpoint``
# policies may name it (``save_only_these_names(*SAVED_NAMES)``): with
# both kept, a recomputed forward pass does not run the kernel again.
SAVED_NAMES = ("hvd_flash_o", "hvd_flash_lse")


# ``where`` is the call's (q_offset, k_offset, kv_len) as Python integers,
# or None where any of them is traced: what the grids' steps are made from
# (_step_table). ``seq_minor``: how q, k, v, the output and the gradients
# are laid out (_fwd_call): 0, or the batch size.

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, lens, sm_scale, causal, block_q, block_k, window=None,
           where=None, seq_minor=False):
    o, _ = _fwd_call(q, k, v, lens, sm_scale, causal, block_q, block_k,
                     window=window, where=where, seq_minor=seq_minor)
    return o


def _flash_fwd(q, k, v, lens, sm_scale, causal, block_q, block_k, window,
               where, seq_minor):
    o, lse = map(ad_checkpoint.checkpoint_name, _fwd_call(
        q, k, v, lens, sm_scale, causal, block_q, block_k, window=window,
        where=where, seq_minor=seq_minor), SAVED_NAMES)
    return o, (q, k, v, o, lse, lens)


def _flash_bwd(sm_scale, causal, block_q, block_k, window, where, seq_minor,
               res, g):
    q, k, v, o, lse, lens = res
    dq, dk, dv = _bwd_call(q, k, v, o, g, lse, lens, sm_scale, causal,
                           block_q, block_k, window=window, where=where,
                           seq_minor=seq_minor)
    dlens = np.zeros((3,), jax.dtypes.float0)
    return dq, dk, dv, dlens


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_with_lse(q, k, v, lens, sm_scale, causal, block_q, block_k,
                    where=None, seq_minor=False):
    return _fwd_call(q, k, v, lens, sm_scale, causal, block_q, block_k,
                     where=where, seq_minor=seq_minor)


def _flash_with_lse_fwd(q, k, v, lens, sm_scale, causal, block_q, block_k,
                        where, seq_minor):
    o, lse = _fwd_call(q, k, v, lens, sm_scale, causal, block_q, block_k,
                       where=where, seq_minor=seq_minor)
    return (o, lse), (q, k, v, o, lse, lens)


def _flash_with_lse_bwd(sm_scale, causal, block_q, block_k, where, seq_minor,
                        res, g):
    q, k, v, o, lse, lens = res
    go, g_lse = g
    dq, dk, dv = _bwd_call(q, k, v, o, go, lse, lens, sm_scale, causal,
                           block_q, block_k, g_lse=g_lse, where=where,
                           seq_minor=seq_minor)
    dlens = np.zeros((3,), jax.dtypes.float0)
    return dq, dk, dv, dlens


_flash_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_masked(q, k, v, lens, mask, sm_scale, causal, block_q, block_k,
                  where=None, seq_minor=False):
    """``(o, lse)`` under a mask of data (``_mask_tile``)."""
    return _fwd_call(q, k, v, lens, sm_scale, causal, block_q, block_k,
                     where=where, mask=mask, seq_minor=seq_minor)


def _flash_masked_fwd(q, k, v, lens, mask, sm_scale, causal, block_q,
                      block_k, where, seq_minor):
    o, lse = map(ad_checkpoint.checkpoint_name, _fwd_call(
        q, k, v, lens, sm_scale, causal, block_q, block_k, where=where,
        mask=mask, seq_minor=seq_minor), SAVED_NAMES)
    return (o, lse), (q, k, v, o, lse, lens, mask)


def _flash_masked_bwd(sm_scale, causal, block_q, block_k, where, seq_minor,
                      res, g):
    q, k, v, o, lse, lens, mask = res
    go, g_lse = g
    dq, dk, dv = _bwd_call(q, k, v, o, go, lse, lens, sm_scale, causal,
                           block_q, block_k, g_lse=g_lse, where=where,
                           mask=mask, seq_minor=seq_minor)
    return (dq, dk, dv, np.zeros((3,), jax.dtypes.float0),
            np.zeros(mask.shape, jax.dtypes.float0))


_flash_masked.defvjp(_flash_masked_fwd, _flash_masked_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_dropout(q, k, v, lens, dm, sm_scale, causal, block_q, block_k,
                   rate, where=None):
    o, _ = _fwd_call(q, k, v, lens, sm_scale, causal, block_q, block_k,
                     dm=dm, dropout_rate=rate, where=where)
    return o


def _flash_dropout_fwd(q, k, v, lens, dm, sm_scale, causal, block_q,
                       block_k, rate, where):
    o, lse = _fwd_call(q, k, v, lens, sm_scale, causal, block_q, block_k,
                       dm=dm, dropout_rate=rate, where=where)
    return o, (q, k, v, o, lse, lens, dm)


def _flash_dropout_bwd(sm_scale, causal, block_q, block_k, rate, where, res,
                       g):
    q, k, v, o, lse, lens, dm = res
    dq, dk, dv = _bwd_call(q, k, v, o, g, lse, lens, sm_scale, causal,
                           block_q, block_k, dm=dm, dropout_rate=rate,
                           where=where)
    dlens = np.zeros((3,), jax.dtypes.float0)
    ddm = np.zeros(dm.shape, jax.dtypes.float0)
    return dq, dk, dv, dlens, ddm


_flash_dropout.defvjp(_flash_dropout_fwd, _flash_dropout_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_seeded(q, k, v, lens, sm_scale, causal, block_q, block_k,
                  rate, where=None):
    o, _ = _fwd_call(q, k, v, lens, sm_scale, causal, block_q, block_k,
                     dropout_rate=rate, seeded=True, where=where)
    return o


def _flash_seeded_fwd(q, k, v, lens, sm_scale, causal, block_q, block_k,
                      rate, where):
    o, lse = _fwd_call(q, k, v, lens, sm_scale, causal, block_q, block_k,
                       dropout_rate=rate, seeded=True, where=where)
    return o, (q, k, v, o, lse, lens)


def _flash_seeded_bwd(sm_scale, causal, block_q, block_k, rate, where, res,
                      g):
    q, k, v, o, lse, lens = res
    dq, dk, dv = _bwd_call(q, k, v, o, g, lse, lens, sm_scale, causal,
                           block_q, block_k, dropout_rate=rate,
                           seeded=True, where=where)
    dlens = np.zeros((4,), jax.dtypes.float0)
    return dq, dk, dv, dlens


_flash_seeded.defvjp(_flash_seeded_fwd, _flash_seeded_bwd)


def _clamp_blocks(sq, sk, block_q, block_k):
    """Clamp requested blocks to the (pow2-rounded) sequence lengths. The
    caller may ask for >128 blocks: a block is what one grid step's DMA
    brings, and a step's fixed cost and the rescaling of the running
    statistics are paid once a block, so the largest that VMEM holds is
    fastest (1024: PERF.md section 7). What the mask leaves of a block
    is resolved finer, in the kernels' sub-tiles (_sub_tile)."""
    return (min(block_q, max(8, 1 << (sq - 1).bit_length())),
            min(block_k, max(8, 1 << (sk - 1).bit_length())))


def _effective_window(window, sq, q_offset, k_offset):
    """``window``, or None where it is at least the distance from the
    call's last query to its first key and so hides nothing (offsets
    known at trace time)."""
    if window is not None and _static(q_offset, k_offset) is not None \
            and window >= q_offset + sq - k_offset:
        return None
    return window


def subtile_counts(kernel, sq, sk, block_q, block_k, causal, q_offset=0,
                   k_offset=0, kv_len=None, head_dim=64, window=None):
    """How ``kernel`` (``"fwd"`` or ``"bwd"``) visits one (batch, head)'s
    score matrix: sub-tiles ``interior`` (no mask built), ``masked`` and
    ``skipped`` (no product, no exponential), and ``steps_without_fetch``,
    of the steps the grid runs (``grid_steps``: the tiles that do
    something, not the rectangle of blocks) those whose blocks are the
    ones already held (K and V in the forward; q and do in the backward,
    its query range taken as one chunk). Under a ``window`` that hides
    something, ``skipped`` are the
    sub-tiles the causal mask and the padding hide and ``window`` those
    that only the window does. A function of shapes and offsets alone,
    by the functions the kernels themselves class tiles and name blocks
    with. A tile that is not walked in sub-tiles counts as one
    sub-tile a side of the kernel's (``head_dim`` is the keys')."""
    backward = kernel == "bwd"
    window = _effective_window(window, sq, q_offset, k_offset)
    block_q, block_k = _clamp_blocks(sq, sk, block_q, block_k)
    n_q, n_k = -(-sq // block_q), -(-sk // block_k)
    kv_len = sk if kv_len is None else kv_len
    qb, kb = np.meshgrid(np.arange(n_q), np.arange(n_k), indexing="ij")
    tile = (causal, q_offset, k_offset, kv_len, qb, kb, block_q, block_k,
            window)
    skip = _block_skip(*tile) & np.ones_like(qb, bool)
    interior = _tile_interior(*tile) & ~skip
    plain = ~skip & ~interior       # through the mask whole, unless walked
    counts = dict.fromkeys(("interior", "masked", "skipped"), 0)
    # Sub-tiles a block: all, and per walked block those its rows see
    # and see whole.
    per_block = 1
    sub = _sub_tile(causal, block_q, block_k, head_dim, backward)
    if sub is not None:
        n = block_q // sub
        per_block = n * n
        for offset in _aligned_offsets(window, block_q):
            spans = [_strip_span(i, n, sub, offset * block_q, window)
                     for i in range(n)]
            seen = sum(s[1] for s in spans if s) // sub
            through = sum(s[2] + s[3] for s in spans if s) // sub
            walked = plain & _aligned(q_offset, k_offset, kv_len, qb, kb,
                                      block_q, offset)
            n_walked = int(walked.sum())
            counts["interior"] += n_walked * (seen - through)
            counts["masked"] += n_walked * through
            counts["skipped"] += n_walked * (per_block - seen)
            plain = plain & ~walked
    counts["interior"] += int(interior.sum()) * per_block
    counts["masked"] += int(plain.sum()) * per_block
    counts["skipped"] += int(skip.sum()) * per_block
    if window is not None:
        hidden = subtile_counts(kernel, sq, sk, block_q, block_k, causal,
                                q_offset, k_offset, kv_len, head_dim)
        counts["window"] = counts["skipped"] - hidden["skipped"]
        counts["skipped"] = hidden["skipped"]
    held = _step_table(backward, (q_offset, k_offset, kv_len), n_q, n_k,
                       block_q, block_k, causal, window).reshape(4, -1)[_FETCH]
    counts["steps_without_fetch"] = int((held[1:] == held[:-1]).sum())
    return counts


fwd_subtile_counts = functools.partial(subtile_counts, "fwd")
bwd_subtile_counts = functools.partial(subtile_counts, "bwd")


def _static(*where):
    """``where`` as Python integers, or None where any is traced."""
    if all(isinstance(x, (int, np.integer)) for x in where):
        return tuple(int(x) for x in where)
    return None


def grid_steps(kernel, sq, sk, block_q, block_k, causal, q_offset=0,
               k_offset=0, kv_len=None, window=None):
    """The steps of one (batch, head) of ``kernel``'s grid (``"fwd"`` or
    ``"bwd"``, its query range taken as one chunk): ``run``, the steps
    the grid has, and ``live``, those of them whose tile does something
    (``_block_skip`` false). A function of shapes and offsets alone, by
    the table the kernel is handed (_step_table). ``run == live``: the
    grid has no idle step (``run`` is larger by the blocks of an output
    that see nothing and are written as zeros). With a traced offset or
    ``kv_len`` the grid runs every tile, ``run = n_q * n_k``, and which
    are live is not known here: no ``live``."""
    window = _effective_window(window, sq, q_offset, k_offset)
    block_q, block_k = _clamp_blocks(sq, sk, block_q, block_k)
    n_q, n_k = -(-sq // block_q), -(-sk // block_k)
    where = _static(q_offset, k_offset, sk if kv_len is None else kv_len)
    if where is None:
        return {"run": n_q * n_k}
    backward = kernel == "bwd"
    steps = _step_table(backward, where, n_q, n_k, block_q, block_k, causal,
                        window).reshape(4, -1)
    kb, qb = steps[_ROW if backward else _INNER], steps[
        _INNER if backward else _ROW]
    live = ~_block_skip(causal, *where, qb, kb, block_q, block_k, window)
    return {"run": steps.shape[1], "live": int(live.sum())}


def _publish_subtiles(sq, sk, block_q, block_k, causal, q_offset, k_offset,
                    kv_len, head_dim, window):
    """Set ``hvd_flash_grid_steps{kernel,kind}`` from ``grid_steps`` of
    the call being traced and, where its offsets and ``kv_len`` are
    known while tracing, ``hvd_flash_fwd_subtiles{kind}`` and
    ``hvd_flash_bwd_subtiles{kind}`` from ``subtile_counts`` (kind
    ``window`` too where the call has a window that hides something);
    docs/metrics.md. A no-op when ``HOROVOD_TPU_METRICS`` is off."""
    from ..telemetry import core as telemetry
    if not telemetry.enabled():
        return
    call = (sq, sk, block_q, block_k, causal, q_offset, k_offset, kv_len)
    steps = telemetry.gauge(
        "hvd_flash_grid_steps",
        "Grid steps of one (batch, head) of the flash kernels of the call "
        "last traced: run, and of those the live ones, whose tile does "
        "something (not set for traced offsets, whose grid runs every "
        "tile)", ("kernel", "kind"))
    for kernel, name, which, held in (
            ("fwd", "hvd_flash_fwd_subtiles", "forward", "K/V"),
            ("bwd", "hvd_flash_bwd_subtiles", "backward", "q/do")):
        for kind, n in grid_steps(kernel, *call, window).items():
            steps.labels(kernel=kernel, kind=kind).set(float(n))
        if _static(q_offset, k_offset, kv_len) is None:
            continue
        gauge = telemetry.gauge(
            name,
            f"Sub-tiles of one (batch, head) the flash {which} kernel of "
            f"the call last traced visits, by kind, and its grid steps "
            f"that fetch no {held}", ("kind",))
        for kind, n in subtile_counts(kernel, *call, head_dim,
                                      window).items():
            gauge.labels(kind=kind).set(float(n))


def _publish_layout(kind):
    """Count the call being traced in ``hvd_flash_layout{kind}``, by how
    its kernels address a head (``addressing``); docs/metrics.md. A
    no-op when ``HOROVOD_TPU_METRICS`` is off."""
    from ..telemetry import core as telemetry
    if not telemetry.enabled():
        return
    telemetry.counter(
        "hvd_flash_layout",
        "Flash calls traced, by how their kernels address a head: "
        "head_major reads [batch x heads, seq, width] and seq_minor "
        "[batch x heads, width, seq], which is how XLA holds a width "
        "under a lane tile; a head_major call at such a width has XLA "
        "copy q, k, v, the output and their gradients into padded "
        "arrays and back", ("kind",)).labels(kind=kind).inc()


def _prepare(q, k, v, block_q, block_k):
    """Reshape (B,H,S,D)→(BH,S,D), pad D to a lane tile (64 when D<=64,
    else 128) and S to block multiples; ``k`` and ``v`` by their own
    heads and ``v`` by its own width. Returns padded tensors +
    original dims."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q, block_k = _clamp_blocks(sq, sk, block_q, block_k)

    def flat(x, block):
        # Head dims <=64 stay at 64 lanes: Mosaic supports 64-wide last
        # dims, and padding d=64 heads to 128 would double both the
        # matmul work and the HBM traffic of every block (round 3's
        # sweep on a v5e, by bench.py's clock: 5.75 -> 5.15 ms a
        # layer at seq 512, batch 24; neutral at seq 2048).
        x = x.reshape((-1,) + x.shape[2:])
        return _pad_to(_pad_to(x, 64 if x.shape[2] <= 64 else _LANE, 2),
                       block, 1)

    return (flat(q, block_q), flat(k, block_k), flat(v, block_k),
            (b, h, sq, sk, d), block_q, block_k)


def _varying(*xs):
    """True when any input is device-varying under shard_map (vma)."""
    return any(jax.typeof(x).vma for x in xs)


@jax.named_scope(SCOPE)
def flash_attention(q, k, v, *, causal=False, sm_scale=None,
                    q_offset=0, k_offset=0, kv_len=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    with_lse=False, dropout_mask=None, dropout_rate=0.0,
                    dropout_seed=None, window=None, mask=None, layout="bhsd"):
    """Flash attention over (batch, heads, seq, head_dim) tensors, or,
    with ``layout="bshd"``, (batch, seq, heads, head_dim) ones.

    ``layout`` says how ``q``, ``k``, ``v`` are laid out, and the output
    and the three gradients with them; the log-sum-exp is (batch, heads,
    seq) either way. It describes data and changes no result. What each
    costs a caller (``addressing``): the kernels of a ``"bhsd"`` call
    read a head as ``[seq, width]``, which is that layout as it stands.
    A caller that holds what a projection writes, ``[batch, seq, heads,
    width]``, and makes ``"bhsd"`` of it pays nothing where the width
    fills whole lane tiles (128, 256): XLA has the projections write
    ``[batch, heads, seq, width]`` to begin with. At a width under a
    lane tile it pays eight copies a layer: XLA holds such arrays
    ``[batch, heads, width, seq]``, where no tile is half padding, and
    copies q, k and v in, the output out, and the four back again in
    the gradient, each array read and written once more and every
    kernel-side array padded to twice its bytes (8 x 24 layers x 33.5
    MB at ``lm365m-seq8192``: 23.6 ms of a 541 ms step). A ``"bshd"``
    call at such a width has the kernels read and write ``[batch x
    heads, width, seq]``, which is XLA's own arrangement, so nothing is
    copied and nothing padded; at any other width, and with dropout,
    it is the ``"bhsd"`` call of the transposed operands.

    ``k`` and ``v`` may hold fewer heads than ``q``, a whole number of
    query heads to each (grouped K/V heads: query head ``h`` reads head
    ``h // group``; neither is repeated in HBM, the kernels' index maps
    name the shared block, and dk, dv are summed over a group in
    float32). ``v`` may be of another width than ``q`` and ``k``, and
    the output is of ``v``'s.

    Args:
      mask: (batch, keys, queries), integers (int8 is what the kernels
        fetch): a query sees a key only where its entry is not 0,
        besides what ``causal`` and ``kv_len`` hide; one mask for all
        the heads of a batch element, key-major as the kernels' tiles
        are. Every query has to keep a key. The grids are those of the
        call without it (a table made from shapes cannot know which
        tiles a mask of data empties), each tile fetching its block of
        the mask beside K and V. Goes with grouped K/V heads and
        ``with_lse``; not with dropout or a window. Without it the call
        traces the program it always did.
      window: with ``causal``, a query at position ``t`` sees the keys at
        positions ``t - window < s <= t`` only (a static integer). The
        kernels run no sub-tile that the window hides and their grids
        no step for a block of it, as for the causal mask (a grid step
        is a (query block, key block) tile that does something, wherever
        the offsets and ``kv_len`` are Python integers: _step_table,
        ``grid_steps``); a window that reaches the call's
        first key from its last query is no window, and traces the
        program that ``None`` traces. Not with dropout or ``with_lse``.
      causal: apply a causal mask in *global* coordinates:
        position(q) = q_offset + row, position(k) = k_offset + col. Offsets
        may be traced scalars (device-varying under shard_map) — this is what
        lets one compiled kernel serve every ring-attention step; its
        grids then run every tile, and skip inside the body.
      kv_len: number of valid keys in ``k`` (defaults to its length);
        keys at or beyond this index are masked (padding).
      with_lse: also return the per-query log-sum-exp (fp32, (B,H,Sq)).
      dropout_mask: optional (B, H, Sq, Sk) keep-mask applied to the
        softmax probabilities (torch attention-dropout semantics: probs
        are dropped after normalization and the kept ones rescaled by
        1/(1-dropout_rate)). Passing the mask explicitly — rather than a
        PRNG seed — keeps the kernel exactly reproducible against the
        einsum oracle; the torch/TF bridges generate it with
        jax.random.bernoulli per attention site.
      dropout_rate: the rate the mask was drawn with (for rescaling).
      dropout_seed: TPU-only alternative to dropout_mask — an int32
        scalar (may be traced) seeding the ON-CHIP prng; the keep
        pattern is regenerated per tile inside the forward and the
        backward kernel, so no mask is ever materialized in HBM (no
        bernoulli program, no O(S²) residual). Unsupported in interpret
        mode (pltpu prng has no CPU lowering) — callers on CPU use
        dropout_mask instead.
    """
    if layout not in ("bhsd", "bshd"):
        raise ValueError(f"flash_attention: layout {layout!r} is neither "
                         f"'bhsd' nor 'bshd'")
    held = None
    if layout == "bshd":
        # From here on q, k, v are the head-major views, whose shapes
        # the checks below read; a call that is read sequence-minor
        # (``addressing``) runs on the arrays as they are held.
        held = (q, k, v)
        q, k, v = (x.swapaxes(1, 2) for x in held)
    orig_dtype = q.dtype
    b, h, sq, d = q.shape
    dv = v.shape[3]
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    if kv_len is None:
        kv_len = k.shape[2]
    if window is not None and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    window = _effective_window(window, sq, q_offset, k_offset)
    if h % k.shape[1] or k.shape[:3] != v.shape[:3]:
        raise ValueError(
            f"flash_attention: {h} query heads over K/V of shapes "
            f"{k.shape}, {v.shape}")
    if dropout_seed is not None and dropout_mask is not None:
        raise ValueError(
            "flash_attention: pass dropout_mask OR dropout_seed, not both")
    has_dropout = (dropout_mask is not None or dropout_seed is not None) \
        and dropout_rate > 0.0
    if has_dropout and with_lse:
        raise NotImplementedError(
            "flash_attention: dropout with with_lse is unsupported "
            "(ring/merged attention never uses attention dropout)")
    if mask is not None and (has_dropout or window is not None
                             or mask.shape != (b, k.shape[2], sq)):
        raise NotImplementedError(
            f"flash_attention: a mask is (batch, keys, queries), here "
            f"{(b, k.shape[2], sq)}, not {mask.shape}, and goes with "
            f"neither dropout nor a window")
    if (has_dropout or (with_lse and mask is None)) and (
            window is not None or k.shape[1] != h or dv != d):
        raise NotImplementedError(
            "flash_attention: a window, grouped K/V heads and a value "
            "of another width go with neither dropout nor with_lse")
    if dropout_seed is not None and dropout_rate > 0.0 and _interpret():
        raise NotImplementedError(
            "flash_attention: dropout_seed needs the on-chip prng "
            "(pltpu) — unavailable in interpret mode; pass an explicit "
            "dropout_mask on CPU")
    def as_held(out):
        """A head-major output, as the caller's operands are laid out."""
        if held is None:
            return out
        if with_lse:
            return out[0].swapaxes(1, 2), out[1]
        return out.swapaxes(1, 2)

    if _interpret() and _varying(q, k, v, q_offset, k_offset):
        # Pallas's HLO interpreter cannot run with device-varying operands
        # inside shard_map (check_vma dynamic_slice limitation); on non-TPU
        # backends use the einsum oracle there. On TPU the compiled kernel
        # handles shard_map natively.
        return as_held(reference_attention(
            q, k, v, causal=causal, sm_scale=sm_scale, q_offset=q_offset,
            k_offset=k_offset, kv_len=kv_len, with_lse=with_lse,
            dropout_mask=dropout_mask, dropout_rate=dropout_rate,
            window=window, mask=mask))
    kind = addressing(d, dv, layout, has_dropout)
    _publish_layout(kind)
    # Known while tracing (every call of a model): the grids run the
    # tiles that do something and no other (_step_table).
    where = _static(q_offset, k_offset, kv_len)
    lens = jnp.asarray([q_offset, k_offset, kv_len], jnp.int32)
    if kind == SEQ_MINOR:
        # [batch, seq, heads, width] as [batch x heads, width, seq]: the
        # order XLA holds such arrays in, so the transposes move
        # nothing. The positions are padded to whole blocks.
        bq, bk = _clamp_blocks(sq, k.shape[2], block_q, block_k)
        qp, kp, vp = (
            _pad_to(x.transpose(0, 2, 3, 1).reshape(-1, x.shape[3],
                                                    x.shape[1]), block, 2)
            for x, block in zip(held, (bq, bk, bk)))
        _publish_subtiles(sq, k.shape[2], bq, bk, bool(causal), q_offset,
                          k_offset, kv_len, d, window)
        call = (float(sm_scale), bool(causal), bq, bk)
        if mask is not None:
            maskp = _pad_to(_pad_to(mask.astype(jnp.int8), bk, 1), bq, 2)
            o, lse = _flash_masked(qp, kp, vp, lens, maskp, *call, where, b)
        elif with_lse:
            o, lse = _flash_with_lse(qp, kp, vp, lens, *call, where, b)
        else:
            o = _flash(qp, kp, vp, lens, *call, window, where, b)
        o = o[..., :sq].transpose(0, 3, 1, 2).astype(orig_dtype)
        return (o, lse[:, :sq].reshape(b, h, sq)) if with_lse else o
    qp, kp, vp, dims, bq, bk = _prepare(q, k, v, block_q, block_k)
    _publish_subtiles(sq, k.shape[2], bq, bk, bool(causal), q_offset,
                    k_offset, kv_len, qp.shape[2], window)
    if mask is not None:
        maskp = _pad_to(_pad_to(mask.astype(jnp.int8), bk, 1), bq, 2)
        o, lse = _flash_masked(qp, kp, vp, lens, maskp, float(sm_scale),
                               bool(causal), bq, bk, where)
        o = o[:, :sq, :dv].reshape(b, h, sq, dv).astype(orig_dtype)
        return as_held((o, lse[:, :sq].reshape(b, h, sq)) if with_lse else o)
    if has_dropout and dropout_seed is not None:
        lens4 = jnp.concatenate(
            [lens, jnp.asarray(dropout_seed, jnp.int32).reshape(1)])
        o = _flash_seeded(qp, kp, vp, lens4, float(sm_scale),
                          bool(causal), bq, bk, float(dropout_rate), where)
        return as_held(o[:, :sq, :d].reshape(b, h, sq, d).astype(orig_dtype))
    if has_dropout:
        # bf16 carries 0/1 exactly at half the HBM traffic of fp32.
        dm = dropout_mask.astype(jnp.bfloat16).reshape(b * h, sq, -1)
        dm = _pad_to(_pad_to(dm, bk, 2), bq, 1)
        o = _flash_dropout(qp, kp, vp, lens, dm, float(sm_scale),
                           bool(causal), bq, bk, float(dropout_rate), where)
        return as_held(o[:, :sq, :d].reshape(b, h, sq, d).astype(orig_dtype))
    if with_lse:
        o, lse = _flash_with_lse(qp, kp, vp, lens, float(sm_scale),
                                 bool(causal), bq, bk, where)
        o = o[:, :sq, :d].reshape(b, h, sq, d).astype(orig_dtype)
        lse = lse[:, :sq].reshape(b, h, sq)
        return as_held((o, lse))
    o = _flash(qp, kp, vp, lens, float(sm_scale), bool(causal), bq, bk,
               window, where)
    return as_held(o[:, :sq, :dv].reshape(b, h, sq, dv).astype(orig_dtype))


def reference_attention(q, k, v, *, causal=False, sm_scale=None,
                        q_offset=0, k_offset=0, kv_len=None,
                        with_lse=False, dropout_mask=None,
                        dropout_rate=0.0, window=None, mask=None):
    """Plain einsum attention with the same masking semantics — the
    correctness oracle for the kernel tests and the shard_map-on-CPU
    fallback. Offsets may be traced scalars. Grouped K/V heads are
    repeated, the window is a mask, and ``mask`` (batch, keys, queries)
    one more."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if k.shape[1] != h:
        k, v = (jnp.repeat(x, h // x.shape[1], axis=1) for x in (k, v))
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    if kv_len is None:
        kv_len = sk
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    cols = jnp.arange(sk)
    keep, mask = mask, (cols < kv_len)[None, None, None, :]
    if causal:
        rows = q_offset + jnp.arange(sq)
        cmask = rows[:, None] >= (k_offset + cols)[None, :]
        if window is not None:
            cmask = jnp.logical_and(
                cmask, rows[:, None] - (k_offset + cols)[None, :] < window)
        mask = jnp.logical_and(mask, cmask[None, None])
    if keep is not None:
        mask = jnp.logical_and(mask, (keep != 0).swapaxes(1, 2)[:, None])
    mask = jnp.broadcast_to(mask, s.shape)
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    any_visible = jnp.any(mask, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    pv = p
    if dropout_mask is not None and dropout_rate > 0.0:
        # Post-softmax dropout: the normalizer l keeps the undropped sum.
        pv = p * (dropout_mask.astype(jnp.float32)
                  / (1.0 - dropout_rate))
    o = (jnp.einsum("bhqk,bhkd->bhqd", pv, v.astype(jnp.float32))
         / safe_l).astype(q.dtype)
    if not with_lse:
        return o
    lse = jnp.where(any_visible[..., 0], m[..., 0] + jnp.log(safe_l[..., 0]),
                    _NEG_INF)
    return o, lse
