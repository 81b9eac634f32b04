"""The recurrence of a Mamba-2 layer as chunked matrix products.

    S_t = exp(dt_t A_h) S_{t-1} + (dt_t u_t) (x) B_t       S: [width, N] a head
    y_t = S_t C_t

(Dao & Gu, "Transformers are SSMs", arXiv:2405.21060: the state-space
dual.) ``A_h`` is one negative scalar a head and ``dt`` positive, so the
decay is in (0, 1]. ``u`` is ``[batch, seq, heads, width]``, ``dt``
``[batch, seq, heads]``, ``A`` ``[heads]``, ``B`` and ``C`` ``[batch,
seq, groups, N]``; head ``h`` reads group ``h // (heads / groups)``. The
``D u`` skip, the gate and the grouped norm are the caller's
(``models/ssm.py: Mamba2Mixer``): element-wise work that XLA fuses with
its neighbours.

Why not position by position. The state is ``heads x width x N`` numbers
a sequence (64 x 64 x 128 = 524,288 in the model this was written for,
6.4 times the Mamba-1 state of ``ops/selective_scan.py``), and walking it
costs one exponential and six multiply-adds a state number a position on
the VPU: tens of G element operations a layer. Because ``A`` is a scalar
a head the same recurrence is, exactly, a sum of matrix products over
chunks of ``L`` positions. With ``a_t = dt_t A_h``, ``Lam_t = sum_{s<=t}
a_s`` inside a chunk, ``v_s = dt_s u_s`` and ``S_in`` the state at the
chunk's start:

    y_t   = sum_{s<=t} exp(Lam_t - Lam_s) (C_t . B_s) v_s + exp(Lam_t) S_in C_t
    S_out = exp(Lam_L) S_in + sum_s exp(Lam_L - Lam_s) v_s (x) B_s

Every exponent is ``<= 0``. ``C B^T`` is one ``L x L x N`` product a
group, shared by its heads; the other three are ``L x L x width``, ``L x
N x width`` and ``L x N x width`` a head. ``Lam``, the decays and the
states are float32; the products take operands in ``u``'s dtype and
accumulate in float32.

Two forms of that algebra, one rule between them (``takes_kernels``:
the call's shapes and the backend, nothing else).

On a TPU, where a group's heads x width and ``N`` are whole lane tiles,
a head is whole 16-row tiles and a chunk is 128 positions, two Mosaic
kernels, ``hvd_ssd_fwd`` and
``hvd_ssd_bwd``, on a grid ``(batch x groups, chunks / 4)`` whose second
axis is walked in order, four chunks a grid step. A group's states live
in VMEM scratch as one ``[heads x width, N]`` float32 array (512 x 128
at 8 heads of 64), zeroed at the first chunk and written out at each
chunk's start; ``C B^T`` is one product a group, the ``L x L`` decay
matrices are made a head at a time in vector registers and never reach
HBM. ``u`` and ``y`` are read and written as they lie, ``[seq, heads x
width]`` in blocks of ``[L, 512]``, and turned inside the kernel so that
positions lie along the lanes: a head is then ``width`` whole sublane
rows, what a position scales (``dt``, ``exp(Lam)``, ``exp(Lam_L -
Lam)``) is a row ``[1, L]`` spread over them, and the products with the
state (``S C^T``, ``weighed B``) are one call for all the group's heads.
``dt`` arrives heads-major (``[each, L]``, one tile); ``Lam``, its
running sum times ``A`` inside the chunk, is made there as a product
with a triangle of ones (``dt A`` in three bfloat16 parts that sum to
it: float32 to the last bit, three plain passes of eight rows) and
turned once for the decay matrix's other side. Every rounding point is
the einsums'. The backward kernel walks the chunks last to
first with the state's adjoint in scratch, makes the chunk's scores and
decays again and writes ``du``, ``dB``, ``dC`` (summed over the group's
heads inside the step), ``ddt`` whole (what ``Lam`` feels, summed from
the chunk's end, is what ``dt A`` feels) and ``dA`` as sums XLA adds up.

Everywhere else (off the TPU, and at shapes the rule refuses) XLA
einsums over all the chunks at once and one ``lax.scan`` over the chunks
that carries the state across their boundaries (``_carry``): a
multiply-add of the state a chunk. The kernels run under Pallas's
interpreter only where a test forces them.

Backward. A ``custom_vjp`` keeps the operands and the chunk-start states
``[batch, chunks, heads, width, N]`` float32 (``state_bytes``) and
nothing else: the ``L x L`` matrices, several times the operands' size,
are made again on the way back (by the einsums: the two chunk-local
parts differentiated as they stand, ``jax.vjp``, and the carry's adjoint,
``g_c = dS_c + exp(Lam_L,c) g_{c+1}``, one more scan, last chunk first).
``SAVED_NAMES`` are what a ``jax.checkpoint`` policy may keep so that a
recomputed block does not run the forward pass again (``remat="flash"``).

Inside ``shard_map`` the kernels' out shapes carry the operands' varying
axes, as the flash kernels' do; off the TPU the einsums run there as
anywhere. ``reference_ssd`` is the recurrence itself, position by
position: the oracle of the tests.
"""

import functools

import jax
import jax.numpy as jnp
from jax import ad_checkpoint, lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.jax_compat import pvary
from . import flash_attention
from .flash_attention import _struct

# Positions a chunk holds. The L x L decay matrices (heads x L numbers a
# position, element-wise work) grow with it and the states kept for the
# way back (``state_bytes``) shrink; 128 fills the MXU's contraction.
CHUNK = 128

# Names in a device trace (docs/tracing.md): readers match the literals.
SCOPE = "hvd_ssd"               # the Mamba-2 mixer (models/ssm.py)
SCOPE_SCAN = "scan"             # inside it: the recurrence, all of it
KERNEL_FWD = "hvd_ssd_fwd"
KERNEL_BWD = "hvd_ssd_bwd"

# What the forward pass hands the backward one beside its operands, as
# ``jax.checkpoint`` policies may name it: with both kept, a recomputed
# forward pass does not make them again.
SAVED_NAMES = ("hvd_ssd_y", "hvd_ssd_states")


def _decays(dt, a):
    """``Lam`` ``[batch, chunks, groups, each, L]`` float32: the running
    sum of ``dt A`` inside each chunk, heads before positions (the
    layout of the ``L x L`` matrices made from it)."""
    return jnp.cumsum(jnp.moveaxis(dt * a, 2, -1), axis=-1)


def _sums(u, dt, a, b):
    """What each chunk adds to the state that enters it, and by what it
    scales that state: ``(sum_s exp(Lam_L - Lam_s) v_s (x) B_s``
    ``[batch, chunks, groups, each, width, N]``, ``Lam_L``)``."""
    lam = _decays(dt, a)
    total = lam[..., -1]
    left = jnp.moveaxis(jnp.exp(total[..., None] - lam), -1, 2) * dt
    weighed = (left[..., None] * u).astype(u.dtype)
    return jnp.einsum("bcsgrp,bcsgn->bcgrpn", weighed, b,
                      preferred_element_type=jnp.float32), total


def _outputs(u, dt, a, b, c, s_in):
    """``y`` ``[batch, chunks, L, groups, each, width]`` in ``u``'s
    dtype from the chunks' own positions and the states ``s_in`` at
    their starts."""
    lam = _decays(dt, a)
    size = lam.shape[-1]
    scores = jnp.einsum("bclgn,bcsgn->bcgls", c, b,
                        preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((size, size), bool))
    # The exponent selected before the exponential: past the diagonal it
    # is positive, and what overflows there would reach the gradient.
    decay = jnp.where(causal, jnp.exp(jnp.where(
        causal, lam[..., :, None] - lam[..., None, :], 0.0)), 0.0)
    pairs = (scores[:, :, :, None] * decay).astype(u.dtype)
    v = (dt[..., None] * u).astype(u.dtype)
    within = jnp.einsum("bcgrls,bcsgrp->bclgrp", pairs, v,
                        preferred_element_type=jnp.float32)
    before = jnp.einsum("bclgn,bcgrpn->bclgrp", c, s_in.astype(u.dtype),
                        preferred_element_type=jnp.float32)
    reach = jnp.moveaxis(jnp.exp(lam), -1, 2)[..., None]
    return (within + reach * before).astype(u.dtype)


def _carry(local, total):
    """The state at every chunk's start, ``[batch, chunks, ...]``
    float32, from what each chunk adds (``_sums``): ``S_{c+1} =
    exp(Lam_L,c) S_c + local_c``, ``S_0 = 0``. The state after the last
    chunk is not made: training reads none."""
    def step(s, at):
        add, scale = at
        return jnp.exp(scale)[..., None, None] * s + add, s

    # Zeros made of the operands: under shard_map the carry then varies
    # over the mesh as what is added to it does.
    s0 = 0.0 * local[:, 0]
    return jnp.moveaxis(lax.scan(
        step, s0, (jnp.moveaxis(local, 1, 0), jnp.moveaxis(total, 1, 0)))[1],
        0, 1)


def _carry_back(d_in, s_in, total):
    """``_carry``'s transpose from the states it made: the cotangents of
    ``local`` and ``total``. ``g_c = d_in_c + exp(total_c) g_{c+1}`` is
    what reaches the state at chunk ``c``'s start; ``local_c`` feels
    ``g_{c+1}`` and ``total_c`` feels ``exp(total_c) <g_{c+1}, S_c>``."""
    def step(g, at):
        d, s, scale = at
        decay = jnp.exp(scale)
        return (d + decay[..., None, None] * g,
                (g, decay * jnp.sum(g * s, axis=(-2, -1))))

    g0 = 0.0 * d_in[:, 0]
    d_local, d_total = lax.scan(
        step, g0, tuple(jnp.moveaxis(z, 1, 0) for z in (d_in, s_in, total)),
        reverse=True)[1]
    return jnp.moveaxis(d_local, 0, 1), jnp.moveaxis(d_total, 0, 1)


@jax.jit
def _fwd_call(u, dt, a, b, c):
    """``(y, states)`` of chunked operands. Through ``jax.jit``, like
    the kernels' calls: the layers of a model make the same call, and it
    is traced and lowered once a program."""
    s_in = _carry(*_sums(u, dt, a, b))
    return _outputs(u, dt, a, b, c, s_in), s_in


@jax.jit
def _bwd_call(u, dt, a, b, c, s_in, dy):
    _, pull = jax.vjp(_outputs, u, dt, a, b, c, s_in)
    *d_out, d_in = pull(dy)
    (_, total), pull = jax.vjp(_sums, u, dt, a, b)
    d_sums = pull(_carry_back(d_in, s_in, total))
    return (*(x + y for x, y in zip(d_out, d_sums)), d_out[4])


# ---- the same algebra as two Mosaic kernels ---------------------------------

_LANE = 128
# Chunks a grid step walks (an inner loop): the step's fixed cost is
# paid once for them. Measured on the chip (PERF.md section 6, PR 49).
_STEP_CHUNKS = 4
_VMEM_LIMIT = 64 * 2 ** 20


def takes_kernels(each, width, n_state, size):
    """Whether a call runs as the kernel pair: on a TPU, where a group's
    heads x width and the state size are whole lane tiles, a head is
    whole sublane tiles at two bytes a number (16 rows) and a chunk is
    ``CHUNK`` positions. The rule, all of it: the call's shapes and the
    backend, asked through ``flash_attention._interpret`` as the other
    kernels ask it (a compile for a described TPU from another host
    patches that name)."""
    return (not flash_attention._interpret() and size == CHUNK == _LANE
            and (each * width) % _LANE == 0 and width % 16 == 0
            and n_state % _LANE == 0)


def _nt(x, y):
    """``x y^T``, float32."""
    return lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _tn(x, y):
    """``x^T y``, float32."""
    return lax.dot_general(x, y, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _nn(x, y):
    return jnp.dot(x, y, preferred_element_type=jnp.float32)


def _running(x, ones):
    """``x ones`` in float32 for a matrix ``ones`` of zeros and ones: ``x``
    as three bfloat16 parts that sum to it, a plain product each. The
    running sum of a chunk's ``dt A`` (``ones`` a triangle) without the
    six passes of a float32 product."""
    out = 0.0
    for _ in range(3):
        part = x.astype(ones.dtype)
        out = out + _nn(part, ones)
        x = x - part.astype(jnp.float32)
    return out


def _triangle(size, upper=False):
    """``[row >= column]`` over an ``L x L`` tile, the causal mask
    (``[row <= column]`` where ``upper``). As numbers a row times the
    upper one is the row's running sum, times the lower one its running
    sum from the end."""
    row = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    column = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    return row <= column if upper else row >= column


def _decay(causal, lam_col, lam_row):
    """``exp(Lam_l - Lam_s)`` at and under the diagonal, 0 over it. The
    exponent is selected before the exponential: every one is <= 0."""
    return jnp.exp(jnp.where(causal, lam_col - lam_row, -jnp.inf))


def _fwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, states_ref,
                s_scr, y_scr, w_scr, grow_scr, *, steps, each, width):
    """A group's chunks, first to last. Inside, positions lie along the
    lanes: ``u`` is turned to ``[heads x width, L]`` as it arrives, so a
    head is ``width`` whole sublane rows, what a position scales (``dt``,
    the decays) is a row ``[1, L]`` spread over them, and the state is
    ``[heads x width, N]`` as it is kept."""
    size = dt_ref.shape[-1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    causal = _triangle(size)
    upper = _triangle(size, upper=True).astype(jnp.bfloat16)

    def chunk(k, _):
        at = pl.ds(pl.multiple_of(k * size, size), size)
        u = u_ref[0, at, :]
        kind = u.dtype
        ut = u.astype(jnp.float32).T
        bm, cm = b_ref[0, at, :], c_ref[0, at, :]
        dt = dt_ref[0, k, 0]
        lam = _running(dt * a_ref[0], upper)
        lam_col = lam.T
        s = s_scr[...]
        states_ref[0, k, 0] = s
        total = lam[:, size - 1:]
        left = jnp.exp(total - lam) * dt
        reach = jnp.exp(lam)
        # Through memory: Mosaic spreads no [1, 1] over a tile, and folds
        # a row taken from a value spread along the lanes into one.
        grow_scr[...] = jnp.broadcast_to(jnp.exp(total), grow_scr.shape)
        scores = _nt(cm, bm)
        before = _nt(s.astype(kind), cm)
        for r in range(each):
            rows = slice(r * width, (r + 1) * width)
            row = slice(r, r + 1)
            pairs = (scores * _decay(causal, lam_col[:, row], lam[row])
                     ).astype(kind)
            v = (dt[row] * ut[rows]).astype(kind)
            y_scr[rows] = _nt(v, pairs) + reach[row] * before[rows]
            w_scr[rows] = (left[row] * ut[rows]).astype(kind)
        y_ref[0, at, :] = y_scr[...].T.astype(kind)
        local = _nn(w_scr[...], bm)
        for r in range(each):
            rows = slice(r * width, (r + 1) * width)
            s_scr[rows] = grow_scr[r:r + 1, :] * s[rows] + local[rows]
        return _

    lax.fori_loop(0, steps, chunk, None)


def _bwd_kernel(u_ref, dy_ref, dt_ref, a_ref, b_ref, c_ref, states_ref,
                du_ref, ddt_ref, da_ref, db_ref, dc_ref,
                g_scr, du_scr, w_scr, reached_scr, grow_scr, dlam_scr, *,
                steps, each, width):
    """A group's chunks, last to first, with the adjoint ``g`` of the
    state that leaves a chunk in scratch. The chunk's ``Lam``, scores
    and decays are made again; its state at the start was kept. What
    ``Lam`` feels is gathered a head a row: as a column of the decay
    matrix, through ``exp(Lam)``, through the weights of the chunk's sum
    and, the chunk's last position, through ``Lam_L``; and as a row of
    the decay matrix (summed along the lanes, turned). Both sums are of
    one float32 matrix, so that what cancels between them cancels. Its
    running sum from the chunk's end is what ``dt A`` feels: ``ddt``
    leaves whole, ``dA`` as the group's sum over positions, a lane a
    position of a chunk (``[each, L]``, added up by XLA)."""
    size = dt_ref.shape[-1]

    @pl.when(pl.program_id(1) == 0)     # the sequence's last chunks
    def _():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    causal = _triangle(size)
    lower = causal.astype(jnp.bfloat16)
    upper = _triangle(size, upper=True).astype(jnp.bfloat16)
    last = lax.broadcasted_iota(jnp.int32, (1, size), 1) == size - 1
    head = lax.broadcasted_iota(jnp.int32, (size, each), 1)

    def chunk(i, _):
        k = steps - 1 - i
        at = pl.ds(pl.multiple_of(k * size, size), size)
        u, dy = u_ref[0, at, :], dy_ref[0, at, :]
        kind = u.dtype
        ut, dyt = u.astype(jnp.float32).T, dy.astype(jnp.float32).T
        bm, cm = b_ref[0, at, :], c_ref[0, at, :]
        dt, a = dt_ref[0, k, 0], a_ref[0]
        lam = _running(dt * a, upper)
        lam_col = lam.T
        s, g = states_ref[0, k, 0], g_scr[...]
        total = lam[:, size - 1:]
        fade = jnp.exp(total - lam)
        left = fade * dt
        reach, grow = jnp.exp(lam), jnp.exp(total)
        grow_scr[...] = jnp.broadcast_to(grow, grow_scr.shape)  # as forward
        scores = _nt(cm, bm)
        before = _nt(s.astype(kind), cm)
        d_weighed = _nt(g.astype(kind), bm)
        d_scores = jnp.zeros((size, size), jnp.float32)
        as_row = jnp.zeros((size, each), jnp.float32)
        for r in range(each):
            rows = slice(r * width, (r + 1) * width)
            row = slice(r, r + 1)
            ur, dyr, dwr = ut[rows], dyt[rows], d_weighed[rows]
            decay = _decay(causal, lam_col[:, row], lam[row])
            pairs = (scores * decay).astype(kind)
            dyk = dyr.astype(kind)
            dv = _nn(dyk, pairs)
            du_scr[rows] = dt[row] * dv + left[row] * dwr
            d_dt = jnp.sum(dv * ur, axis=0, keepdims=True)
            d_left = jnp.sum(dwr * ur, axis=0, keepdims=True)
            d_reach = jnp.sum(dyr * before[rows], axis=0, keepdims=True)
            held = jnp.sum(jnp.sum(g[rows] * s[rows], axis=0, keepdims=True),
                           axis=1, keepdims=True)
            d_total = grow[row] * held + jnp.sum(
                d_left * left[row], axis=1, keepdims=True)
            # d pairs = dy v^T with dt taken out of v; times the decay
            # it is what the scores feel, times the scores the decay's
            # exponent Lam_l - Lam_s.
            felt = _tn(dyk, ur.astype(kind)) * dt[row] * decay
            d_scores = d_scores + felt
            d_exponent = felt * scores
            dlam_scr[row, :] = (
                d_reach * reach[row] - d_left * left[row]
                - jnp.sum(d_exponent, axis=0, keepdims=True)
                + jnp.where(last, d_total, 0.0))
            as_row = jnp.where(head == r, jnp.sum(
                d_exponent, axis=1, keepdims=True), as_row)
            ddt_ref[0, k, 0, row, :] = d_dt + d_left * fade[row]
            reached_scr[rows] = (reach[row] * dyr).astype(kind)
            w_scr[rows] = (left[row] * ur).astype(kind)
            g_scr[rows] = grow_scr[row, :] * g[rows]
        d_step = _running(dlam_scr[...] + as_row.T, lower)
        ddt_ref[0, k, 0] = ddt_ref[0, k, 0] + a * d_step
        da_ref[0, 0] += dt * d_step
        du_ref[0, at, :] = du_scr[...].T.astype(kind)
        d_scores = d_scores.astype(kind)
        reached = reached_scr[...]
        dc_ref[0, at, :] = (_nn(d_scores, bm)
                            + _tn(reached, s.astype(kind))).astype(kind)
        db_ref[0, at, :] = (_tn(d_scores, cm)
                            + _tn(w_scr[...], g.astype(kind))).astype(kind)
        g_scr[...] += _nn(reached, cm)
        return _

    lax.fori_loop(0, steps, chunk, None)


def _blocks(u, b, steps, reverse):
    """The grid ``(batch x groups, chunks / steps)`` and the blocks of
    chunked operands: rows ``[steps x L, heads x width]`` of ``u``-like
    arrays and ``[steps x L, N]`` of ``B``-like ones as they lie in
    ``[batch, seq, groups x ...]``, what a position holds a head
    (``[batch, chunks, groups, each, L]``), and the states."""
    batch, chunks, size, groups, each, width = u.shape
    n_state, lanes = b.shape[-1], each * width
    n = chunks // steps

    def at(j):
        return n - 1 - j if reverse else j

    wide = pl.BlockSpec((1, steps * size, lanes),
                        lambda i, j: (i // groups, at(j), i % groups))
    narrow = pl.BlockSpec((1, steps * size, n_state),
                          lambda i, j: (i // groups, at(j), i % groups))
    row = pl.BlockSpec((1, steps, 1, each, size),
                       lambda i, j: (i // groups, at(j), i % groups, 0, 0))
    # ``A`` a head as a column, and ``dA``'s sums: a group's, whole.
    rate = pl.BlockSpec((1, each, 1), lambda i, j: (i % groups, 0, 0))
    d_rate = pl.BlockSpec((1, 1, each, size),
                          lambda i, j: (i // groups, i % groups, 0, 0))
    states = pl.BlockSpec((1, steps, 1, lanes, n_state),
                          lambda i, j: (i // groups, at(j), i % groups, 0, 0))
    return (batch * groups, n), wide, narrow, row, rate, d_rate, states


def _step_chunks(chunks):
    return max(k for k in range(1, _STEP_CHUNKS + 1) if chunks % k == 0)


def _flat(z):
    """``[batch, chunks, L, ...]`` as ``[batch, seq, everything else]``."""
    return z.reshape(z.shape[0], z.shape[1] * z.shape[2], -1)


@functools.partial(jax.jit, static_argnums=(5,))
def _fwd_kernels(u, dt, a, b, c, interpret):
    """``_fwd_call`` as one Mosaic call."""
    batch, chunks, size, groups, each, width = u.shape
    n_state, lanes = b.shape[-1], each * width
    steps = _step_chunks(chunks)
    grid, wide, narrow, row, rate, _, states = _blocks(u, b, steps, False)
    y, s_in = pl.pallas_call(
        functools.partial(_fwd_kernel, steps=steps, each=each, width=width),
        grid=grid,
        in_specs=[wide, row, rate, narrow, narrow],
        out_specs=[wide, states],
        out_shape=[
            _struct((batch, chunks * size, groups * lanes), u.dtype,
                    u, dt, a, b, c),
            _struct((batch, chunks, groups, lanes, n_state), jnp.float32,
                    u, dt, a, b, c)],
        scratch_shapes=[pltpu.VMEM((lanes, n_state), jnp.float32),
                        pltpu.VMEM((lanes, size), jnp.float32),
                        pltpu.VMEM((lanes, size), u.dtype),
                        pltpu.VMEM((each, n_state), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_FWD,
    )(_flat(u), jnp.moveaxis(dt, 2, -1), a[..., None], _flat(b), _flat(c))
    return (y.reshape(u.shape),
            s_in.reshape(batch, chunks, groups, each, width, n_state))


@functools.partial(jax.jit, static_argnums=(7,))
def _bwd_kernels(u, dt, a, b, c, s_in, dy, interpret):
    """``_bwd_call`` as one Mosaic call; ``dA``'s sums a group are
    added up here."""
    batch, chunks, size, groups, each, width = u.shape
    n_state, lanes = b.shape[-1], each * width
    steps = _step_chunks(chunks)
    grid, wide, narrow, row, rate, d_rate, states = _blocks(u, b, steps, True)
    like = (u, dt, a, b, c, s_in, dy)
    du, d_dt, da, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, steps=steps, each=each, width=width),
        grid=grid,
        in_specs=[wide, wide, row, rate, narrow, narrow, states],
        out_specs=[wide, row, d_rate, narrow, narrow],
        out_shape=[
            _struct((batch, chunks * size, groups * lanes), u.dtype, *like),
            _struct((batch, chunks, groups, each, size), jnp.float32, *like),
            _struct((batch, groups, each, size), jnp.float32, *like),
            _struct((batch, chunks * size, groups * n_state), b.dtype, *like),
            _struct((batch, chunks * size, groups * n_state), c.dtype, *like)],
        scratch_shapes=[pltpu.VMEM((lanes, n_state), jnp.float32),
                        pltpu.VMEM((lanes, size), jnp.float32),
                        pltpu.VMEM((lanes, size), u.dtype),
                        pltpu.VMEM((lanes, size), u.dtype),
                        pltpu.VMEM((each, n_state), jnp.float32),
                        pltpu.VMEM((each, size), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_BWD,
    )(_flat(u), _flat(dy), jnp.moveaxis(dt, 2, -1), a[..., None], _flat(b),
      _flat(c),
      s_in.reshape(batch, chunks, groups, lanes, n_state))
    return (du.reshape(u.shape), jnp.moveaxis(d_dt, -1, 2),
            jnp.sum(da, axis=(0, 3)),
            db.reshape(b.shape), dc.reshape(c.shape))


def _varying_alike(*xs):
    """``xs``, each varying over every mesh axis any of them varies
    over. Inside ``shard_map`` a kernel's results vary as the union of
    its operands does, and a ``custom_vjp`` must hand each operand a
    cotangent of the operand's own type: a replicated ``A`` beside
    sharded rows is cast first, and the cast's transpose sums ``dA``
    over the axis (what the einsums' ``jax.vjp`` does unasked)."""
    axes = frozenset().union(*(jax.typeof(x).vma for x in xs))
    return [functools.reduce(pvary, sorted(axes - jax.typeof(x).vma), x)
            for x in xs]


def _forward(u, dt, a, b, c, kernels):
    if kernels:
        return _fwd_kernels(u, dt, a, b, c, flash_attention._interpret())
    return _fwd_call(u, dt, a, b, c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd(u, dt, a, b, c, kernels):
    return _forward(u, dt, a, b, c, kernels)[0]


def _ssd_fwd(u, dt, a, b, c, kernels):
    y, states = map(ad_checkpoint.checkpoint_name,
                    _forward(u, dt, a, b, c, kernels), SAVED_NAMES)
    return y, (u, dt, a, b, c, states)


def _ssd_bwd(kernels, res, dy):
    # The rule is traced outside the scopes of the call it belongs to.
    with jax.named_scope(SCOPE), jax.named_scope(SCOPE_SCAN):
        if kernels:
            return _bwd_kernels(*res, dy, flash_attention._interpret())
        return _bwd_call(*res, dy)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_chunks(seq, chunk=None):
    """Chunks one call cuts ``seq`` positions into."""
    return -(-seq // _chunk_len(seq, chunk))


def _chunk_len(seq, chunk=None):
    """``chunk`` (``CHUNK``) clamped to the sequence rounded up to
    eight."""
    return min(chunk or CHUNK, -(-seq // 8) * 8)


def state_bytes(batch, seq, heads, width, n_state, chunk=None):
    """Bytes of chunk-start states one call keeps for its backward."""
    return 4 * batch * ssd_chunks(seq, chunk) * heads * width * n_state


def _publish(batch, seq, heads, width, n_state, chunk, kernels):
    """Set ``hvd_ssd_chunks``, ``hvd_ssd_chunk_len``,
    ``hvd_ssd_state_bytes`` and ``hvd_ssd_kernel`` (docs/metrics.md)
    from the call being traced. A no-op when ``HOROVOD_TPU_METRICS`` is
    off."""
    from ..telemetry import core as telemetry
    if not telemetry.enabled():
        return
    telemetry.gauge(
        "hvd_ssd_chunks",
        "Chunks of the sequence the Mamba-2 recurrence last traced is "
        "cut into").set(float(ssd_chunks(seq, chunk)))
    telemetry.gauge(
        "hvd_ssd_chunk_len",
        "Positions a chunk of that call holds").set(
            float(_chunk_len(seq, chunk)))
    telemetry.gauge(
        "hvd_ssd_state_bytes",
        "Bytes of chunk-start states that call keeps for its backward "
        "pass").set(float(state_bytes(batch, seq, heads, width, n_state,
                                      chunk)))
    telemetry.gauge(
        "hvd_ssd_kernel",
        "1 where that call runs as the Mosaic kernel pair, 0 where as "
        "XLA einsums").set(float(kernels))


@jax.named_scope(SCOPE_SCAN)
def ssd(u, dt, a, b, c, *, chunk=None):
    """``y[batch, seq, heads, width]`` of the recurrence above, in
    ``u``'s dtype, differentiable in all five arguments. ``chunk``
    defaults to ``CHUNK``; a sequence that is no whole number of chunks
    is padded (a padded position has ``dt = 0`` and ``u = 0``: the state
    passes through it unchanged)."""
    batch, seq, heads, width = u.shape
    groups, n_state = b.shape[2:]
    if heads % groups:
        raise ValueError(f"ssd: {heads} heads over {groups} groups")
    size = _chunk_len(seq, chunk)
    each = heads // groups
    kernels = takes_kernels(each, width, n_state, size)
    _publish(batch, seq, heads, width, n_state, chunk, kernels)

    def chunked(z, *dims):
        z = jnp.pad(z, ((0, 0), (0, (-seq) % size))
                    + ((0, 0),) * (z.ndim - 2))
        return z.reshape(batch, -1, size, *dims)

    operands = (chunked(u, groups, each, width),
                chunked(dt.astype(jnp.float32), groups, each),
                a.astype(jnp.float32).reshape(groups, each),
                chunked(b.astype(u.dtype), groups, n_state),
                chunked(c.astype(u.dtype), groups, n_state))
    if kernels:
        operands = _varying_alike(*operands)
    y = _ssd(*operands, kernels)
    return y.reshape(batch, -1, heads, width)[:, :seq]


def reference_ssd(u, dt, a, b, c):
    """The same recurrence as a sequential ``lax.scan`` over positions,
    float32: the oracle of the chunked form's tests."""
    each = u.shape[2] // b.shape[2]

    def step(s, at):
        u_t, dt_t, b_t, c_t = at
        b_t, c_t = (jnp.repeat(z, each, axis=1) for z in (b_t, c_t))
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * u_t)[..., None] * b_t[:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t,
                             precision=lax.Precision.HIGHEST)

    seq_first = [jnp.moveaxis(z.astype(jnp.float32), 1, 0)
                 for z in (u, dt, b, c)]
    first = [z[0] for z in seq_first]
    s0 = 0.0 * step(jnp.zeros((), jnp.float32), first)[0]
    return jnp.moveaxis(lax.scan(step, s0, seq_first)[1], 0, 1)
