"""Pallas TPU selective scan: the recurrence of a Mamba layer.

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * x_t) (x) B_t      s: [channels, N]
    y_t = s_t . C_t

(Gu & Dao, "Mamba", arXiv:2312.00752, the S6 layer; ``A`` is negative,
``dt`` positive, so the decay is in (0, 1].) ``x`` and ``dt`` are
``[batch, seq, channels]``, ``B`` and ``C`` ``[batch, seq, N]``, ``A``
``[channels, N]``; everything float32. The ``D * x`` skip and the gate
are the caller's (``models/ssm.py``): element-wise work that XLA fuses
with its neighbours.

Why a kernel. The state is channels x N numbers a sequence (5120 x 16 in
the model this was written for). As ``jax.lax.associative_scan`` the
recurrence materialises ``[seq, channels, N]`` operands in HBM, several
of them; as ``lax.scan`` over positions it is ``seq`` dependent launches.
Here the state lives in VMEM, the sequence is walked in chunks of
``CHUNK`` positions (one grid step each, its ``x``, ``dt`` and ``y``
blocks double-buffered by the pipeline), and ``x``, ``dt``, ``B``, ``C``
are read once and ``y`` written once.

Layout. Nothing here is a matrix product: per position and state index
``n`` the work is one exponential and six multiply-adds on every
channel, and the MXU has no part in it. So a position's channels fill
whole vregs, ``[channels // 128, 128]`` with eight rows a vreg, the ``N``
state indices are separate arrays of that shape, and ``B_t[n]``,
``C_t[n]`` are scalars read from SMEM: the sum over ``n`` adds vregs to
each other and the forward pass has no reduction across lanes or
sublanes at all.

Backward. One more kernel walks the chunks last to first. It makes a
chunk's states again from the state at the chunk's start, which the
forward kernel saved (``[batch, chunks, N, channels]``: the only thing
kept beside the inputs), holds them in VMEM, and then runs the adjoint
recurrence ``g_t = C_t dy_t + a_{t+1} g_{t+1}`` backwards through the
chunk. ``dx``, ``ddt`` are sums over ``n`` (vreg adds), ``dA`` accumulates
in its resident output block, and ``dB_t[n]``, ``dC_t[n]`` are sums over
all channels: one reduction of a vreg to a scalar each, written to SMEM.

On non-TPU backends the kernels run in Pallas interpret mode (inside
``shard_map`` the sequential oracle, as for the flash kernels).
"""

import functools

import jax
import jax.numpy as jnp
from jax import ad_checkpoint, lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _struct, _varying

_LANE = 128
# Positions a grid step walks. The forward holds a chunk's x, dt and y
# blocks twice; the backward five such blocks and the chunk's states
# (CHUNK x N x channels float32: 21 MB at 64 x 16 x 5120). Measured on
# the chip (PERF.md section 6, PR 32).
CHUNK = 64
_VMEM_LIMIT = 96 * 2 ** 20

# Names in a device trace (docs/tracing.md): readers match the literals.
SCOPE = "hvd_ssm"               # the Mamba mixer (models/ssm.py)
SCOPE_SCAN = "scan"             # inside it: the kernels and their glue
KERNEL_FWD = "hvd_ssm_fwd"
KERNEL_BWD = "hvd_ssm_bwd"

# What the forward kernel hands the backward one beside its inputs, as
# ``jax.checkpoint`` policies may name it: with both kept, a recomputed
# forward pass does not run the kernel again.
SAVED_NAMES = ("hvd_ssm_y", "hvd_ssm_states")


def _interpret():
    return jax.default_backend() != "tpu"


def _fwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, y_ref, bound_ref, s_scr,
                *, chunk, n_state):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[:] = jnp.zeros_like(s_scr)

    bound_ref[0, 0] = s_scr[:]

    def step(t, _):
        dt = dt_ref[0, t]                       # [channels // 128, 128]
        dtx = dt * x_ref[0, t]
        y = jnp.zeros_like(dt)
        for n in range(n_state):
            s = (jnp.exp(dt * a_ref[n]) * s_scr[n]
                 + dtx * b_ref[0, 0, t * n_state + n])
            s_scr[n] = s
            y = y + s * c_ref[0, 0, t * n_state + n]
        y_ref[0, t] = y
        return _

    lax.fori_loop(0, chunk, step, None)


def _bwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, bound_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, states, g_scr,
                *, chunk, n_state):
    first = jnp.logical_and(pl.program_id(0) == 0, pl.program_id(1) == 0)

    @pl.when(first)
    def _():
        da_ref[:] = jnp.zeros_like(da_ref)

    @pl.when(pl.program_id(1) == 0)     # the sequence's last chunk
    def _():
        g_scr[:] = jnp.zeros_like(g_scr)

    # The chunk's states again: states[t] is the state before position
    # t, states[t + 1] the one after.
    states[0] = bound_ref[0, 0]

    def forward(t, _):
        dt = dt_ref[0, t]
        dtx = dt * x_ref[0, t]
        for n in range(n_state):
            states[t + 1, n] = (jnp.exp(dt * a_ref[n]) * states[t, n]
                                + dtx * b_ref[0, 0, t * n_state + n])
        return _

    lax.fori_loop(0, chunk, forward, None)

    def backward(i, _):
        t = chunk - 1 - i
        dt, x, dy = dt_ref[0, t], x_ref[0, t], dy_ref[0, t]
        dtx = dt * x
        ddt = jnp.zeros_like(dt)
        gb = jnp.zeros_like(dt)
        for n in range(n_state):
            at = t * n_state + n
            a_n = a_ref[n]
            g = dy * c_ref[0, 0, at] + g_scr[n]          # dL/ds_t
            dc_ref[0, 0, at] = jnp.sum(states[t + 1, n] * dy)
            db_ref[0, 0, at] = jnp.sum(g * dtx)
            decay = jnp.exp(dt * a_n)
            da = g * states[t, n] * decay             # dL/d(dt_t A)
            ddt = ddt + da * a_n
            da_ref[n] = da_ref[n] + da * dt
            gb = gb + g * b_ref[0, 0, at]
            g_scr[n] = decay * g
        dx_ref[0, t] = gb * dt
        ddt_ref[0, t] = ddt + gb * x
        return _

    lax.fori_loop(0, chunk, backward, None)


def _wide(z, chunk):
    """``[batch, seq, channels]`` as a kernel reads it: the sequence
    padded to whole chunks (a padded position has dt = 0 and x = 0: the
    state passes through it unchanged), channels as ``[channels // 128,
    128]``."""
    batch, seq, channels = z.shape
    if channels % _LANE:
        raise ValueError(f"selective_scan: {channels} channels are not a "
                         f"multiple of {_LANE}")
    z = jnp.pad(z, ((0, 0), (0, (-seq) % chunk), (0, 0)))
    return z.reshape(batch, -1, channels // _LANE, _LANE)


def _flat(z, chunk):
    """``[batch, seq, N]`` padded to whole chunks, a row a chunk
    (``[batch * chunks, 1, chunk * N]``, position-major): a block of
    scalars in SMEM is a whole row, whatever the batch."""
    z = jnp.pad(z, ((0, 0), (0, (-z.shape[1]) % chunk), (0, 0)))
    return z.reshape(-1, 1, chunk * z.shape[2])


def _by_state(a):
    """``A`` ``[channels, N]`` by state index, channels as in _wide."""
    return a.T.reshape(a.shape[1], -1, _LANE)


def _specs(chunk, groups, n_state, reverse, n_chunks):
    def at(j):
        return n_chunks - 1 - j if reverse else j

    row = pl.BlockSpec((1, chunk, groups, _LANE),
                       lambda i, j: (i, at(j), 0, 0))
    scalars = pl.BlockSpec((1, 1, chunk * n_state),
                           lambda i, j: (i * n_chunks + at(j), 0, 0),
                           memory_space=pltpu.SMEM)
    by_state = pl.BlockSpec((n_state, groups, _LANE), lambda i, j: (0, 0, 0))
    bound = pl.BlockSpec((1, 1, n_state, groups, _LANE),
                         lambda i, j: (i, at(j), 0, 0, 0))
    return row, scalars, by_state, bound


@functools.partial(jax.jit, static_argnums=(5, 6))
def _fwd_call(x, dt, a, b, c, chunk, interpret):
    """``(y, states)``: ``states[:, k]`` the state before chunk ``k``.
    Through ``jax.jit``, like the flash kernels' calls: the layers of a
    model make the same call, and it is traced and lowered once a
    program."""
    batch, seq, channels = x.shape
    n_state = a.shape[1]
    xl, dtl, bf, cf, al = (_wide(x, chunk), _wide(dt, chunk),
                           _flat(b, chunk), _flat(c, chunk), _by_state(a))
    n_chunks, groups = xl.shape[1] // chunk, channels // _LANE
    row, scalars, by_state, bound = _specs(chunk, groups, n_state, False,
                                           n_chunks)
    y, states = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, n_state=n_state),
        grid=(batch, n_chunks),
        in_specs=[scalars, scalars, row, row, by_state],
        out_specs=[row, bound],
        out_shape=[
            _struct(xl.shape, jnp.float32, x, dt, a, b, c),
            _struct((batch, n_chunks, n_state, groups, _LANE), jnp.float32,
                    x, dt, a, b, c)],
        scratch_shapes=[pltpu.VMEM((n_state, groups, _LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_FWD,
    )(bf, cf, xl, dtl, al)
    return y.reshape(batch, -1, channels)[:, :seq], states


@functools.partial(jax.jit, static_argnums=(7, 8))
def _bwd_call(x, dt, a, b, c, states, dy, chunk, interpret):
    batch, seq, channels = x.shape
    n_state = a.shape[1]
    xl, dtl, dyl = (_wide(z, chunk) for z in (x, dt, dy))
    bf, cf, al = _flat(b, chunk), _flat(c, chunk), _by_state(a)
    n_chunks, groups = xl.shape[1] // chunk, channels // _LANE
    row, scalars, by_state, bound = _specs(chunk, groups, n_state, True,
                                           n_chunks)
    state = pltpu.VMEM((n_state, groups, _LANE), jnp.float32)
    dx, ddt, da, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, n_state=n_state),
        grid=(batch, n_chunks),
        in_specs=[scalars, scalars, row, row, by_state, bound, row],
        out_specs=[row, row, by_state, scalars, scalars],
        out_shape=[_struct(z.shape, jnp.float32, x, dt, a, b, c, dy)
                   for z in (xl, xl, al, bf, bf)],
        scratch_shapes=[
            pltpu.VMEM((chunk + 1, n_state, groups, _LANE), jnp.float32),
            state],
        compiler_params=pltpu.CompilerParams(
            # dA accumulates over the whole grid in its output block.
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_BWD,
    )(bf, cf, xl, dtl, al, states, dyl)

    def rows(z):
        return z.reshape(batch, -1, channels)[:, :seq]

    def scalars_of(z):
        return z.reshape(batch, -1, n_state)[:, :seq]

    return (rows(dx), rows(ddt), da.reshape(n_state, channels).T,
            scalars_of(db), scalars_of(dc))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, dt, a, b, c, chunk):
    return _fwd_call(x, dt, a, b, c, chunk, _interpret())[0]


def _scan_fwd(x, dt, a, b, c, chunk):
    y, states = map(ad_checkpoint.checkpoint_name,
                    _fwd_call(x, dt, a, b, c, chunk, _interpret()),
                    SAVED_NAMES)
    return y, (x, dt, a, b, c, states)


def _scan_bwd(chunk, res, dy):
    # The rule is traced outside the scopes of the call it belongs to.
    with jax.named_scope(SCOPE), jax.named_scope(SCOPE_SCAN):
        return _bwd_call(*res, dy, chunk, _interpret())


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan_chunks(seq, chunk=None):
    """Chunks one scan call walks over ``seq`` positions."""
    return -(-seq // (chunk or CHUNK))


def state_bytes(batch, seq, channels, n_state, chunk=None):
    """Bytes of chunk-boundary states one call keeps for its backward."""
    return 4 * batch * scan_chunks(seq, chunk) * channels * n_state


def _publish(batch, seq, channels, n_state, chunk):
    """Set ``hvd_ssm_chunks`` and ``hvd_ssm_state_bytes``
    (docs/metrics.md) from the call being traced. A no-op when
    ``HOROVOD_TPU_METRICS`` is off."""
    from ..telemetry import core as telemetry
    if not telemetry.enabled():
        return
    telemetry.gauge(
        "hvd_ssm_chunks",
        "Chunks of the sequence the selective-scan call last traced "
        "walks, one grid step each").set(float(scan_chunks(seq, chunk)))
    telemetry.gauge(
        "hvd_ssm_state_bytes",
        "Bytes of chunk-boundary states the selective-scan call last "
        "traced keeps for its backward pass").set(
            float(state_bytes(batch, seq, channels, n_state, chunk)))


@jax.named_scope(SCOPE_SCAN)
def selective_scan(x, dt, a, b, c, *, chunk=None):
    """``y[batch, seq, channels]`` of the recurrence above, float32,
    differentiable in all five arguments. ``chunk`` defaults to
    ``CHUNK`` (clamped to the sequence rounded up to eight)."""
    batch, seq, channels = x.shape
    chunk = min(chunk or CHUNK, -(-seq // 8) * 8)
    _publish(batch, seq, channels, a.shape[1], chunk)
    f32 = [z.astype(jnp.float32) for z in (x, dt, a, b, c)]
    if _interpret() and _varying(*f32):
        # As flash_attention: Pallas's HLO interpreter cannot run with
        # device-varying operands inside shard_map, so off the TPU the
        # sequential oracle stands in there. On the TPU the compiled
        # kernel runs under shard_map as it is.
        return reference_scan(*f32)
    return _scan(*f32, chunk)


def reference_scan(x, dt, a, b, c):
    """The same recurrence as a sequential ``lax.scan`` over positions:
    the oracle of the kernel's tests."""
    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        s = (jnp.exp(dt_t[..., None] * a) * s
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return s, jnp.einsum("bcn,bn->bc", s, c_t,
                             precision=lax.Precision.HIGHEST)

    seq_first = [jnp.moveaxis(z.astype(jnp.float32), 1, 0)
                 for z in (x, dt, b, c)]
    # Zeros made of the operands: under shard_map the carry then varies
    # over the mesh as what is added to it does.
    first = [z[0] for z in seq_first]
    s0 = 0.0 * step(jnp.zeros((), jnp.float32), first)[0]
    return jnp.moveaxis(lax.scan(step, s0, seq_first)[1], 0, 1)
