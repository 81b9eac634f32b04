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

The form that ships is that algebra as XLA einsums over all the chunks
at once, and one ``lax.scan`` over the chunks that carries the state
across their boundaries (``_carry``): a multiply-add of the state a
chunk. No Mosaic kernel yet: a pair (``hvd_ssd_fwd`` / ``hvd_ssd_bwd``,
grid ``(batch x head blocks, chunks)``, a head block's states in VMEM
scratch, the ``L x L`` decay matrices never in HBM) is the next step
(ROADMAP), and would stand where ``_outputs``, ``_sums`` and ``_carry``
stand; callers, scopes and what is saved stay.

Backward. A ``custom_vjp`` keeps the operands and the chunk-start states
``[batch, chunks, heads, width, N]`` float32 (``state_bytes``) and
nothing else: the ``L x L`` matrices, several times the operands' size,
are made again on the way back, where the two chunk-local parts are
differentiated as they stand (``jax.vjp``) and the carry's adjoint, ``g_c
= dS_c + exp(Lam_L,c) g_{c+1}``, is one more scan, last chunk first.
``SAVED_NAMES`` are what a ``jax.checkpoint`` policy may keep so that a
recomputed block does not run the forward pass again (``remat="flash"``).

Inside ``shard_map`` off the TPU nothing changes: there is no Pallas
here, so the same einsums run wherever JAX does. ``reference_ssd`` is
the recurrence itself, position by position: the oracle of the tests.
"""

import functools

import jax
import jax.numpy as jnp
from jax import ad_checkpoint, lax

# Positions a chunk holds. The L x L decay matrices (heads x L numbers a
# position, element-wise work) grow with it and the states kept for the
# way back (``state_bytes``) shrink; 128 fills the MXU's contraction.
CHUNK = 128

# Names in a device trace (docs/tracing.md): readers match the literals.
SCOPE = "hvd_ssd"               # the Mamba-2 mixer (models/ssm.py)
SCOPE_SCAN = "scan"             # inside it: the recurrence, all of it

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


@jax.custom_vjp
def _ssd(u, dt, a, b, c):
    return _fwd_call(u, dt, a, b, c)[0]


def _ssd_fwd(u, dt, a, b, c):
    y, states = map(ad_checkpoint.checkpoint_name,
                    _fwd_call(u, dt, a, b, c), SAVED_NAMES)
    return y, (u, dt, a, b, c, states)


def _ssd_bwd(res, dy):
    # The rule is traced outside the scopes of the call it belongs to.
    with jax.named_scope(SCOPE), jax.named_scope(SCOPE_SCAN):
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


def _publish(batch, seq, heads, width, n_state, chunk):
    """Set ``hvd_ssd_chunks``, ``hvd_ssd_chunk_len`` and
    ``hvd_ssd_state_bytes`` (docs/metrics.md) from the call being
    traced. A no-op when ``HOROVOD_TPU_METRICS`` is off."""
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
    _publish(batch, seq, heads, width, n_state, chunk)
    size = _chunk_len(seq, chunk)

    def chunked(z, *dims):
        z = jnp.pad(z, ((0, 0), (0, (-seq) % size))
                    + ((0, 0),) * (z.ndim - 2))
        return z.reshape(batch, -1, size, *dims)

    each = heads // groups
    y = _ssd(chunked(u, groups, each, width),
             chunked(dt.astype(jnp.float32), groups, each),
             a.astype(jnp.float32).reshape(groups, each),
             chunked(b.astype(u.dtype), groups, n_state),
             chunked(c.astype(u.dtype), groups, n_state))
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
