"""Token mixers of a hybrid decoder that are not attention: the Mamba
layer and the gated memory unit that reads a Mamba layer's scan output
further up the stack (SambaY, arXiv:2507.06607), the Mamba-2 layer
(``Mamba2Mixer``), and the gated short convolution of LFM2
(``ShortConv``). ``transformer.Block`` chooses them per layer
(``TransformerConfig.mixers``), as it chooses the expert layer of
``parallel/moe.py``; Mamba's recurrence itself is the kernel pair of
``ops/selective_scan.py``, Mamba-2's the chunked products of
``ops/ssd.py``.

Mamba-1 (Gu & Dao, arXiv:2312.00752), on a normed input ``h``:

    [x, z] = W_in h                      hidden -> 2 x d_inner, no bias
    x = silu(conv1d_causal(x) + b_c)     depthwise, d_conv taps
    [r, B_t, C_t] = W_x x                d_inner -> dt_rank + 2 N, no bias
    dt = softplus(W_dt r + b_dt)
    s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) (x) B_t,   A = -exp(A_log)
    y_t = s_t . C_t + D x_t
    out = W_out (y silu(z))              d_inner -> hidden, no bias

``dt``, ``A``, the state and ``y`` are float32; the products take
``cfg.dtype`` operands and accumulate in float32. The layer also returns
``y`` (with the ``D`` skip, before the gate): the memory that gated
memory units read.

Mamba-2 (Dao & Gu, arXiv:2405.21060, as Nemotron-H runs it,
arXiv:2504.03624), on a normed input ``h``; ``heads`` heads of
``head_dim`` (``d_inner = heads x head_dim``), ``groups`` groups of
``B`` / ``C`` with ``N = d_state`` numbers each, head ``i`` reading
group ``i // (heads / groups)``:

    [z, xBC, dt] = W_in h            hidden -> d_inner + (d_inner + 2 groups N) + heads
    xBC = silu(conv1d_causal(xBC) + b_c)     depthwise, d_conv taps
    [u, B, C] = xBC
    dt = softplus(dt + dt_bias)              a number a head a token
    S_t = exp(dt_t A_i) S_{t-1} + (dt_t u_t) (x) B_t,   A_i = -exp(A_log_i)
    y_t = S_t C_t + D_i u_t                  S: [head_dim, N] a head
    g   = RMSNorm_groups(y silu(z))          over each d_inner / groups lanes
    out = W_out g                            d_inner -> hidden, no bias

``A`` is a scalar a head, so the state is a matrix a head and the
recurrence a sum of matrix products over chunks (``ops/ssd.py``).
``dt``, ``A``, the decays, the states and the norm's statistics are
float32; the products take ``cfg.dtype`` operands and accumulate in
float32. The layer hands nothing on.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import selective_scan as scan_ops
from ..ops import ssd as ssd_ops

# Names in a device trace (docs/tracing.md); readers match the literals.
SCOPE_SSM = scan_ops.SCOPE      # "hvd_ssm": the Mamba mixer
SCOPE_SSD = ssd_ops.SCOPE        # "hvd_ssd": the Mamba-2 mixer
SCOPE_GMU = "hvd_gmu"           # the gated memory unit
# The gated short convolution, products and all; inside it ``mix``: the
# two gates and the taps between the products.
SCOPE_SHORTCONV = "hvd_shortconv"


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """The sizes of a Mamba layer: ``d_inner`` channels, each with a
    state of ``d_state`` numbers, a depthwise convolution of ``d_conv``
    taps before the scan, the step size made through ``dt_rank``. A
    Mamba-2 layer besides has ``heads`` heads of ``head_dim`` channels
    (``d_inner`` in all) and ``groups`` groups of B and C; its step size
    is a number a head from the input product, and ``dt_rank`` is not
    read."""
    d_inner: int
    dt_rank: int
    d_state: int = 16
    d_conv: int = 4
    heads: int = 0              # Mamba-2 alone, as the two below
    head_dim: int = 0
    groups: int = 1


def causal_conv(x, kernel, bias=None):
    """Depthwise causal convolution over positions: ``x`` ``[batch, seq,
    channels]``, ``kernel`` ``[taps, channels]``; tap ``taps - 1`` meets
    the position itself, and before the row's start lie zeros. Shifted
    multiply-adds, a pass a tap, that XLA fuses into one."""
    taps = kernel.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    seq = x.shape[1]
    out = sum(padded[:, i:i + seq] * kernel[i] for i in range(taps))
    return out if bias is None else bias + out


class MambaMixer(nn.Module):
    """``(out, y)``: the layer's output and its memory."""
    cfg: object                 # TransformerConfig (cfg.ssm set)

    @nn.compact
    def __call__(self, h):
        cfg, m = self.cfg, self.cfg.ssm
        n = m.d_state
        dense = dict(use_bias=False, dtype=cfg.dtype)
        with jax.named_scope(SCOPE_SSM):
            xz = nn.Dense(2 * m.d_inner, name="in_proj", **dense)(h)
            x, z = xz[..., :m.d_inner], xz[..., m.d_inner:]
            conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (m.d_conv, m.d_inner))
            conv_b = self.param("conv_bias", nn.initializers.zeros,
                                (m.d_inner,))
            x = nn.silu(causal_conv(x, conv_w.astype(cfg.dtype),
                                    conv_b.astype(cfg.dtype)))
            rbc = nn.Dense(m.dt_rank + 2 * n, name="x_proj", **dense)(x)
            dt = nn.Dense(m.d_inner, name="dt_proj", dtype=cfg.dtype,
                          param_dtype=jnp.float32)(rbc[..., :m.dt_rank])
            dt = jax.nn.softplus(dt.astype(jnp.float32))
            a_log = self.param("A_log", nn.initializers.zeros,
                               (m.d_inner, n))
            skip = self.param("D", nn.initializers.ones, (m.d_inner,))
            xf = x.astype(jnp.float32)
            y = scan_ops.selective_scan(
                xf, dt, -jnp.exp(a_log.astype(jnp.float32)),
                rbc[..., m.dt_rank:m.dt_rank + n],
                rbc[..., m.dt_rank + n:]) + skip * xf
            gated = (y * nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)
            out = nn.Dense(cfg.hidden, name="out_proj", **dense)(gated)
            return out, y.astype(cfg.dtype)


class Mamba2Mixer(nn.Module):
    """The layer's output; it hands nothing on."""
    cfg: object                 # TransformerConfig (cfg.ssm set)

    @nn.compact
    def __call__(self, h):
        cfg, m = self.cfg, self.cfg.ssm
        if m.heads * m.head_dim != m.d_inner or m.heads % m.groups:
            raise ValueError(
                f"SSMConfig: {m.heads} heads of {m.head_dim} over "
                f"{m.groups} groups for d_inner {m.d_inner}")
        bc = m.groups * m.d_state
        dense = dict(use_bias=False, dtype=cfg.dtype)
        with jax.named_scope(SCOPE_SSD):
            z, xbc, dt = jnp.split(
                nn.Dense(2 * m.d_inner + 2 * bc + m.heads, name="in_proj",
                         **dense)(h),
                (m.d_inner, 2 * m.d_inner + 2 * bc), axis=-1)
            conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (m.d_conv, m.d_inner + 2 * bc))
            conv_b = self.param("conv_bias", nn.initializers.zeros,
                                (m.d_inner + 2 * bc,))
            xbc = nn.silu(causal_conv(xbc, conv_w.astype(cfg.dtype),
                                      conv_b.astype(cfg.dtype)))
            u, b, c = jnp.split(xbc, (m.d_inner, m.d_inner + bc), axis=-1)
            dt_bias = self.param("dt_bias", nn.initializers.zeros,
                                 (m.heads,))
            a_log = self.param("A_log", nn.initializers.zeros, (m.heads,))
            skip = self.param("D", nn.initializers.ones, (m.heads,))
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            u = u.reshape(*u.shape[:2], m.heads, m.head_dim)
            by_group = (*b.shape[:2], m.groups, m.d_state)
            y = ssd_ops.ssd(u, dt, -jnp.exp(a_log.astype(jnp.float32)),
                            b.reshape(by_group), c.reshape(by_group))
            y = y.astype(jnp.float32) + skip[:, None] * u.astype(jnp.float32)
            gated = y.reshape(z.shape) * nn.silu(z.astype(jnp.float32))
            # One gain of d_inner, the statistics over each group's lanes.
            gain = self.param("norm", nn.initializers.ones, (m.d_inner,))
            lanes = gated.reshape(*gated.shape[:2], m.groups, -1)
            normed = lanes * jax.lax.rsqrt(jnp.mean(
                jnp.square(lanes), -1, keepdims=True) + cfg.norm_eps)
            normed = (normed.reshape(gated.shape) * gain).astype(cfg.dtype)
            return nn.Dense(cfg.hidden, name="out_proj", **dense)(normed)


class ShortConv(nn.Module):
    """``W_out (C * conv(B * z))`` with ``[B, C, z] = W_in h``: a
    depthwise causal convolution of ``cfg.conv_taps`` taps between two
    gates made by the product that made its input. It hands nothing
    on."""
    cfg: object                 # TransformerConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        dense = dict(use_bias=False, dtype=cfg.dtype)
        with jax.named_scope(SCOPE_SHORTCONV):
            b, c, z = jnp.split(
                nn.Dense(3 * cfg.hidden, name="in_proj", **dense)(h), 3,
                axis=-1)
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (cfg.conv_taps, cfg.hidden))
            with jax.named_scope("mix"):
                gated = c * causal_conv(b * z, kernel.astype(cfg.dtype))
            return nn.Dense(cfg.hidden, name="out_proj", **dense)(gated)


class GatedMemoryUnit(nn.Module):
    """``W_2 (memory * silu(W_1 h))``: the layer's own input gates a
    Mamba layer's scan output, element by element, in place of a token
    mixer of its own (arXiv:2507.06607, section 2)."""
    cfg: object

    @nn.compact
    def __call__(self, h, memory):
        cfg = self.cfg
        dense = dict(use_bias=False, dtype=cfg.dtype)
        with jax.named_scope(SCOPE_GMU):
            gate = nn.silu(nn.Dense(memory.shape[-1], name="in_proj",
                                    **dense)(h))
            return nn.Dense(cfg.hidden, name="out_proj", **dense)(
                memory.astype(cfg.dtype) * gate)
