"""Transformer models: BERT-style encoder and a decoder-only LM.

Counterpart of the reference's BERT-large pretraining benchmark config
(BASELINE.json: "BERT-large pretraining (examples/pytorch, torch-xla
backend)"). TPU-first choices: bfloat16 activations with fp32 params;
attention either einsum-formulated (``attention_impl="einsum"``, the
default and the one that takes padding masks) or the Pallas flash kernel
of ``ops/flash_attention.py`` (``"flash"``, what the benchmark's cells
run); rotary positions as one rotation with its own
backward (``_rope``); optional jax.checkpoint rematerialization per
block. One ``Block`` / ``Backbone`` / ``TransformerLM`` skeleton serves
every configuration; ``TransformerConfig`` chooses the norm, the
attention (multi-head, or latent: ``mla``), the FFN (biased GELU,
bias-free SwiGLU, or per layer the expert layer of ``parallel/moe.py``)
and a multi-token-prediction module (``mtp_layers``). Hidden sizes are multiples of 128 for MXU tiling; the head
dimension is ``hidden // heads``, 64 at BERT-large's widths (half of the
128 lanes, which the kernel and XLA's layouts pay for), and has to be
even for rope. Sequence/tensor sharding is applied externally via
horovod_tpu.parallel (logical axis annotations would over-couple the model
to one partitioning).
"""

import dataclasses
import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.moe import MoEConfig, MoELayer

# Names in a device trace (docs/tracing.md); readers match the literals.
SCOPE_MLA = "hvd_mla"
SCOPE_MTP = "hvd_mtp"


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Latent attention: low-rank q and k/v, rope on ``rope_dim`` of the
    ``nope_dim + rope_dim`` query/key dimensions with one rope key
    shared by all heads."""
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    hidden: int = 1024
    layers: int = 24
    heads: int = 16
    mlp_ratio: int = 4
    max_len: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    # False | True/"full" (recompute everything) | "dots" (save matmul
    # outputs, recompute elementwise — near-free recompute, most of the
    # memory win; the policy that unlocks larger batches on 16G HBM).
    remat: object = False
    causal: bool = True
    use_rope: bool = True          # decoder LM; BERT uses learned positions
    attention_impl: str = "einsum"  # 'einsum' | 'flash' (pallas kernel)
    rope_theta: float = 10000.0
    norm: str = "layernorm"         # 'layernorm' | 'rmsnorm'
    norm_eps: float = 1e-6
    bias: bool = True               # biases in every projection and head
    mlp: str = "gelu"               # 'gelu' (two matrices) | 'swiglu'
    mlp_width: Optional[int] = None  # None: hidden * mlp_ratio
    mla: Optional[MLAConfig] = None  # latent attention in place of MHA
    moe: Optional[MoEConfig] = None  # expert FFN after moe.first_dense
    # Multi-token prediction (DeepSeek-V3, arXiv:2412.19437, eq. 21-25):
    # modules that predict the token after next from the last hidden
    # state and the next token's embedding; embedding and head shared.
    mtp_layers: int = 0


# BERT-large hyperparameters (the reference benchmark target).
def BertConfig(**overrides):
    base = dict(vocab_size=30522, hidden=1024, layers=24, heads=16,
                mlp_ratio=4, max_len=512, causal=False, use_rope=False)
    base.update(overrides)
    return TransformerConfig(**base)


def _rotate(x, cos, sin):
    """``x * cos + rotate_half(x) * sin`` in float32, cast to ``x.dtype``
    last: ``x`` (``[..., seq, heads, head_dim]``) rotated by the angles
    whose ``cos`` and ``sin`` (``[seq, 1, head_dim]``) these are.

    ``rotate_half(x) = [-x2, x1]`` is ``x`` times a constant signed
    permutation, not a slice and concatenate of the head dimension: those
    the TPU compiler answers with layout copies of float32 half heads,
    while the product shuffles the lanes on the MXU and takes the
    multiply-add as its output fusion, one pass over ``x``. Each entry of
    the product is one ``+-x`` element, so it is exact."""
    swap = np.kron([[0.0, 1.0], [-1.0, 0.0]], np.eye(x.shape[-1] // 2))
    swapped = jnp.matmul(x, jnp.asarray(swap, x.dtype),
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    return (x * cos + swapped * sin).astype(x.dtype)


_rotary = jax.custom_vjp(_rotate)
# The transpose of a rotation is the rotation by the negative angle: one
# pass over the cotangent, as forward (autodiff's transpose of the
# product and of the casts is not), with nothing saved but the tables.
_rotary.defvjp(lambda x, cos, sin: (_rotate(x, cos, sin), (cos, sin)),
               lambda tables, g: (_rotate(g, tables[0], -tables[1]),
                                  None, None))


@jax.named_scope("rope")
def _rope(q, k, theta=10000.0):
    """Rotary position embeddings over the head dimension of ``q`` and
    ``k`` (``[..., seq, heads, head_dim]``, head_dim even): base
    ``theta``, lane ``i`` paired with lane ``i + head_dim // 2``."""
    seq, half = q.shape[-3], q.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(half) / half))
    angles = jnp.asarray(np.einsum("s,d->sd", np.arange(seq), freqs),
                         jnp.float32)
    cos, sin = (jnp.concatenate([t, t], axis=-1)[:, None]
                for t in (jnp.cos(angles), jnp.sin(angles)))
    return _rotary(q, cos, sin), _rotary(k, cos, sin)


def _norm(cfg, name):
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)


def _attend(cfg, q, k, v, mask=None):
    """Softmax attention of ``[batch, seq, heads, dim]`` q, k, v by the
    configuration's implementation; the scale is that of q's width."""
    if cfg.attention_impl == "flash":
        # Pallas kernel path (ops/flash_attention.py): BHSD layout,
        # causal handled in-kernel. Per-sample padding masks need the
        # einsum path (the kernel's kv_len is per-call, not per-row).
        if mask is not None:
            raise ValueError(
                "attention_impl='flash' does not support padding "
                "masks; use 'einsum'")
        from ..ops.flash_attention import flash_attention
        # 1024 blocks: what a grid step fetches. Measured fastest at
        # head dimension 64 (round 3, by the deleted bench.py: 2048²
        # exceeds the 16M scoped-VMEM stack) and at 256 (PERF.md, PR 26:
        # 12.57 ms a layer against 13.3-17.7 at smaller blocks);
        # _clamp_blocks clamps to the sequence for shorter contexts. What
        # the mask leaves of a block the forward resolves finer, in its
        # own sub-tiles (ops/flash_attention.py: _sub_tile).
        return flash_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            causal=cfg.causal, block_q=1024,
            block_k=1024).swapaxes(1, 2)
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    seq = q.shape[1]
    if cfg.causal:
        causal = jnp.tril(jnp.ones((seq, seq), dtype=bool))
        logits = jnp.where(causal[None, None], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cfg.dtype), v)


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        head_dim = cfg.hidden // cfg.heads
        qkv = nn.DenseGeneral((3, cfg.heads, head_dim), dtype=cfg.dtype,
                              use_bias=cfg.bias, name="qkv")(x)
        q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        # (batch, seq, heads, head_dim) -> attention in einsum form.
        if cfg.use_rope:
            q, k = _rope(q, k, cfg.rope_theta)
        out = _attend(cfg, q, k, v, mask)
        return nn.DenseGeneral(cfg.hidden, axis=(-2, -1), dtype=cfg.dtype,
                               use_bias=cfg.bias, name="proj")(out)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) as
    training runs it: k and v are materialised per head from the latent
    (no absorbed form), every head's key ends in the one rope key."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None):
        cfg, m = self.cfg, self.cfg.mla
        dense = functools.partial(nn.DenseGeneral, use_bias=False,
                                  dtype=cfg.dtype)
        with jax.named_scope(SCOPE_MLA):
            c_q = _norm(cfg, "q_norm")(dense(m.q_rank, name="q_a")(x))
            q = dense((cfg.heads, m.nope_dim + m.rope_dim), name="q_b")(c_q)
            kv = dense(m.kv_rank + m.rope_dim, name="kv_a")(x)
            c_kv = _norm(cfg, "kv_norm")(kv[..., :m.kv_rank])
            k_rope = kv[..., None, m.kv_rank:]      # [b, s, 1, rope_dim]
            kv = dense((cfg.heads, m.nope_dim + m.v_dim), name="kv_b")(c_kv)
            q_rope, k_rope = _rope(q[..., m.nope_dim:], k_rope,
                                   cfg.rope_theta)
            q = jnp.concatenate([q[..., :m.nope_dim], q_rope], axis=-1)
            k = jnp.concatenate(
                [kv[..., :m.nope_dim],
                 jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
            out = _attend(cfg, q, k, kv[..., m.nope_dim:], mask)
            return dense(cfg.hidden, axis=(-2, -1), name="proj")(out)


class Block(nn.Module):
    cfg: TransformerConfig
    expert: bool = False    # the FFN is the expert layer (cfg.moe)

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        attention = LatentAttention if cfg.mla else Attention
        h = _norm(cfg, "ln1")(x)
        x = x + attention(cfg, name="attn")(h, mask)
        h = _norm(cfg, "ln2")(x)
        if self.expert:
            return x + MoELayer(cfg.moe, dtype=cfg.dtype, name="moe")(h)
        width = cfg.mlp_width or cfg.hidden * cfg.mlp_ratio
        dense = functools.partial(nn.Dense, dtype=cfg.dtype,
                                  use_bias=cfg.bias)
        if cfg.mlp == "swiglu":
            h = nn.silu(dense(width, name="mlp_gate")(h)) * dense(
                width, name="mlp_in")(h)
        else:
            h = nn.gelu(dense(width, name="mlp_in")(h))
        return x + dense(cfg.hidden, name="mlp_out")(h)


def _block(cfg):
    if cfg.remat == "dots":
        return nn.remat(
            Block,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return nn.remat(Block) if cfg.remat else Block


class MTPModule(nn.Module):
    """One multi-token-prediction module: the last hidden state and the
    next token's embedding, each normed, projected together to the model
    width; one more block; an output norm of its own."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h, embedded):
        cfg = self.cfg
        joined = jnp.concatenate([_norm(cfg, "embed_norm")(embedded),
                                  _norm(cfg, "hidden_norm")(h)], axis=-1)
        x = nn.Dense(cfg.hidden, dtype=cfg.dtype, use_bias=False,
                     name="proj")(joined)
        x = _block(cfg)(cfg, expert=cfg.moe is not None, name="block")(x)
        return x, _norm(cfg, "ln_f")(x)


class Backbone(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, mask=None, next_tokens=None):
        """The final hidden states; with ``next_tokens`` (the tokens
        shifted by one) and ``cfg.mtp_layers``, a tuple of them: the
        main model's, then each MTP module's."""
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=cfg.dtype,
                         name="tok_embed")
        x = embed(tokens)
        if not cfg.use_rope:
            pos = nn.Embed(cfg.max_len, cfg.hidden, dtype=cfg.dtype,
                           name="pos_embed")(jnp.arange(tokens.shape[1]))
            x = x + pos[None]
        block = _block(cfg)
        for i in range(cfg.layers):
            expert = cfg.moe is not None and i >= cfg.moe.first_dense
            x = block(cfg, expert=expert, name=f"block_{i}")(x, mask)
        out = _norm(cfg, "ln_f")(x)
        if next_tokens is None or not cfg.mtp_layers:
            return out
        outs = [out]
        with jax.named_scope(SCOPE_MTP):
            for i in range(cfg.mtp_layers):
                # Module i reads the token i + 1 places on: the shift of
                # the last position wraps and predicts nothing the loss
                # counts.
                x, out = MTPModule(cfg, name=f"mtp_{i}")(
                    x, embed(jnp.roll(next_tokens, -i, axis=1)))
                outs.append(out)
        return tuple(outs)


class TransformerLM(nn.Module):
    """Decoder-only causal LM (flagship model for long-context /
    sequence-parallel training)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, mask=None, next_tokens=None):
        """Float32 logits; with ``next_tokens`` and ``cfg.mtp_layers`` a
        tuple: the main model's, then each MTP module's (logits ``i`` of
        module ``d`` are for token ``i + d + 2``)."""
        cfg = self.cfg
        x = Backbone(cfg, name="backbone")(tokens, mask, next_tokens)
        # bf16 matmul on the MXU (fp32 here costs several passes of MXU
        # time on a 1024x30k projection), fp32 logits for the softmax.
        head = nn.Dense(cfg.vocab_size, dtype=cfg.dtype, use_bias=cfg.bias,
                        name="lm_head")
        if isinstance(x, tuple):
            return tuple(head(h).astype(jnp.float32) for h in x)
        return head(x).astype(jnp.float32)


class BertModel(nn.Module):
    """BERT-style encoder with a masked-LM head (pretraining objective of
    the reference's BERT-large benchmark)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, mask=None):
        cfg = self.cfg
        x = Backbone(cfg, name="backbone")(tokens, mask)
        x = nn.Dense(cfg.hidden, dtype=cfg.dtype, name="mlm_dense")(x)
        x = nn.gelu(x)
        x = nn.LayerNorm(dtype=cfg.dtype, name="mlm_ln")(x)
        return nn.Dense(cfg.vocab_size, dtype=cfg.dtype,
                        name="mlm_head")(x).astype(jnp.float32)
