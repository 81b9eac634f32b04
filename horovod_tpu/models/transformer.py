"""Transformer models: BERT-style encoder and a decoder-only LM.

Counterpart of the reference's BERT-large pretraining benchmark config
(BASELINE.json: "BERT-large pretraining (examples/pytorch, torch-xla
backend)"). TPU-first choices: bfloat16 activations with fp32 params;
attention either einsum-formulated (``attention_impl="einsum"``, the
default and the one that takes padding masks) or the Pallas flash kernel
of ``ops/flash_attention.py`` (``"flash"``, what the benchmark's cells
run, at 1024 tiles); rotary positions as one rotation with its own
backward (``_rope``); optional jax.checkpoint rematerialization per
block. Hidden sizes are multiples of 128 for MXU tiling; the head
dimension is ``hidden // heads``, 64 at BERT-large's widths (half of the
128 lanes, which the kernel and XLA's layouts pay for), and has to be
even for rope. Sequence/tensor sharding is applied externally via
horovod_tpu.parallel (logical axis annotations would over-couple the model
to one partitioning).
"""

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


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
def _rope(q, k):
    """Rotary position embeddings over the head dimension of ``q`` and
    ``k`` (``[..., seq, heads, head_dim]``, head_dim even): base 10000,
    lane ``i`` paired with lane ``i + head_dim // 2``."""
    seq, half = q.shape[-3], q.shape[-1] // 2
    freqs = 1.0 / (10000.0 ** (np.arange(half) / half))
    angles = jnp.asarray(np.einsum("s,d->sd", np.arange(seq), freqs),
                         jnp.float32)
    cos, sin = (jnp.concatenate([t, t], axis=-1)[:, None]
                for t in (jnp.cos(angles), jnp.sin(angles)))
    return _rotary(q, cos, sin), _rotary(k, cos, sin)


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        head_dim = cfg.hidden // cfg.heads
        qkv = nn.DenseGeneral((3, cfg.heads, head_dim), dtype=cfg.dtype,
                              name="qkv")(x)
        q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        # (batch, seq, heads, head_dim) -> attention in einsum form.
        if cfg.use_rope:
            q, k = _rope(q, k)
        if cfg.attention_impl == "flash":
            # Pallas kernel path (ops/flash_attention.py): BHSD layout,
            # causal handled in-kernel. Per-sample padding masks need the
            # einsum path (the kernel's kv_len is per-call, not per-row).
            if mask is not None:
                raise ValueError(
                    "attention_impl='flash' does not support padding "
                    "masks; use 'einsum'")
            from ..ops.flash_attention import flash_attention
            # 1024-tiles measured fastest (round-3 sweep, docs/PERF.md:
            # 2048² exceeds the 16M scoped-VMEM stack; _prepare clamps to
            # the sequence for shorter contexts).
            out = flash_attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                causal=cfg.causal, block_q=1024,
                block_k=1024).swapaxes(1, 2)
        else:
            scale = 1.0 / np.sqrt(head_dim)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
            seq = x.shape[1]
            if cfg.causal:
                causal = jnp.tril(jnp.ones((seq, seq), dtype=bool))
                logits = jnp.where(causal[None, None], logits, -1e30)
            if mask is not None:
                logits = jnp.where(mask[:, None, None, :], logits, -1e30)
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            probs = probs.astype(cfg.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return nn.DenseGeneral(cfg.hidden, axis=(-2, -1), dtype=cfg.dtype,
                               name="proj")(out)


class Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln1")(x)
        x = x + Attention(cfg, name="attn")(h, mask)
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln2")(x)
        h = nn.Dense(cfg.hidden * cfg.mlp_ratio, dtype=cfg.dtype,
                     name="mlp_in")(h)
        h = nn.gelu(h)
        h = nn.Dense(cfg.hidden, dtype=cfg.dtype, name="mlp_out")(h)
        return x + h


class Backbone(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, mask=None):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=cfg.dtype,
                     name="tok_embed")(tokens)
        if not cfg.use_rope:
            pos = nn.Embed(cfg.max_len, cfg.hidden, dtype=cfg.dtype,
                           name="pos_embed")(jnp.arange(tokens.shape[1]))
            x = x + pos[None]
        block = Block
        if cfg.remat == "dots":
            block = nn.remat(
                Block,
                policy=jax.checkpoint_policies.
                dots_with_no_batch_dims_saveable)
        elif cfg.remat:
            block = nn.remat(Block)
        for i in range(cfg.layers):
            x = block(cfg, name=f"block_{i}")(x, mask)
        return nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)


class TransformerLM(nn.Module):
    """Decoder-only causal LM (flagship model for long-context /
    sequence-parallel training)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, mask=None):
        cfg = self.cfg
        x = Backbone(cfg, name="backbone")(tokens, mask)
        # bf16 matmul on the MXU (fp32 here costs several passes of MXU
        # time on a 1024x30k projection), fp32 logits for the softmax.
        logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype,
                          name="lm_head")(x)
        return logits.astype(jnp.float32)


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
