"""Transformer models: BERT-style encoder and a decoder-only LM.

Counterpart of the reference's BERT-large pretraining benchmark config
(BASELINE.json: "BERT-large pretraining (examples/pytorch, torch-xla
backend)"). TPU-first choices: bfloat16 activations with fp32 params,
einsum-formulated attention (MXU-friendly), optional jax.checkpoint
rematerialization per block, and head/hidden dimensions kept in multiples
of 128 for MXU tiling. Sequence/tensor sharding is applied externally via
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


@jax.named_scope("rope")
def _rope(q, k):
    """Rotary position embeddings (applied over the head dim)."""
    *_, seq, head_dim = q.shape
    half = head_dim // 2
    freqs = 1.0 / (10000.0 ** (np.arange(0, half) / half))
    t = np.arange(seq)
    angles = jnp.asarray(np.einsum("s,d->sd", t, freqs))
    sin, cos = jnp.sin(angles), jnp.cos(angles)

    def rot(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)
    return rot(q), rot(k)


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
            q = q.swapaxes(1, 2)
            k = k.swapaxes(1, 2)
            q, k = _rope(q, k)
            q = q.swapaxes(1, 2)
            k = k.swapaxes(1, 2)
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
