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
bias-free SwiGLU, or per layer the expert layer of ``parallel/moe.py``),
a multi-token-prediction module (``mtp_layers``), and a looped stack:
the blocks run ``passes`` times with the same weights, with norms on the
sub-layers' outputs too (``sandwich_norm``) and an exit gate whose loss
is ``looped_lm_loss``; or a stack with a mixer a layer (``mixers``: Mamba,
Mamba-2, gated memory units and gated short convolutions of
``models/ssm.py``, no token mixer at all,
windowed, full and cross differential attention with grouped K/V
heads, and plain softmax attention, full or under a window, each with
rope or without positions: ``PLAIN``; with a norm on every head's q
and k where ``qk_norm``; and attention over the keys a learned indexer
picks for every query, ``SPARSE``, with rope whose lanes take their
angle from three position streams), in which a layer may
read what an earlier layer made, with an FFN a layer too (``ffns``:
dense, the expert layer, or none, so that a block may be a mixer or an
FFN alone), and a head tied to the embedding.
Hidden sizes are multiples of 128 for MXU tiling; the head
dimension is ``head_dim`` where the configuration states one (28 heads
of 128 on a hidden size of 2560) and else ``hidden // heads``, 64 at
BERT-large's widths (half of the
128 lanes, which the kernel and XLA's layouts pay for), and has to be
even for rope. Sequence/tensor sharding is applied externally via
horovod_tpu.parallel (logical axis annotations would over-couple the model
to one partitioning).
"""

import contextlib
import dataclasses
import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..parallel.moe import MoEConfig, MoELayer
from .ssm import (GatedMemoryUnit, Mamba2Mixer, MambaMixer, ShortConv,
                  SSMConfig)

# Names in a device trace (docs/tracing.md); readers match the literals.
SCOPE_MLA = "hvd_mla"
SCOPE_DIFF = "hvd_diff"     # what differential attention adds to the kernels
# A plain attention layer of a mixed stack (``PLAIN``), by what its keys
# are: every one before the query, or the window's.
SCOPE_FULL = "hvd_attn_full"
SCOPE_WINDOW = "hvd_attn_window"
SCOPE_MTP = "hvd_mtp"
SCOPE_LOOP = "hvd_loop"     # the stack of a looped model, all its passes
SCOPE_EXIT = "hvd_exit"     # its exit gates, heads and the loss's mix
# A ``SPARSE`` layer's indexer, selection, kernels and alignment pass run
# under ``ops.sparse_attention.SCOPE`` ("hvd_dsa") and its four parts.
DSA_STATE = "dsa_state"     # flax collection: alignment loss, keys selected


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
class IndexerConfig:
    """The indexer of a ``SPARSE`` layer (DeepSeek-V3.2-Exp's sparse
    attention): ``heads`` query heads of ``head_dim`` over one key head
    score every causal pair, and a query attends over the ``topk`` keys
    of largest score."""
    heads: int
    head_dim: int
    topk: int


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    hidden: int = 1024
    layers: int = 24
    heads: int = 16
    head_dim: Optional[int] = None   # None: hidden // heads
    mlp_ratio: int = 4
    max_len: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    # What a block keeps for its backward pass. False: everything.
    # "dots": the matrix products' outputs (element-wise work and the
    # flash kernel are made again). "flash": the block's input and what
    # the Mosaic kernels hand their backward passes: the flash kernel's
    # output and log-sum-exp, the selective scan's output and chunk
    # states, the Mamba-2 recurrence's output and chunk states
    # (everything but those is made again). True/"full": the block's
    # input alone.
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
    # A looped stack (Universal Transformer, arXiv:1807.03819; looped
    # language models, arXiv:2510.25741): the ``layers`` blocks and the
    # final norm are applied ``passes`` times with the same weights, each
    # pass's normed state the next one's input and an output of its own.
    passes: int = 1
    # A norm on the output of attention and of the FFN before the
    # residual add (``ln1_out``, ``ln2_out``), besides those on the inputs.
    sandwich_norm: bool = False
    # A ``hidden -> 1`` product with bias on each pass's normed state:
    # the logit of leaving after that pass (``looped_lm_loss``).
    exit_gate: bool = False
    # A stack whose layers differ in their token mixer (SambaY,
    # arXiv:2507.06607): one kind a layer, of MIXERS. None: every layer
    # is plain attention. A layer may read what an earlier layer made: a
    # "gmu" layer the scan output of the last "mamba" layer before it, a
    # "cross" layer the K and V of the last "attention" layer before it.
    # "attention", "window" and "cross" are differential attention (Ye et
    # al., arXiv:2410.05258; ``DiffAttention``); the kinds of ``PLAIN``
    # are plain softmax attention, which hands nothing on and takes its
    # positions from its kind (``use_rope`` is for a stack without
    # ``mixers``); a "conv" layer is a gated short convolution of
    # ``conv_taps`` taps (``ssm.ShortConv``), which hands nothing on; a
    # ``SPARSE`` layer is plain attention with rope over the keys its
    # indexer (``indexer``) selects for each query, and hands out its
    # alignment loss through the collection ``DSA_STATE``; a "mamba2"
    # layer is ``ssm.Mamba2Mixer`` (sizes in ``ssm``) and hands nothing
    # on; a ``NO_MIXER`` layer has no token mixer and no ``ln1``: the
    # block is its FFN under ``ln2`` and one residual add.
    mixers: Optional[tuple] = None
    # The FFN of every layer, one of FFNS a layer: "dense" (``mlp``),
    # "expert" (``moe``), or ``NO_FFN``: the block is its mixer under
    # ``ln1`` and one residual add. None: the expert layer from
    # ``moe.first_dense`` on where there is ``moe``, else dense. A layer
    # with neither part is refused.
    ffns: Optional[tuple] = None
    # The layers' indices in the published model where the stack is a
    # cut of it (differential attention's lambda_init depends on depth).
    layer_indices: Optional[tuple] = None
    window: Optional[int] = None     # keys a "window" / "sliding" layer sees
    kv_heads: Optional[int] = None   # K/V heads (None: as many as heads)
    ssm: Optional[SSMConfig] = None  # the "mamba" / "mamba2" layers' sizes
    mlp_bias: Optional[bool] = None  # None: as ``bias``
    positions: bool = True           # False: neither rope nor a table
    tie_embeddings: bool = False     # the head is the embedding's transpose
    # A norm of the model's kind over every head's q and k (one gain of
    # ``head_width`` each, shared by the heads) before rope, in
    # ``Attention``; v is not normed.
    qk_norm: bool = False
    conv_taps: int = 3               # taps of a "conv" layer's filter
    indexer: Optional[IndexerConfig] = None     # of the ``SPARSE`` layers
    # Rope over several position streams (M-RoPE, Qwen2-VL,
    # arXiv:2409.12191) in the ``SPARSE`` layers: frequency pair ``i`` of
    # a head turns by stream ``s``'s position where ``i`` lies in the
    # ``s``-th run of ``rope_sections`` (consecutive pairs, summing to
    # half the head's width). ``rope_layout`` says what a row holds, from
    # which its table of positions is made (``mrope_positions``):
    # ``("text", n)`` and ``("image", rows, columns)`` spans, a constant
    # of the configuration. None: every stream is the token's index.
    rope_sections: Optional[tuple] = None
    rope_layout: Optional[tuple] = None

    @property
    def head_width(self):
        return self.head_dim or self.hidden // self.heads


# Plain softmax attention as a layer kind: (sees ``cfg.window`` keys
# only, rotates q and k). Without rope such a layer has no positions.
PLAIN = {"full": (False, False), "full_rope": (False, True),
         "sliding": (True, False), "sliding_rope": (True, True)}
# Attention over the keys the layer's indexer selects, with rope.
SPARSE = "sparse_rope"
NO_MIXER = NO_FFN = "none"      # a block without that part
# "window": attention that sees ``cfg.window`` keys and hands nothing on.
MIXERS = ("attention", "window", "mamba", "gmu", "cross", "conv", *PLAIN,
          SPARSE, "mamba2", NO_MIXER)
FFNS = ("dense", "expert", NO_FFN)


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


def mrope_positions(layout):
    """The table of positions ``[tokens, 3]`` (time, height, width) of a
    row that holds the spans of ``layout`` in order (Qwen2-VL's rule): a
    text token's three are the running position ``p``; an image of
    ``rows x columns`` merged patches that starts at ``p0`` gives the
    patch in row ``r``, column ``c`` ``(p0, p0 + r, p0 + c)``, and the
    text after it resumes at ``p0 + max(rows, columns)``."""
    out, p = [], 0
    for kind, *size in layout:
        if kind == "text":
            at = p + np.arange(size[0])
            out.append(np.stack([at, at, at], axis=1))
            p += size[0]
        elif kind == "image":
            r, c = np.divmod(np.arange(size[0] * size[1]), size[1])
            out.append(np.stack([np.full_like(r, p), p + r, p + c], axis=1))
            p += max(size)
        else:
            raise ValueError(f"a span is text or image, not {kind!r}")
    return np.concatenate(out)


@jax.named_scope("rope")
def _rope(q, k, theta=10000.0, positions=None, sections=None):
    """Rotary position embeddings over the head dimension of ``q`` and
    ``k`` (``[..., seq, heads, head_dim]``, head_dim even): base
    ``theta``, lane ``i`` paired with lane ``i + head_dim // 2``.
    ``positions`` (numpy, ``[seq]`` or ``[seq, streams]``; None: the
    token's index) are what the angles are taken at; with ``sections``,
    frequency pair ``i`` reads the stream whose run of ``sections`` it
    lies in, and without, the first."""
    seq, half = q.shape[-3], q.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(half) / half))
    positions = np.arange(seq) if positions is None else np.asarray(positions)
    if positions.ndim == 2:
        stream = (np.repeat(np.arange(len(sections)), sections)
                  if sections else np.zeros(half, int))
        if len(stream) != half or len(positions) != seq:
            raise ValueError(
                f"rope: sections {sections} over {half} pairs, "
                f"{len(positions)} positions for {seq} tokens")
        positions = positions[:, stream]
        products = positions * freqs[None, :]
    else:
        products = np.einsum("s,d->sd", positions, freqs)
    angles = jnp.asarray(products, jnp.float32)
    cos, sin = (jnp.concatenate([t, t], axis=-1)[:, None]
                for t in (jnp.cos(angles), jnp.sin(angles)))
    return _rotary(q, cos, sin), _rotary(k, cos, sin)


def _norm(cfg, name):
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)


def _head(cfg):
    # bf16 matmul on the MXU (fp32 here costs several passes of MXU
    # time on a 1024x30k projection), fp32 logits for the softmax.
    return nn.Dense(cfg.vocab_size, dtype=cfg.dtype, use_bias=cfg.bias,
                    name="lm_head")


def _attend(cfg, q, k, v, mask=None, window=None):
    """Softmax attention of ``[batch, seq, heads, dim]`` q, k, v by the
    configuration's implementation; the scale is that of q's width.
    ``k`` and ``v`` may hold fewer heads than ``q`` (query head ``h``
    reads head ``h // group``) and ``v`` another width; with ``window``
    a query sees that many keys, itself the last. ``mask`` is a padding
    mask of ``[batch, keys]`` and takes the einsum path; the flash path
    takes the causal mask, a window and grouped heads, and, not from
    here, a mask of (key, query) pairs shared by the heads: a
    ``SPARSE`` layer's selected set goes to the kernels through
    ``ops.sparse_attention`` (``flash_attention(mask=)``)."""
    if cfg.attention_impl == "flash":
        # Pallas kernel path (ops/flash_attention.py): q, k, v handed
        # over as they are held (``layout="bshd"``: at a head of 64 the
        # kernels read the order XLA keeps them in, and nothing is
        # copied; ``flash_attention.addressing``), the
        # causal mask and the window handled in-kernel (tiles they hide
        # are neither run nor fetched), grouped K/V heads read through
        # the kernels' index maps. Per-sample padding masks need the
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
            q, k, v, causal=cfg.causal, block_q=1024, block_k=1024,
            window=window, layout="bshd")
    if k.shape[2] != q.shape[2]:
        k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2)
                for x in (k, v))
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    seq = q.shape[1]
    if cfg.causal:
        causal = jnp.tril(jnp.ones((seq, seq), dtype=bool))
        if window is not None:
            causal = jnp.logical_and(causal, jnp.triu(
                jnp.ones((seq, seq), dtype=bool), 1 - window))
        logits = jnp.where(causal[None, None], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cfg.dtype), v)


def _qkv(cfg, x, name="qkv"):
    """q, k, v of ``x`` from one product: ``[.., 3, heads, head_dim]``
    features, or with fewer K/V heads ``[.., heads + 2 kv_heads,
    head_dim]`` (q's heads, then k's, then v's)."""
    head_dim = cfg.head_width
    kv = cfg.kv_heads or cfg.heads
    if kv == cfg.heads:
        qkv = nn.DenseGeneral((3, cfg.heads, head_dim), dtype=cfg.dtype,
                              use_bias=cfg.bias, name=name)(x)
        return qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
    qkv = nn.DenseGeneral((cfg.heads + 2 * kv, head_dim), dtype=cfg.dtype,
                          use_bias=cfg.bias, name=name)(x)
    return (qkv[..., :cfg.heads, :], qkv[..., cfg.heads:cfg.heads + kv, :],
            qkv[..., cfg.heads + kv:, :])


class Indexer(nn.Module):
    """The indexer of a ``SPARSE`` layer on the layer's normed input,
    detached: ``(q_i, k_i, w)``, ``cfg.indexer.heads`` query heads of
    ``head_dim`` and one key head (a LayerNorm on it), both turned by
    plain rope at the first position stream, and a weight a head a
    token in float32, scaled by ``heads ** -0.5 * head_dim ** -0.5``.
    The index score of a pair is ``sum_j w[t, j] relu(q_i[t, j] .
    k_i[s])`` (``ops.sparse_attention``). Nothing upstream of the layer
    feels the indexer: its input carries no gradient."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h, positions=None):
        cfg, ix = self.cfg, self.cfg.indexer
        h = jax.lax.stop_gradient(h)
        q_i = nn.DenseGeneral((ix.heads, ix.head_dim), dtype=cfg.dtype,
                              use_bias=False, name="q")(h)
        k_i = nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                           name="k_norm")(
            nn.Dense(ix.head_dim, dtype=cfg.dtype, use_bias=False,
                     name="k")(h))
        q_i, k_i = _rope(q_i, k_i[..., None, :], cfg.rope_theta,
                         None if positions is None else positions[:, 0])
        w = nn.Dense(ix.heads, dtype=jnp.float32, use_bias=False,
                     precision=jax.lax.Precision.HIGHEST, name="w")(h)
        return q_i, k_i[..., 0, :], w * (ix.heads * ix.head_dim) ** -0.5


class Attention(nn.Module):
    """Softmax attention over ``cfg.heads`` query heads and
    ``cfg.kv_heads`` K/V heads. ``kind``, one of ``PLAIN`` or
    ``SPARSE``, is for a layer of a mixed stack: its window and its
    positions are its kind's, and it runs under a scope that says which
    keys it sees. A ``SPARSE`` layer sees, of the keys before a query,
    the ``cfg.indexer.topk`` its ``Indexer`` scores highest
    (``ops.sparse_attention``, whatever ``cfg.attention_impl``: the
    flash kernels under the selected set as a mask), rotates q and k by
    ``cfg.rope_sections`` over the positions of ``cfg.rope_layout``,
    and leaves in collection ``DSA_STATE`` its alignment loss, the mean
    over rows and queries of ``KL(head-mean attention || softmax of the
    index scores)`` on the selected sets, which trains the indexer alone
    (``dsa_align_loss`` sums the layers'), and the mean number of keys a
    query selected."""
    cfg: TransformerConfig
    kind: Optional[str] = None

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        sliding, rope = PLAIN.get(self.kind, (False, cfg.use_rope))
        q, k, v = _qkv(cfg, x)
        # (batch, seq, heads, head_dim) -> attention in einsum form.
        if cfg.qk_norm:
            q, k = _norm(cfg, "q_norm")(q), _norm(cfg, "k_norm")(k)
        if self.kind == SPARSE:
            return self._sparse(x, q, k, v, mask)
        if rope:
            q, k = _rope(q, k, cfg.rope_theta)
        scope = (contextlib.nullcontext() if self.kind is None else
                 jax.named_scope(SCOPE_WINDOW if sliding else SCOPE_FULL))
        with scope:
            out = _attend(cfg, q, k, v, mask, cfg.window if sliding else None)
        return nn.DenseGeneral(cfg.hidden, axis=(-2, -1), dtype=cfg.dtype,
                               use_bias=cfg.bias, name="proj")(out)

    def _sparse(self, x, q, k, v, mask):
        from ..ops import sparse_attention as dsa
        cfg = self.cfg
        if mask is not None or not cfg.causal or cfg.indexer is None:
            raise ValueError(
                f"a {SPARSE!r} layer is causal, takes no padding mask and "
                f"needs TransformerConfig.indexer")
        positions = (None if cfg.rope_layout is None
                     else mrope_positions(cfg.rope_layout))
        q, k = _rope(q, k, cfg.rope_theta, positions, cfg.rope_sections)
        with jax.named_scope(dsa.SCOPE), jax.named_scope(dsa.SCOPE_INDEX):
            q_i, k_i, w = Indexer(cfg, name="indexer")(x, positions)
        align = self.variable(DSA_STATE, "align_loss", jnp.zeros, ())
        selected = self.variable(DSA_STATE, "selected_keys", jnp.zeros, ())
        keeps = self.is_mutable_collection(DSA_STATE)
        out, loss, count = dsa.sparse_attention(
            q, k, v, q_i, k_i, w, cfg.indexer.topk, with_align=keeps)
        if keeps:
            align.value, selected.value = loss, count
        return nn.DenseGeneral(cfg.hidden, axis=(-2, -1), dtype=cfg.dtype,
                               use_bias=cfg.bias, name="proj")(out)


def dsa_align_loss(state):
    """``sum_l L_I(l)``: the alignment losses that the ``SPARSE`` layers
    of a call left in collection ``DSA_STATE`` (``state``: that
    collection, or the tree of collections ``apply(..., mutable=)``
    returned). Differentiable: add it to the language model's loss
    inside the loss function; its gradient reaches the indexers' leaves
    and nothing else."""
    found = [leaf for path, leaf in
             jax.tree_util.tree_leaves_with_path(state)
             if getattr(path[-1], "key", None) == "align_loss"]
    return sum(found) if found else 0.0


def publish_dsa(state, seq=None, topk=None):
    """Set ``hvd_dsa_align_loss{layer}`` and ``hvd_dsa_selected_keys
    {layer}`` from the ``DSA_STATE`` collection a train step returned
    and, given the row's length and the indexer's ``topk``,
    ``hvd_dsa_kept_share`` (selected over causal pairs) and
    ``hvd_dsa_mask_bytes`` (a layer's kept mask, a row), both from
    shapes. Call it outside the step; it fetches the arrays. A no-op
    when ``HOROVOD_TPU_METRICS`` is off."""
    from ..ops import sparse_attention as dsa
    from ..parallel.sharding import _path_str
    from ..telemetry import core as telemetry
    if not telemetry.enabled():
        return
    gauges = {
        "align_loss": telemetry.gauge(
            "hvd_dsa_align_loss",
            "The layer's alignment loss in the last step: the mean over "
            "queries of KL(head-mean attention || softmax of the index "
            "scores) on the selected keys", ("layer",)),
        "selected_keys": telemetry.gauge(
            "hvd_dsa_selected_keys",
            "Mean number of keys a query of the layer selected in the last "
            "step, counted from the mask", ("layer",))}
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        gauge = gauges.get(getattr(path[-1], "key", None))
        if gauge is not None:
            gauge.labels(layer=_path_str(path[:-1])).set(
                float(jax.device_get(leaf)))
    if seq and topk:
        telemetry.gauge(
            "hvd_dsa_kept_share",
            "Selected pairs over causal pairs of a row (from shapes)").set(
                dsa.kept_share(seq, topk))
        telemetry.gauge(
            "hvd_dsa_mask_bytes",
            "Bytes of the selected set a sparse layer keeps for its "
            "backward pass, a row: int8 [keys, queries]").set(
                float(seq * seq))


def diff_lambda_init(depth):
    """Differential attention's ``lambda_init`` at layer ``depth`` of the
    whole model, 0-based (arXiv:2410.05258, section 2.1)."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * depth))


class DiffAttention(nn.Module):
    """Differential attention with grouped K/V heads (arXiv:2410.05258 as
    arXiv:2507.06607 runs it). Adjacent heads pair: query pair ``p`` is
    heads ``(2p, 2p + 1)``, K/V pair ``g = p // group`` the K heads
    ``(2g, 2g + 1)`` and their two V heads side by side, twice a head
    wide. ``a1 = softmax(q1 k1^T) v``, ``a2 = softmax(q2 k2^T) v``, and
    the pair's output is ``RMSNorm(a1 - lambda a2) (1 - lambda_init)``.

    Returns ``(out, (k, v))``. With ``shared`` (an earlier layer's ``(k,
    v)``) the layer projects q alone: cross-attention over one K/V that
    several layers read."""
    cfg: TransformerConfig
    depth: int = 0
    window: Optional[int] = None
    cross: bool = False

    @nn.compact
    def __call__(self, x, mask=None, shared=None):
        cfg = self.cfg
        head_dim = cfg.head_width
        if self.cross:
            q = nn.DenseGeneral((cfg.heads, head_dim), dtype=cfg.dtype,
                                use_bias=cfg.bias, name="q")(x)
            k, v = shared
        else:
            q, k, v = _qkv(cfg, x)
        if cfg.use_rope:
            q, k = _rope(q, k, cfg.rope_theta)
        pairs = v.shape[:2] + (v.shape[2] // 2, 2 * head_dim)
        maps = [_attend(cfg, q[..., i::2, :], k[..., i::2, :],
                        v.reshape(pairs), mask, self.window)
                for i in (0, 1)]
        with jax.named_scope(SCOPE_DIFF):
            vectors = [self.param(f"lambda_{name}",
                                  nn.initializers.normal(0.1), (head_dim,))
                       for name in ("q1", "k1", "q2", "k2")]
            lambda_init = diff_lambda_init(self.depth)
            lam = (jnp.exp(jnp.sum(vectors[0] * vectors[1]))
                   - jnp.exp(jnp.sum(vectors[2] * vectors[3])) + lambda_init)
            mixed = (maps[0].astype(jnp.float32)
                     - lam * maps[1].astype(jnp.float32))
            out = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                             name="subln")(mixed) * (1.0 - lambda_init)
            out = out.astype(cfg.dtype).reshape(*x.shape[:2], cfg.hidden)
        return nn.Dense(cfg.hidden, dtype=cfg.dtype, use_bias=cfg.bias,
                        name="proj")(out), (k, v)


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
    ffn: str = "dense"              # one of FFNS: "expert" is cfg.moe's
    mixer: Optional[str] = None     # one of MIXERS (cfg.mixers[layer])
    depth: int = 0                  # the layer's index in the whole model

    @nn.compact
    def __call__(self, x, mask=None, memory=None, shared_kv=None):
        """The block's output; in a stack of mixed layers
        (``self.mixer``) a pair: the output, and what the mixer made for
        the layers after it (a "mamba" layer its scan output, an
        "attention" layer its ``(k, v)``, the others None). ``memory``
        and ``shared_kv`` are what earlier layers made: inputs of the
        block, which recomputation keeps and does not make again."""
        cfg = self.cfg
        if self.mixer == NO_MIXER and self.ffn == NO_FFN:
            raise ValueError("a block with neither a mixer nor an FFN")

        def out(name, y):
            return _norm(cfg, name)(y) if cfg.sandwich_norm else y

        # A part that is not there has no norm and no residual add.
        h = None if self.mixer == NO_MIXER else _norm(cfg, "ln1")(x)
        a = made = None
        if self.mixer == "mamba":
            a, made = MambaMixer(cfg, name="mamba")(h)
        elif self.mixer == "mamba2":
            a = Mamba2Mixer(cfg, name="mamba2")(h)
        elif self.mixer == NO_MIXER:
            pass
        elif self.mixer == "gmu":
            a = GatedMemoryUnit(cfg, name="gmu")(h, memory)
        elif self.mixer == "conv":
            a = ShortConv(cfg, name="conv")(h)
        elif self.mixer in PLAIN or self.mixer == SPARSE:
            a = Attention(cfg, kind=self.mixer, name="attn")(h, mask)
        elif self.mixer is not None:
            a, kv = DiffAttention(
                cfg, depth=self.depth, cross=self.mixer == "cross",
                window=cfg.window if self.mixer == "window" else None,
                name="attn")(h, mask, shared_kv)
            made = kv if self.mixer == "attention" else None
        else:
            attention = LatentAttention if cfg.mla else Attention
            a = attention(cfg, name="attn")(h, mask)
        if a is not None:
            x = x + out("ln1_out", a)
        if self.ffn == NO_FFN:
            return x, made
        # A router placed before attention scores what attention read.
        scores_from = h if (self.ffn == "expert" and cfg.moe.router_reads
                            == "attention") else None
        h = _norm(cfg, "ln2")(x)
        if self.ffn == "expert":
            ffn = MoELayer(cfg.moe, dtype=cfg.dtype, name="moe")(
                h, scores_from)
        else:
            width = cfg.mlp_width or cfg.hidden * cfg.mlp_ratio
            dense = functools.partial(
                nn.Dense, dtype=cfg.dtype,
                use_bias=cfg.bias if cfg.mlp_bias is None else cfg.mlp_bias)
            if cfg.mlp == "swiglu":
                h = nn.silu(dense(width, name="mlp_gate")(h)) * dense(
                    width, name="mlp_in")(h)
            else:
                h = nn.gelu(dense(width, name="mlp_in")(h))
            ffn = dense(cfg.hidden, name="mlp_out")(h)
        x = x + out("ln2_out", ffn)
        return x if self.mixer is None else (x, made)


def _block(cfg):
    policies = jax.checkpoint_policies
    if cfg.remat == "dots":
        return nn.remat(Block,
                        policy=policies.dots_with_no_batch_dims_saveable)
    if cfg.remat == "flash":
        # What the Mosaic kernels and the Mamba-2 recurrence hand their
        # backward passes: neither the flash forward nor either scan
        # runs again.
        from ..ops import flash_attention, selective_scan, ssd
        return nn.remat(Block, policy=policies.save_only_these_names(
            *flash_attention.SAVED_NAMES, *selective_scan.SAVED_NAMES,
            *ssd.SAVED_NAMES))
    return nn.remat(Block) if cfg.remat else Block


# ``nn.scan`` over a function of a module that uses the module's own
# parameters at every iteration: one leaf a weight, however often used.
_scan_shared = functools.partial(nn.scan, variable_broadcast="params",
                                 split_rngs={"params": False})


def _looped(module, body, x, passes):
    """``body(module, x)`` applied ``passes`` times, each result the next
    call's ``x``, with the same parameters (one leaf a weight, whose
    gradient is the sum over its uses). Rolled: ``body`` is traced once,
    whatever ``passes`` is. Returns every call's result, stacked."""
    def step(module, x, _):
        x = body(module, x)
        return x, x

    return _scan_shared(step, length=passes)(module, x, None)[1]


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
        x = _block(cfg)(cfg, ffn="expert" if cfg.moe else "dense",
                        name="block")(x)
        return x, _norm(cfg, "ln_f")(x)


class Backbone(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, mask=None, next_tokens=None):
        """The final hidden states; with ``next_tokens`` (the tokens
        shifted by one) and ``cfg.mtp_layers``, a tuple of them: the
        main model's, then each MTP module's. With ``cfg.passes`` over
        1, every pass's, stacked: ``[passes, batch, seq, hidden]``."""
        cfg = self.cfg
        if cfg.passes > 1 and (cfg.moe is not None or cfg.mtp_layers):
            raise ValueError(
                "a stack that runs several times (passes > 1) takes "
                "neither the expert layer, whose state is per layer and "
                "not per pass, nor MTP modules")
        if cfg.mixers is not None and (
                len(cfg.mixers) != cfg.layers or set(cfg.mixers) - set(MIXERS)
                or cfg.passes > 1 or cfg.mtp_layers or cfg.mla):
            raise ValueError(
                f"mixers {cfg.mixers}: one of {MIXERS} a layer "
                f"({cfg.layers}), in a stack that runs once with "
                f"neither latent attention nor MTP modules")
        ffns = cfg.ffns or tuple(
            "expert" if cfg.moe is not None and i >= cfg.moe.first_dense
            else "dense" for i in range(cfg.layers))
        if (len(ffns) != cfg.layers or set(ffns) - set(FFNS)
                or (NO_FFN in ffns and cfg.mixers is None)
                or ("expert" in ffns and cfg.moe is None)):
            raise ValueError(
                f"ffns {cfg.ffns}: one of {FFNS} a layer ({cfg.layers}); "
                f"\"expert\" needs moe, and {NO_FFN!r} a stack of mixers")
        if cfg.mixers is not None:
            publish_stack_layers(cfg.mixers, ffns)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=cfg.dtype,
                         name="tok_embed")
        x = embed(tokens)
        if not cfg.use_rope and cfg.positions:
            pos = nn.Embed(cfg.max_len, cfg.hidden, dtype=cfg.dtype,
                           name="pos_embed")(jnp.arange(tokens.shape[1]))
            x = x + pos[None]
        block = _block(cfg)
        if cfg.passes > 1:
            def stack(module, x):
                for i in range(cfg.layers):
                    x = block(cfg, name=f"block_{i}")(x, mask)
                return _norm(cfg, "ln_f")(x)

            with jax.named_scope(SCOPE_LOOP):
                return _looped(self, stack, x, cfg.passes)
        # What a layer made for the layers after it: the last "mamba"
        # layer's scan output, the last "attention" layer's (k, v). Their
        # gradients are the sums over their readers.
        made = {"mamba": None, "attention": None}
        for i in range(cfg.layers):
            if cfg.mixers is None:
                x = block(cfg, ffn=ffns[i], name=f"block_{i}")(x, mask)
                continue
            mixer = cfg.mixers[i]
            depth = cfg.layer_indices[i] if cfg.layer_indices else i
            x, new = block(cfg, ffn=ffns[i], mixer=mixer, depth=depth,
                           name=f"block_{i}")(x, mask, made["mamba"],
                                              made["attention"])
            if mixer in made:
                made[mixer] = new
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


def publish_stack_layers(mixers, ffns=()):
    """Set ``hvd_stack_layers{kind}`` from the kinds of a mixed stack's
    layers (``cfg.mixers``) and ``hvd_stack_ffns{kind}`` from their
    FFNs, so that a run's metrics say what stack it ran; a kind of
    ``MIXERS`` or ``FFNS`` that the stack lacks reads 0. ``Backbone``
    calls it as it is built (under ``jit``: as it is traced). A no-op
    when ``HOROVOD_TPU_METRICS`` is off."""
    from ..telemetry import core as telemetry
    if not telemetry.enabled():
        return
    layers = telemetry.gauge(
        "hvd_stack_layers",
        "Layers of the mixed stack last built, by the kind of their "
        "token mixer (TransformerConfig.mixers)", ("kind",))
    for kind in MIXERS:
        layers.labels(kind=kind).set(float(mixers.count(kind)))
    blocks = telemetry.gauge(
        "hvd_stack_ffns",
        "Layers of the mixed stack last built, by their FFN "
        "(TransformerConfig.ffns: dense, expert, or none)", ("kind",))
    for kind in FFNS:
        blocks.labels(kind=kind).set(float(ffns.count(kind)))


class TransformerLM(nn.Module):
    """Decoder-only causal LM (flagship model for long-context /
    sequence-parallel training)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, mask=None, next_tokens=None, targets=None):
        """Float32 logits; with ``next_tokens`` and ``cfg.mtp_layers`` a
        tuple: the main model's, then each MTP module's (logits ``i`` of
        module ``d`` are for token ``i + d + 2``).

        With ``cfg.passes`` over 1, a pair ``(exits, gate_logits)``, each
        ``[passes, batch, seq, ...]``: ``exits`` the logits of every
        pass's state under the one head, or with ``targets`` their
        cross-entropy at every position, in which case one pass's logits
        live at a time, forward and backward; ``gate_logits`` the exit
        gate's (float32; None without ``cfg.exit_gate``). What
        ``looped_lm_loss`` takes."""
        cfg = self.cfg
        backbone = Backbone(cfg, name="backbone")
        x = backbone(tokens, mask, next_tokens)
        if cfg.passes > 1:
            with jax.named_scope(SCOPE_EXIT):
                return self._exits(x, targets)
        if cfg.tie_embeddings:
            # One leaf, used as a gather and as a product: its gradient
            # is the sum of both uses.
            table = backbone.variables["params"]["tok_embed"]["embedding"]

            def head(h):
                return jnp.einsum("...h,vh->...v", h,
                                  table.astype(cfg.dtype),
                                  preferred_element_type=jnp.float32)
        else:
            head = _head(cfg)
        if isinstance(x, tuple):
            return tuple(head(h).astype(jnp.float32) for h in x)
        return head(x).astype(jnp.float32)

    def _exits(self, states, targets):
        cfg = self.cfg

        def one(module, _, h, targets):
            out = _head(cfg)(h).astype(jnp.float32)
            if targets is not None:
                out = optax.softmax_cross_entropy_with_integer_labels(
                    out, targets)
            gate = None
            if cfg.exit_gate:
                gate = nn.Dense(1, dtype=jnp.float32,
                                name="exit_gate")(h)[..., 0]
            return None, (out, gate)

        # Rolled and made again on the way back: a pass's logits
        # ([tokens, vocabulary] in float32) are temporaries of its own
        # iteration, and what is kept is a number a token a pass.
        if targets is not None:
            one = nn.remat(one)
        return _scan_shared(one, in_axes=(0, nn.broadcast))(
            self, None, states, targets)[1]


def looped_lm_loss(xent, gate_logits, beta):
    """The loss of a looped language model with an exit gate (Zhu et
    al., arXiv:2510.25741, the entropy-regularised objective under a
    uniform prior over exit steps), and the mean share of positions
    leaving at each pass (``[passes]``; no gradient flows to it).

    ``xent`` and ``gate_logits``: ``[passes, ...]``, the cross-entropy of
    every pass's logits at every position and the gate's logit there.
    ``lambda_t = sigmoid(gate_logits[t])`` is the chance of leaving after
    pass ``t`` having got there, so the exit distribution is ``p_t =
    lambda_t prod_{j<t} (1 - lambda_j)``, the last pass taking what is
    left (its own gate is not used). The loss is ``mean_i [sum_t p_t
    l_t - beta H(p)]``, worked out in log space so that ``p log p`` is
    finite wherever a gate saturates."""
    with jax.named_scope(SCOPE_EXIT):
        gate_logits = gate_logits.astype(jnp.float32)
        stay = jax.nn.log_sigmoid(-gate_logits)         # log(1 - lambda)
        reached = jnp.cumsum(stay, axis=0) - stay       # sum over j < t
        log_p = jnp.concatenate(
            [reached[:-1] + jax.nn.log_sigmoid(gate_logits[:-1]),
             reached[-1:]], axis=0)
        p = jnp.exp(log_p)
        loss = jnp.mean(jnp.sum(p * (xent + beta * log_p), axis=0))
        share = jnp.mean(p.reshape(p.shape[0], -1), axis=1)
        return loss, jax.lax.stop_gradient(share)


def publish_exit_shares(shares):
    """Set ``hvd_loop_exit_share{step}`` and ``hvd_loop_passes`` from the
    exit shares a train step returned (``looped_lm_loss``'s second
    result). Call it outside the step; it fetches the array. A no-op
    when ``HOROVOD_TPU_METRICS`` is off."""
    from ..telemetry import core as telemetry
    if not telemetry.enabled():
        return
    share = telemetry.gauge(
        "hvd_loop_exit_share",
        "Mean share of positions whose exit distribution leaves after "
        "this pass of the looped stack, in the last step", ("step",))
    shares = jax.device_get(shares)
    for step, value in enumerate(shares, start=1):
        share.labels(step=step).set(float(value))
    telemetry.gauge(
        "hvd_loop_passes",
        "Times the looped stack runs in one forward pass").set(
            float(len(shares)))


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
