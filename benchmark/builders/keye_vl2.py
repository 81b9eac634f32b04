"""``KeyeVL2`` configurations (Keye-VL-2.0-30B-A3B's language model)
through the program's train step: ``models.TransformerLM`` as a stack of
sparse-attention layers (``"sparse_rope"``: grouped K/V heads of their
own head dimension, a norm on every head's q and k, rope over three
position streams at the positions of the configuration's row layout,
and an indexer that picks each query's keys), in every block the expert
layer with a softmax router and SwiGLU experts and no shared one, an
untied head, under ``DistributedOptimizer(optax.adamw)`` and
``make_train_step(has_aux=True)``. The loss is the language model's plus
``align_loss_weight`` times the layers' alignment losses, which the
stack leaves in its non-trained state beside the mean number of keys a
query selected and the tokens each expert drew."""

import jax
import optax

from benchmark import harness
from benchmark.builders import Program
from benchmark.builders.glm4_moe_lite import DRAW, _KeepsDraw  # noqa: F401
from benchmark.references import common

ROOT = harness.__file__.rsplit("/", 2)[0]


def model_config(cfg, traffic):
    from horovod_tpu.models.transformer import (IndexerConfig,
                                                TransformerConfig)
    from horovod_tpu.parallel.moe import MoEConfig
    reference = harness.load_module(ROOT, cfg["reference"])
    sa = cfg["sa_config"]
    assert cfg["norm_topk_prob"] and cfg["hidden_act"] == "silu"
    assert not cfg["attention_bias"] and not cfg["mlp_only_layers"]
    assert cfg["decoder_sparse_step"] == 1 and not cfg["use_sliding_window"]
    assert sa["indexer_num_kv_heads"] == 1
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_len=traffic["seq_len"], causal=True, use_rope=False,
        positions=False, rope_theta=float(cfg["rope_theta"]),
        attention_impl=cfg["attention_impl"], remat=cfg["remat"],
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], bias=False,
        mixers=tuple(reference.kinds(cfg)), qk_norm=True,
        tie_embeddings=cfg["tie_word_embeddings"],
        indexer=IndexerConfig(heads=sa["indexer_num_heads"],
                              head_dim=sa["indexer_head_dim"],
                              topk=sa["topk"]),
        rope_sections=tuple(cfg["rope_scaling"]["mrope_section"]),
        rope_layout=reference.layout(cfg),
        moe=MoEConfig(experts=cfg["num_experts_published"],
                      per_token=cfg["num_experts_per_tok"],
                      width=cfg["moe_intermediate_size"],
                      held=tuple(cfg["experts_held"]), shared=0,
                      first_dense=0, scoring="softmax", gate="silu"))


def build(cfg, traffic, mesh, hvd_jax):
    from horovod_tpu.models import TransformerLM
    from horovod_tpu.models.transformer import dsa_align_loss

    opt_cfg = cfg["optimizer"]
    model = TransformerLM(model_config(cfg, traffic))
    opt = hvd_jax.DistributedOptimizer(optax.adamw(
        opt_cfg["learning_rate"], b1=opt_cfg["b1"], b2=opt_cfg["b2"],
        eps=opt_cfg["eps"], weight_decay=opt_cfg["weight_decay"]))

    def loss_fn(params, aux, batch):
        tokens, targets = batch
        logits, aux = model.apply({**params, **aux}, tokens,
                                  mutable=list(aux))
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()
        return loss + cfg["align_loss_weight"] * dsa_align_loss(aux), aux

    @jax.jit
    def sqnorms_from_adam(opt_state):
        # AdamW's first moment after one step is (1 - b1) x gradient.
        adam = [s for s in jax.tree.leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu"))
            if hasattr(s, "mu")]
        return common.leaf_sqnorms(adam[0].mu) / (1 - opt_cfg["b1"]) ** 2

    return Program(
        step=_KeepsDraw(hvd_jax.make_train_step(loss_fn, opt, mesh=mesh,
                                                has_aux=True)),
        init_state=lambda params, aux: (params, aux,
                                        jax.jit(opt.init)(params)),
        first_grad_sqnorms=lambda state, before: sqnorms_from_adam(state[2]),
        model=model)
