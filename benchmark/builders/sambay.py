"""``sambay`` configurations (Phi-4-mini-flash-reasoning) through the
program's train step: ``models.TransformerLM`` with a mixer a layer
(Mamba, windowed and full differential attention, gated memory units,
cross-attention over one shared K/V), a tied head and no positional
encoding, under ``DistributedOptimizer(optax.adamw)`` and
``make_train_step``. Which layer gets which mixer is the reference's
rule (``mixer_kind``), read from the configuration file's
``layer_indices``."""

import jax
import optax

from benchmark import harness
from benchmark.builders import Program
from benchmark.references import common

ROOT = harness.__file__.rsplit("/", 2)[0]


def model_config(cfg, traffic):
    from horovod_tpu.models import SSMConfig, TransformerConfig
    reference = harness.load_module(ROOT, cfg["reference"])
    sizes = cfg["assumed_sizes"]
    assert sizes["dt_rank"] * 16 == cfg["hidden_size"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], max_len=traffic["seq_len"],
        causal=True, use_rope=False, positions=False,
        attention_impl=cfg["attention_impl"], remat=cfg["remat"],
        norm="layernorm", norm_eps=cfg["layer_norm_eps"], bias=True,
        mlp_bias=cfg["mlp_bias"], mlp="swiglu",
        mlp_width=cfg["intermediate_size"],
        mixers=tuple(reference.kinds(cfg)),
        layer_indices=tuple(cfg["layer_indices"]),
        window=cfg["sliding_window"],
        tie_embeddings=cfg["tie_word_embeddings"],
        ssm=SSMConfig(d_inner=sizes["expand"] * cfg["hidden_size"],
                      d_state=sizes["d_state"], d_conv=sizes["d_conv"],
                      dt_rank=sizes["dt_rank"]))


def build(cfg, traffic, mesh, hvd_jax):
    from horovod_tpu.models import TransformerLM

    opt_cfg = cfg["optimizer"]
    model = TransformerLM(model_config(cfg, traffic))
    opt = hvd_jax.DistributedOptimizer(optax.adamw(
        opt_cfg["learning_rate"], b1=opt_cfg["b1"], b2=opt_cfg["b2"],
        eps=opt_cfg["eps"], weight_decay=opt_cfg["weight_decay"]))

    def loss_fn(params, batch):
        tokens, targets = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(params, tokens), targets).mean()

    @jax.jit
    def sqnorms_from_adam(opt_state):
        # AdamW's first moment after one step is (1 - b1) x gradient.
        adam = [s for s in jax.tree.leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu"))
            if hasattr(s, "mu")]
        return common.leaf_sqnorms(adam[0].mu) / (1 - opt_cfg["b1"]) ** 2

    return Program(
        step=hvd_jax.make_train_step(loss_fn, opt, mesh=mesh),
        init_state=lambda params, aux: (params, jax.jit(opt.init)(params)),
        first_grad_sqnorms=lambda state, before: sqnorms_from_adam(state[1]),
        model=model)
