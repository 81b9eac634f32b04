"""``looped_lm`` configurations through the program's train step:
``models.TransformerLM`` with a stack that runs ``total_ut_steps`` times,
sandwich norms and an exit gate, its loss ``models.looped_lm_loss``,
under ``DistributedOptimizer(optax.adamw)`` and
``make_train_step(has_aux=True)``. The mean share of positions leaving
after each pass travels as the step's non-trained state, as the expert
layers' draw and ResNet's batch statistics do."""

import jax
import optax

from benchmark.builders import Program
from benchmark.references import common


def model_config(cfg, traffic):
    from horovod_tpu.models import TransformerConfig
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        max_len=traffic["seq_len"], causal=True, use_rope=True,
        rope_theta=float(cfg["rope_theta"]),
        attention_impl=cfg["attention_impl"], remat=cfg["remat"],
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], bias=False,
        mlp="swiglu", mlp_width=cfg["intermediate_size"],
        passes=cfg["total_ut_steps"], sandwich_norm=True, exit_gate=True)


def build(cfg, traffic, mesh, hvd_jax):
    from horovod_tpu.models import TransformerLM, looped_lm_loss

    opt_cfg = cfg["optimizer"]
    model = TransformerLM(model_config(cfg, traffic))
    opt = hvd_jax.DistributedOptimizer(optax.adamw(
        opt_cfg["learning_rate"], b1=opt_cfg["b1"], b2=opt_cfg["b2"],
        eps=opt_cfg["eps"], weight_decay=opt_cfg["weight_decay"]))

    def loss_fn(params, aux, batch):
        tokens, targets = batch
        xent, gate_logits = model.apply(params, tokens, targets=targets)
        loss, share = looped_lm_loss(xent, gate_logits,
                                     cfg["exit_entropy_beta"])
        return loss, {"loop_state": {"exit_share": share}}

    @jax.jit
    def sqnorms_from_adam(opt_state):
        # AdamW's first moment after one step is (1 - b1) x gradient.
        adam = [s for s in jax.tree.leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu"))
            if hasattr(s, "mu")]
        return common.leaf_sqnorms(adam[0].mu) / (1 - opt_cfg["b1"]) ** 2

    return Program(
        step=hvd_jax.make_train_step(loss_fn, opt, mesh=mesh, has_aux=True),
        init_state=lambda params, aux: (params, aux,
                                        jax.jit(opt.init)(params)),
        first_grad_sqnorms=lambda state, before: sqnorms_from_adam(state[2]),
        model=model)
