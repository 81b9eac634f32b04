"""``transformer_lm`` configurations through the program's train step:
``models.TransformerLM`` under ``DistributedOptimizer(optax.adamw)`` and
``make_train_step``, as ``bench.py`` and ``chip_smoke.py`` set it up."""

import jax
import optax

from benchmark.builders import Program
from benchmark.references import common


def build(cfg, traffic, mesh, hvd_jax):
    from horovod_tpu.models import TransformerConfig, TransformerLM

    opt_cfg = cfg["optimizer"]
    model = TransformerLM(TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        mlp_ratio=cfg["intermediate_size"] // cfg["hidden_size"],
        max_len=traffic["seq_len"], causal=True, use_rope=True,
        attention_impl=cfg["attention_impl"], remat=False))
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
