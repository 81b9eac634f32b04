"""``glm4_moe_lite`` configurations through the program's train step:
``models.TransformerLM`` with latent attention, the expert layer and a
multi-token-prediction module, under ``DistributedOptimizer(optax.adamw)``
and ``make_train_step(has_aux=True)``. The expert layers' selection bias
and the tokens each expert drew travel as the step's non-trained state,
as ResNet's batch statistics do."""

import jax
import optax

from benchmark.builders import Program
from benchmark.references import common

# The newest non-trained state a step returned, for the reader
# ``layer_metrics/moe_held_pairs.py``: the harness frees its own copy
# before the readers run.
DRAW = {}


class _KeepsDraw:
    """The step in each form the harness takes it through (jitted,
    lowered, compiled), with one thing added: a call keeps a reference
    to the non-trained state it returned. That is a few hundred
    counters on the device; nothing fetches or waits for them until a
    reader does, after the window."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):        # memory_analysis, as_text, ...
        return getattr(self._inner, name)

    def lower(self, *args):
        return _KeepsDraw(self._inner.lower(*args))

    def compile(self):
        return _KeepsDraw(self._inner.compile())

    def __call__(self, *args):
        out = self._inner(*args)
        DRAW["aux"] = out[1]
        return out


def model_config(cfg, traffic):
    from horovod_tpu.models.transformer import MLAConfig, TransformerConfig
    from horovod_tpu.parallel.moe import MoEConfig
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        max_len=traffic["seq_len"], causal=True, use_rope=True,
        rope_theta=float(cfg["rope_theta"]),
        attention_impl=cfg["attention_impl"], remat=cfg["remat"],
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        bias=False, mlp="swiglu", mlp_width=cfg["intermediate_size"],
        mla=MLAConfig(q_rank=cfg["q_lora_rank"],
                      kv_rank=cfg["kv_lora_rank"],
                      nope_dim=cfg["qk_nope_head_dim"],
                      rope_dim=cfg["qk_rope_head_dim"],
                      v_dim=cfg["v_head_dim"]),
        moe=MoEConfig(experts=cfg["n_routed_experts_published"],
                      per_token=cfg["num_experts_per_tok"],
                      width=cfg["moe_intermediate_size"],
                      held=tuple(cfg["experts_held"]),
                      shared=cfg["n_shared_experts"],
                      scale=cfg["routed_scaling_factor"],
                      first_dense=cfg["first_k_dense_replace"]),
        mtp_layers=cfg["num_nextn_predict_layers"])


def build(cfg, traffic, mesh, hvd_jax):
    from horovod_tpu.models import TransformerLM

    opt_cfg = cfg["optimizer"]
    model = TransformerLM(model_config(cfg, traffic))
    opt = hvd_jax.DistributedOptimizer(optax.adamw(
        opt_cfg["learning_rate"], b1=opt_cfg["b1"], b2=opt_cfg["b2"],
        eps=opt_cfg["eps"], weight_decay=opt_cfg["weight_decay"]))
    xent = optax.softmax_cross_entropy_with_integer_labels

    def loss_fn(params, aux, batch):
        tokens, targets = batch
        (main, *extra), aux = model.apply(
            {**params, **aux}, tokens, next_tokens=targets,
            mutable=list(aux))
        loss = xent(main, targets).mean()
        # MTP module d predicts the token d + 2 on, over the positions
        # that have one.
        mtp = [xent(logits[:, :-(d + 1)], targets[:, d + 1:]).mean()
               for d, logits in enumerate(extra)]
        return loss + cfg["mtp_loss_weight"] * sum(mtp) / len(mtp), aux

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
