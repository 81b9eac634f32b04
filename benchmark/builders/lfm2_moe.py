"""``lfm2_moe`` configurations (LFM2-24B-A2B) through the program's train
step: ``models.TransformerLM`` as a stack of gated short convolutions
and plain attention layers by the published pattern, a norm on every
head's q and k, grouped K/V heads, a dense SwiGLU FFN in the leading
layers and in the others the expert layer with a sigmoid router, a
selection bias and no shared expert, a head tied to the embedding, under
``DistributedOptimizer(optax.adamw)`` and
``make_train_step(has_aux=True)``. Which layer is which kind is the
reference's rule (``kinds``), read from the configuration file's
``layer_types`` at ``layers_held``. The expert layers' selection bias
and the tokens each expert drew travel as the step's non-trained
state."""

import jax
import optax

from benchmark import harness
from benchmark.builders import Program
from benchmark.builders.glm4_moe_lite import DRAW, _KeepsDraw  # noqa: F401
from benchmark.references import common

ROOT = harness.__file__.rsplit("/", 2)[0]


def model_config(cfg, traffic):
    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.parallel.moe import MoEConfig
    reference = harness.load_module(ROOT, cfg["reference"])
    assert cfg["norm_topk_prob"] and cfg["use_expert_bias"]
    assert not cfg["conv_bias"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], max_len=traffic["seq_len"],
        causal=True, use_rope=False, positions=False,
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        attention_impl=cfg["attention_impl"], remat=cfg["remat"],
        norm="rmsnorm", norm_eps=cfg["norm_eps"], bias=False,
        mlp="swiglu", mlp_width=cfg["intermediate_size"],
        mixers=tuple(reference.kinds(cfg)),
        conv_taps=cfg["conv_L_cache"], qk_norm=True, tie_embeddings=True,
        moe=MoEConfig(experts=cfg["num_experts_published"],
                      per_token=cfg["num_experts_per_tok"],
                      width=cfg["moe_intermediate_size"],
                      held=tuple(cfg["experts_held"]), shared=0,
                      scale=float(cfg["routed_scaling_factor"]),
                      first_dense=cfg["num_dense_layers"],
                      scoring="sigmoid"))


def build(cfg, traffic, mesh, hvd_jax):
    from horovod_tpu.models import TransformerLM

    opt_cfg = cfg["optimizer"]
    model = TransformerLM(model_config(cfg, traffic))
    opt = hvd_jax.DistributedOptimizer(optax.adamw(
        opt_cfg["learning_rate"], b1=opt_cfg["b1"], b2=opt_cfg["b2"],
        eps=opt_cfg["eps"], weight_decay=opt_cfg["weight_decay"]))

    def loss_fn(params, aux, batch):
        tokens, targets = batch
        logits, aux = model.apply({**params, **aux}, tokens,
                                  mutable=list(aux))
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean(), aux

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
