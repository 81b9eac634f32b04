"""``nemotron_h`` configurations (NVIDIA-Nemotron-3-Nano-30B-A3B) through
the program's train step: ``models.TransformerLM`` as a stack whose
every layer is one sub-layer, by the published pattern: a Mamba-2 mixer
(``"mamba2"``, no FFN), the expert layer alone (no token mixer; sigmoid
router with a selection bias, ungated squared-ReLU experts and a shared
one), or plain grouped-head attention without positions (``"full"``, no
FFN), an untied head, under ``DistributedOptimizer(optax.adamw)`` and
``make_train_step(has_aux=True)``. Which layer is which is the
reference's rule (``kinds``, ``ffns``), read from the configuration
file's ``hybrid_override_pattern`` at ``layers_held``. The expert
layers' selection bias and the tokens each expert drew travel as the
step's non-trained state."""

import jax
import optax

from benchmark import harness
from benchmark.builders import Program
from benchmark.builders.glm4_moe_lite import DRAW, _KeepsDraw  # noqa: F401
from benchmark.references import common

ROOT = harness.__file__.rsplit("/", 2)[0]
# ``assumed.attention_positions``: the alternative is "full_rope".
ATTENTION = "full"


def model_config(cfg, traffic):
    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.models.ssm import SSMConfig
    from horovod_tpu.parallel.moe import MoEConfig
    reference = harness.load_module(ROOT, cfg["reference"])
    assert cfg["norm_topk_prob"] and cfg["mlp_hidden_act"] == "relu2"
    assert cfg["use_conv_bias"] and not (
        cfg["mamba_proj_bias"] or cfg["attention_bias"] or cfg["mlp_bias"])
    heads, width, groups, n_state, _, _ = reference.mamba_dims(cfg)
    shared, rest = divmod(cfg["moe_shared_expert_intermediate_size"],
                          cfg["moe_intermediate_size"])
    assert not rest and cfg["n_shared_experts"] == 1
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_len=traffic["seq_len"], causal=True, use_rope=False,
        positions=False, rope_theta=float(cfg["rope_theta"]),
        attention_impl=cfg["attention_impl"], remat=cfg["remat"],
        norm="rmsnorm", norm_eps=cfg["layer_norm_epsilon"], bias=False,
        mixers=tuple(ATTENTION if k == "full" else k
                     for k in reference.kinds(cfg)),
        ffns=tuple(reference.ffns(cfg)),
        ssm=SSMConfig(d_inner=heads * width, dt_rank=0, d_state=n_state,
                      d_conv=cfg["conv_kernel"], heads=heads,
                      head_dim=width, groups=groups),
        moe=MoEConfig(experts=cfg["n_routed_experts_published"],
                      per_token=cfg["num_experts_per_tok"],
                      width=cfg["moe_intermediate_size"],
                      held=tuple(cfg["experts_held"]), shared=shared,
                      scale=float(cfg["routed_scaling_factor"]),
                      scoring="sigmoid", gate="relu2"))


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
