"""``resnet`` configurations through the program's train step:
``models.ResNet`` under ``DistributedOptimizer(optax.sgd)`` and
``make_train_step(has_aux=True)``, BatchNorm statistics ``pmean``'d, as
``bench.py`` sets up Horovod's synthetic benchmark."""

import jax
import jax.numpy as jnp
import optax

from benchmark.builders import Program
from benchmark.references import common


def build(cfg, traffic, mesh, hvd_jax):
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet

    lr = cfg["optimizer"]["learning_rate"]
    model = ResNet(stage_sizes=cfg["stage_sizes"], block_cls=BottleneckBlock,
                   num_classes=cfg["num_classes"],
                   num_filters=cfg["num_filters"], remat=False)
    opt = hvd_jax.DistributedOptimizer(optax.sgd(lr))

    def loss_fn(params, aux, batch):
        images, labels = batch
        logits, updates = model.apply({"params": params, **aux}, images,
                                      mutable=list(aux.keys()))
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean(), updates

    def init_state(params, aux):
        return params, aux, jax.jit(opt.init)(params)

    @jax.jit
    def sqnorms_from_change(before, after):
        # Plain SGD keeps no state: the first gradient is the first
        # change of the parameters over the learning rate.
        return common.leaf_sqnorms(jax.tree.map(
            jnp.subtract, before, after)) / lr ** 2

    return Program(
        step=hvd_jax.make_train_step(loss_fn, opt, mesh=mesh, has_aux=True),
        init_state=init_state,
        first_grad_sqnorms=lambda state, before: sqnorms_from_change(
            before(), state[0]), model=model)

