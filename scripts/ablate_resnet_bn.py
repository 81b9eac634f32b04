"""Ablation: quantify BN cost in the ResNet-50 train step on the chip."""
import os, sys, timeit
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax, optax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn
import horovod_tpu as hvd
import horovod_tpu.jax as hvd_jax
from horovod_tpu.models import ResNet50
from horovod_tpu.models import resnet as resnet_mod

hvd.init()

class NoNorm(nn.Module):
    use_running_average: bool = True
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: object = None
    axis_name: object = None
    scale_init: object = None
    @nn.compact
    def __call__(self, x):
        return x

def bench(model, tag, batch=384):
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)))
    params = variables["params"]
    aux = {k: v for k, v in variables.items() if k != "params"}
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.1))
    def loss_fn(p, aux_state, b):
        x, y = b
        if aux_state:
            logits, updates = model.apply({"params": p, **aux_state}, x,
                                          mutable=list(aux_state.keys()))
        else:
            logits = model.apply({"params": p}, x)
            updates = type(aux)()
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean(), updates
    step = hvd_jax.make_train_step(loss_fn, opt, has_aux=True)
    opt_state = opt.init(params)
    rng = np.random.RandomState(0)
    data = jnp.asarray(rng.uniform(size=(batch, 224, 224, 3)), dtype=jnp.bfloat16)
    target = jnp.asarray(rng.randint(0, 1000, size=(batch,)))
    state = [params, aux, opt_state]
    def run_block():
        loss = None
        for _ in range(5):
            state[0], state[1], state[2], loss = step(state[0], state[1], state[2], (data, target))
        float(loss)
    timeit.timeit(run_block, number=2)
    t = timeit.timeit(run_block, number=3)
    ips = batch * 5 * 3 / t
    print(f"{tag}: {ips:.0f} img/s", flush=True)
    return ips

base = bench(ResNet50(num_classes=1000), "baseline-bn")
saved = resnet_mod.nn.BatchNorm
resnet_mod.nn.BatchNorm = NoNorm
try:
    nonorm = bench(ResNet50(num_classes=1000), "no-norm")
finally:
    resnet_mod.nn.BatchNorm = saved
print(f"BN cost: {(1 - base / nonorm) * 100:.1f}% of no-norm step", flush=True)
