"""MNIST training example (analog of reference examples/keras/keras_mnist.py).

Run single-controller (one process drives every local TPU chip):

    python examples/jax_mnist.py

or under the launcher for multi-process SPMD:

    hvdrun -np 2 python examples/jax_mnist.py

Uses synthetic MNIST-shaped data so it runs hermetically (the reference
example downloads MNIST; this repo is built for zero-egress environments).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
import horovod_tpu.jax as hvd_jax
from horovod_tpu.models import MnistCNN


def synthetic_mnist(n, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.uniform(size=(n, 28, 28, 1)).astype(np.float32)
    y = rng.randint(0, 10, size=(n,))
    return x, y


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--batch-size", type=int, default=64,
                        help="per-replica batch size")
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--use-adasum", action="store_true")
    args = parser.parse_args()

    hvd.init()
    n = hvd.size()

    model = MnistCNN()
    params = model.init(jax.random.PRNGKey(42),
                        jnp.zeros((1, 28, 28, 1)))

    # Reference LR scaling rule: scale by world size, except under Adasum
    # (reference: examples/pytorch/pytorch_synthetic_benchmark.py lr_scaler).
    lr = args.lr if args.use_adasum else args.lr * n
    op = hvd.Adasum if args.use_adasum else hvd.Average
    opt = hvd_jax.DistributedOptimizer(optax.adam(lr), op=op)

    def loss_fn(p, batch):
        x, y = batch
        logits = model.apply(p, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    step = hvd_jax.make_train_step(loss_fn, opt)
    opt_state = opt.init(params)

    # Broadcast initial state so every process starts identically
    # (reference: BroadcastGlobalVariablesCallback / broadcast_parameters).
    params = hvd_jax.broadcast_parameters(params, root_rank=0)
    opt_state = hvd_jax.broadcast_optimizer_state(opt_state, root_rank=0)

    x, y = synthetic_mnist(n * args.batch_size * 10)
    steps_per_epoch = len(x) // (n * args.batch_size)
    for epoch in range(args.epochs):
        for i in range(steps_per_epoch):
            lo = i * n * args.batch_size
            hi = lo + n * args.batch_size
            batch = (jnp.asarray(x[lo:hi]), jnp.asarray(y[lo:hi]))
            params, opt_state, loss = step(params, opt_state, batch)
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={float(loss):.4f}")
    return float(loss)


if __name__ == "__main__":
    main()
