"""Synthetic throughput benchmark (analog of reference
examples/pytorch/pytorch_synthetic_benchmark.py and
examples/tensorflow2/tensorflow2_synthetic_benchmark.py).

Measures img/sec (ResNet) or tokens/sec (transformer) for a full
data-parallel training step over the local mesh.
"""

import argparse
import os
import sys
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
import horovod_tpu.jax as hvd_jax
from horovod_tpu import models
from horovod_tpu.utils import compile_cache


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="ResNet50",
                        choices=["ResNet18", "ResNet50", "ResNet101",
                                 "TransformerLM", "BertModel"])
    parser.add_argument("--batch-size", type=int, default=32,
                        help="per-replica batch size")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--seq-len", type=int, default=512)
    parser.add_argument("--num-warmup-batches", type=int, default=10)
    parser.add_argument("--num-batches-per-iter", type=int, default=10)
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--fp16-allreduce", action="store_true")
    parser.add_argument("--use-adasum", action="store_true")
    args = parser.parse_args()

    compile_cache.enable()
    hvd.init()
    n = hvd.size()
    global_batch = n * args.batch_size

    is_lm = args.model in ("TransformerLM", "BertModel")
    if is_lm:
        cfg = models.TransformerConfig(layers=4, hidden=512, heads=8,
                                       max_len=args.seq_len,
                                       causal=args.model == "TransformerLM")
        model = getattr(models, args.model)(cfg)
        data = jnp.asarray(np.random.randint(
            0, cfg.vocab_size, size=(global_batch, args.seq_len)))
        target = jnp.asarray(np.random.randint(
            0, cfg.vocab_size, size=(global_batch, args.seq_len)))
        init_arg = jnp.zeros((1, args.seq_len), jnp.int32)
    else:
        model = getattr(models, args.model)(num_classes=1000)
        data = jnp.asarray(np.random.uniform(size=(
            global_batch, args.image_size, args.image_size, 3)),
            dtype=jnp.float32)
        target = jnp.asarray(np.random.randint(0, 1000,
                                               size=(global_batch,)))
        init_arg = jnp.zeros((1, args.image_size, args.image_size, 3))

    variables = model.init(jax.random.PRNGKey(0), init_arg)
    params = variables["params"]
    aux = {k: v for k, v in variables.items() if k != "params"}

    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)
    op = hvd.Adasum if args.use_adasum else hvd.Average
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.01), op=op,
                                       compression=compression)

    def loss_fn(p, aux_state, batch):
        x, y = batch
        if aux_state:
            logits, updates = model.apply({"params": p, **aux_state}, x,
                                          mutable=list(aux_state.keys()))
        else:
            logits, updates = model.apply({"params": p}, x), {}
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, updates

    step = hvd_jax.make_train_step(loss_fn, opt, has_aux=True)
    opt_state = opt.init(params)

    # Broadcast initial state so every process starts identically under
    # hvdrun (fixed-seed init makes this a no-op today, but nothing
    # enforces that; flagged by hvd-lint rule HVD202).
    params = hvd_jax.broadcast_parameters(params, root_rank=0)
    opt_state = hvd_jax.broadcast_optimizer_state(opt_state, root_rank=0)

    state = [params, aux, opt_state]

    def benchmark_step():
        state[0], state[1], state[2], loss = step(
            state[0], state[1], state[2], (data, target))
        jax.block_until_ready(loss)

    if hvd.rank() == 0:
        print(f"Model: {args.model}, global batch {global_batch} "
              f"({n} replicas x {args.batch_size})")
    timeit.timeit(benchmark_step, number=args.num_warmup_batches)

    img_secs = []
    unit = "tokens" if is_lm else "img"
    scale = args.seq_len if is_lm else 1
    for _ in range(args.num_iters):
        t = timeit.timeit(benchmark_step,
                          number=args.num_batches_per_iter)
        rate = global_batch * scale * args.num_batches_per_iter / t
        img_secs.append(rate)
    if hvd.rank() == 0:
        print(f"{unit}/sec: {np.mean(img_secs):.1f} "
              f"+- {1.96 * np.std(img_secs):.1f}")
    return float(np.mean(img_secs))


if __name__ == "__main__":
    main()
