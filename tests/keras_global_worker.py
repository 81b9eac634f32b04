"""Keras-on-jax over the jax.distributed global mesh: one rank of an
N-process job where set_data_parallel spans every process's devices and
model.fit's jitted train step is one global-SPMD program (the multi-host
TPU deployment shape; launched by test_xla_global.py with
HVDTPU_CPU_OPERATIONS=xla)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["KERAS_BACKEND"] = "jax"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
import horovod_tpu.keras as hk  # noqa: E402


def main():
    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    import keras

    # Data is pre-sharded per process (the hvdrun idiom), so keras's
    # multi-worker auto-sharding is off; the global mesh still shards
    # each jitted step's batch across every device of every process.
    hk.set_data_parallel(auto_shard_dataset=False)
    n_local = int(os.environ.get("XGW_LOCAL_DEVICES", "2"))
    assert len(jax.devices()) == size * n_local

    keras.utils.set_random_seed(0)
    model = keras.Sequential([
        keras.layers.Input((8,)),
        keras.layers.Dense(16, activation="relu"),
        keras.layers.Dense(1),
    ])
    model.compile(
        optimizer=hk.DistributedOptimizer(keras.optimizers.SGD(0.05)),
        loss="mse")

    rng = np.random.RandomState(7)
    X = rng.randn(64, 8).astype(np.float32)
    y = (X @ rng.randn(8, 1)).astype(np.float32)
    per = 64 // size
    Xl, yl = X[rank * per:(rank + 1) * per], y[rank * per:(rank + 1) * per]
    hist = model.fit(Xl, yl, batch_size=per // 2, epochs=2, shuffle=False,
                     verbose=0)
    losses = hist.history["loss"]
    assert losses[-1] < losses[0], losses

    # The global-SPMD step keeps weights replicated: every rank holds
    # the identical trained model.
    from horovod_tpu.functions import allgather_object
    w = [np.asarray(x) for x in model.get_weights()]
    all_w = allgather_object(w)
    for rank_w in all_w[1:]:
        for a, b in zip(rank_w, all_w[0]):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    print(f"rank {rank}/{size}: KERAS-GLOBAL OK", flush=True)
    hvd.shutdown()


if __name__ == "__main__":
    main()
