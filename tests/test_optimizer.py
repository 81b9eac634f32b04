"""Distributed optimizer + end-to-end training tests (reference analog:
DistributedOptimizer tests in test/parallel/test_torch.py and the MNIST
example smoke runs in CI, .buildkite/gen-pipeline.sh:155-279)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map

import horovod_tpu as hvd_mod
import horovod_tpu.jax as hvd_jax
from horovod_tpu.models import MLP


@pytest.fixture(autouse=True)
def _init(hvd):
    pass


def _loss_fn(model):
    def loss(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
    return loss


def _make_data(n, batch_per_rank, key=0):
    rng = np.random.RandomState(key)
    x = rng.uniform(size=(n * batch_per_rank, 8, 8, 1)).astype(np.float32)
    y = rng.randint(0, 10, size=(n * batch_per_rank,))
    return jnp.asarray(x), jnp.asarray(y)


def test_train_step_loss_decreases(hvd, n_devices):
    model = MLP(features=(32,), num_classes=10)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8, 8, 1)))
    opt = hvd_jax.DistributedOptimizer(optax.adam(1e-2))
    step = hvd_jax.make_train_step(_loss_fn(model), opt)
    opt_state = opt.init(params)
    batch = _make_data(n_devices, 16)
    losses = []
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses


def test_grads_reduced_identically(hvd, n_devices):
    """After a step, params on every replica must be identical (the
    defining property of DP allreduce training)."""
    model = MLP(features=(16,), num_classes=4)
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 4, 4, 1)))
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.1))
    step = hvd_jax.make_train_step(_loss_fn(model), opt, donate=False)
    opt_state = opt.init(params)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.uniform(size=(n_devices * 4, 4, 4, 1)),
                    dtype=jnp.float32)
    y = jnp.asarray(rng.randint(0, 4, size=(n_devices * 4,)))
    new_params, _, _ = step(params, opt_state, (x, y))
    # Replicated output: sharding must report full replication.
    leaf = jax.tree.leaves(new_params)[0]
    assert leaf.sharding.is_fully_replicated


def test_train_step_matches_single_device_sgd(hvd, n_devices):
    """Sharded training must be numerically equivalent to one big-batch
    SGD step on a single device (grad of mean over full batch)."""
    model = MLP(features=(8,), num_classes=3)
    params = model.init(jax.random.PRNGKey(2), jnp.zeros((1, 2, 2, 1)))
    loss = _loss_fn(model)
    batch = _make_data(n_devices, 8, key=9)
    batch = (batch[0][:, :2, :2, :], batch[1] % 3)

    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.5))
    step = hvd_jax.make_train_step(loss, opt, donate=False)
    opt_state = opt.init(params)
    dist_params, _, dist_loss = step(params, opt_state, batch)

    ref_grads = jax.grad(loss)(params, batch)
    ref_params = jax.tree.map(lambda p, g: p - 0.5 * g, params, ref_grads)
    for a, b in zip(jax.tree.leaves(dist_params),
                    jax.tree.leaves(ref_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_backward_passes_per_step(hvd, n_devices):
    """Local gradient aggregation: updates apply only every k-th step
    (reference: horovod/tensorflow/gradient_aggregation.py)."""
    model = MLP(features=(8,), num_classes=3)
    params = model.init(jax.random.PRNGKey(4), jnp.zeros((1, 2, 2, 1)))
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.1),
                                       backward_passes_per_step=2)
    step = hvd_jax.make_train_step(_loss_fn(model), opt, donate=False)
    opt_state = opt.init(params)
    batch = _make_data(n_devices, 4, key=5)
    batch = (batch[0][:, :2, :2, :], batch[1] % 3)

    p1, s1, _ = step(params, opt_state, batch)
    # First micro-batch: no update applied yet.
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    p2, s2, _ = step(p1, s1, batch)
    # Second micro-batch: aggregated update applied.
    changed = any(not np.allclose(np.asarray(a), np.asarray(b))
                  for a, b in zip(jax.tree.leaves(p2),
                                  jax.tree.leaves(params)))
    assert changed


def test_adasum_optimizer_runs(hvd, n_devices):
    model = MLP(features=(8,), num_classes=3)
    params = model.init(jax.random.PRNGKey(6), jnp.zeros((1, 2, 2, 1)))
    opt = hvd_jax.DistributedAdasumOptimizer(optax.sgd(0.1))
    step = hvd_jax.make_train_step(_loss_fn(model), opt, donate=False)
    opt_state = opt.init(params)
    batch = _make_data(n_devices, 4, key=7)
    batch = (batch[0][:, :2, :2, :], batch[1] % 3)
    p, s, loss = step(params, opt_state, batch)
    assert np.isfinite(float(loss))
    changed = any(not np.allclose(np.asarray(a), np.asarray(b))
                  for a, b in zip(jax.tree.leaves(p),
                                  jax.tree.leaves(params)))
    assert changed


def test_compression_bf16_training(hvd, n_devices):
    model = MLP(features=(8,), num_classes=3)
    params = model.init(jax.random.PRNGKey(8), jnp.zeros((1, 2, 2, 1)))
    opt = hvd_jax.DistributedOptimizer(
        optax.sgd(0.1), compression=hvd_mod.Compression.bf16)
    step = hvd_jax.make_train_step(_loss_fn(model), opt, donate=False)
    opt_state = opt.init(params)
    batch = _make_data(n_devices, 4, key=11)
    batch = (batch[0][:, :2, :2, :], batch[1] % 3)
    p, s, loss = step(params, opt_state, batch)
    assert np.isfinite(float(loss))


def test_zero_train_step_matches_regular(hvd, n_devices):
    """ZeRO-1 step == regular step numerically; optimizer moments live
    sharded 1/N per device (global state leaves are flat vectors padded
    to N x shard_len)."""
    import optax

    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(13, 5), jnp.float32),
              "b": jnp.zeros(5)}

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    x = jnp.asarray(rng.randn(2 * n_devices, 13), jnp.float32)
    y = jnp.asarray(rng.randn(2 * n_devices, 5), jnp.float32)

    opt = hvd_jax.DistributedOptimizer(optax.adam(1e-2))
    step = hvd_jax.make_train_step(loss_fn, opt, donate=False)
    s = opt.init(params)
    zopt = hvd_jax.DistributedOptimizer(optax.adam(1e-2))
    zstep, zinit = hvd_jax.make_zero_train_step(loss_fn, zopt,
                                                donate=False)
    zs = zinit(params)

    n_elem = sum(int(np.prod(v.shape)) for v in params.values())
    padded = n_elem + (-n_elem) % n_devices
    vec_shapes = {np.shape(t) for t in jax.tree.leaves(zs)
                  if np.ndim(t) >= 1}
    assert vec_shapes == {(padded,)}, vec_shapes

    pp, zpp = params, params
    for i in range(4):
        pp, s, loss = step(pp, s, (x, y))
        zpp, zs, zloss = zstep(zpp, zs, (x, y))
        assert abs(float(loss) - float(zloss)) < 1e-5, i
    np.testing.assert_allclose(np.asarray(pp["w"]),
                               np.asarray(zpp["w"]), atol=1e-4)


def test_zero_train_step_rejects_unsupported(hvd):
    import optax

    def loss_fn(p, b):
        return jnp.sum(p["w"])

    for bad in (dict(op=hvd_mod.Sum),
                dict(backward_passes_per_step=2),
                dict(compression=hvd_mod.Compression.bf16)):
        opt = hvd_jax.DistributedOptimizer(optax.sgd(0.1), **bad)
        with pytest.raises(ValueError, match="make_zero_train_step"):
            hvd_jax.make_zero_train_step(loss_fn, opt)


def test_broadcast_variables_single_mode_identity(hvd):
    params = {"w": jnp.ones((3, 3)), "b": jnp.zeros((3,))}
    out = hvd_jax.broadcast_parameters(params, root_rank=0)
    assert out is params


def test_broadcast_object_single_mode(hvd):
    obj = {"epoch": 3, "lr": 0.1}
    assert hvd_jax.broadcast_object(obj) == obj
    assert hvd_jax.allgather_object(obj) == [obj]


from horovod_tpu.ops.adasum import adasum_vhdd_np as _np_vhdd  # noqa: E402


def test_adasum_axis_matches_pairwise_vhdd_oracle(hvd, n_devices):
    """adasum_axis (ppermute VHDD inside shard_map — the compiled data
    plane) is allclose to the numpy pairwise recursion, mirroring the
    host-plane oracle (tests/spmd_worker.py)."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.ops.adasum import adasum_axis

    n = n_devices
    rng = np.random.RandomState(3)
    # scale the ranks very differently: Adasum's whole point is scale
    # awareness, and mismatched norms exercise both coefficients
    stacked = np.stack([
        rng.normal(size=(4, 5)).astype(np.float32) * (10.0 ** (i % 3 - 1))
        for i in range(n)])
    mesh = Mesh(np.array(jax.devices()[:n]), ("r",))

    out = jax.jit(shard_map(
        lambda x: adasum_axis(x[0], "r")[None],
        mesh=mesh, in_specs=P("r"), out_specs=P("r")))(jnp.asarray(stacked))
    expect = _np_vhdd(stacked)
    for i in range(n):
        np.testing.assert_allclose(np.asarray(out)[i], expect,
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"rank {i} diverges from the "
                                           "pairwise VHDD recursion")


def test_adasum_optimizer_matches_tree_oracle(hvd, n_devices):
    """One DistributedAdasumOptimizer step equals a manual SGD update
    with the numpy-VHDD combination of the per-shard gradients — the
    compiled analog of the host plane's oracle-tested VhddAdasum."""
    model = MLP(features=(8,), num_classes=3)
    params = model.init(jax.random.PRNGKey(6), jnp.zeros((1, 2, 2, 1)))
    opt = hvd_jax.DistributedAdasumOptimizer(optax.sgd(0.1))
    step = hvd_jax.make_train_step(_loss_fn(model), opt, donate=False)
    opt_state = opt.init(params)
    batch = _make_data(n_devices, 4, key=7)
    batch = (batch[0][:, :2, :2, :], batch[1] % 3)
    p, s, loss = step(params, opt_state, batch)
    assert np.isfinite(float(loss))

    per = batch[0].shape[0] // n_devices
    loss_fn = _loss_fn(model)
    shard_grads = []
    for i in range(n_devices):
        shard = (batch[0][i * per:(i + 1) * per],
                 batch[1][i * per:(i + 1) * per])
        shard_grads.append(jax.grad(loss_fn)(params, shard))
    leaves = [jax.tree.leaves(g) for g in shard_grads]
    flat_params = jax.tree.leaves(params)
    flat_new = jax.tree.leaves(p)
    for leaf_idx, (p0, p1) in enumerate(zip(flat_params, flat_new)):
        combined = _np_vhdd([np.asarray(leaves[i][leaf_idx])
                             for i in range(n_devices)])
        expected = np.asarray(p0, np.float64) - 0.1 * combined
        np.testing.assert_allclose(np.asarray(p1), expected,
                                   rtol=2e-4, atol=2e-5)
