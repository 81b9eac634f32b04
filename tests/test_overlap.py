"""Bucketed comm/compute overlap (HVDTPU_OVERLAP; docs/performance.md).

Covers the bucket planner, the in-jit bucketed axis reduction, the
pinned bit-exactness contract (ISSUE 7: overlapped bucketed grads ==
single-barrier grads, fp32, fixed seed, 1/2/4-way CPU meshes), the
compression composition, and the coordinator's priority-ordered async
bucket dispatch on the eager plane.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops import bucketing, reduce_ops


# ==========================================================================
# Bucket planner
# ==========================================================================
def _leaves(*shapes, dtype=jnp.float32):
    return [jnp.zeros(s, dtype) for s in shapes]


def test_plan_respects_budget_and_covers_all():
    leaves = _leaves((256,), (256,), (256,), (256,))  # 1 KiB each
    plan = bucketing.plan_buckets(leaves, bucket_bytes=2048)
    assert sorted(i for b in plan for i in b.indices) == [0, 1, 2, 3]
    assert all(b.nbytes <= 2048 for b in plan)
    assert len(plan) == 2


def test_plan_reverse_order_first_bucket_holds_last_leaves():
    # Backprop produces LAST leaves first: the first planned bucket must
    # hold the tail of the tree so its collective can issue earliest.
    leaves = _leaves((256,), (256,), (256,), (256,))
    plan = bucketing.plan_buckets(leaves, bucket_bytes=2048)
    assert plan[0].indices == [2, 3]
    assert plan[1].indices == [0, 1]


def test_plan_groups_by_dtype():
    leaves = [jnp.zeros((64,), jnp.float32), jnp.zeros((64,), jnp.bfloat16),
              jnp.zeros((64,), jnp.float32)]
    plan = bucketing.plan_buckets(leaves, bucket_bytes=1 << 20)
    by_dtype = {str(b.dtype): b.indices for b in plan}
    assert by_dtype[str(jnp.dtype(jnp.float32))] == [0, 2]
    assert by_dtype[str(jnp.dtype(jnp.bfloat16))] == [1]


def test_plan_oversized_leaf_gets_own_bucket():
    leaves = _leaves((1024,), (16,), (16,))   # 4 KiB whale, two minnows
    plan = bucketing.plan_buckets(leaves, bucket_bytes=256)
    whale = [b for b in plan if 0 in b.indices]
    assert len(whale) == 1 and whale[0].indices == [0]


# ==========================================================================
# In-jit bucketed reduction: numerics + bit-exactness
# ==========================================================================
def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("hvd",))


def _rand_tree(seed, shapes):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.normal(size=s).astype(np.float32))
            for s in shapes]


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("op", [reduce_ops.Average, reduce_ops.Sum])
def test_bucketed_reduce_bitwise_equals_per_leaf(n, op):
    shapes = [(3, 5), (17,), (4, 4, 2), (1,)]
    stacked = [jnp.stack([t * (r + 1) for r in range(n)])
               for t in _rand_tree(0, shapes)]

    def body_bucketed(*xs):
        locals_ = [x[0] for x in xs]
        return tuple(bucketing.bucketed_reduce_axis(
            locals_, op, "hvd", bucket_bytes=64))

    def body_perleaf(*xs):
        from jax import lax
        red = lax.pmean if op == reduce_ops.Average else lax.psum
        return tuple(red(x[0], "hvd") for x in xs)

    mesh = _mesh(n)
    specs = tuple(P("hvd") for _ in stacked)
    outs = tuple(P() for _ in stacked)
    a = jax.jit(shard_map(body_bucketed, mesh=mesh, in_specs=specs,
                          out_specs=outs, check_vma=False))(*stacked)
    b = jax.jit(shard_map(body_perleaf, mesh=mesh, in_specs=specs,
                          out_specs=outs, check_vma=False))(*stacked)
    for x, y in zip(a, b):
        assert (np.asarray(x) == np.asarray(y)).all(), \
            "bucketed reduction is not bit-identical to per-leaf"


def test_bucketed_reduce_rejects_adasum():
    with pytest.raises(ValueError, match="Adasum"):
        bucketing.bucketed_reduce_axis(
            [jnp.zeros((4,))], reduce_ops.Adasum, "hvd")


def test_bucketed_reduce_scales_match_per_leaf():
    n = 2
    stacked = [jnp.stack([t * (r + 1) for r in range(n)])
               for t in _rand_tree(1, [(6,), (9,)])]
    mesh = _mesh(n)

    def body(*xs):
        return tuple(bucketing.bucketed_reduce_axis(
            [x[0] for x in xs], reduce_ops.Sum, "hvd", bucket_bytes=16,
            prescale=0.5, postscale=2.0))

    out = jax.jit(shard_map(body, mesh=mesh,
                            in_specs=tuple(P("hvd") for _ in stacked),
                            out_specs=tuple(P() for _ in stacked),
                            check_vma=False))(*stacked)
    for x, o in zip(stacked, out):
        expect = 2.0 * sum(0.5 * np.asarray(x)[r] for r in range(n))
        np.testing.assert_allclose(np.asarray(o), expect, rtol=1e-6)


# ==========================================================================
# Pinned regression: overlapped train step == single-barrier train step
# ==========================================================================
def _train_artifacts(hvd, seed=0):
    import optax
    from horovod_tpu.models import MLP

    model = MLP(features=(8,), num_classes=3)
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 2, 2, 1)))

    def loss_fn(p, batch):
        x, y = batch
        import horovod_tpu.jax  # noqa: F401 (binding import side effects)
        logits = model.apply(p, x)
        one_hot = jax.nn.one_hot(y, 3)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * one_hot,
                                 axis=-1))
    rng = np.random.RandomState(seed + 1)
    x = jnp.asarray(rng.normal(size=(8, 2, 2, 1)).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 3, size=(8,)))
    return model, params, loss_fn, (x, y), optax.sgd(0.1)


@pytest.mark.parametrize("n", [1, 2, 4])
def test_overlap_bit_exact_vs_barrier(hvd, monkeypatch, n):
    """ISSUE 7 acceptance: HVDTPU_OVERLAP=1 bucketed gradients are
    bit-identical to the OVERLAP=0 single-barrier reduction (fp32,
    fixed seed) across 1/2/4-way CPU meshes."""
    import horovod_tpu.jax as hvd_jax
    _, params, loss_fn, batch, sgd = _train_artifacts(hvd)
    mesh = _mesh(n)
    results = {}
    for overlap in ("0", "1"):
        monkeypatch.setenv("HVDTPU_OVERLAP", overlap)
        monkeypatch.setenv("HVDTPU_BUCKET_BYTES", "128")
        opt = hvd_jax.DistributedOptimizer(sgd)
        step = hvd_jax.make_train_step(loss_fn, opt, mesh=mesh,
                                       donate=False)
        p, s = params, opt.init(params)
        loss = None
        for _ in range(3):
            p, s, loss = step(p, s, batch)
        results[overlap] = (jax.tree.leaves(p), float(loss))
    assert results["0"][1] == results["1"][1]
    for a, b in zip(results["0"][0], results["1"][0]):
        assert (np.asarray(a) == np.asarray(b)).all(), \
            "overlapped step diverged from the barrier step"


def test_overlap_composes_with_wire_compression(hvd, monkeypatch):
    """OVERLAP=1 + Compression.int8: the per-bucket quantized pipeline
    trains and lands near the uncompressed gradients (block-quantization
    noise only)."""
    import horovod_tpu.jax as hvd_jax
    _, params, loss_fn, batch, sgd = _train_artifacts(hvd)
    mesh = _mesh(4)
    monkeypatch.setenv("HVDTPU_OVERLAP", "1")
    monkeypatch.setenv("HVDTPU_BUCKET_BYTES", "256")
    opt_q = hvd_jax.DistributedOptimizer(sgd, compression=hvd.Compression.int8)
    opt_f = hvd_jax.DistributedOptimizer(sgd)
    step_q = hvd_jax.make_train_step(loss_fn, opt_q, mesh=mesh,
                                     donate=False)
    step_f = hvd_jax.make_train_step(loss_fn, opt_f, mesh=mesh,
                                     donate=False)
    pq, sq, lq = step_q(params, opt_q.init(params), batch)
    pf, sf, lf = step_f(params, opt_f.init(params), batch)
    assert np.isfinite(float(lq))
    for a, b in zip(jax.tree.leaves(pq), jax.tree.leaves(pf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=5e-2)


def test_overlap_adasum_stays_per_tensor(hvd, monkeypatch):
    """Adasum under OVERLAP=1 must keep the per-leaf reduction — same
    result as OVERLAP=0, never a concatenated-bucket VHDD."""
    import horovod_tpu.jax as hvd_jax
    _, params, loss_fn, batch, sgd = _train_artifacts(hvd)
    mesh = _mesh(4)
    results = {}
    for overlap in ("0", "1"):
        monkeypatch.setenv("HVDTPU_OVERLAP", overlap)
        opt = hvd_jax.DistributedAdasumOptimizer(sgd)
        step = hvd_jax.make_train_step(loss_fn, opt, mesh=mesh,
                                       donate=False)
        p, s, loss = step(params, opt.init(params), batch)
        results[overlap] = jax.tree.leaves(p)
    for a, b in zip(results["0"], results["1"]):
        assert (np.asarray(a) == np.asarray(b)).all()


# ==========================================================================
# Eager plane: coordinator priority-ordered async bucket dispatch
# ==========================================================================
def _entries(hvd, count, elems=16):
    from horovod_tpu import basics
    from horovod_tpu.coordinator import TensorEntry
    from horovod_tpu.process_sets import global_process_set

    n = hvd.size()
    entries = []
    for j in range(count):
        stacked = jnp.stack([jnp.full((elems,), float(r + j))
                             for r in range(n)])
        entries.append(TensorEntry(f"ov{j}", "allreduce", [stacked],
                                   global_process_set,
                                   op=reduce_ops.Average))
    return entries


def _coordinator(hvd):
    from horovod_tpu import basics
    return basics.runtime().coordinator, basics.runtime().backend


def test_coordinator_overlap_results_and_priority(hvd):
    """Overlap on: many small buckets issue asynchronously in submission
    order and every handle completes with the correct reduction."""
    co, backend = _coordinator(hvd)
    saved = (co._overlap, co._bucket_bytes, co._metrics_on)
    co._overlap, co._bucket_bytes = True, 8  # every entry its own bucket
    co._metrics_on = True                    # exercise _observe_overlap
    try:
        entries = _entries(hvd, 5)
        co._run_fused_allreduces(backend, entries, None)
        n = hvd.size()
        for j, e in enumerate(entries):
            out = e.handle.wait()
            expect = np.mean([r + j for r in range(n)])
            np.testing.assert_allclose(np.asarray(out)[0],
                                       np.full((16,), expect), rtol=1e-6)
    finally:
        co._overlap, co._bucket_bytes, co._metrics_on = saved


def test_coordinator_overlap_off_single_barrier_path(hvd):
    """OVERLAP=0 keeps the original blocking fused path (one bucket at
    the fusion threshold) — and the results stay identical."""
    co, backend = _coordinator(hvd)
    assert co._overlap is False  # default: knob unset in the test env
    entries = _entries(hvd, 3)
    co._run_fused_allreduces(backend, entries, None)
    n = hvd.size()
    for j, e in enumerate(entries):
        out = e.handle.wait()
        np.testing.assert_allclose(
            np.asarray(out)[0],
            np.full((16,), np.mean([r + j for r in range(n)])), rtol=1e-6)


def test_coordinator_overlap_failure_isolated_per_bucket(hvd):
    """A backend failure on one bucket fails only that bucket's handles;
    the other buckets still complete."""
    co, backend = _coordinator(hvd)
    saved = (co._overlap, co._bucket_bytes)
    co._overlap, co._bucket_bytes = True, 8

    real = backend.allreduce
    calls = []

    def flaky(arrays, op, ps, prescale=None, postscale=None):
        calls.append(len(arrays))
        if len(calls) == 2:
            raise RuntimeError("injected bucket failure")
        return real(arrays, op, ps, prescale=prescale,
                    postscale=postscale)

    backend.allreduce = flaky
    try:
        entries = _entries(hvd, 3)
        co._run_fused_allreduces(backend, entries, None)
        oks, fails = [], []
        for e in entries:
            try:
                e.handle.wait()
                oks.append(e.name)
            except Exception:
                fails.append(e.name)
        assert len(fails) == 1 and len(oks) == 2
    finally:
        backend.allreduce = real
        co._overlap, co._bucket_bytes = saved


def test_knobs_registered():
    from horovod_tpu.utils import envparse
    assert envparse.OVERLAP in envparse.KNOBS
    assert envparse.BUCKET_BYTES in envparse.KNOBS
