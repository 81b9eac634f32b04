"""The bucket planner (ops/bucketing.py) and the eager plane's bucketed
comm/compute overlap (HVDTPU_OVERLAP; docs/performance.md): the
coordinator's priority-ordered async bucket dispatch. The compiled
step's exchange is tests/test_exchange_schedule.py's.
"""

import jax.numpy as jnp
import numpy as np

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
