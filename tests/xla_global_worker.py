"""Worker for the XLA-global data plane tests (HVDTPU_CPU_OPERATIONS=xla).

One rank of an N-process job whose eager collectives execute as jitted XLA
collectives over the jax.distributed global mesh while the native TCP core
negotiates (see horovod_tpu/backend/xla_global.py). Also jits a step over
ALL global devices to prove multi-host compiled SPMD works through the
same bootstrap — the driver's dryrun_multichip story spanning processes.
"""

import math
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import basics  # noqa: E402


def main():
    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    rt = basics.runtime()
    assert rt.backend.name == "xla-global", rt.backend.name
    assert rt.backend.delegate_data_ops

    if os.environ.get("XGW_MODE") == "kill":
        # Adversity: peer death on the delegated plane. The native TCP
        # control plane must surface HorovodInternalError to survivors
        # BEFORE any jitted collective launches over the global mesh
        # (an XLA collective with a dead participant would hang in the
        # distributed runtime).
        warm = hvd.allreduce(jnp.ones(4), op=hvd.Sum, name="warm")
        np.testing.assert_allclose(np.asarray(warm), float(size))
        if rank == size - 1:
            os._exit(17)  # die abruptly: no shutdown, no consensus
        try:
            for i in range(50):
                hvd.allreduce(jnp.ones(256), op=hvd.Sum, name=f"k{i}")
            raise SystemExit("collectives kept succeeding w/ dead peer")
        except hvd.HorovodInternalError:
            pass
        print(f"rank {rank}/{size}: XLA-GLOBAL-KILL OK", flush=True)
        # Skip hvd.shutdown(): its final consensus would need the dead
        # peer; abrupt exit is the point of this scenario.
        os._exit(0)

    local_n = int(os.environ.get("XGW_LOCAL_DEVICES", "4"))
    assert len(jax.devices()) == size * local_n, (
        f"global mesh missing: {len(jax.devices())} != {size}x{local_n}")
    assert len(jax.local_devices()) == local_n

    # -- allreduce sum / average / steady-state cache ----------------------
    x = jnp.arange(5, dtype=jnp.float32) + rank
    expect = np.arange(5, dtype=np.float32) * size + sum(range(size))
    out = hvd.allreduce(x, op=hvd.Sum, name="ar")
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)
    avg = hvd.allreduce(x, name="avg")
    np.testing.assert_allclose(np.asarray(avg), expect / size, rtol=1e-6)
    for _ in range(3):
        again = hvd.allreduce(x, op=hvd.Sum, name="ar")
        np.testing.assert_allclose(np.asarray(again), expect, rtol=1e-6)

    # -- grouped allreduce (one fused XLA call) ----------------------------
    outs = hvd.grouped_allreduce(
        [jnp.full((2,), float(rank)), jnp.full((3, 2), 2.0 * rank)],
        name="gar", op=hvd.Sum)
    s = sum(range(size))
    np.testing.assert_allclose(np.asarray(outs[0]), np.full((2,), s))
    np.testing.assert_allclose(np.asarray(outs[1]), np.full((3, 2), 2.0 * s))

    # -- min / max / product ----------------------------------------------
    v = jnp.full((4,), float(rank + 1))
    np.testing.assert_allclose(
        np.asarray(hvd.allreduce(v, op=hvd.Min, name="mn")), 1.0)
    np.testing.assert_allclose(
        np.asarray(hvd.allreduce(v, op=hvd.Max, name="mx")), float(size))
    np.testing.assert_allclose(
        np.asarray(hvd.allreduce(v, op=hvd.Product, name="pr")),
        float(math.factorial(size)))

    # -- broadcast ---------------------------------------------------------
    b = hvd.broadcast(jnp.full((3,), float(rank)), root_rank=1, name="bc")
    np.testing.assert_allclose(np.asarray(b), 1.0)

    # -- allgather with uneven dim0 ---------------------------------------
    g = hvd.allgather(jnp.full((rank + 1, 2), float(rank)), name="ag")
    g = np.asarray(g)
    assert g.shape == (sum(r + 1 for r in range(size)), 2), g.shape
    off = 0
    for r in range(size):
        np.testing.assert_allclose(g[off:off + r + 1], float(r))
        off += r + 1

    # -- reducescatter (uneven rows: remainder to low ranks) --------------
    rs = hvd.reducescatter(jnp.ones((size + 1, 3)), op=hvd.Sum, name="rs")
    rs = np.asarray(rs)
    base, rem = divmod(size + 1, size)
    my_rows = base + (1 if rank < rem else 0)
    assert rs.shape == (my_rows, 3), rs.shape
    np.testing.assert_allclose(rs, float(size))

    # -- fp16 --------------------------------------------------------------
    h16 = hvd.allreduce(jnp.ones(3, jnp.float16) * (rank + 1), op=hvd.Sum,
                        name="h16")
    np.testing.assert_allclose(np.asarray(h16, dtype=np.float32),
                               sum(r + 1 for r in range(size)))

    # -- Adasum: excluded from delegation, runs native VHDD ---------------
    if size & (size - 1) == 0:  # power-of-two ranks only
        ada = np.random.RandomState(7).randn(size, 17).astype(np.float32)
        out_ada = np.asarray(hvd.allreduce(jnp.asarray(ada[rank]),
                                           op=hvd.Adasum, name="ada"))

        from horovod_tpu.ops.adasum import adasum_vhdd_np

        expect = adasum_vhdd_np([ada[i] for i in range(size)])
        np.testing.assert_allclose(out_ada, expect, rtol=1e-5,
                                   atol=1e-6)

    # -- barrier + alltoall still ride the native TCP plane ---------------
    hvd.barrier()
    a = jnp.full((size, 2), float(rank), jnp.float32)
    at = hvd.alltoall(a, name="a2a")
    np.testing.assert_allclose(
        np.asarray(at),
        np.repeat(np.arange(size, dtype=np.float32), 2).reshape(size, 2))

    # -- compiled SPMD over ALL global devices (multi-host pjit) ----------
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    n_global = size * local_n
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    w = jnp.ones((8, 8), jnp.float32)

    @jax.jit
    def step(batch, w):
        def inner(b, w):
            y = b @ w
            loss_grad = jax.lax.psum(y.sum(0, keepdims=True), "dp")
            return loss_grad
        return jax.shard_map(inner, mesh=mesh, in_specs=(P("dp"), P()),
                             out_specs=P())(batch, w)

    local_batch = np.full((local_n, 8), 1.0 + rank, np.float32)
    batch = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), local_batch)
    res = np.asarray(step(batch, w).addressable_data(0))
    expect_sum = 8.0 * sum((1.0 + r) * local_n for r in range(size))
    np.testing.assert_allclose(res[0], expect_sum, rtol=1e-6)

    print(f"rank {rank}/{size}: XLA-GLOBAL OK", flush=True)
    hvd.shutdown()


if __name__ == "__main__":
    main()
