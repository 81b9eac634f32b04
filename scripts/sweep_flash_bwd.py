"""Microbench: flash attention fwd+bwd wall time on the real chip.

Sweeps backward tile sizes and the bf16-operand change. Not a test —
a measurement script behind docs/PERF.md numbers.
"""
import os
import sys
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.ops.flash_attention import flash_attention


def bench(seq, batch, heads=16, d=64, block_q=256, block_k=256,
          iters=20, fwd_only=False, **kw):
    rng = np.random.RandomState(0)
    shape = (batch, heads, seq, d)
    q = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=block_q,
                            block_k=block_k, **kw)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def chained(q, k, v, n=10):
        # chain grad steps so one host fetch covers n of them
        def body(carry, _):
            qq, kk, vv = carry
            if fwd_only:
                l = loss(qq, kk, vv)
                return ((qq + l * 1e-12).astype(qq.dtype), kk, vv), None
            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(qq, kk, vv)
            return (qq + dq * 1e-6, kk + dk * 1e-6, vv + dv * 1e-6), None
        (qq, _, _), _ = jax.lax.scan(body, (q, k, v), None, length=n)
        return jnp.sum(qq.astype(jnp.float32))

    chain = 50
    iters = 5
    g = jax.jit(lambda q, k, v: chained(q, k, v, chain))
    float(g(q, k, v))  # warm + fence

    def run():
        float(g(q, k, v))

    run()
    t = timeit.timeit(run, number=iters) / iters / chain
    # causal attention FLOPs (fwd 2 matmuls + bwd 5 matmuls), half for causal
    nmm = 2 if fwd_only else 7
    flops = nmm * 2 * batch * heads * seq * seq * d / 2
    print(f"seq={seq} batch={batch} bq={block_q} bk={block_k} "
          f"fwd_only={fwd_only} kw={kw}: "
          f"{t*1e3:.2f} ms  {flops/t/1e12:.1f} TF/s(causal-counted)",
          flush=True)
    return t


if __name__ == "__main__":
    for args in sys.argv[1:] or ["512,24,256,256", "2048,4,256,256"]:
        parts = args.split(",")
        seq, batch, bq, bk = map(int, parts[:4])
        fwd_only = len(parts) > 4 and parts[4] == "f"
        bench(seq, batch, block_q=bq, block_k=bk, fwd_only=fwd_only)
