"""End-to-end long-context training on the 8-device mesh (VERDICT r3 #7):
ring attention + the flash entry point at seq 4096, full train step
(fwd+bwd+update) with gradient parity against a dense single-device
oracle. On CPU the flash call inside shard_map falls back to the einsum
oracle by design (pallas interpreter can't take device-varying offsets;
on TPU the compiled kernel engages) — the ring schedule, collectives and
autodiff path are identical either way."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops.flash_attention import reference_attention
from horovod_tpu.utils.jax_compat import pvary
from horovod_tpu.parallel import ring_attention

B, H, S, DH, DM = 1, 2, 4096, 32, 64


def _params(seed=0):
    rng = np.random.RandomState(seed)

    def r(*shape, scale=0.15):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32)
                           * scale)
    return {"wq": r(DM, H, DH), "wk": r(DM, H, DH), "wv": r(DM, H, DH),
            "wo": r(H, DH, DM)}


def _data(seed=1):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.normal(size=(B, S, DM)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(B, S, DM)).astype(np.float32))
    return x, y


def _model(p, x, attn):
    q = jnp.einsum("bsd,dhe->bhse", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bhse", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bhse", x, p["wv"])
    o = attn(q, k, v)
    return jnp.einsum("bhse,hed->bsd", o, p["wo"])


def _mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


def test_ring_flash_seq4k_gradient_parity():
    """d(loss)/d(params) of the 8-way ring at seq 4096 matches the dense
    single-device causal-attention oracle."""
    p = _params()
    x, y = _data()

    def ring_loss(p, x, y):
        # p is a replicated shard_map input: declare it varying so its
        # cotangent reduces across 'sp' (vma-jax auto-inserts this).
        p = jax.tree.map(lambda w: pvary(w, "sp"), p)
        out = _model(p, x, lambda q, k, v: ring_attention(
            q, k, v, "sp", causal=True, impl="flash"))
        return jax.lax.pmean(jnp.mean((out - y) ** 2), "sp")

    g_ring = jax.jit(shard_map(
        jax.grad(ring_loss), mesh=_mesh(),
        in_specs=(P(), P(None, "sp", None), P(None, "sp", None)),
        out_specs=P()))(p, x, y)

    def dense_loss(p, x, y):
        out = _model(p, x, lambda q, k, v: reference_attention(
            q, k, v, causal=True))
        return jnp.mean((out - y) ** 2)

    g_dense = jax.grad(dense_loss)(p, x, y)
    for k in p:
        np.testing.assert_allclose(np.asarray(g_ring[k]),
                                   np.asarray(g_dense[k]),
                                   atol=2e-5, rtol=2e-3)


def test_ring_flash_seq4k_training_descends():
    """Three full train steps (fwd+bwd+SGD) at seq 4096 over the 8-way
    sequence mesh: loss strictly decreases and parameters stay finite."""
    p = _params()
    x, y = _data()
    lr = 0.5

    def step(p, x, y):
        def loss_fn(p):
            p = jax.tree.map(lambda w: pvary(w, "sp"), p)
            out = _model(p, x, lambda q, k, v: ring_attention(
                q, k, v, "sp", causal=True, impl="flash"))
            return jax.lax.pmean(jnp.mean((out - y) ** 2), "sp")
        loss, g = jax.value_and_grad(loss_fn)(p)
        new_p = jax.tree.map(lambda w, gw: w - lr * gw, p, g)
        return new_p, loss

    jstep = jax.jit(shard_map(
        step, mesh=_mesh(),
        in_specs=(P(), P(None, "sp", None), P(None, "sp", None)),
        out_specs=(P(), P())))

    losses = []
    for _ in range(3):
        p, loss = jstep(p, x, y)
        losses.append(float(loss))
    assert losses[2] < losses[1] < losses[0], losses
    assert all(np.isfinite(np.asarray(v)).all()
               for v in jax.tree.leaves(p))


@pytest.mark.parametrize("n", [2, 8])
def test_ring_flash_seq4k_output_matches_dense(n):
    p = _params(3)
    x, _ = _data(4)

    out = jax.jit(shard_map(
        lambda p, x: _model(p, x, lambda q, k, v: ring_attention(
            q, k, v, "sp", causal=True, impl="flash")),
        mesh=_mesh(n), in_specs=(P(), P(None, "sp", None)),
        out_specs=P(None, "sp", None)))(p, x)
    ref = _model(p, x, lambda q, k, v: reference_attention(
        q, k, v, causal=True))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=2e-3)
