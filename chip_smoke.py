"""Smoke test on the chip: the main training path, once, at full width.

    python chip_smoke.py

One process on however many TPU chips JAX reports (1, or the 4 of a
v5e host). Through the public API only it runs the Horovod-shaped
eager collectives, checks the Pallas flash-attention kernel against the
einsum reference at the trainer's layer shapes, takes a few steps of
the 365M decoder at seq 2048 through DistributedOptimizer /
make_train_step, and checks that every chip did its share. Any failed
check raises, so the exit status is the result. Without a TPU it exits
non-zero and prints no result line. The seconds it prints are a smoke
reading, not a benchmark.

The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
"""

import functools
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

# The lm365m configuration (benchmark/configs/lm365m.json) at seq 2048:
# BERT-large widths as a causal LM, 6 sequences per chip.
VOCAB, HIDDEN, LAYERS, HEADS = 30522, 1024, 24, 16
SEQ, SEQS_PER_CHIP, STEPS = 2048, 6, 5
TILE = 1024              # models/transformer.py asks for 1024-token tiles
KERNELS_PER_LAYER = 2    # forward, and one backward for dq, dk and dv
# max|kernel - reference| / max|reference| on bf16 inputs, the reference
# in fp32 at highest matmul precision. bf16 carries 8 bits of mantissa;
# a masking or block-skip bug shows as an error of order 1.
FWD_TOL, GRAD_TOL = 2e-2, 5e-2


class SmokeFailure(RuntimeError):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)
    print(f"ok: {what}", flush=True)


def require_tpu():
    devices = jax.devices()
    if any(d.platform != "tpu" for d in devices):
        sys.exit(f"chip_smoke: needs a TPU and sets no platform itself; "
                 f"jax.default_backend()={jax.default_backend()!r}, "
                 f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")
    print(f"platform={devices[0].platform} "
          f"device_kind={devices[0].device_kind!r} count={len(devices)}",
          flush=True)
    return devices


def eager_phase(hvd, n):
    """XlaSingleBackend on real devices: single-controller inputs are
    stacked, axis 0 is the virtual rank."""
    check(hvd.size() == n, f"hvd.size() == {n} devices")
    x = np.arange(n * 8, dtype=np.float32).reshape(n, 8)
    out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name="smoke.allreduce"))
    check(np.array_equal(out, np.broadcast_to(x.sum(0), x.shape)),
          "named hvd.allreduce of a stacked (n, 8) array equals the sum")
    out = np.asarray(hvd.broadcast(x, root_rank=n - 1,
                                   name="smoke.broadcast"))
    check(np.array_equal(out, np.broadcast_to(x[n - 1], x.shape)),
          "hvd.broadcast from the last rank reaches every rank")
    tree = {"w": jnp.arange(4.0), "b": jnp.ones((2, 2))}
    back = hvd.broadcast_parameters(tree, root_rank=0)
    check(all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(tree), jax.tree.leaves(back))),
        "hvd.broadcast_parameters round-trips a pytree")


def _attention_errors(q, k, v, w):
    """Relative errors (out, dq, dk, dv) of the kernel against the
    reference for the loss sum(out * w)."""
    from horovod_tpu.ops.flash_attention import (flash_attention,
                                                 reference_attention)

    def out_and_grads(attention):
        def loss(q, k, v):
            o = attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32) * w), o
        (_, o), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (o,) + grads

    got = out_and_grads(functools.partial(
        flash_attention, block_q=TILE, block_k=TILE))
    with jax.default_matmul_precision("highest"):
        want = out_and_grads(reference_attention)

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))
    return jnp.stack([rel(a, b) for a, b in zip(got, want)])


def kernel_phase(mesh, n):
    """flash_attention and its jax.grad against reference_attention at
    the trainer's layer shapes, outside and inside a shard_map over the
    mesh."""
    head_dim = HIDDEN // HEADS
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    shape = (n * SEQS_PER_CHIP, HEADS, SEQ, head_dim)
    q, k, v, w = (jax.random.normal(key, shape, jnp.bfloat16)
                  for key in keys)

    def report(where, errs):
        errs = [float(e) for e in np.asarray(errs)]
        print(f"kernel vs reference {where}: " + " ".join(
            f"{name}={e:.2e}" for name, e in zip(
                ("out", "dq", "dk", "dv"), errs)), flush=True)
        check(np.isfinite(errs).all() and errs[0] <= FWD_TOL
              and max(errs[1:]) <= GRAD_TOL,
              f"flash_attention agrees with reference_attention {where} "
              f"(out <= {FWD_TOL}, grads <= {GRAD_TOL})")

    one = slice(0, SEQS_PER_CHIP)
    report("outside shard_map",
           jax.jit(_attention_errors)(q[one], k[one], v[one], w[one]))

    sharding = NamedSharding(mesh, P("hvd"))
    q, k, v, w = (jax.device_put(x, sharding) for x in (q, k, v, w))
    inside = jax.jit(jax.shard_map(
        lambda *xs: lax.pmax(_attention_errors(*xs), "hvd"),
        mesh=mesh, in_specs=P("hvd"), out_specs=P()))
    report(f"inside shard_map over {n} device(s)", inside(q, k, v, w))


def seeded_dropout_phase():
    """The on-chip-prng dropout variant is not on the trainer's path;
    compile it once and check what a keep-mask must satisfy."""
    from horovod_tpu.ops.flash_attention import flash_attention
    shape = (2, HEADS, SEQ, HIDDEN // HEADS)
    q, k = (jax.random.normal(key, shape, jnp.bfloat16)
            for key in jax.random.split(jax.random.PRNGKey(2)))
    v = jnp.ones(shape, jnp.bfloat16)

    @jax.jit
    def run(q, k, v, seed):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, block_q=TILE,
                                block_k=TILE, dropout_rate=0.1,
                                dropout_seed=seed)
            return jnp.sum(o.astype(jnp.float32)), o
        (_, o), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return o, grads

    a, grads = run(q, k, v, 1)
    b, _ = run(q, k, v, 1)
    c, _ = run(q, k, v, 2)
    a, b, c = (np.asarray(x, np.float32) for x in (a, b, c))
    # With v == 1 each output is sum(p * keep / 0.9) / sum(p): mean 1.
    check(np.isfinite(a).all() and all(
        np.isfinite(np.asarray(g, np.float32)).all() for g in grads)
        and np.array_equal(a, b) and not np.array_equal(a, c)
        and abs(a.mean() - 1.0) < 0.02,
        f"seeded dropout: finite, same seed same mask, other seed other "
        f"mask, mean keep scale {a.mean():.4f} ~ 1")


def train_phase(mesh, devices):
    """A few steps of the 365M decoder through DistributedOptimizer and
    make_train_step on a fixed batch made from a seed."""
    import optax

    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.models import TransformerConfig, TransformerLM

    n = len(devices)
    cfg = TransformerConfig(
        vocab_size=VOCAB, hidden=HIDDEN, layers=LAYERS, heads=HEADS,
        max_len=SEQ, causal=True, use_rope=True, attention_impl="flash")
    model = TransformerLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, SEQ), jnp.int32))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n_params / 1e6:.1f}M parameters, {LAYERS} layers, "
          f"hidden {HIDDEN}, seq {SEQ} x {SEQS_PER_CHIP} per chip",
          flush=True)
    initial = jax.tree.map(np.asarray, params)

    opt = hvd_jax.DistributedOptimizer(optax.adamw(1e-4))

    def loss_fn(p, batch):
        tokens, targets = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(p, tokens), targets).mean()

    step = hvd_jax.make_train_step(loss_fn, opt)
    opt_state = opt.init(params)

    tokens = np.random.RandomState(0).randint(
        0, VOCAB, size=(n * SEQS_PER_CHIP, SEQ + 1)).astype(np.int32)
    sharding = NamedSharding(mesh, P("hvd"))
    batch = (jax.device_put(tokens[:, :-1], sharding),
             jax.device_put(tokens[:, 1:], sharding))
    shards = batch[0].addressable_shards
    rows = [np.asarray(s.data) for s in shards]
    check(len(shards) == n
          and {s.device for s in shards} == set(devices)
          and len({str(s.index) for s in shards}) == n
          and all(r.shape == (SEQS_PER_CHIP, SEQ) for r in rows)
          and all(not np.array_equal(rows[i], rows[j])
                  for i in range(n) for j in range(i)),
          f"the batch has {n} distinct shard(s) of {SEQS_PER_CHIP} "
          f"sequences, one per device")

    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, batch).compile()
    compile_s = time.perf_counter() - t0
    # In the compiled step: the layers share one lowering of the
    # forward kernel (ops/flash_attention.py: _fwd_call), which XLA
    # inlines a layer.
    mosaic_calls = compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    check(mosaic_calls == KERNELS_PER_LAYER * LAYERS,
          f"the compiled step holds {mosaic_calls} Mosaic custom calls "
          f"({KERNELS_PER_LAYER} per layer)")
    memory = compiled.memory_analysis()
    print(f"compiled step, bytes per device: arguments "
          f"{memory.argument_size_in_bytes} outputs "
          f"{memory.output_size_in_bytes} temporaries "
          f"{memory.temp_size_in_bytes}", flush=True)
    hlo = compiled.as_text()
    reductions = hlo.count(" all-reduce") + hlo.count(" reduce-scatter")
    if n > 1:
        check(reductions > 0, f"the compiled step holds {reductions} "
              f"cross-device all-reduce op(s)")

    losses, step_s = [], []
    for _ in range(STEPS):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, batch)
        jax.block_until_ready((params, opt_state, loss))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    print("losses: " + " ".join(f"{x:.4f}" for x in losses), flush=True)
    print(f"smoke reading, not a benchmark: compile {compile_s:.1f} s, "
          f"steps " + " ".join(f"{s:.3f}" for s in step_s) + " s",
          flush=True)
    check(np.isfinite(losses).all() and losses[-1] < losses[0],
          f"{STEPS} finite losses, the last below the first")

    def replicated_and_changed(leaf, before):
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        return (len(copies) == n
                and {s.device for s in leaf.addressable_shards}
                == set(devices)
                and all(np.array_equal(copies[0], c) for c in copies[1:])
                and not np.array_equal(copies[0], before))

    failing = [jax.tree_util.keystr(path) for (path, leaf), before in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree.leaves(initial))
        if not replicated_and_changed(leaf, before)]
    check(not failing, f"every parameter leaf has {n} bit-identical "
          f"per-device copies, changed from the initial value"
          + (f" (failing: {failing[:3]})" if failing else ""))


def memory_phase(devices):
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    print("peak bytes in use per device: "
          + " ".join(str(p) for p in peaks), flush=True)
    check(min(peaks) >= 0.75 * max(peaks),
          "every device's peak memory is within 25% of the largest")


def main():
    devices = require_tpu()
    from horovod_tpu.utils import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)

    import horovod_tpu as hvd
    if os.path.dirname(os.path.dirname(os.path.abspath(
            hvd.__file__))) != HERE:
        raise SmokeFailure(f"horovod_tpu came from {hvd.__file__}, not "
                           f"from this checkout ({HERE})")
    hvd.init()
    n = len(devices)
    eager_phase(hvd, n)
    kernel_phase(hvd.mesh(), n)
    seeded_dropout_phase()
    train_phase(hvd.mesh(), devices)
    memory_phase(devices)
    hvd.shutdown()

    check("horovod_tpu.native" not in sys.modules,
          "the single-controller path never imported horovod_tpu.native")
    alive = [t.name for t in threading.enumerate()
             if t is not threading.main_thread() and not t.daemon]
    check(not alive, f"no thread outlives hvd.shutdown() (alive: {alive})")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": n}}), flush=True)


if __name__ == "__main__":
    main()
