"""Gradient-compression plane tests (fast lane, tier-1; ISSUE 6).

Covers the codec numerics matrix (round-trip error bounds per block
size), the quantized allreduce vs the fp32 oracle on the CPU backend,
the error-feedback convergence result (a synthetic SGD problem where
naive int8 stalls and error feedback recovers the optimum), policy
glob/threshold selection with the loud Adasum/process-set rejects,
residual reset on an elastic version bump, the guardian digest's codec
field, the HVD205 lint fixture, and the disabled-mode zero-overhead
guard (the telemetry/chaos acceptance contract).

NOTE: the disabled-guard test is first in the file on purpose — it
asserts the session coordinator has built NO plane, which must be
checked before this module's own compression tests lazily create one.
"""

import os

import numpy as np
import pytest

import horovod_tpu as hvd_mod
from horovod_tpu import basics, guardian
from horovod_tpu.compression import codecs, make_plane, policy
from horovod_tpu.compression.residual import ResidualStore
from horovod_tpu.coordinator import TensorEntry
from horovod_tpu.ops import reduce_ops
from horovod_tpu.process_sets import global_process_set

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rand(n, *shape, lo=-1.0, hi=1.0, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    return rng.uniform(lo, hi, size=(n,) + shape).astype(dtype)


# ---------------------------------------------------------------------------
# Disabled-mode guard (FIRST: see module docstring)
# ---------------------------------------------------------------------------

def test_disabled_mode_zero_per_submit_state(hvd, n_devices,
                                             monkeypatch):
    """HVDTPU_COMPRESSION unset: no plane object exists, entries carry
    codec=None, and a plain allreduce never touches the quantized
    pipeline — the telemetry/chaos/guardian disabled contract."""
    assert make_plane() is None
    coord = basics.runtime().coordinator
    assert coord._compression is None
    backend = basics.runtime().backend

    def _boom(*a, **k):  # pragma: no cover - the assertion is that it
        raise AssertionError("quantized pipeline used in disabled mode")
    monkeypatch.setattr(type(backend), "allreduce_quantized", _boom,
                        raising=False)
    x = rand(n_devices, 2048)
    out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name="comp.disabled"))
    np.testing.assert_allclose(out, np.broadcast_to(x.sum(0), x.shape),
                               rtol=1e-5)
    assert coord._compression is None  # still no per-submit state
    e = TensorEntry("t", "allreduce", [x], global_process_set,
                    op=reduce_ops.Sum)
    assert e.codec is None


# ---------------------------------------------------------------------------
# Codec numerics matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [32, 64, 256])
def test_int8_roundtrip_error_bound_per_block(block):
    """|x - dq(q(x))| <= max|block| / 254 — the documented bound."""
    c = codecs.get_codec("int8")
    x = rand(4, 4 * block, lo=-3, hi=3, seed=block)
    import jax.numpy as jnp
    q, s = c.encode(jnp.asarray(x), block)
    assert np.asarray(q).dtype == np.int8
    assert s.shape == (4, 4 * block // block)
    dq = np.asarray(c.decode(q, s, block))
    err = np.abs(dq - x).reshape(4, -1, block)
    bound = np.abs(x).reshape(4, -1, block).max(axis=-1, keepdims=True)
    assert (err <= bound / 254.0 + 1e-7).all()


def test_int8_all_zero_block_is_exact():
    c = codecs.get_codec("int8")
    import jax.numpy as jnp
    x = jnp.zeros((2, 128), jnp.float32)
    q, s = c.encode(x, 64)
    dq = np.asarray(c.decode(q, s, 64))
    assert not np.isnan(dq).any() and (dq == 0).all()


@pytest.mark.skipif(not codecs.fp8_supported(),
                    reason="no float8_e4m3fn in this jax")
def test_fp8_roundtrip_relative_error():
    """fp8 e4m3 keeps ~3 mantissa bits: per-block relative error under
    ~6.7% of the block max (1/(2*8) plus scale rounding headroom)."""
    c = codecs.get_codec("fp8")
    x = rand(2, 1024, lo=-5, hi=5, seed=7)
    import jax.numpy as jnp
    q, s = c.encode(jnp.asarray(x), 128)
    dq = np.asarray(c.decode(q, s, 128))
    err = np.abs(dq - x).reshape(2, -1, 128)
    bound = np.abs(x).reshape(2, -1, 128).max(axis=-1, keepdims=True)
    assert (err <= bound * 0.067 + 1e-7).all()


def test_padded_len():
    assert codecs.padded_len(0, 8, 64) == 0
    assert codecs.padded_len(1, 8, 64) == 512
    assert codecs.padded_len(512, 8, 64) == 512
    assert codecs.padded_len(513, 8, 64) == 1024


def test_unknown_codec_is_loud():
    with pytest.raises(ValueError, match="unknown compression codec"):
        codecs.get_codec("int4")


def test_compression_surface_markers():
    """The Horovod-shaped user surface: casts keep compress/decompress
    semantics, wire codecs are identity + marker."""
    from horovod_tpu.ops.compression import Compression
    assert Compression.int8.wire_codec == "int8"
    assert Compression.fp8.wire_codec == "fp8"
    assert getattr(Compression.fp16, "wire_codec", None) is None
    import jax.numpy as jnp
    t = jnp.ones((4, 4))
    out, ctx = Compression.int8.compress(t)
    assert out is t and ctx is None


# ---------------------------------------------------------------------------
# Quantized allreduce vs the fp32 oracle (CPU backend matrix)
# ---------------------------------------------------------------------------

def _pipeline_bound(x, n, block, postscale=1.0):
    """Documented end-to-end bound: n per-rank quantization errors
    accumulate through the Sum, plus one requantization of the reduced
    value (docs/compression.md)."""
    per_rank = np.abs(x).reshape(n, -1)
    reduced = np.abs(x.sum(axis=0) * postscale)
    return (n * per_rank.max() / 254.0 * abs(postscale)
            + reduced.max() / 254.0)


@pytest.mark.parametrize("block", [64, 256])
@pytest.mark.parametrize("op_name", ["Sum", "Average"])
def test_quantized_allreduce_within_documented_bound(hvd, n_devices,
                                                     block, op_name):
    op = getattr(reduce_ops, op_name)
    backend = basics.runtime().backend
    codec = codecs.get_codec("int8")
    x = rand(n_devices, 777, seed=block)
    outs, errs = backend.allreduce_quantized([x], op, global_process_set,
                                             codec, block)
    assert errs is None
    expect = x.sum(0) if op == reduce_ops.Sum else x.mean(0)
    scale = 1.0 if op == reduce_ops.Sum else 1.0 / n_devices
    bound = _pipeline_bound(x, n_devices, block, postscale=scale)
    err = np.max(np.abs(np.asarray(outs[0])
                        - np.broadcast_to(expect, x.shape)))
    assert err <= bound, (err, bound)
    assert np.asarray(outs[0]).dtype == x.dtype


def test_quantized_allreduce_multi_array_and_scales(hvd, n_devices):
    """Fused bucket of unequal shapes + pre/postscale, with residuals
    threaded through."""
    backend = basics.runtime().backend
    codec = codecs.get_codec("int8")
    xs = [rand(n_devices, 100, 3, seed=1), rand(n_devices, 57, seed=2)]
    res_in = [np.zeros_like(a) for a in xs]
    outs, errs = backend.allreduce_quantized(
        xs, reduce_ops.Sum, global_process_set, codec, 64,
        prescale=0.5, postscale=2.0, residuals=res_in)
    assert len(outs) == 2 and len(errs) == 2
    for x, o, e in zip(xs, outs, errs):
        expect = (x * 0.5).sum(0) * 2.0
        bound = _pipeline_bound(x * 0.5, n_devices, 64, postscale=2.0)
        assert np.max(np.abs(np.asarray(o)
                             - np.broadcast_to(expect, x.shape))) <= bound
        assert np.asarray(e).shape == x.shape
        # The residual IS the local reconstruction error of the
        # (prescaled) input — bounded by the per-block step.
        assert np.max(np.abs(np.asarray(e))) <= np.abs(x * 0.5).max() / 254.0 + 1e-7


def test_quantized_allreduce_rejects_nonlinear_ops(hvd):
    backend = basics.runtime().backend
    codec = codecs.get_codec("int8")
    x = rand(hvd.size(), 64)
    with pytest.raises(ValueError, match="Sum/Average"):
        backend.allreduce_quantized([x], reduce_ops.Max,
                                    global_process_set, codec, 64)


def test_quantized_allreduce_bf16_inputs(hvd, n_devices):
    """bf16 gradients ride the pipeline (f32 accumulation inside) and
    come back bf16."""
    import jax.numpy as jnp
    backend = basics.runtime().backend
    codec = codecs.get_codec("int8")
    x = jnp.asarray(rand(n_devices, 512, seed=5), jnp.bfloat16)
    outs, _ = backend.allreduce_quantized([x], reduce_ops.Average,
                                          global_process_set, codec, 64)
    assert outs[0].dtype == jnp.bfloat16
    expect = np.asarray(x, np.float32).mean(0)
    err = np.max(np.abs(np.asarray(outs[0], np.float32)
                        - np.broadcast_to(expect, x.shape)))
    assert err < 0.05  # quantization + bf16 rounding


# ---------------------------------------------------------------------------
# End-to-end through the coordinator (explicit marker + env policy)
# ---------------------------------------------------------------------------

def test_explicit_int8_compression_through_public_api(hvd, n_devices):
    x = rand(n_devices, 4096, seed=11)
    out = np.asarray(hvd.allreduce(
        x, op=hvd.Sum, name="comp.explicit",
        compression=hvd_mod.Compression.int8))
    expect = np.broadcast_to(x.sum(0), x.shape)
    err = np.max(np.abs(out - expect))
    assert 0 < err <= _pipeline_bound(x, n_devices, 256)
    # The lazily-created plane stored this tensor's residual.
    plane = basics.runtime().coordinator._compression
    assert plane is not None and plane.residuals.get("comp.explicit")


def test_grouped_int8_compression(hvd, n_devices):
    xs = [rand(n_devices, 2000, seed=20 + i) for i in range(3)]
    outs = hvd_mod.grouped_allreduce(
        xs, op=hvd_mod.Average, name="comp.grouped",
        compression=hvd_mod.Compression.int8)
    for x, o in zip(xs, outs):
        err = np.max(np.abs(np.asarray(o)
                            - np.broadcast_to(x.mean(0), x.shape)))
        assert err <= _pipeline_bound(x, n_devices, 256, 1.0 / n_devices)


def test_adasum_with_wire_codec_is_loud(hvd, n_devices):
    x = rand(n_devices, 4096)
    with pytest.raises(ValueError, match="Adasum"):
        hvd.allreduce(x, op=hvd_mod.Adasum, name="comp.adasum",
                      compression=hvd_mod.Compression.int8)


def test_process_set_with_wire_codec_is_loud(hvd, n_devices):
    ps = hvd_mod.add_process_set([0, 2])
    try:
        x = rand(2, 4096)
        with pytest.raises(ValueError, match="process set"):
            hvd.allreduce(x, op=hvd_mod.Sum, name="comp.ps",
                          compression=hvd_mod.Compression.int8,
                          process_set=ps)
    finally:
        hvd_mod.remove_process_set(ps)


def _install_plane(coord, rules, **kwargs):
    """Swap a policy-driven plane onto the live coordinator; returns
    (plane, restore_fn)."""
    saved = coord._compression
    plane = make_plane(force=True)
    plane.policy = policy.CompressionPolicy(policy.parse_rules(rules),
                                            **kwargs)
    coord._compression = plane

    def restore():
        coord._compression = saved
    return plane, restore


def test_env_policy_glob_and_threshold_selection(hvd, n_devices):
    coord = basics.runtime().coordinator
    plane, restore = _install_plane(coord, "*bias*=none;int8",
                                    threshold=256)
    try:
        x = rand(n_devices, 4096, seed=31)
        out = np.asarray(hvd.allreduce(x, op=hvd.Average,
                                       name="dense_kernel"))
        err = np.max(np.abs(out - np.broadcast_to(x.mean(0), x.shape)))
        assert 0 < err <= _pipeline_bound(x, n_devices, plane.block,
                                          1.0 / n_devices)
        assert plane.residuals.get("dense_kernel") is not None
        # Glob exclusion: bias tensors stay exact.
        out2 = np.asarray(hvd.allreduce(x, op=hvd.Average,
                                        name="dense_bias"))
        np.testing.assert_allclose(
            out2, np.broadcast_to(x.mean(0), x.shape), rtol=1e-5)
        # Threshold: small tensors stay exact.
        small = rand(n_devices, 16, seed=32)
        out3 = np.asarray(hvd.allreduce(small, op=hvd.Average,
                                        name="tiny_kernel"))
        np.testing.assert_allclose(
            out3, np.broadcast_to(small.mean(0), small.shape), rtol=1e-5)
        # Integer dtype: never selected.
        xi = np.arange(n_devices * 2048, dtype=np.int32)
        xi = xi.reshape(n_devices, 2048)
        oi = np.asarray(hvd.allreduce(xi, op=hvd.Sum, name="int_kernel"))
        np.testing.assert_array_equal(
            oi, np.broadcast_to(xi.sum(0), xi.shape))
        # Min/Max: silently uncompressed (not gradient math).
        om = np.asarray(hvd.allreduce(x, op=hvd_mod.Min,
                                      name="min_kernel"))
        np.testing.assert_allclose(om,
                                   np.broadcast_to(x.min(0), x.shape))
    finally:
        restore()


def test_cast_codec_bucket_through_coordinator(hvd, n_devices):
    """A policy-selected bf16 cast codec: narrow wire dtype, result cast
    back, correctness within bf16 rounding."""
    coord = basics.runtime().coordinator
    plane, restore = _install_plane(coord, "bf16", threshold=1)
    try:
        x = rand(n_devices, 2048, seed=41)
        out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name="cast_w"))
        assert out.dtype == np.float32
        np.testing.assert_allclose(
            out, np.broadcast_to(x.sum(0), x.shape), rtol=0.05,
            atol=0.05)
        err = np.max(np.abs(out - np.broadcast_to(x.sum(0), x.shape)))
        assert err > 0  # the narrow wire really was used
    finally:
        restore()


def test_policy_parse_malformed_is_loud():
    with pytest.raises(ValueError, match="malformed"):
        policy.parse_rules("=int8")
    with pytest.raises(ValueError, match="unknown compression codec"):
        policy.parse_rules("*=int4")


def test_policy_select_matrix():
    import jax.numpy as jnp
    pol = policy.CompressionPolicy(
        policy.parse_rules("*bias*=none;embed*=bf16;int8"), threshold=100)
    sel = lambda name, n=1000, dt=jnp.float32, op=reduce_ops.Average, \
        ps=0: pol.select(name, n, dt, op, ps)
    assert sel("dense_w") == "int8"
    assert sel("layer_bias") is None          # glob → none
    assert sel("embed_table") == "bf16"       # first-wins ordering
    assert sel("dense_w", n=99) is None       # threshold
    assert sel("dense_w", dt=jnp.int32) is None
    assert sel("dense_w", op=reduce_ops.Max) is None
    with pytest.raises(ValueError, match="Adasum"):
        sel("dense_w", op=reduce_ops.Adasum)
    with pytest.raises(ValueError, match="process set"):
        sel("dense_w", ps=3)
    # Empty policy selects nothing and never raises.
    empty = policy.CompressionPolicy([])
    assert empty.select("w", 10**6, jnp.float32, reduce_ops.Adasum,
                        5) is None


# ---------------------------------------------------------------------------
# Error feedback
# ---------------------------------------------------------------------------

def test_error_feedback_converges_where_naive_int8_stalls(hvd,
                                                          n_devices):
    """The EF acceptance test (docs/compression.md): per-rank gradients
    carry large mutually-cancelling components (±c), so the true mean
    gradient is tiny but each rank's quantization error scales with c.
    Naive int8 SGD random-walks at the quantization noise floor; error
    feedback carries each step's error into the next and converges to
    the uncompressed optimum. 150 steps, same problem, same seeds."""
    coord = basics.runtime().coordinator
    plane, restore = _install_plane(coord, "int8", threshold=1)
    d = 512
    rng = np.random.RandomState(0)
    # Cancelling pattern: large per-rank offsets with exact mean
    # zero, so the true mean gradient is w alone but each rank's
    # quantization step scales with the offsets.
    c = 32.0 * rng.uniform(0.5, 1.0, size=(n_devices, d))
    c -= c.mean(axis=0, keepdims=True)
    lr = 0.1

    def run(ef, name):
        plane.error_feedback = ef
        plane.residuals.reset()
        w = np.full(d, 1.0, np.float32)
        for t in range(150):
            grads = (w[None, :] + c).astype(np.float32)
            g = np.asarray(hvd_mod.allreduce(
                grads, op=hvd_mod.Average, name=f"{name}.g"))[0]
            w = w - lr * g
        return float(np.max(np.abs(w)))

    try:
        final_ef = run(True, "ef_on")
        final_naive = run(False, "ef_off")
    finally:
        restore()
    # Naive: stuck at the quantization noise floor (c_max/254-scale
    # kicks every step; measured ~2.1e-2 here). EF: converges well
    # below it (measured ~2.6e-3).
    assert final_naive > 1e-2, final_naive
    assert final_ef < final_naive / 5.0, (final_ef, final_naive)
    assert final_ef < 3e-3, final_ef


def test_residual_reset_on_elastic_version_bump(monkeypatch):
    monkeypatch.delenv("HVDTPU_ELASTIC_VERSION", raising=False)
    store = ResidualStore()
    store.put("t", [np.ones(4)])
    assert store.get("t") is not None and len(store) == 1
    monkeypatch.setenv("HVDTPU_ELASTIC_VERSION", "3")
    # Any access notices the version moved and drops everything.
    assert store.get("t") is None
    assert len(store) == 0
    store.put("t2", [np.ones(2)])
    assert store.get("t2") is not None  # new-version state accumulates


def test_residual_shape_change_discards_stale_residual(hvd, n_devices):
    """A tensor legally resubmitted with a new shape must get zeros,
    not a stale differently-shaped residual."""
    coord = basics.runtime().coordinator
    plane, restore = _install_plane(coord, "int8", threshold=1)
    try:
        x1 = rand(n_devices, 300, seed=50)
        hvd_mod.allreduce(x1, op=hvd_mod.Sum, name="reshaper")
        assert plane.residuals.get("reshaper")[0].shape == x1.shape
        x2 = rand(n_devices, 700, seed=51)
        out = np.asarray(hvd_mod.allreduce(x2, op=hvd_mod.Sum,
                                           name="reshaper"))
        assert out.shape == x2.shape
        assert plane.residuals.get("reshaper")[0].shape == x2.shape
    finally:
        restore()


# ---------------------------------------------------------------------------
# Guardian digest carries the codec
# ---------------------------------------------------------------------------

def test_digest_includes_codec_and_mismatch_names_field():
    e_q = TensorEntry("t", "allreduce", [np.zeros((2, 8), np.float32)],
                      global_process_set, op=reduce_ops.Average)
    e_q.codec = ("int8", 256)
    e_plain = TensorEntry("t", "allreduce",
                          [np.zeros((2, 8), np.float32)],
                          global_process_set, op=reduce_ops.Average)
    dq = guardian.entry_digest(e_q)
    dp = guardian.entry_digest(e_plain)
    assert dq["codec"] == "int8@b256"
    assert dp["codec"] is None
    divs = guardian.compare_digests(dq, {1: dp})
    assert [(r, f) for r, f, _, _ in divs] == [(1, "codec")]
    # Block-size divergence is a codec mismatch too.
    e_b = TensorEntry("t", "allreduce", [np.zeros((2, 8), np.float32)],
                      global_process_set, op=reduce_ops.Average)
    e_b.codec = ("int8", 64)
    divs = guardian.compare_digests(dq, {1: guardian.entry_digest(e_b)})
    assert divs and divs[0][1] == "codec"


# ---------------------------------------------------------------------------
# In-jit quantized reduction (DistributedOptimizer axis path)
# ---------------------------------------------------------------------------

def test_quantized_allreduce_axis_numerics(hvd, n_devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    mesh = basics.runtime().mesh
    x = rand(n_devices, 1000, seed=60)

    def body(v):
        return codecs.quantized_allreduce_axis(v, "hvd", "int8", 128,
                                               average=False)
    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("hvd"),
                           out_specs=P("hvd"), check_vma=False))
    out = np.asarray(fn(jnp.asarray(x)))
    bound = _pipeline_bound(x, n_devices, 128)
    assert np.max(np.abs(out - np.broadcast_to(x.sum(0), x.shape))) \
        <= bound


def test_train_step_with_int8_compression_converges(hvd, n_devices):
    """make_train_step + DistributedOptimizer(compression=int8): the
    gradient reduction inside the compiled step runs the quantized
    pipeline and the toy regression still trains."""
    import jax.numpy as jnp
    import optax
    import horovod_tpu.jax as hvd_jax
    rng = np.random.RandomState(1)

    def loss_fn(p, batch):
        xb, yb = batch
        return jnp.mean((xb @ p - yb) ** 2)

    opt = hvd_jax.DistributedOptimizer(
        optax.sgd(0.05), compression=hvd_mod.Compression.int8)
    step = hvd_jax.make_train_step(loss_fn, opt)
    params = jnp.zeros((8, 1), jnp.float32)
    opt_state = opt.init(params)
    xb = jnp.asarray(rng.uniform(size=(n_devices * 16, 8)), jnp.float32)
    yb = jnp.asarray(np.asarray(xb) @ np.linspace(1, 2, 8)[:, None],
                     jnp.float32)
    first = last = None
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state, (xb, yb))
        last = float(loss)
        first = last if first is None else first
    assert last < first * 0.1, (first, last)


def test_distributed_optimizer_adasum_plus_wire_codec_is_loud():
    import optax
    import horovod_tpu.jax as hvd_jax
    with pytest.raises(ValueError, match="Average/Sum"):
        hvd_jax.DistributedOptimizer(optax.sgd(0.1),
                                     op=reduce_ops.Adasum,
                                     compression=hvd_mod.Compression.int8)


# ---------------------------------------------------------------------------
# HVD205 lint fixture
# ---------------------------------------------------------------------------

def test_hvd205_fixture_corpus():
    from horovod_tpu.analysis import ast_lint
    diags = ast_lint.lint_file(
        os.path.join(REPO, "tests", "lint_fixtures",
                     "bad_lossy_compression.py"))
    assert [d.rule for d in diags] == ["HVD205"] * 3
    msgs = " ".join(d.message for d in diags)
    assert "broadcast" in msgs and "integer/bool" in msgs


def test_hvd205_not_triggered_by_float_gradients():
    from horovod_tpu.analysis import ast_lint
    src = (
        "import horovod_tpu as hvd\n"
        "grads = compute()\n"
        "hvd.allreduce(grads, compression=hvd.Compression.int8)\n"
        "hvd.grouped_allreduce(grads, "
        "compression=hvd.Compression.bf16)\n")
    assert ast_lint.lint_source(src) == []


def test_hvd205_suppressible():
    from horovod_tpu.analysis import ast_lint
    src = (
        "import horovod_tpu as hvd\n"
        "hvd.broadcast(w, root_rank=0, "
        "compression=hvd.Compression.int8)"
        "  # hvd-lint: disable=HVD205\n")
    assert ast_lint.lint_source(src) == []
