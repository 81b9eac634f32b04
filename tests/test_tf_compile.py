"""graph→JAX compile path (horovod_tpu/tensorflow/compile.py): TF2 model
math on the accelerator. Oracle is TF itself — forward parity, then
training behavior (loss decrease, buffer updates, write-back).

Reference contract being replaced: the TF binding delivering accelerator
compute (horovod/tensorflow/mpi_ops.cc:486-493 kernel registration,
xla_mpi_ops.cc:174-232 XLA bridge); here the accelerator path is the
traced-to-JAX function."""

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")

import jax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.tensorflow.compile import tpu_compile  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _init():
    hvd.init()
    yield


class _ConvNet(tf.Module):
    def __init__(self):
        tf.random.set_seed(0)
        init = tf.random.normal
        self.wc = tf.Variable(init([3, 3, 1, 8], stddev=0.1), name="wc")
        self.bc = tf.Variable(tf.zeros([8]), name="bc")
        self.w1 = tf.Variable(init([14 * 14 * 8, 32], stddev=0.05),
                              name="w1")
        self.b1 = tf.Variable(tf.zeros([32]), name="b1")
        self.w2 = tf.Variable(init([32, 10], stddev=0.05), name="w2")
        self.b2 = tf.Variable(tf.zeros([10]), name="b2")

    def loss(self, x, y):
        h = tf.nn.conv2d(x, self.wc, strides=1, padding="SAME") + self.bc
        h = tf.nn.relu(h)
        h = tf.nn.max_pool2d(h, 2, 2, padding="VALID")
        h = tf.reshape(h, [tf.shape(h)[0], -1])
        h = tf.nn.relu(tf.matmul(h, self.w1) + self.b1)
        logits = tf.matmul(h, self.w2) + self.b2
        return tf.reduce_mean(
            tf.nn.sparse_softmax_cross_entropy_with_logits(
                labels=y, logits=logits))


def _mnist_batch(batch=16, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(batch, 28, 28, 1).astype(np.float32)
    y = rng.randint(0, 10, size=(batch,)).astype(np.int64)
    return x, y


def test_convnet_forward_parity():
    m = _ConvNet()
    x, y = _mnist_batch()
    tf_loss = float(m.loss(tf.constant(x), tf.constant(y)))
    compiled = tpu_compile(m.loss, example_inputs=(x, y))
    jax_loss = float(compiled(x, y))
    assert abs(tf_loss - jax_loss) < 1e-4


def test_convnet_trains_and_writes_back():
    optax = pytest.importorskip("optax")
    m = _ConvNet()
    x, y = _mnist_batch()
    compiled = tpu_compile(m.loss, example_inputs=(x, y))
    step = compiled.make_train_step(optax.sgd(0.1))
    losses = [float(step((x, y))) for _ in range(8)]
    assert losses[-1] < losses[0], losses
    compiled.copy_params_to_variables()
    # TF-side eval sees the trained weights: its loss matches the jax
    # loss at the final parameters.
    tf_loss = float(m.loss(tf.constant(x), tf.constant(y)))
    jax_loss = float(compiled(x, y))
    assert abs(tf_loss - jax_loss) < 1e-3


def test_gradient_parity_with_tf():
    """d(loss)/d(vars) computed by JAX on the rebuilt graph matches
    tf.GradientTape on the original — the contract that makes
    make_train_step equivalent to TF-side training."""
    m = _ConvNet()
    x, y = _mnist_batch(8)
    with tf.GradientTape() as tape:
        loss = m.loss(tf.constant(x), tf.constant(y))
    tf_vars = [m.wc, m.bc, m.w1, m.b1, m.w2, m.b2]
    tf_grads = {v.name: g.numpy() for v, g in
                zip(tf_vars, tape.gradient(loss, tf_vars))}

    compiled = tpu_compile(m.loss, example_inputs=(x, y))

    def scalar_loss(params):
        out, _ = compiled.apply(params, [x, y])
        return out

    jax_grads = jax.grad(scalar_loss)(compiled.params)
    assert set(jax_grads) == set(tf_grads)
    for name, g in tf_grads.items():
        np.testing.assert_allclose(np.asarray(jax_grads[name]), g,
                                   rtol=1e-3, atol=1e-5)


_KERAS_MODEL_SCRIPT = r"""
import os, sys
os.environ["KERAS_BACKEND"] = "tensorflow"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})
import numpy as np
import tensorflow as tf
import jax, optax
import horovod_tpu as hvd_core
from horovod_tpu.tensorflow.compile import tpu_compile
hvd_core.init()

# BN/dropout keras model: PartitionedCall recursion, FusedBatchNormV3
# buffer writes, PRNG-driven stateless dropout.
tf.random.set_seed(0)
model = tf.keras.Sequential([
    tf.keras.layers.Input((16,)),
    tf.keras.layers.Dense(32, activation="relu"),
    tf.keras.layers.BatchNormalization(),
    tf.keras.layers.Dropout(0.1),
    tf.keras.layers.Dense(10),
])
lossf = tf.keras.losses.SparseCategoricalCrossentropy(from_logits=True)
def loss_fn(x, y):
    return lossf(y, model(x, training=True))
rng = np.random.RandomState(0)
x = rng.rand(32, 16).astype(np.float32)
y = rng.randint(0, 10, size=(32,)).astype(np.int64)
compiled = tpu_compile(loss_fn, example_inputs=(x, y))
step = compiled.make_train_step(optax.sgd(0.05))
mmk = next(k for k in compiled.buffers if "moving_mean" in k)
mm0 = np.array(compiled.buffers[mmk])
losses = [float(step((x, y), rng=jax.random.PRNGKey(i))) for i in range(8)]
assert losses[-1] < losses[0], losses
assert not np.allclose(mm0, np.array(compiled.buffers[mmk])), "BN stale"

# training=False parity: BN moving stats, dropout off — exact vs eager.
tf.random.set_seed(1)
model2 = tf.keras.Sequential([
    tf.keras.layers.Input((16,)),
    tf.keras.layers.Dense(32, activation="tanh"),
    tf.keras.layers.BatchNormalization(),
    tf.keras.layers.Dropout(0.5),
    tf.keras.layers.Dense(4),
])
def fwd(x):
    return model2(x, training=False)
x2 = np.random.RandomState(3).rand(8, 16).astype(np.float32)
compiled2 = tpu_compile(fwd, example_inputs=(x2,))
np.testing.assert_allclose(np.asarray(compiled2(x2)),
                           model2(tf.constant(x2)).numpy(),
                           rtol=1e-4, atol=1e-5)

# MHA transformer block (Einsum, Erfc-gelu, Softmax, BatchMatMul):
# forward parity + training descent.
tf.random.set_seed(0)
inp = tf.keras.Input((16, 32))
h = tf.keras.layers.MultiHeadAttention(num_heads=4, key_dim=8)(inp, inp)
h = tf.keras.layers.LayerNormalization()(h + inp)
f = tf.keras.layers.Dense(64, activation="gelu")(h)
f = tf.keras.layers.Dense(32)(f)
mha_model = tf.keras.Model(inp, tf.keras.layers.LayerNormalization()(h + f))
xm = np.random.RandomState(0).rand(2, 16, 32).astype(np.float32)
cm = tpu_compile(lambda x: mha_model(x, training=False),
                 example_inputs=(xm,))
np.testing.assert_allclose(np.asarray(cm(xm)),
                           mha_model(tf.constant(xm)).numpy(),
                           rtol=1e-4, atol=1e-5)
xt = np.random.RandomState(3).rand(8, 16, 32).astype(np.float32)
yt = np.random.RandomState(1).rand(8, 16, 32).astype(np.float32)
def mha_loss(x, y):
    return tf.reduce_mean(tf.square(mha_model(x, training=True) - y))
cmt = tpu_compile(mha_loss, example_inputs=(xt, yt))
ms = cmt.make_train_step(optax.adam(1e-3))
mlosses = [float(ms((xt, yt))) for _ in range(6)]
assert mlosses[-1] < mlosses[0], mlosses

# Recurrence (LSTM -> TensorList while loop) must fail LOUD, not
# silently mis-train.
tf.random.set_seed(1)
lstm = tf.keras.Sequential([
    tf.keras.layers.Input((12,), dtype="int32"),
    tf.keras.layers.Embedding(100, 16),
    tf.keras.layers.LSTM(8),
    tf.keras.layers.Dense(2)])
ids = np.random.RandomState(1).randint(0, 100, size=(2, 12)).astype(np.int32)
cl = tpu_compile(lambda x: lstm(x, training=False), example_inputs=(ids,))
try:
    cl(ids)
    raise SystemExit("LSTM did not fail loud")
except NotImplementedError:
    pass

print("KERAS-BRIDGE OK")
"""


def _run_bridge_subprocess(script_body, marker, **fmt):
    """Run a bridge scenario in its own interpreter, on the CPU: the
    keras backend binds at import (another module may have claimed
    jax)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, KERAS_BACKEND="tensorflow",
               JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", script_body.format(repo=repo, **fmt)],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    assert marker in out.stdout


def test_keras_model_bridge_subprocess():
    """tf.keras models through the bridge: PartitionedCall recursion, BN
    buffer writes, PRNG dropout, inference parity, the MHA transformer
    block, and LSTM failing loud."""
    _run_bridge_subprocess(_KERAS_MODEL_SCRIPT, "KERAS-BRIDGE OK")


def test_image_resize_parity():
    def fwd(x):
        up = tf.image.resize(x, (8, 8), method="bilinear")
        return tf.image.resize(up, (2, 2), method="nearest")

    x = np.random.RandomState(2).rand(2, 4, 4, 3).astype(np.float32)
    compiled = tpu_compile(fwd, example_inputs=(x,))
    np.testing.assert_allclose(np.asarray(compiled(x)),
                               fwd(tf.constant(x)).numpy(),
                               rtol=1e-5, atol=1e-6)


_APPLICATIONS_SCRIPT = r"""
import os, sys
os.environ["KERAS_BACKEND"] = "tensorflow"
sys.path.insert(0, {repo!r})
import numpy as np
import tensorflow as tf
import jax
jax.config.update("jax_platforms", "cpu")
import horovod_tpu as hvd_core
from horovod_tpu.tensorflow.compile import tpu_compile
hvd_core.init()
tf.random.set_seed(0)
model = getattr(tf.keras.applications, {name!r})(
    weights=None, input_shape=(96, 96, 3), classes=10)
x = np.random.RandomState(0).rand(2, 96, 96, 3).astype(np.float32)
c = tpu_compile(lambda a: model(a, training=False), example_inputs=(x,))
d = float(np.abs(np.asarray(c(x)) - model(tf.constant(x)).numpy()).max())
assert d < 1e-4, d
print("APPLICATIONS OK", d)
"""


@pytest.mark.parametrize("name", ["MobileNetV2", "EfficientNetB0",
                                  "DenseNet121", "InceptionV3",
                                  "ConvNeXtTiny", "Xception",
                                  "MobileNetV3Small"])
def test_keras_applications_through_bridge(name):
    """The tf.keras.applications families the tf_on_tpu doc advertises:
    exact forward parity through the graph→JAX bridge (depthwise convs,
    swish/relu6, BN inference, skip connections, global pooling).
    Subprocess: keras backend binds per process."""
    _run_bridge_subprocess(_APPLICATIONS_SCRIPT, "APPLICATIONS OK",
                           name=name)


def test_embedding_and_einsum():
    """ResourceGather (embedding) + Einsum + LayerNorm-style math."""
    tf.random.set_seed(2)
    table = tf.Variable(tf.random.normal([64, 8]), name="emb")
    wq = tf.Variable(tf.random.normal([8, 8], stddev=0.3), name="wq")

    def fwd(ids):
        e = tf.nn.embedding_lookup(table, ids)
        q = tf.einsum("bsd,de->bse", e, wq)
        s = tf.nn.softmax(tf.matmul(q, e, transpose_b=True), axis=-1)
        return tf.reduce_mean(tf.matmul(s, e), axis=1)

    ids = np.random.RandomState(0).randint(0, 64, size=(4, 10))
    compiled = tpu_compile(fwd, example_inputs=(ids,))
    np.testing.assert_allclose(
        np.asarray(compiled(ids)),
        fwd(tf.constant(ids, tf.int32)).numpy(), rtol=1e-4, atol=1e-5)


def test_div_no_nan_gradient_finite():
    """divide_no_nan with a zero denominator must have finite gradients
    (the where-div pitfall): masked-mean losses hit this on all-masked
    batches."""
    w = tf.Variable(tf.ones([4]), name="w")

    def fwd(x, mask):
        s = tf.reduce_sum(x * w * mask)
        return tf.math.divide_no_nan(s, tf.reduce_sum(mask))

    x = np.ones(4, np.float32)
    mask = np.zeros(4, np.float32)  # fully masked: denominator 0
    compiled = tpu_compile(fwd, example_inputs=(x, mask))

    def loss(params):
        out, _ = compiled.apply(params, [x, mask])
        return out

    g = jax.grad(loss)(compiled.params)
    assert np.isfinite(np.asarray(g["w:0"])).all()


def test_unsupported_op_is_loud():
    def fwd(x):
        return tf.raw_ops.MatrixInverse(input=x)

    x = np.eye(3, dtype=np.float32)[None]
    compiled = tpu_compile(fwd, example_inputs=(x,))
    with pytest.raises(NotImplementedError, match="MatrixInverse"):
        compiled(x)


def test_int64_inputs_narrow():
    def fwd(ids):
        return tf.cast(ids, tf.float32) * 2.0

    ids = np.arange(6, dtype=np.int64)
    compiled = tpu_compile(fwd, example_inputs=(ids,))
    np.testing.assert_allclose(np.asarray(compiled(ids)),
                               (ids * 2).astype(np.float32))


def _attention_module(causal, heads=4, key_dim=16, d_model=64,
                      out_dim=8):
    """The exact op pattern keras-3 MultiHeadAttention emits (einsum
    projections, scalar Mul scale, SelectV2 masked softmax, combine
    einsum) hand-rolled with raw TF ops. keras itself binds to whichever
    backend the test SESSION imported first (process-global), so
    building a tf.keras layer here is not order-safe — the bridge's
    pattern matcher sees the identical graph either way (it is
    label-generic, verified standalone against real keras MHA)."""
    tf.random.set_seed(0)

    class MHA(tf.Module):
        def __init__(self):
            init = tf.random.normal
            self.wq = tf.Variable(init([d_model, heads, key_dim],
                                       stddev=0.05), name="wq")
            self.wk = tf.Variable(init([d_model, heads, key_dim],
                                       stddev=0.05), name="wk")
            self.wv = tf.Variable(init([d_model, heads, key_dim],
                                       stddev=0.05), name="wv")
            self.wo = tf.Variable(init([heads, key_dim, out_dim],
                                       stddev=0.05), name="wo")

        def __call__(self, x):
            q = tf.einsum("bsc,cnh->bsnh", x, self.wq)
            k = tf.einsum("bsc,cnh->bsnh", x, self.wk)
            v = tf.einsum("bsc,cnh->bsnh", x, self.wv)
            s = tf.einsum("bqnh,bknh->bnqk", q, k)
            s = s * (1.0 / float(key_dim) ** 0.5)
            if causal:
                n = tf.shape(x)[1]
                rows = tf.range(n)
                keep = rows[:, None] >= rows[None, :]
                cond = tf.logical_and(tf.ones_like(s, tf.bool),
                                      keep[None, None])
                s = tf.where(cond, s, tf.constant(-1e9))
            p = tf.nn.softmax(s)
            out = tf.einsum("bnqk,bknh->bqnh", p, v)
            return tf.einsum("bqnh,nho->bqo", out, self.wo)

    return MHA()


@pytest.mark.parametrize("use_causal_mask", [False, True])
def test_attention_pattern_flash_routing_parity(monkeypatch,
                                                use_causal_mask):
    """The Einsum→[scale]→[mask]→Softmax→Einsum pattern lowers to the
    Pallas flash kernel (the SelectV2 causal mask is recognized as such
    after shape-derived const folding) with einsum-path parity."""
    model = _attention_module(use_causal_mask)
    x = np.random.RandomState(0).normal(size=(2, 32, 64)).astype(
        np.float32)

    monkeypatch.setenv("HVDTPU_BRIDGE_FLASH", "never")
    ref = np.asarray(tpu_compile(model, example_inputs=(
        tf.constant(x),))(x))

    from horovod_tpu.ops import flash_attention as fa_mod
    hits = []
    orig = fa_mod.flash_attention

    def spy(*args, **kwargs):
        hits.append(kwargs.get("causal"))
        return orig(*args, **kwargs)

    monkeypatch.setattr(fa_mod, "flash_attention", spy)
    monkeypatch.setenv("HVDTPU_BRIDGE_FLASH", "always")
    out = np.asarray(tpu_compile(model, example_inputs=(
        tf.constant(x),))(x))
    assert hits == [use_causal_mask]
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_attention_pattern_flash_training_gradients(monkeypatch):
    """Training through the flash-routed attention still converges (the
    kernel's custom VJP feeds the projection weights)."""
    optax = pytest.importorskip("optax")
    model = _attention_module(False)
    x = np.random.RandomState(1).normal(size=(8, 32, 64)).astype(
        np.float32)
    y = np.random.RandomState(2).normal(size=(8, 32, 8)).astype(
        np.float32)

    def loss_fn(a, t):
        pred = model(a)
        return tf.reduce_mean(tf.square(pred - t))

    monkeypatch.setenv("HVDTPU_BRIDGE_FLASH", "always")
    compiled = tpu_compile(loss_fn,
                           example_inputs=(tf.constant(x), tf.constant(y)))
    step = compiled.make_train_step(optax.adam(1e-2))
    losses = [float(step((x, y))) for _ in range(5)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_attention_pattern_flash_fallback_on_padding_mask(monkeypatch):
    """A data-dependent key-padding mask cannot const-fold: the pattern
    must fall back to the einsum lowering and stay correct."""
    base = _attention_module(False)

    def masked_model(x, mask):
        q = tf.einsum("bsc,cnh->bsnh", x, base.wq)
        k = tf.einsum("bsc,cnh->bsnh", x, base.wk)
        v = tf.einsum("bsc,cnh->bsnh", x, base.wv)
        s = tf.einsum("bqnh,bknh->bnqk", q, k) * 0.25
        cond = tf.logical_and(tf.ones_like(s, tf.bool),
                              mask[:, None, None, :])
        s = tf.where(cond, s, tf.constant(-1e9))
        p = tf.nn.softmax(s)
        out = tf.einsum("bnqk,bknh->bqnh", p, v)
        return tf.einsum("bqnh,nho->bqo", out, base.wo)

    x = np.random.RandomState(0).normal(size=(2, 32, 64)).astype(
        np.float32)
    mask = np.ones((2, 32), bool)
    mask[:, -7:] = False

    monkeypatch.setenv("HVDTPU_BRIDGE_FLASH", "never")
    ref = np.asarray(tpu_compile(masked_model, example_inputs=(
        tf.constant(x), tf.constant(mask)))(x, mask))

    from horovod_tpu.ops import flash_attention as fa_mod
    hits = []
    orig = fa_mod.flash_attention

    def spy(*args, **kwargs):
        hits.append(1)
        return orig(*args, **kwargs)

    monkeypatch.setattr(fa_mod, "flash_attention", spy)
    monkeypatch.setenv("HVDTPU_BRIDGE_FLASH", "always")
    out = np.asarray(tpu_compile(masked_model, example_inputs=(
        tf.constant(x), tf.constant(mask)))(x, mask))
    assert not hits, "padding mask must not route to the flash kernel"
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_compute_dtype_bf16_parity_and_training():
    """compute_dtype=bf16 (the torch bridge's XLA_USE_BF16 analog on
    the TF side): master weights stay fp32, forward parity holds at
    bf16 tolerance, and training still converges."""
    import jax.numpy as jnp
    optax = pytest.importorskip("optax")
    m = _ConvNet()
    x, y = _mnist_batch()
    c32 = tpu_compile(m.loss, example_inputs=(x, y))
    c16 = tpu_compile(m.loss, example_inputs=(x, y),
                      compute_dtype=jnp.bfloat16)
    l32 = float(np.asarray(c32(x, y)))
    l16 = float(np.asarray(c16(x, y)))
    assert abs(l32 - l16) / max(abs(l32), 1e-6) < 0.05
    # params stay fp32 masters
    assert all(np.asarray(v).dtype == np.float32
               for v in c16.params.values())
    step = c16.make_train_step(optax.sgd(0.05))
    losses = [float(step((x, y))) for _ in range(6)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_real_keras_mha_flash_routing_subprocess():
    """The REAL tf.keras MultiHeadAttention graph routes to the flash
    kernel — run in a fresh interpreter because keras binds its backend
    at first import (this test session may already hold the jax
    backend). Guards against a keras upgrade changing the emitted
    attention pattern without the hand-rolled replica tests noticing."""
    import subprocess
    from conftest import clean_spawn_env

    script = r"""
import os, sys
sys.path.insert(0, os.environ["HVDTPU_REPO"])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import tensorflow as tf
import horovod_tpu.tensorflow as hvd
from horovod_tpu.tensorflow.compile import tpu_compile
hvd.init()
tf.keras.utils.set_random_seed(0)
inp = tf.keras.Input((32, 64))
h = tf.keras.layers.MultiHeadAttention(num_heads=4, key_dim=16)(
    inp, inp, use_causal_mask=True)
model = tf.keras.Model(inp, h)
x = np.random.RandomState(0).normal(size=(2, 32, 64)).astype(np.float32)
def f(a):
    return model(a, training=False)
os.environ["HVDTPU_BRIDGE_FLASH"] = "never"
ref = np.asarray(tpu_compile(f, example_inputs=(tf.constant(x),))(x))
from horovod_tpu.ops import flash_attention as fa
hits = []
orig = fa.flash_attention
def spy(*a, **kw):
    hits.append(kw.get("causal")); return orig(*a, **kw)
fa.flash_attention = spy
os.environ["HVDTPU_BRIDGE_FLASH"] = "always"
out = np.asarray(tpu_compile(f, example_inputs=(tf.constant(x),))(x))
assert hits == [True], hits
np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)
print("MHA-FLASH OK")
"""
    import os
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = clean_spawn_env(HVDTPU_REPO=repo)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, timeout=600)
    out = proc.stdout.decode() + proc.stderr.decode()
    assert proc.returncode == 0, out[-4000:]
    assert "MHA-FLASH OK" in out
