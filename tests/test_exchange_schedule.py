"""The gradient exchange of the compiled step: what the program hands
XLA (every large leaf an all-reduce of its own, the small ones packed),
the compile options ``make_train_step`` asks for on a TPU mesh of more
than one chip and nowhere else, and ``exchange_schedule``, which reads
where the compiler put the all-reduces.

The TPU half compiles for a described v5e:2x2 (nothing runs; the
topology is described inside a fixture and the persistent compilation
cache is off around the compiles, as in
``tests/benchmark/test_compile_v5e.py``).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu.jax as hvd_jax
from horovod_tpu.ops import reduce_ops

AXIS = hvd_jax.HVD_AXIS
# Temporary memory of the step compiled with the options, over the
# same step compiled without: the weight-gradient products wait for the
# all-reduces they run beside, and their operands wait with them
# (1.32 at these two layers, 1.72 at four; PERF.md section 6, PR 35).
TEMP_FACTOR = 2.0


def per_leaf(tree, op, prescale=None, postscale=None):
    """The parent's exchange: one ``pmean`` / ``psum`` a leaf."""
    def red(g):
        if prescale is not None:
            g = g * jnp.asarray(prescale).astype(g.dtype)
        g = (lax.pmean if op == reduce_ops.Average else lax.psum)(g, AXIS)
        if postscale is not None:
            g = g * jnp.asarray(postscale).astype(g.dtype)
        return g
    return jax.tree.map(red, tree)


def spy_on_jit(monkeypatch):
    """Record the ``compiler_options`` of the ``jax.jit`` calls that
    donate arguments, as ``make_train_step``'s does."""
    seen, real = [], jax.jit

    def jit(fn, **kwargs):
        if "donate_argnums" in kwargs:
            seen.append(kwargs.get("compiler_options"))
        return real(fn, **kwargs)
    monkeypatch.setattr(hvd_jax.jax, "jit", jit)
    return seen


# -- on the CPU mesh --------------------------------------------------------

@pytest.fixture(scope="module")
def cpu_mesh():
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs the 8-device CPU mesh of tests/conftest.py")
    return Mesh(np.array(devices[:8]), (AXIS,))


def replica_tree(n):
    """Large, small and scalar leaves of two dtypes, different on every
    replica (leading axis: the replica)."""
    rng = np.random.default_rng(0)

    def leaf(shape, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal((n,) + shape), dtype)
    return {"big": leaf((300, 256)),                # 300 KiB: its own
            "big_bf16": leaf((512, 512), jnp.bfloat16),
            "bias": leaf((1024,)), "scale": leaf((3, 16, 64)),
            "half": leaf((4096,), jnp.bfloat16),
            "half2": leaf((64,), jnp.bfloat16), "lone": leaf((7,), jnp.float16),
            "scalar": leaf(())}


@pytest.mark.parametrize("n", [1, 2, 4, 8])
@pytest.mark.parametrize("scales", [(None, None), (0.5, 3.0)],
                         ids=["plain", "scaled"])
@pytest.mark.parametrize("op", [reduce_ops.Average, reduce_ops.Sum],
                         ids=["average", "sum"])
def test_packed_exchange_is_per_leaf_bit_for_bit(cpu_mesh, op, scales, n):
    """An elementwise collective of a concatenation is the collective
    of its parts, at every mesh size."""
    mesh = Mesh(cpu_mesh.devices[:n], (AXIS,))
    tree = replica_tree(n)
    assert any(x[0].nbytes >= hvd_jax._PACK_BELOW_BYTES
               for x in jax.tree.leaves(tree))

    def both(t):
        t = jax.tree.map(lambda x: x[0], t)
        return (hvd_jax._reduce_in_axis(t, op, AXIS, *scales, pack=True),
                per_leaf(t, op, *scales))
    ours, theirs = jax.jit(jax.shard_map(
        both, mesh=mesh, in_specs=P(AXIS), out_specs=P()))(tree)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_packed_exchange_is_one_all_reduce_a_dtype_for_the_small(cpu_mesh):
    tree = jax.tree.map(lambda x: x[0], replica_tree(1))
    text = jax.jit(jax.shard_map(
        lambda t: hvd_jax._reduce_in_axis(t, reduce_ops.Average, AXIS,
                                          pack=True),
        mesh=cpu_mesh, in_specs=P(), out_specs=P(),
        check_vma=False)).lower(tree).as_text()
    # big, big_bf16, the float32 pack, the bfloat16 pack, lone.
    assert len(re.findall(r"stablehlo\.all_reduce", text)) == 5


def adasum_per_leaf(tree):
    """The parent's Adasum: the tree reduction a leaf, then a psum of
    ``g / n`` that makes the value replica-invariant again."""
    from horovod_tpu.ops.adasum import adasum_axis

    def red(g):
        g = adasum_axis(g, AXIS)
        return lax.psum(g / lax.axis_size(AXIS), AXIS)
    return jax.tree.map(red, tree)


def int8_per_leaf(tree, prescale, postscale):
    """The parent's wire-codec reduction on the axis: one quantized
    pipeline a leaf, between the scales."""
    from horovod_tpu.compression.codecs import quantized_allreduce_axis

    def red(g):
        g = g * jnp.asarray(prescale).astype(g.dtype)
        g = quantized_allreduce_axis(g, AXIS, codec="int8", block=128,
                                     average=True)
        return g * jnp.asarray(postscale).astype(g.dtype)
    return jax.tree.map(red, tree)


@pytest.mark.parametrize("ours, theirs", [
    (lambda t: hvd_jax._reduce_in_axis(t, reduce_ops.Average, AXIS),
     lambda t: per_leaf(t, reduce_ops.Average)),
    (lambda t: hvd_jax._reduce_in_axis(t, reduce_ops.Sum, AXIS),
     lambda t: per_leaf(t, reduce_ops.Sum)),
    (lambda t: hvd_jax._reduce_in_axis(t, reduce_ops.Sum, AXIS, 0.5, 3.0),
     lambda t: per_leaf(t, reduce_ops.Sum, 0.5, 3.0)),
    # Adasum and a codec are defined a tensor: ``pack`` packs nothing.
    (lambda t: hvd_jax._reduce_in_axis(t, reduce_ops.Adasum, AXIS,
                                       pack=True),
     lambda t: adasum_per_leaf(t)),
    (lambda t: hvd_jax._reduce_in_axis(t, reduce_ops.Average, AXIS, 0.5,
                                       3.0, pack=True, codec="int8",
                                       block=128),
     lambda t: int8_per_leaf(t, 0.5, 3.0)),
], ids=["average", "sum", "scaled", "adasum", "int8"])
def test_without_pack_the_exchange_is_the_parents(cpu_mesh, ours, theirs):
    """The one reducer's contract: for every op and codec the program
    of the parent's recipe, written out here, text for text."""
    tree = jax.tree.map(lambda x: x[0], replica_tree(1))

    def lowered(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=cpu_mesh, in_specs=P(), out_specs=P(),
            check_vma=False)).lower(tree).as_text()
    assert lowered(ours) == lowered(theirs)


def test_a_codec_reduces_by_average_or_sum_only():
    with pytest.raises(ValueError, match="Average or Sum"):
        hvd_jax._reduce_in_axis({"w": jnp.ones((4,))}, reduce_ops.Adasum,
                                AXIS, codec="int8", block=128)


def toy_step(mesh, has_aux=False):
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones((16, 4)), "b": jnp.zeros((4,))}

    def loss_fn(p, batch):
        return jnp.mean((batch @ p["w"] + p["b"]) ** 2)

    def loss_aux(p, aux, batch):
        return loss_fn(p, batch), {"seen": aux["seen"] + 1.0}
    step = hvd_jax.make_train_step(loss_aux if has_aux else loss_fn, opt,
                                   mesh=mesh, has_aux=has_aux)
    rows = 2 * mesh.devices.size
    batch = jnp.arange(rows * 16, dtype=jnp.float32).reshape(rows, 16) / 100
    state = (params,) + (({"seen": jnp.zeros(())},) if has_aux else ())
    return step, state + (opt.init(params), batch)


@pytest.mark.parametrize("has_aux", [False, True], ids=["plain", "has_aux"])
def test_make_train_step_passes_no_option_on_the_cpu_mesh(
        cpu_mesh, monkeypatch, has_aux):
    seen = spy_on_jit(monkeypatch)
    step, args = toy_step(cpu_mesh, has_aux)
    assert seen == [None]
    out = step(*args)
    assert np.isfinite(float(out[-1]))
    assert float(out[-1]) > 0


# -- for a described v5e:2x2 -------------------------------------------------

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        found = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops it, skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield found
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def placed(mesh, tree, spec=P()):
    """``tree``'s shapes and dtypes, laid out on ``mesh`` by ``spec``."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)


def lm_step(devices, layers, monkeypatch, seq=2048, rows=6):
    """``lm365m``'s widths at ``layers`` layers through
    ``make_train_step`` over ``devices``: (step, abstract arguments)."""
    from horovod_tpu.models import TransformerConfig, TransformerLM
    from horovod_tpu.ops import flash_attention as fa
    # The kernel asks the default backend whether to interpret; here
    # that is the CPU, and the compile is for the TPU.
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    mesh = Mesh(np.array(devices), (AXIS,))
    model = TransformerLM(TransformerConfig(
        vocab_size=30522, hidden=1024, layers=layers, heads=16,
        mlp_ratio=4, max_len=seq, causal=True, use_rope=True,
        attention_impl="flash", remat=False))
    opt = hvd_jax.DistributedOptimizer(optax.adamw(1e-4, weight_decay=1e-4))

    def loss_fn(p, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(p, batch[0]), batch[1]).mean()
    step = hvd_jax.make_train_step(loss_fn, opt, mesh=mesh)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, seq), jnp.int32))
    batch = placed(mesh, (jax.ShapeDtypeStruct((rows * len(devices), seq),
                                               jnp.int32),) * 2, P(AXIS))
    return step, (placed(mesh, params),
                  placed(mesh, jax.eval_shape(opt.init, params)), batch)


def test_four_chip_step_schedules_its_exchange_under_the_backward(
        topo, monkeypatch):
    layers = 2
    seen = spy_on_jit(monkeypatch)
    step, args = lm_step(topo.devices, layers, monkeypatch)
    assert seen == [hvd_jax._OVERLAP_OPTIONS]
    compiled = step.lower(*args).compile()
    found = hvd_jax.exchange_schedule(compiled)
    # A layer's four matrices each ride a pair with a product of the
    # backward pass between start and done.
    assert found["async"] >= 4 * layers
    assert found["async_over_backward"] >= layers
    assert found["async_bytes_share"] > 0.25
    # Nothing was combined: no all-reduce holds leaves of two layers.
    assert found["max_operands"] == 1
    # The 8 small leaves a layer and the 6 outside the layers ride one
    # packed all-reduce, not one each.
    assert found["sync"] + found["async"] <= 4 * layers + 5

    # The same step without the options: everything combined, at the end.
    monkeypatch.setattr(hvd_jax, "_OVERLAP_OPTIONS", None)
    step, args = lm_step(topo.devices, layers, monkeypatch)
    plain = step.lower(*args).compile()
    before = hvd_jax.exchange_schedule(plain)
    assert before["async"] == 0 and before["max_operands"] > 1
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= TEMP_FACTOR * plain.memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("how", ["aggregated", "adasum", "wire_codec"])
def test_only_the_plain_exchange_is_compiled_to_overlap(
        topo, monkeypatch, how):
    """``backward_passes_per_step > 1``, Adasum and the wire codecs
    keep the parent's program on the TPU mesh too: no option on the
    jit, nothing packed."""
    from horovod_tpu.ops.compression import Compression
    kwargs = {"aggregated": {"backward_passes_per_step": 2},
              "adasum": {"op": reduce_ops.Adasum},
              "wire_codec": {"compression": Compression.int8}}[how]
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.1), **kwargs)
    seen = spy_on_jit(monkeypatch)
    packed = []
    real = hvd_jax._reduce_in_axis
    monkeypatch.setattr(
        hvd_jax, "_reduce_in_axis",
        lambda *a, pack=False, **kw: packed.append(pack) or real(
            *a, pack=pack, **kw))
    mesh = Mesh(np.array(topo.devices), (AXIS,))
    step = hvd_jax.make_train_step(
        lambda p, batch: jnp.mean((batch @ p["w"]) ** 2), opt, mesh=mesh)
    assert seen == [None]
    params = placed(mesh, {"w": jax.ShapeDtypeStruct((16, 4), jnp.float32)})
    step.lower(params, placed(mesh, jax.eval_shape(opt.init, params)),
               placed(mesh, jax.ShapeDtypeStruct((8, 16), jnp.float32),
                      P(AXIS)))
    assert not any(packed)


def test_the_eager_planes_knobs_leave_the_compiled_step_alone(
        topo, monkeypatch):
    """``HVDTPU_OVERLAP`` and ``HVDTPU_BUCKET_BYTES`` are the eager
    plane's: in the environment they change neither the options on the
    step's jit nor a character of what it lowers to."""
    seen = spy_on_jit(monkeypatch)
    mesh = Mesh(np.array(topo.devices), (AXIS,))

    def lowered():
        step, args = toy_step(mesh)
        return step.lower(*placed(mesh, args[:-1]),
                          placed(mesh, args[-1], P(AXIS))).as_text()
    without = lowered()
    monkeypatch.setenv("HVDTPU_OVERLAP", "1")
    monkeypatch.setenv("HVDTPU_BUCKET_BYTES", "128")
    assert lowered() == without
    assert seen == [hvd_jax._OVERLAP_OPTIONS] * 2
    assert "stablehlo.concatenate" in without       # w and b: one pack


def test_one_chip_step_is_the_parents(topo, monkeypatch):
    seen = spy_on_jit(monkeypatch)
    step, args = lm_step(topo.devices[:1], 1, monkeypatch)
    assert seen == [None]
    ours = step.lower(*args)
    # The parent's recipe: one pmean a leaf, a jit without options.
    monkeypatch.setattr(
        hvd_jax, "_reduce_in_axis",
        lambda tree, op, axis, prescale=None, postscale=None, **kw:
        per_leaf(tree, op, prescale, postscale))
    theirs, args = lm_step(topo.devices[:1], 1, monkeypatch)
    assert ours.as_text() == theirs.lower(*args).as_text()
    text = ours.compile().as_text()
    assert " all-reduce(" not in text
    assert hvd_jax.exchange_schedule(text)["collectives"] == []
