"""Compile the Mamba-2 recurrence's kernel pair (``ops/ssd.py``) for a
described TPU v5e at the shapes of ``nemotron3nano30b-seq16384-1chip``
(64 heads of 64 over 8 groups with a state of 128 at 16,384 positions):
forward one Mosaic call, the gradient two, ``hvd_ssd_fwd`` and
``hvd_ssd_bwd``, on a grid ``(batch x groups, chunks / chunks a step)``,
no loop over the chunk boundaries outside them, and what the way back
holds at once well under the einsum form's 1.18 GB. Inside ``shard_map``
on the 2 x 2 mesh, a row a chip. And a Mamba-2 mixer whole, with its
products, convolution and norm around the kernels: no copy of ``u``,
``y`` or their gradients, which is what a kernel that reads a layout XLA
does not hold costs. What the chip's compiler refuses it refuses here, at no
chip time. Nothing runs, so this says nothing about results or times;
``tests/test_ssd_kernels.py`` runs the pair in the interpreter.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around the compiles, as in
``tests/test_compile_flash_v5e.py``.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import ssd
from test_compile_flash_v5e import _grids, _q_sized_copies

SEQ, HEADS, WIDTH, GROUPS, N_STATE = 16384, 64, 64, 8, 128
# The einsum form's temporaries at these shapes (PERF.md section 6,
# PR 48; ``tests/benchmark/test_bench_nemotron_h.py`` bounds it at 1.5).
EINSUMS_HOLD = 1.18e9


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops it, skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(four_chips):
    return SingleDeviceSharding(four_chips[0])


@pytest.fixture
def on_the_tpu(monkeypatch):
    # The rule asks the default backend; here that is the CPU, and the
    # compile is for the TPU.
    monkeypatch.setattr(fa, "_interpret", lambda: False)


def operands(at, batch=1):
    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=at)

    return (shape((batch, SEQ, HEADS, WIDTH)),
            shape((batch, SEQ, HEADS), jnp.float32),
            shape((HEADS,), jnp.float32),
            shape((batch, SEQ, GROUPS, N_STATE)),
            shape((batch, SEQ, GROUPS, N_STATE)))


def loss(u, dt, a, b, c, weigh):
    return jnp.sum(ssd.ssd(u, dt, a, b, c).astype(jnp.float32) * weigh)


def kernels(text):
    return sorted(re.findall(r"/(hvd_ssd_\w+)/pallas_call", "\n".join(
        line for line in text.splitlines() if "tpu_custom_call" in line)))


def test_the_forward_compiles_to_one_call(one_chip, on_the_tpu):
    args = operands(one_chip)
    forward = jax.jit(ssd.ssd)
    steps = SEQ // ssd.CHUNK // ssd._STEP_CHUNKS
    assert _grids(forward.trace(*args).jaxpr.jaxpr) == {
        ssd.KERNEL_FWD: (GROUPS, steps)}
    text = forward.lower(*args).compile().as_text()
    assert kernels(text) == ["hvd_ssd_fwd"]
    assert not re.findall(r" while\(", text)


def test_the_gradient_compiles_to_the_pair(one_chip, on_the_tpu):
    args = operands(one_chip)
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    steps = SEQ // ssd.CHUNK // ssd._STEP_CHUNKS
    assert _grids(grad.trace(*args, args[0]).jaxpr.jaxpr) == {
        ssd.KERNEL_FWD: (GROUPS, steps), ssd.KERNEL_BWD: (GROUPS, steps)}
    compiled = grad.lower(*args, args[0]).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert kernels(text) == ["hvd_ssd_bwd", "hvd_ssd_fwd"]
    # The chunk boundaries are walked inside the kernels.
    assert not re.findall(r" while\(", text)
    # The chunk states (268 MB) and the operands' other layouts.
    assert compiled.memory_analysis().temp_size_in_bytes < 0.65 * EINSUMS_HOLD


def test_the_pair_compiles_inside_shard_map(four_chips, on_the_tpu):
    """A row a chip: the calls' out shapes carry the operands' varying
    axes, as the flash kernels' do."""
    mesh = Mesh(np.array(four_chips), ("hvd",))
    rows = operands(NamedSharding(mesh, P("hvd")), batch=4)
    a = jax.ShapeDtypeStruct((HEADS,), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    args = (*rows[:2], a, *rows[3:])

    def sharded(*xs):
        return jax.shard_map(
            lambda *local: jax.lax.psum(loss(*local), "hvd"), mesh=mesh,
            in_specs=(P("hvd"), P("hvd"), P(), P("hvd"), P("hvd"), P("hvd")),
            out_specs=P())(*xs)

    text = jax.jit(jax.grad(sharded, argnums=(0, 1, 2, 3, 4))).lower(
        *args, rows[0]).compile().as_text()
    assert kernels(text) == ["hvd_ssd_bwd", "hvd_ssd_fwd"]


def test_a_mixer_compiles_without_a_copy_of_u(one_chip, on_the_tpu):
    """Two Mamba-2 mixers at the cell's widths, forward and backward:
    the kernels read ``u`` and ``dy`` and write ``y`` and ``du`` where
    the convolution, the gate and their transposes hold them, ``[seq,
    heads x width]`` bfloat16. (With ``u`` taken sequence-minor XLA
    turned each of the four, 134 MB a copy: PERF.md section 6, PR 49.)
    The copies that are left are the grouped norm's, not the kernels':
    its statistics want the float32 gated output group-major, once
    forward and once each way back (ROADMAP A11)."""
    from horovod_tpu.models import ssm, transformer
    cfg = transformer.TransformerConfig(
        hidden=2688, max_len=SEQ, layers=1, bias=False, norm="rmsnorm",
        ssm=ssm.SSMConfig(d_inner=HEADS * WIDTH, dt_rank=0, d_state=N_STATE,
                          d_conv=4, heads=HEADS, head_dim=WIDTH,
                          groups=GROUPS))
    layer = ssm.Mamba2Mixer(cfg)
    x = jax.ShapeDtypeStruct((1, SEQ, cfg.hidden), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, SEQ, cfg.hidden), jnp.bfloat16)))

    def two(params, x):
        for _ in range(2):
            x = layer.apply(params, x).astype(x.dtype)
        return jnp.sum(x.astype(jnp.float32))

    text = jax.jit(jax.grad(two, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    assert kernels(text) == ["hvd_ssd_bwd"] * 2 + ["hvd_ssd_fwd"] * 2
    copies = _q_sized_copies(text, SEQ * HEADS * WIDTH)
    assert not [c for c in copies if "bf16[" in c]
    assert len(copies) <= 3 and all(
        f"f32[{SEQ // 8},8,{GROUPS},{HEADS * WIDTH // GROUPS}]" in c
        for c in copies)
