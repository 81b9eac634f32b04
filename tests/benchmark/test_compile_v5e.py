"""Compile the flash-attention kernels of the two ``lm365m`` cells, and
their gradients, for a described TPU v5e: what the chip's compiler
refuses it refuses here, at no chip time. Nothing runs, so this says
nothing about results or times.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around the compiles (an entry
written for a described chip cannot be read back without one).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# (batch, heads, seq, head_dim) per chip: lm365m-seq8192-1chip and
# lm365m-seq2048-4chip.
LAYER_SHAPES = [(2, 16, 8192, 64), (6, 16, 2048, 64)]
TILE = 1024


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernel(monkeypatch):
    # The kernel asks the default backend whether to interpret; here
    # that is the CPU, and the compile is for the TPU.
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    return fa.flash_attention


@pytest.mark.parametrize("shape", LAYER_SHAPES, ids=["seq8192", "seq2048"])
def test_flash_forward_compiles_for_v5e(one_chip, compiled_kernel, shape):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def forward(q, k, v):
        return compiled_kernel(q, k, v, causal=True, block_q=TILE,
                               block_k=TILE)

    text = jax.jit(forward).lower(x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("shape", LAYER_SHAPES, ids=["seq8192", "seq2048"])
def test_flash_gradient_compiles_for_v5e(one_chip, compiled_kernel, shape):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = compiled_kernel(q, k, v, causal=True, block_q=TILE,
                              block_k=TILE)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    # Forward, dk/dv and dq: the three Mosaic calls a layer makes.
    assert compiled.as_text().count("tpu_custom_call") == 3
