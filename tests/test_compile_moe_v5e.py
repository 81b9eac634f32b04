"""Compile one expert layer with its gradient, at the shapes of
``glm47flash-seq4096-1chip``, for a described TPU v5e: the grouped
kernels at both buffer sizes inside a conditional, and what the way
back holds. Nothing runs, so this says nothing about results or times.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around the compile, as in
``tests/benchmark/test_compile_v5e.py``.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.parallel import moe

TOKENS, HIDDEN, WIDTH, HELD, EXPERTS, PER_TOKEN = 8192, 2048, 1536, 8, 64, 4


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


@pytest.fixture(scope="module")
def compiled_layer(one_chip):
    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = {"router": shape((HIDDEN, EXPERTS)),
              "w_gate": shape((HELD, HIDDEN, WIDTH)),
              "w_up": shape((HELD, HIDDEN, WIDTH)),
              "w_down": shape((HELD, WIDTH, HIDDEN)),
              "shared_gate": shape((HIDDEN, WIDTH)),
              "shared_up": shape((HIDDEN, WIDTH)),
              "shared_down": shape((WIDTH, HIDDEN))}
    tokens = shape((TOKENS, HIDDEN), jnp.bfloat16)

    def loss(x, params, bias, weigh):
        y, _ = moe.moe_apply(x, params, bias, k=PER_TOKEN, scale=1.8)
        return jnp.sum((y * weigh).astype(jnp.float32))

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        tokens, params, shape((EXPERTS,)), tokens).compile()


def test_the_layer_compiles_with_both_sizes_inside_a_conditional(
        compiled_layer):
    text = compiled_layer.as_text()
    # Forward and backward each choose between the two sizes.
    assert len(re.findall(r" conditional\(", text)) == 2
    rows = {int(n) for n in re.findall(
        r"ragged-dot-none[.\d]* = bf16\[(\d+),(?:%d|%d)\]" % (HIDDEN, WIDTH),
        text)}
    assert rows == {moe.sized_rows(TOKENS * PER_TOKEN, HELD, EXPERTS),
                    TOKENS * PER_TOKEN} == {8192, 32768}


def test_the_way_back_keeps_no_full_size_buffer(compiled_layer):
    # The branch taken is made again inside the backward pass's own
    # conditional: differentiating one conditional, or one checkpoint
    # round it, keeps the union of the branches' residuals, 2.0 GB here.
    assert compiled_layer.memory_analysis().temp_size_in_bytes < 1e9
