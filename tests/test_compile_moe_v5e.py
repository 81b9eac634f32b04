"""Compile one expert layer with its gradient, at the shapes of
``glm47flash-seq4096-1chip`` and of ``smallthinker21b-seq16384-1chip``,
for a described TPU v5e: the grouped kernels at both buffer sizes inside
a conditional, how many of them work on the sized rows, and what the
way back holds. Nothing runs, so this says nothing about results or
times.

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

EXPERTS = 64
# (tokens, hidden, an expert's width, experts held, experts a token,
# the layer's kind, a shared expert or none)
SHAPES = {
    "glm47flash": (8192, 2048, 1536, 8, 4, dict(scale=1.8), True),
    "smallthinker21b": (16384, 2560, 768, 16, 6,
                        dict(scoring="softmax", gate="relu"), False)}


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


@pytest.fixture(scope="module", params=list(SHAPES))
def compiled_layer(one_chip, request):
    tokens, hidden, width, held, per_token, kind, shared = SHAPES[
        request.param]

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = {"router": shape((hidden, EXPERTS)),
              "w_gate": shape((held, hidden, width)),
              "w_up": shape((held, hidden, width)),
              "w_down": shape((held, width, hidden))}
    if shared:
        params.update(shared_gate=shape((hidden, width)),
                      shared_up=shape((hidden, width)),
                      shared_down=shape((width, hidden)))
    x = shape((tokens, hidden), jnp.bfloat16)

    def loss(x, params, bias, weigh):
        y, _ = moe.moe_apply(x, params, bias, k=per_token, **kind)
        return jnp.sum((y * weigh).astype(jnp.float32))

    return request.param, jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1))).lower(
            x, params, shape((EXPERTS,)), x).compile()


def test_the_layer_compiles_with_both_sizes_inside_a_conditional(
        compiled_layer):
    cell, compiled = compiled_layer
    tokens, hidden, width, held, per_token, _, _ = SHAPES[cell]
    text = compiled.as_text()
    # Forward and backward each choose between the two sizes.
    assert len(re.findall(r" conditional\(", text)) == 2
    rows = [int(n) for n in re.findall(
        r"ragged-dot-none[.\d]* = bf16\[(\d+),(?:%d|%d)\]" % (hidden, width),
        text)]
    sized, full = moe.sized_rows(tokens * per_token, held, EXPERTS), (
        tokens * per_token)
    assert (sized, full) == {"glm47flash": (8192, 32768),
                             "smallthinker21b": (49152, 98304)}[cell]
    assert set(rows) == {sized, full}
    # Grouped products whose result has a row a buffer row. On the
    # sized rows: gate, up and down forward and the three cotangents'
    # on the way back, which makes no forward product again (9 when it
    # made the branch taken again, before PR 39). On a row for every
    # pair: those 6 and the 3 that ``_routed`` makes again there.
    assert (rows.count(sized), rows.count(full)) == (6, 9)


def test_the_way_back_keeps_no_full_size_buffer(compiled_layer):
    # The full-size branch is made again inside the backward pass's own
    # conditional: differentiating one conditional, or one checkpoint
    # round it, keeps the union of the branches' residuals, 2.0 GB at
    # ``glm47flash``'s shapes. What is kept is the sized path's gate and
    # up products and its rows' places (``kept_bytes``): 50 MB there
    # (0.80 GB of temporaries in all where it was 0.74 with nothing
    # kept), 151 MB at ``smallthinker21b``'s (2.36 GB for 2.21, most of
    # it the full-size branch's backward pass on four 98,304-row
    # buffers of 2560; with the tokens' gathered rows kept too, 252 MB
    # more, it read 2.62).
    cell, compiled = compiled_layer
    assert compiled.memory_analysis().temp_size_in_bytes < {
        "glm47flash": 1e9, "smallthinker21b": 2.5e9}[cell]
