"""Compile one expert layer with its gradient, at the shapes of
``glm47flash-seq4096-1chip`` and of ``smallthinker21b-seq16384-1chip``,
for a described TPU v5e: the grouped kernels at both buffer sizes inside
a conditional, how many of them work on the sized rows, what the way
back holds, and how the sized rows return to token order: through the
kernel of ``ops/rows_to_tokens.py`` as on the chip (``mxu``), and by
the gathers that other shapes and other backends take (``gathers``).
Nothing runs, so this says nothing about results or times.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around the compile, as in
``tests/benchmark/test_compile_v5e.py``.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from horovod_tpu.ops import flash_attention, rows_to_tokens
from horovod_tpu.parallel import moe
from moe_fixtures import clear_traces

EXPERTS = 64
# (tokens, hidden, an expert's width, experts held, experts a token,
# the layer's kind, a shared expert or none)
SHAPES = {
    "glm47flash": (8192, 2048, 1536, 8, 4, dict(scale=1.8), True),
    "smallthinker21b": (16384, 2560, 768, 16, 6,
                        dict(scoring="softmax", gate="relu"), False)}


@pytest.fixture(scope="module")
def four_chips():
    """The described host's devices, the compilation cache off round
    whatever is compiled for them."""
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


# (cell, the sized rows' way back): the kernel, which the process
# takes where the flash kernels run compiled, and the gathers.
WAYS = [("glm47flash", "mxu"), ("smallthinker21b", "mxu"),
        ("smallthinker21b", "gathers")]


@pytest.fixture(scope="module", params=WAYS, ids="-".join)
def compiled_layer(one_chip, request):
    cell, way = request.param
    tokens, hidden, width, held, per_token, kind, shared = SHAPES[cell]
    # The process runs on the CPU: steer the layer onto the TPU's path
    # here, as the flash kernels' compile tests do, not in the program.
    patch = pytest.MonkeyPatch()
    patch.setattr(flash_attention, "_interpret", lambda: way != "mxu")
    request.addfinalizer(patch.undo)
    clear_traces()
    request.addfinalizer(clear_traces)

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

    return cell, way, jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1))).lower(
            x, params, shape((EXPERTS,)), x).compile()


def test_the_layer_compiles_with_both_sizes_inside_a_conditional(
        compiled_layer):
    cell, _, compiled = compiled_layer
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
    cell, _, compiled = compiled_layer
    assert compiled.memory_analysis().temp_size_in_bytes < {
        "glm47flash": 1e9, "smallthinker21b": 2.5e9}[cell]


def test_no_row_is_scattered_back_into_token_order(compiled_layer):
    """Neither direction of the sized path scatters a row (at
    ``smallthinker21b``'s shape two scatter-adds of ``[49152, 2560]``
    took 46.9 ms of a 399 ms step: PERF.md section 6, PR 41); the
    full-size path never did. What is scattered is scalars: the
    weights' gradient, the tokens each expert drew and, under the
    gathers, each pair's row."""
    cell, way, compiled = compiled_layer
    tokens, hidden, width, held, per_token, _, _ = SHAPES[cell]
    text = compiled.as_text()
    scattered = re.findall(r"= (\w+)\[([\d,]*)\][^ ]* scatter\(", text)
    assert scattered and all("," not in dims for _, dims in scattered)
    rows = moe.sized_rows(tokens * per_token, held, EXPERTS)
    assert f"[{rows},{hidden}]" in text     # the buffers are there
    # The kernel once a direction on the chip, and its tiling from the
    # shapes alone; the gathers elsewhere, a token's choice at a time.
    calls = len(re.findall(rf"%{rows_to_tokens.KERNEL}[.\d]* = ", text))
    assert calls == (2 if way == "mxu" else 0)
    assert rows_to_tokens.tiling(tokens, per_token, EXPERTS, held, rows,
                                 hidden, jnp.bfloat16) == {
        "glm47flash": (256, 48), "smallthinker21b": (256, 64)}[cell]
    back = len(re.findall(
        rf"= bf16\[{tokens},{hidden}\][^ ]* fusion\([^\n]*"
        r"branch_1_fun/route/gather", text))
    if tokens != rows:      # else the gathers into the buffers look alike
        assert back == (0 if way == "mxu" else 2 * per_token)


def test_the_kernel_compiles_inside_shard_map(four_chips, monkeypatch):
    """Four chips hold two experts each and see the same tokens
    (``tests/test_parallel.py: test_moe_shares_over_a_mesh_add_up``
    runs it on the CPU, by the gathers): on the TPU each chip's sized
    rows return through the kernel, whose operands vary with the chip."""
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    clear_traces()
    mesh = Mesh(np.array(four_chips), ("ep",))
    tokens, hidden, width, per_token = 1024, 256, 128, 2

    def shape(dims, dtype=jnp.float32, spec=P()):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))

    params = {"router": shape((hidden, 8)),
              "w_gate": shape((8, hidden, width), spec=P("ep")),
              "w_up": shape((8, hidden, width), spec=P("ep")),
              "w_down": shape((8, width, hidden), spec=P("ep"))}

    def share(x, params):
        first = jax.lax.axis_index("ep") * params["w_gate"].shape[0]
        y, _ = moe.moe_apply(x, params, jnp.zeros((8,)), k=per_token,
                             first_held=first)
        return jax.lax.psum(y, "ep")

    sharded = jax.shard_map(
        share, mesh=mesh, out_specs=P(),
        in_specs=(P(), {name: P() if name == "router" else P("ep")
                        for name in params}))
    assert rows_to_tokens.tiling(
        tokens, per_token, 8, 2, moe.sized_rows(tokens * per_token, 2, 8),
        hidden, jnp.bfloat16) == (256, 144)
    try:
        text = jax.jit(jax.grad(
            lambda x, p: jnp.sum(sharded(x, p).astype(jnp.float32) ** 2),
            argnums=(0, 1))).lower(
                shape((tokens, hidden), jnp.bfloat16), params
            ).compile().as_text()
    finally:
        clear_traces()
    assert len(re.findall(rf"%{rows_to_tokens.KERNEL}[.\d]* = ", text)) == 2
