"""Compile the flash kernels, forward and gradient, for a described TPU
v5e at the shapes of ``smallthinker21b-seq16384-1chip`` (28 query heads
of 128 over 4 K/V heads at 16,384 positions, the full layer and the
4096-window ones) and of ``lm365m-seq8192-1chip``: two Mosaic calls a
layer, each on a grid ``(batch x heads, live tiles)`` whose second axis
is the step table's (``flash_attention._step_table``), and, with traced
offsets (a ring step), on every tile. What the chip's compiler refuses
it refuses here, at no chip time. And attention layers whole, with
their projections and rope around the kernels (``layout="bshd"``): the
compiled step holds the two Mosaic calls a layer and no copy of an
array as large as q, which is what a kernel that reads a layout XLA
does not hold costs. Nothing runs, so this says nothing about results
or times.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around the compiles, as in
``tests/test_compile_moe_v5e.py``.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.ops import flash_attention as fa

TILE = 1024
# (q's shape, K/V heads, window): per (batch, head) the forward's and
# the backward's live tiles.
CALLS = {
    "smallthinker21b-full": ((1, 28, 16384, 128), 4, None, 136),
    "smallthinker21b-window4096": ((1, 28, 16384, 128), 4, 4096, 70),
    "lm365m-seq8192": ((2, 16, 8192, 64), 16, None, 36),
}


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
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    return fa.flash_attention


def _grids(jaxpr):
    """The grid of every ``pallas_call`` of a jaxpr, by kernel name."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = tuple(
                eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.update(_grids(sub))
    return found


def _shapes(call, one_chip):
    shape, kv_heads, window, live = CALLS[call]
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((shape[0], kv_heads) + shape[2:],
                              jnp.bfloat16, sharding=one_chip)
    return q, kv, window, shape[0] * shape[1], live


@pytest.mark.parametrize("call", sorted(CALLS))
def test_flash_gradient_compiles_on_the_live_tiles(one_chip, compiled_kernel,
                                                   call):
    q, kv, window, bh, live = _shapes(call, one_chip)

    def loss(q, k, v):
        out = compiled_kernel(q, k, v, causal=True, block_q=TILE,
                              block_k=TILE, window=window)
        return jnp.sum(out.astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    assert _grids(grad.trace(q, kv, kv).jaxpr.jaxpr) == {
        fa.KERNEL_FWD: (bh, live), fa.KERNEL_BWD_DKDV: (bh, live)}
    for kernel in ("fwd", "bwd"):
        assert fa.grid_steps(kernel, q.shape[2], q.shape[2], TILE, TILE,
                             True, window=window) == {
            "run": live, "live": live}
    # Forward and the one backward kernel: the two Mosaic calls a layer.
    assert grad.lower(q, kv, kv).compile().as_text().count(
        "tpu_custom_call") == 2


@pytest.mark.parametrize("call", sorted(CALLS))
def test_flash_forward_compiles_on_the_live_tiles(one_chip, compiled_kernel,
                                                  call):
    q, kv, window, bh, live = _shapes(call, one_chip)
    forward = jax.jit(lambda q, k, v: compiled_kernel(
        q, k, v, causal=True, block_q=TILE, block_k=TILE, window=window))
    assert _grids(forward.trace(q, kv, kv).jaxpr.jaxpr) == {
        fa.KERNEL_FWD: (bh, live)}
    assert forward.lower(q, kv, kv).compile().as_text().count(
        "tpu_custom_call") == 1


def test_traced_offsets_compile_on_every_tile(one_chip, compiled_kernel):
    """A ring step's call: the offsets are arguments, the table is made
    from them on the device and lists every tile. 256 blocks over a
    shard of 8192 positions (``ring_attention``'s default) are 1024
    tiles, 16 KiB of scalar memory."""
    x = jax.ShapeDtypeStruct((1, 8, 8192, 64), jnp.bfloat16,
                             sharding=one_chip)
    at = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def loss(q, k, v, q_offset, k_offset):
        out, lse = compiled_kernel(
            q, k, v, causal=True, block_q=256, block_k=256, with_lse=True,
            q_offset=q_offset, k_offset=k_offset)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    assert _grids(grad.trace(x, x, x, at, at).jaxpr.jaxpr) == {
        fa.KERNEL_FWD: (8, 1024), fa.KERNEL_BWD_DKDV: (8, 1024)}
    assert fa.grid_steps("fwd", 8192, 8192, 256, 256, True,
                         q_offset=jnp.int32(0)) == {"run": 1024}
    assert grad.lower(x, x, x, at, at).compile().as_text().count(
        "tpu_custom_call") == 2


# An attention layer of the cell: (TransformerConfig's fields, (batch,
# seq)). At width 64 the kernels read [batch x heads, width, seq], which
# is how XLA holds q, k and v there (``addressing``: "seq_minor"); at 128
# the head-major form is XLA's own. Compiled from the parent's kernels,
# which took [batch x heads, seq, width] at every width, the lm365m
# layers held eight q-sized copies each.
LAYERS = {
    "lm365m-seq8192": (dict(hidden=1024, heads=16), (2, 8192)),
    "lm365m-seq2048": (dict(hidden=1024, heads=16), (6, 2048)),
    "smallthinker21b": (dict(hidden=2560, heads=28, head_dim=128,
                             kv_heads=4, bias=False, norm="rmsnorm"),
                        (1, 16384)),
}


def _q_sized_copies(text, size):
    """The ``copy`` and ``transpose`` instructions of a compiled
    module's text whose result has ``size`` elements or more, outside
    fusions (inside one, a copy is how the fusion reads an operand, not
    a pass over HBM)."""
    import re
    import numpy as np
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    found, inside = [], None
    for line in text.split("\n"):
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(1)
        if inside in fused:
            continue
        made = re.search(
            r"= \w+\[([\d,]+)\]\{[^ ]*\} (copy|transpose)\(", line)
        if made and np.prod([int(n) for n in made.group(1).split(",")]) \
                >= size:
            found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize("cell", sorted(LAYERS))
def test_attention_layers_compile_without_a_copy_of_q(one_chip,
                                                      compiled_kernel, cell):
    from horovod_tpu.models import transformer
    fields, (batch, seq) = LAYERS[cell]
    cfg = transformer.TransformerConfig(
        max_len=seq, attention_impl="flash", layers=1, **fields)
    layer = transformer.Attention(cfg)
    x = jax.ShapeDtypeStruct((batch, seq, cfg.hidden), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, seq, cfg.hidden), jnp.bfloat16)))

    def loss(params, x):
        # Two layers: the first's output product and the second's
        # projections are each other's neighbours, as in a stack.
        for _ in range(2):
            x = layer.apply(params, x).astype(x.dtype)
        return jnp.sum(x.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    assert text.count("tpu_custom_call") == 4
    assert _q_sized_copies(
        text, batch * seq * cfg.heads * cfg.head_width) == []
    assert fa.addressing(cfg.head_width) == (
        fa.SEQ_MINOR if cfg.head_width == 64 else fa.HEAD_MAJOR)
