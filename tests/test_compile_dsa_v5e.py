"""Compile the alignment kernel of learned sparse attention
(``ops/sparse_attention.py``: ``hvd_dsa_align``), the forward of
``align_loss``'s ``custom_vjp`` and all, for a described TPU v5e at the
shape of ``keyevl30b-seq16384-1chip``: 32 query heads of 128 over 4 K/V
heads at 16,384 positions, an indexer of 16 heads of 64, the selected
sets as an int8 mask of 16,384 x 16,384. One Mosaic call on the tiles
that hold a causal pair. What the chip's compiler refuses it refuses
here, at no chip time. Nothing runs, so this says nothing about results
or times.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around the compile, as in
``tests/test_compile_flash_v5e.py``.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import sparse_attention as dsa

SEQ, HEADS, KV_HEADS, HEAD_DIM, INDEX_HEADS, INDEX_DIM = (
    16384, 32, 4, 128, 16, 64)


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


def test_the_alignment_kernel_compiles_for_v5e_at_the_cells_shape(
        one_chip, monkeypatch):
    # The kernel asks the flash module whether to interpret; here the
    # default backend is the CPU, and the compile is for the TPU.
    monkeypatch.setattr(fa, "_interpret", lambda: False)

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (of((HEADS, SEQ, HEAD_DIM), jnp.bfloat16),
            of((KV_HEADS, SEQ, HEAD_DIM), jnp.bfloat16),
            of((HEADS, SEQ), jnp.float32), of((SEQ, SEQ), jnp.int8),
            of((SEQ, INDEX_HEADS, INDEX_DIM), jnp.bfloat16),
            of((SEQ, INDEX_DIM), jnp.bfloat16),
            of((SEQ, INDEX_HEADS), jnp.float32), of((SEQ,), jnp.float32))

    def loss(q_i, k_i, w, q, k, lse, mask_t, lse_i):
        with jax.named_scope(dsa.SCOPE), jax.named_scope(dsa.SCOPE_ALIGN):
            return dsa.align_loss(q, k, lse, mask_t, q_i, k_i, w, lse_i,
                                  HEAD_DIM ** -0.5)

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    compiled = grad.lower(*args[4:7], *args[:4], args[7]).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert f"%{dsa.KERNEL_ALIGN}" in calls[0].split("=")[0]
    # Its scopes, each without the ``jvp(...)`` a transformation wraps
    # it in, as the benchmark's readers take them.
    scopes = [re.sub(r"^(?:jvp|transpose|jit)\((.*)\)$", r"\1", part)
              for part in re.search(r'op_name="([^"]+)"',
                                    calls[0]).group(1).split("/")]
    at = scopes.index(dsa.SCOPE)
    assert scopes[at + 1] == dsa.SCOPE_ALIGN
    assert dsa.KERNEL_ALIGN in scopes[at + 2:]
    # The gradients leave in the operands' types; nothing the size of a
    # score matrix is an output or a temporary.
    shapes = [(x.shape, x.dtype) for x in jax.tree.leaves(
        compiled.out_info)]
    assert shapes == [((), jnp.float32),
                      ((SEQ, INDEX_HEADS, INDEX_DIM), jnp.bfloat16),
                      ((SEQ, INDEX_DIM), jnp.bfloat16),
                      ((SEQ, INDEX_HEADS), jnp.float32)]
    assert compiled.memory_analysis().temp_size_in_bytes < SEQ * SEQ
