"""The Mamba-2 recurrence's Mosaic kernel pair (``ops/ssd.py``:
``hvd_ssd_fwd`` / ``hvd_ssd_bwd``), forced onto Pallas's interpreter at
small shapes: a group of 2 heads of 64 with a state of 128, two and
three chunks of 128 and a row that is no whole number of chunks. Held
against the recurrence position by position (``reference_ssd``) and
against the einsum form that runs wherever the rule refuses the pair;
the rule itself, by shapes; and what the telemetry plane is told. What
the chip's compiler makes of the pair is ``tests/test_compile_ssd_v5e.py``.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention, ssd
from moe_fixtures import telemetry_plane  # noqa: F401 (fixture)

NAMES = "u dt A B C".split()
# Rows of two chunks, of three (the inner loop walks three a grid
# step), and of two and a part.
ROWS = [256, 384, 300]


def operands(seq, seed=0, batch=2, heads=4, width=64, groups=2, n=128,
             dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(keys[0], (batch, seq, heads, width), dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, heads)))
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    b = jax.random.normal(keys[3], (batch, seq, groups, n), dtype)
    c = jax.random.normal(keys[4], (batch, seq, groups, n), dtype)
    weigh = jax.random.normal(keys[5], (batch, seq, heads, width))
    return (u, dt, a, b, c), weigh


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


@contextlib.contextmanager
def Forced(kernels):
    """``ssd.takes_kernels`` answering as told for the length of a
    ``with``: off the TPU the pair then runs in the interpreter."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ssd, "takes_kernels", lambda *s: kernels)
        yield


@functools.lru_cache(maxsize=None)
def three_ways(seq):
    """Output and the five gradients at one row length: the kernel
    pair's, the einsum form's and the recurrence's."""
    args, weigh = operands(seq, seed=seq)

    def both(f):
        return jax.value_and_grad(
            lambda *xs: jnp.sum(f(*xs) * weigh), argnums=(0, 1, 2, 3, 4))

    with Forced(True):
        kernels = ssd.ssd(*args), both(ssd.ssd)(*args)[1]
    with Forced(False):
        einsums = ssd.ssd(*args), both(ssd.ssd)(*args)[1]
    recurrence = (ssd.reference_ssd(*args),
                  both(ssd.reference_ssd)(*args)[1])
    return kernels, einsums, recurrence


@pytest.mark.parametrize("seq", ROWS)
def test_the_forward_kernel_is_the_recurrence_and_the_einsums(seq):
    (got, _), (einsums, _), (want, _) = three_ways(seq)
    assert got.shape == want.shape == (2, seq, 4, 64)
    assert worst(got, want) < 1e-5
    # The same products of the same rounded operands; the running sum
    # inside a chunk is a product with a triangle of ones, not XLA's
    # cumsum, so Lam differs in its last bit.
    assert worst(got, einsums) < 1e-5


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("seq", ROWS)
def test_the_backward_kernels_gradient_is_the_recurrences(seq, name):
    (_, got), (_, einsums), (_, want) = three_ways(seq)
    at = NAMES.index(name)
    assert got[at].shape == want[at].shape
    assert worst(got[at], want[at]) < 1e-4
    assert worst(got[at], einsums[at]) < 1e-4


def test_the_kernels_keep_the_chunk_states_the_einsums_keep():
    """Same numbers in the same layout, ``[batch, chunks, groups, each,
    width, N]`` float32: ``state_bytes`` counts either."""
    (u, dt, a, b, c), _ = operands(384, seed=5)
    chunked = [z.reshape(2, 3, 128, *z.shape[2:])
               for z in (u.reshape(2, 384, 2, 2, 64),
                         dt.reshape(2, 384, 2, 2), b, c)]
    chunked.insert(2, a.reshape(2, 2))
    y, states = ssd._fwd_kernels(*chunked, True)
    y_e, states_e = ssd._fwd_call(*chunked)
    assert states.shape == states_e.shape == (2, 3, 2, 2, 64, 128)
    assert states.dtype == jnp.float32
    assert states.size * 4 == ssd.state_bytes(2, 384, 4, 64, 128)
    assert worst(states, states_e) < 1e-5 and worst(y, y_e) < 1e-5
    assert not np.any(np.asarray(states[:, 0]))


def test_bfloat16_operands_give_the_float32_result_to_rounding():
    args, weigh = operands(256, seed=3, dtype=jnp.bfloat16)

    def loss(*xs):
        return jnp.sum(ssd.ssd(*xs).astype(jnp.float32) * weigh)

    with Forced(True):
        got = ssd.ssd(*args)
        grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    with Forced(False):
        einsums = ssd.ssd(*args)
    assert got.dtype == jnp.bfloat16
    want = ssd.reference_ssd(*args)
    wanted = jax.grad(lambda *xs: jnp.sum(ssd.reference_ssd(*xs) * weigh),
                      argnums=(0, 1, 2, 3, 4))(*args)

    def gap(x, y):
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        return float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))

    assert gap(got, want) < 0.02
    # Rounded where the einsums round: the same bfloat16 numbers.
    assert gap(got, einsums) < 1e-3
    for name, g, w in zip(NAMES, grads, wanted):
        assert g.dtype == w.dtype and gap(g, w) < 0.02, name


def test_a_long_row_with_large_steps_stays_finite():
    """Every exponent is <= 0 in the kernels too: steps of 20 dt over
    five chunks overflow neither the decays nor the gradients, and the
    first chunk is forgotten by the last."""
    (u, dt, a, b, c), weigh = operands(640, seed=2, batch=1)
    dt = 20.0 * dt
    with Forced(True):
        value, grads = jax.value_and_grad(
            lambda *xs: jnp.sum(ssd.ssd(*xs) * weigh),
            argnums=(0, 1, 2, 3, 4))(u, dt, a, b, c)
        moved = ssd.ssd(u.at[:, :128].add(1.0), dt, a, b, c)
        still = ssd.ssd(u, dt, a, b, c)
    assert np.isfinite(float(value))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)
    assert worst(moved[:, -128:], still[:, -128:]) < 1e-6


def test_with_the_saved_names_kept_the_forward_kernel_runs_once():
    args, weigh = operands(256, seed=4, batch=1)
    policy = jax.checkpoint_policies.save_only_these_names(*ssd.SAVED_NAMES)

    def loss(*xs):
        return jnp.sum(ssd.ssd(*xs) * weigh)

    with Forced(True):
        for kept, calls in ((policy, 1), (None, 2)):
            text = str(jax.make_jaxpr(jax.grad(
                jax.checkpoint(loss, policy=kept)))(*args))
            assert text.count("name=_fwd_kernels") == calls, kept
            assert text.count("name=_bwd_kernels") == 1
            assert "name=_fwd_call" not in text


# (a group's heads, their width, N, chunk, off the TPU) -> the pair or
# not.
RULE = {
    "the-cells": ((8, 64, 128, 128, False), True),
    "a-group-of-two-heads": ((2, 64, 128, 128, False), True),
    "a-group-that-is-no-lane-tile": ((8, 40, 128, 128, False), False),
    "heads-of-eight": ((16, 8, 128, 128, False), False),
    "a-state-of-64": ((8, 64, 64, 128, False), False),
    "a-short-row-of-one-chunk-of-64": ((8, 64, 128, 64, False), False),
    "off-the-tpu": ((8, 64, 128, 128, True), False),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_which_calls_take_the_kernels_is_from_shapes_and_backend(
        case, monkeypatch):
    (*shapes, off), want = RULE[case]
    monkeypatch.setattr(flash_attention, "_interpret", lambda: off)
    assert ssd.takes_kernels(*shapes) is want


@pytest.mark.parametrize("kernels", [True, False])
def test_which_form_was_traced_reaches_the_telemetry_plane(
        telemetry_plane, kernels):  # noqa: F811
    args, _ = operands(256, batch=1)
    with Forced(kernels):
        # A function of its own: a trace of ``ssd.ssd`` itself at these
        # shapes may be cached, and a cached trace publishes nothing.
        jax.eval_shape(lambda *xs: ssd.ssd(*xs), *args)
    families = telemetry_plane.snapshot()["families"]
    assert families["hvd_ssd_kernel"]["samples"][0]["value"] == float(
        kernels)
    assert families["hvd_ssd_chunks"]["samples"][0]["value"] == 2.0


def test_off_the_tpu_a_call_at_the_cells_widths_is_the_einsums(
        telemetry_plane):  # noqa: F811
    """Nothing forced: the rule itself sends a CPU process to the
    einsums, and the jaxpr holds no ``pallas_call``."""
    args, _ = operands(256, batch=1, heads=8, groups=1)
    text = str(jax.make_jaxpr(lambda *xs: ssd.ssd(*xs))(*args))
    assert "pallas_call" not in text and "name=_fwd_call" in text
    families = telemetry_plane.snapshot()["families"]
    assert families["hvd_ssd_kernel"]["samples"][0]["value"] == 0.0
