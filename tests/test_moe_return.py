"""The expert layer's sized path brings its rows back into token order
without scattering a row (``parallel/moe.py: _to_tokens``): by gathers
off the TPU (``_into_tokens``), through the MXU on it
(``ops/rows_to_tokens.py``, here in interpret mode). Both against
``_routed``, the program on a row for every pair, for draws in which
some tokens have no held pair and some have all k, for a draw that
fills the rows exactly, and with NaN in every row past the experts'
groups, where a grouped product may leave anything."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.ops import rows_to_tokens
from horovod_tpu.parallel import moe
from moe_fixtures import (clear_traces, poison,  # noqa: F401 (fixtures)
                          telemetry_plane)

TOKENS, PER_TOKEN, EXPERTS, HIDDEN, WIDTH = 512, 2, 8, 16, 32
FIRST, HELD = 2, 2          # experts 2 and 3 of 8 are held here
ROWS = 512                  # sized_rows(1024, 2, 8)


def _draw(kind):
    """``chosen`` (T, k): 'mixed' has tokens with no held pair, with
    one and with both, 384 held pairs in all; 'fills_the_rows' has 128
    tokens with both, 256 with one and 128 with none: exactly 512."""
    other = np.array([0, 1, 4, 5, 6, 7])
    token = np.arange(TOKENS)
    both = np.stack([np.full(TOKENS, 2), np.full(TOKENS, 3)], 1)
    one = np.stack([other[token % 6], 2 + token % 2], 1)
    none = np.stack([other[token % 6], other[(token + 1) % 6]], 1)
    every = {"mixed": 8, "fills_the_rows": 4}[kind]
    has_both, has_one = token % every == 0, np.isin(token % 4, (1, 2))
    chosen = np.where(has_both[:, None], both,
                      np.where(has_one[:, None], one, none))
    return jnp.asarray(chosen, jnp.int32)


def _routed_args(kind, seed=0):
    assert moe.sized_rows(TOKENS * PER_TOKEN, HELD, EXPERTS) == ROWS
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    chosen = _draw(kind)
    drawn = jnp.sum(jax.nn.one_hot(chosen, EXPERTS), axis=(0, 1))
    return (jax.random.normal(keys[0], (TOKENS, HIDDEN)),
            jax.random.normal(keys[1], (HELD, HIDDEN, WIDTH)) / 4,
            jax.random.normal(keys[2], (HELD, HIDDEN, WIDTH)) / 4,
            jax.random.normal(keys[3], (HELD, WIDTH, HIDDEN)) / 4,
            chosen, jax.random.uniform(keys[4], chosen.shape, minval=0.2),
            drawn, FIRST)


def _both_ways(path, routed, gate="silu"):
    """(the output, the five trained gradients) of ``path`` at
    ``routed``, under a loss that weighs every entry differently."""
    weigh = jnp.cos(jnp.arange(TOKENS * HIDDEN * 1.0)).reshape(
        TOKENS, HIDDEN)

    def of(*trained):
        args = list(routed)
        for i, a in zip(moe._TRAINED, trained):
            args[i] = a
        return path(*args, gate=gate)

    trained = [routed[i] for i in moe._TRAINED]
    return of(*trained), jax.grad(
        lambda *t: jnp.sum(of(*t) * weigh), argnums=tuple(range(5)))(
            *trained)


def _sized_path(*routed, gate="silu"):
    return moe._sized_or_routed(ROWS, gate, *routed)


def _assert_close(got, want, atol, rtol):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol,
                                   rtol=rtol)


@pytest.mark.parametrize("gate", ["silu", "relu"])
@pytest.mark.parametrize("kind", ["mixed", "fills_the_rows"])
def test_sized_path_equals_routed_output_and_five_gradients(
        poison, kind, gate):
    routed = _routed_args(kind)
    held = int(routed[6][FIRST:FIRST + HELD].sum())
    assert held == {"mixed": 384, "fills_the_rows": ROWS}[kind]
    per_token = np.asarray(
        (routed[4] >= FIRST) & (routed[4] < FIRST + HELD)).sum(1)
    assert {0, PER_TOKEN} <= set(per_token.tolist())
    want = _both_ways(moe._routed, routed, gate)
    poison("_routed")           # what follows came through the sized path
    got = _both_ways(_sized_path, routed, gate)
    _assert_close(got[0], want[0], atol=2e-5, rtol=2e-4)
    _assert_close(got[1], want[1], atol=2e-4, rtol=2e-3)
    assert all(float(jnp.abs(g).max()) > 0 for g in want[1])


@pytest.fixture
def nan_past_the_groups(monkeypatch):
    """``lax.ragged_dot`` writing NaN into every row past its groups:
    what it leaves there is not specified, and this is the worst of
    it (PR 37 met it as NaN gradients after a multiply by 0)."""
    real = lax.ragged_dot

    def wrapped(lhs, rhs, group_sizes, **kwargs):
        out = real(lhs, rhs, group_sizes, **kwargs)
        live = jnp.arange(out.shape[0])[:, None] < jnp.sum(group_sizes)
        return jnp.where(live, out, jnp.nan)
    clear_traces()
    monkeypatch.setattr(lax, "ragged_dot", wrapped)
    yield
    clear_traces()


@pytest.mark.parametrize("kind", ["mixed", "fills_the_rows"])
def test_rows_past_the_groups_reach_no_result(kind, request):
    routed = _routed_args(kind, seed=1)
    want = _both_ways(moe._routed, routed)
    request.getfixturevalue("nan_past_the_groups")
    got = _both_ways(_sized_path, routed)
    _assert_close(got[0], want[0], atol=2e-5, rtol=2e-4)
    _assert_close(got[1], want[1], atol=2e-4, rtol=2e-3)


def test_no_direction_of_the_sized_path_scatters_a_row():
    routed = _routed_args("mixed")
    kept = moe._sized_rows(ROWS, *routed[:3], routed[4], *routed[6:])
    g = jnp.ones((TOKENS, HIDDEN))
    for text in (
            str(jax.make_jaxpr(lambda *r: moe._sized(ROWS, kept, *r))(
                *routed)),
            str(jax.make_jaxpr(lambda *r: moe._sized_back(
                ROWS, "silu", g, kept, *r))(*routed))):
        scatters = [line for line in text.splitlines() if "scatter" in line]
        # The rows' places (int32) and the weights' gradient (float32
        # scalars): nothing HIDDEN wide.
        assert scatters and not any(f",{HIDDEN}]" in line
                                    for line in scatters)


# -- the kernel, in interpret mode ----------------------------------------

K_TOKENS, K_PER_TOKEN, K_EXPERTS, K_HELD, K_WIDTH = 512, 3, 16, 4, 256


def _sorted_case(seed, tilt=0.0, rows=768):
    """A draw as the layer sorts it, and a buffer whose rows past the
    live ones are NaN."""
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((K_TOKENS, K_EXPERTS))
    logits[:, :2] += tilt
    chosen = np.argsort(-logits, axis=1)[:, :K_PER_TOKEN]
    key = np.where(chosen < K_HELD, chosen, K_HELD).reshape(-1)
    order = np.argsort(key, kind="stable")[:rows].astype(np.int32)
    sizes = np.bincount(key, minlength=K_HELD + 1)[:K_HELD].astype(np.int32)
    assert sizes.sum() <= rows
    buffer = rng.standard_normal((rows, K_WIDTH)).astype(np.float32)
    buffer[sizes.sum():] = np.nan
    weights = rng.uniform(0.25, 1.0, (K_TOKENS, K_PER_TOKEN))
    # Weights that bfloat16 holds exactly: the kernel rounds them to it.
    weights = np.asarray(jnp.asarray(weights, jnp.bfloat16), np.float32)
    return (jnp.asarray(buffer, jnp.bfloat16), jnp.asarray(order),
            jnp.asarray(key.astype(np.int32)), jnp.asarray(sizes),
            jnp.asarray(chosen.astype(np.int32)), jnp.asarray(weights))


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "plain"])
@pytest.mark.parametrize("case,chunk,rounds", [
    ("even", 64, 1), ("short_chunks", 16, 3), ("one_expert_draws_most", 32, 5)])
def test_kernel_equals_the_gathers(case, chunk, rounds, weighted):
    tilt, rows = {"even": (0.0, 768), "short_chunks": (0.0, 768),
                  "one_expert_draws_most": (3.0, 1280)}[case]
    buffer, order, key, sizes, chosen, weights = _sorted_case(7, tilt, rows)
    weights = weights if weighted else None
    _, _, turns = rows_to_tokens.plan(key, sizes, 128 * K_PER_TOKEN, chunk)
    assert int(turns.max()) == rounds       # runs longer than a chunk too
    got = rows_to_tokens.rows_to_tokens(
        buffer, order, key, sizes, K_PER_TOKEN, (128, chunk),
        None if weights is None else weights.reshape(-1))
    place, has = moe._places(order, chosen, jnp.sum(sizes))
    want = moe._into_tokens(buffer, place, has, weights)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(got, np.float32)).all()
    # Both sum in float32 and round once: the same numbers but for the
    # order of the float32 sum.
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-2,
                               rtol=1e-2)
    untouched = ~np.asarray(has).any(1)     # tokens with no held pair
    assert untouched.any() == (tilt == 0.0)
    assert not np.asarray(got, np.float32)[untouched].any()


def test_kernel_takes_a_draw_that_fills_the_rows():
    buffer, order, key, sizes, chosen, weights = _sorted_case(3)
    rows = int(sizes.sum()) // 16 * 16          # no dead row, no slack
    order, buffer = order[:rows], buffer[:rows]
    sizes = sizes.at[K_HELD - 1].add(rows - int(sizes.sum()))
    key = jnp.where(
        jnp.zeros_like(key).at[order].set(1) == 1, key, K_HELD)
    got = rows_to_tokens.rows_to_tokens(
        buffer, order, key, sizes, K_PER_TOKEN, (128, 32),
        weights.reshape(-1))
    place, has = moe._places(order, chosen, jnp.sum(sizes))
    want = moe._into_tokens(buffer, place, has, weights)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-2,
                               rtol=1e-2)


@pytest.mark.parametrize("cell,shape,tiles", [
    ("smallthinker21b", (16384, 6, 64, 16, 49152, 2560), (256, 64)),
    ("lfm2moe24b", (16384, 4, 64, 8, 16384, 2048), (256, 48)),
    ("glm47flash", (8192, 4, 64, 8, 8192, 2048), (256, 48)),
    ("chunks_that_pass_the_scratch_take_the_smaller_block",
     (16384, 8, 64, 32, 131072, 4096), (128, 48)),
    ("tokens_in_no_whole_block", (1000, 4, 64, 8, 512, 2048), None),
    ("a_width_that_is_no_whole_tile", (16384, 4, 64, 8, 16384, 2000), None)])
def test_tiling_is_from_shapes_alone(cell, shape, tiles):
    assert rows_to_tokens.tiling(*shape, jnp.bfloat16) == tiles
    assert rows_to_tokens.tiling(*shape, jnp.float32) is None


def test_return_rows_reach_the_telemetry_plane(telemetry_plane):
    """Set as the layer is traced: a row for every pair off the TPU,
    the kernel's chunks on it."""
    routed = _routed_args("mixed")
    clear_traces()
    _sized_path(*routed)
    families = telemetry_plane.snapshot()["families"]
    assert families["hvd_moe_return_rows"]["samples"][0]["value"] == (
        TOKENS * PER_TOKEN)
    clear_traces()
    assert moe.return_rows(16384, 6, 16, None) == 98304
    assert moe.return_rows(16384, 6, 16, (256, 64)) == 65536
    assert moe.return_rows(8192, 4, 8, (256, 48)) == 12288
