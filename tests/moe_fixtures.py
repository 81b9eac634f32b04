"""Fixtures shared by the expert layer's tests (``test_parallel.py``,
``test_glm4_moe_lite.py``, ``test_smallthinker.py``,
``test_lfm2_moe.py``): import the fixture by name."""

import jax.numpy as jnp
import pytest

from horovod_tpu.parallel import moe


def clear_traces():
    """Drop what ``jax.jit`` keeps of the layer's two ways: a test that
    patches what they trace calls this before and after."""
    moe._either.clear_cache()
    moe._either_back.clear_cache()


@pytest.fixture
def poison(monkeypatch):
    """``poison(path)`` makes ``path`` (``_sized`` or ``_routed``) and
    its backward pass return NaN: a result or a gradient that is finite
    did not come through it. They are traced under ``jax.jit``, whose
    traces are dropped before and after."""
    def make(path):
        clear_traces()
        # Both end in the routed part's eight arguments, tokens first.
        monkeypatch.setattr(moe, path, lambda *args, **kwargs: (
            jnp.full_like(args[-8], jnp.nan)))
        monkeypatch.setattr(moe, path + "_back", lambda *args: tuple(
            jnp.full_like(args[-8:][i], jnp.nan) for i in moe._TRAINED))
    yield make
    clear_traces()


@pytest.fixture
def telemetry_plane(monkeypatch):
    """The telemetry module with metrics on and a registry of the
    test's own. The process-wide one holds whatever earlier tests of the
    worker published under the same families (``hvd_moe_*`` has three
    models' tests as publishers), and a test that compares a family's
    whole sample set must not see them."""
    from horovod_tpu.telemetry import core as telemetry
    monkeypatch.setattr(telemetry, "_ENABLED", True)
    monkeypatch.setattr(telemetry, "_REGISTRY", telemetry.Registry())
    return telemetry
