"""Fixtures shared by the expert layer's tests (``test_parallel.py``,
``test_smallthinker.py``): import the fixture by name."""

import jax.numpy as jnp
import pytest

from horovod_tpu.parallel import moe


@pytest.fixture
def poison(monkeypatch):
    """``poison(path)`` makes ``path`` (``_sized`` or ``_routed``) and
    its backward pass return NaN: a result or a gradient that is finite
    did not come through it. They are traced under ``jax.jit``, whose
    traces are dropped before and after."""
    def clear():
        moe._either.clear_cache()
        moe._either_back.clear_cache()

    def make(path):
        clear()
        # Both end in the routed part's eight arguments, tokens first.
        monkeypatch.setattr(moe, path, lambda *args, **kwargs: (
            jnp.full_like(args[-8], jnp.nan)))
        monkeypatch.setattr(moe, path + "_back", lambda *args: tuple(
            jnp.full_like(args[-8:][i], jnp.nan) for i in moe._TRAINED))
    yield make
    clear()
