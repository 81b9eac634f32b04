"""Builders: each puts one family of configurations under test through
the program's normal path (``DistributedOptimizer``, ``make_train_step``)
and hands the harness a ``Program``."""

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass
class Program:
    """The system under test, as the harness drives it.

    ``step(*state, batch)`` returns ``(*state, loss)``; ``state[0]`` is
    the parameter tree. ``init_state(params, aux)`` takes the seeded
    weights and the family's non-trained state as the reference makes
    them. ``first_grad_sqnorms(state, before)`` gives the
    squared norm of every leaf of the first gradient as the optimizer
    got it, worked out from the state after one step (``before()`` gives
    the seeded parameter tree again, for an optimizer that keeps none).
    """
    step: Callable
    init_state: Callable            # (params, aux) -> state tuple
    first_grad_sqnorms: Callable
    model: Any = None
