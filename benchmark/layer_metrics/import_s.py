"""Seconds importing the program's packages before the window: the
union of the start-up log's ``import`` spans (``horovod_tpu`` and,
inside or after it, ``.ops``, ``.jax``, ``.models``, ``.parallel``).
None where the program keeps no such log."""

from benchmark import startup_reduce


def read(ctx):
    return startup_reduce.seconds(ctx, ("import",))
