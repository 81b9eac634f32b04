"""Seconds from the process's start (the kernel's record) to the first
line of ``horovod_tpu/__init__.py``: the interpreter, the harness's own
imports and the backend's start, which ``jax.devices()`` makes before
the program is imported (the program's start-up log, the
``before_program`` span). None where the program keeps no such log."""

from benchmark import startup_reduce


def read(ctx):
    return startup_reduce.seconds(ctx, ("before_program",))
