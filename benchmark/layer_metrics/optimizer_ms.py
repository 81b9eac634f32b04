"""Device milliseconds per step under ``hvd_optimizer``: the inner
optax update and its application (trace, first chip)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "optimizer")
