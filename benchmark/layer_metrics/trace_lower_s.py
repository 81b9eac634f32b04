"""Seconds JAX spent tracing to jaxprs and lowering them to MLIR before
the window, every program of the process (the program's compile log,
``compile_cache.events()``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.compile_seconds(ctx, "trace", "lower")
