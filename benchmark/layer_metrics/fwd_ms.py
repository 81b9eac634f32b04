"""Device milliseconds per step under ``hvd_grad`` without a
``transpose(``: the forward pass, its flash kernel included (trace,
first chip; ``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "fwd")
