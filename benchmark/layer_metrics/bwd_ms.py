"""Device milliseconds per step under ``hvd_grad`` with a
``transpose(``: the backward pass, its two flash kernels included
(trace, first chip; ``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "bwd")
