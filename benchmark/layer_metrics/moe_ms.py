"""Device milliseconds per step under scope ``hvd_moe``, forward and
backward, every expert layer (the MTP module's too): router, dispatch,
the experts' grouped products and the shared expert's, combine (trace,
first chip; ``benchmark/scope_sum.py``)."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_moe")
