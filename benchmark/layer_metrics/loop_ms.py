"""Device milliseconds per step under scope ``hvd_loop``: the stack of a
looped model over all its passes, forward, the blocks made again under
recomputation and backward, the flash kernels included (trace, first
chip). The embedding, the exit gates, the heads and the loss lie outside
it (``exit_ms``)."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_loop")
