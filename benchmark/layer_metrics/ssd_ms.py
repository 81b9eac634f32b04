"""Device milliseconds per step under scope ``hvd_ssd``: the Mamba-2
mixers, forward, made again under recomputation and backward: the input
and output products, the convolution, the softplus, the recurrence (its
kernels, where it has them, and all), the ``D`` skip, the gate and the
grouped norm (trace, first chip). None where the program has no such
scope."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_ssd")
