"""Device milliseconds per step under scope ``hvd_ssm``: the Mamba
mixers, forward, made again under recomputation and backward: the input,
``x``, ``dt`` and output projections, the convolution, the gate, and the
selective-scan kernels with their glue (trace, first chip). None where
the program has no such scope."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_ssm")
