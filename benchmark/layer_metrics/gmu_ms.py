"""Device milliseconds per step under scope ``hvd_gmu``: the gated
memory units' two products and the gate on the shared scan output,
forward, made again under recomputation and backward (trace, first
chip). None where the program has no such scope."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_gmu")
