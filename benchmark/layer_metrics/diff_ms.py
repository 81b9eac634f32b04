"""Device milliseconds per step under scope ``hvd_diff`` less the
kernels: what differential attention adds beside the flash kernels, the
lambda mix of a pair's two maps, the norm a head and the scale, forward,
made again under recomputation and backward (trace, first chip). None
where the program has no such scope."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_diff", kernels=False)
