"""Device milliseconds per step under scope ``hvd_mla`` less the flash
kernels: the low-rank products, their norms, rope, the concatenations
into q and k, the output projection, and the XLA operations the kernels
drag along (trace, first chip)."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_mla", kernels=False)
