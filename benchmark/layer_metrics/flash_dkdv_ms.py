"""Device milliseconds per step in the ``hvd_flash_bwd_dkdv`` kernel
(trace, first chip)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.kernel_ms(ctx, "hvd_flash_bwd_dkdv")
