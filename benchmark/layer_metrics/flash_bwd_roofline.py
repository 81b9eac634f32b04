"""The least time the chip could take for the backward half of the
attention a step requires (causal half, 4 matrix products per head; q,
k, v, o, do read and dq, dk, dv written), as a share of the time
``hvd_flash_bwd_dkdv`` and ``hvd_flash_bwd_dq`` took together."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.flash_roofline(ctx, 1, "hvd_flash_bwd_dkdv",
                                       "hvd_flash_bwd_dq")
