"""The least time the chip could take for the forward half of the
attention a step requires (causal half, 2 matrix products per head; q,
k, v read and o written), as a share of the time ``hvd_flash_fwd``
took. FLOP-bound at the cells' shapes."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.flash_roofline(ctx, 0, "hvd_flash_fwd")
