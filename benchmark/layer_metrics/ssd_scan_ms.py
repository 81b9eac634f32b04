"""Device milliseconds per step under ``hvd_ssd/scan``: the Mamba-2
recurrence, whatever form computes it, forward (a second time where
recomputation runs it again) and backward: chunked products, the carry
of the state across the chunks, the decays and masks between them, and
kernels if it has any (trace, first chip). None where the program has no
such scope."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_ssd", "scan")
