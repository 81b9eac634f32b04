"""Device milliseconds per step under scope ``hvd_dsa``: what learned
sparse attention adds to a layer's q, k, v and output products, the
indexer's projections and index scores, the selection, the flash
kernels under the selected set and the alignment pass, forward and
backward, kernels included (trace, first chip). None where the program
has no such scope."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_dsa")
