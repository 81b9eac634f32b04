"""Device milliseconds per step under scope ``hvd_dsa/select``: the
selection: the scores' order-keeping integer form, the 32 counts that
find each query's 2048th largest, the mask's assembly and its count
(trace, first chip). None where the program has no such scope."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_dsa", "select")
