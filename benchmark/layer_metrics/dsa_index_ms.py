"""Device milliseconds per step under scope ``hvd_dsa/index``: the
indexer's three projections, its LayerNorm and rope, and the index
scores of every causal pair (a product a head and the weighted sum of
their positive parts, in float32), a block of queries at a time (trace,
first chip). None where the program has no such scope."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_dsa", "index")
