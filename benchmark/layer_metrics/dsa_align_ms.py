"""Device milliseconds per step under scope ``hvd_dsa/align``: the
alignment pass: the heads' probabilities made again from q, k and the
log-sum-exp and averaged, the index scores made again, the KL to their
softmax over the selected set and its gradient to the indexer's q, k and
weights, in one pass (an XLA pass: no kernel, so no roofline share of
its own; trace, first chip). None where the program has no such
scope."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_dsa", "align")
