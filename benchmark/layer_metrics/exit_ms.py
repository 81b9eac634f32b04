"""Device milliseconds per step under scope ``hvd_exit``: the exit gate
and the shared head on every pass's state, each pass's cross-entropy
(its logits made again on the way back) and the mix of the passes'
losses by the exit distribution, forward and backward (trace, first
chip)."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_exit")
