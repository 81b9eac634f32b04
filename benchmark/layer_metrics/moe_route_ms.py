"""Device milliseconds per step under scope ``hvd_moe/route``:
everything of the expert layer that is not an expert's product: the
router's scores and top-k, the sort of the (token, choice) pairs, the
gather into expert order and back, the weighted sum (trace, first
chip)."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_moe", "route")
