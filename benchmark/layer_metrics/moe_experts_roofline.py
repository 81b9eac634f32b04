"""The least time the chip could take for the experts' products a step
requires (the held experts' grouped products by expectation and the
shared expert's, forward and backward: ``expert_products`` beside the
configuration's reference gives FLOPs and bytes; FLOP-bound at the
cell's shape), as a share of the time under scope ``hvd_moe/experts``,
which also holds the casts of the weights and the gated activation."""

from benchmark import scope_sum


def read(ctx):
    ms = scope_sum.scope_ms(ctx, "hvd_moe", "experts")
    products = getattr(ctx["reference"], "expert_products", None)
    if not ms or products is None:
        return None
    least = scope_sum.least_seconds(ctx, *products(
        ctx["cell"]["cfg"], ctx["cell"]["traffic_params"]))
    return 100.0 * least / (ms / 1e3)
