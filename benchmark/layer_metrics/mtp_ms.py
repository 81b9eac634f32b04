"""Device milliseconds per step under scope ``hvd_mtp``, forward and
backward: the multi-token-prediction module's norms, projection and
block (its attention with the flash kernels, its router and its shared
expert) **without the grouped products of its expert layer**: XLA's
grouped-product kernels carry no scope, ``scope_sum`` files them all
under ``hvd_moe/experts`` and cannot tell which layer's they are, so a
fifth of them (one expert layer of five) is missing here. The shared
head's second use is outside the scope (trace, first chip)."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_mtp")
