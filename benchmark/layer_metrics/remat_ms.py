"""Device milliseconds per step in operations whose ``op_name`` holds
``rematted_computation``: the forward work that ``jax.checkpoint`` makes
a second time on the way back (the blocks under the configuration's
``remat`` policy, a pass's logits), Mosaic kernels included. ``mfu``
counts none of it as required work (trace, first chip). None where the
step recomputes nothing."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "rematted_computation")
