"""Device milliseconds per step in the XLA operations under
``hvd_flash`` that are not the kernels: pads, ``delta``, layout copies
(trace, first chip). None where the program has no such scope."""

from benchmark import scope_reduce


def read(ctx):
    scopes = scope_reduce.of(ctx)
    if not scopes or not scopes["flash_seen"]:
        return None
    return scopes["flash_glue_ns"] / 1e6 / ctx.steps
