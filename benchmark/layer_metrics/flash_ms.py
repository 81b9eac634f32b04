"""Device milliseconds per step in the Mosaic flash-attention kernels
(trace, first chip)."""


def read(ctx):
    device = next(iter(ctx["trace"]["devices"].values()))
    ns = device["by_class"]["kernel"]
    return ns / 1e6 / ctx.steps if ns else None
