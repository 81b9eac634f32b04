"""Device milliseconds per step in operations that are neither Mosaic
kernels nor collectives (trace, first chip)."""


def read(ctx):
    device = next(iter(ctx["trace"]["devices"].values()))
    return device["by_class"]["xla"] / 1e6 / ctx.steps
