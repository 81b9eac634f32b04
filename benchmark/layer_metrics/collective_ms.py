"""Milliseconds per step in which a collective is in flight on the
first chip (trace)."""


def read(ctx):
    if ctx["cell"]["chips"] < 2:
        return None
    device = next(iter(ctx["trace"]["devices"].values()))
    return device["collective_ns"] / 1e6 / ctx.steps
