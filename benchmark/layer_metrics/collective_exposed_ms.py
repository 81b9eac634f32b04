"""The part of ``collective_ms`` during which no other operation runs on
that chip (trace)."""


def read(ctx):
    if ctx["cell"]["chips"] < 2:
        return None
    device = next(iter(ctx["trace"]["devices"].values()))
    return device["collective_exposed_ns"] / 1e6 / ctx.steps
