"""Device milliseconds per step under ``hvd_exchange``: the step's
cross-replica reductions with their packing and unpacking (trace, first
chip, self time). At least ``collective_ms`` while the collectives run
synchronously."""

from benchmark import scope_reduce


def read(ctx):
    if ctx["cell"]["chips"] < 2:
        return None
    return scope_reduce.phase_ms(ctx, "exchange")
