"""The longest gap between two executions in a row of module
``jit_hvd_train_step`` on the first chip inside the window, in
milliseconds (trace): a launch that came late."""

from benchmark import host_reduce


def read(ctx):
    gaps = host_reduce.of(ctx)["gap_ms"]
    return max(gaps) if gaps else None
