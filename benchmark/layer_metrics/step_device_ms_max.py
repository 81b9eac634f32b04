"""The longest execution of module ``jit_hvd_train_step`` on the first
chip inside the window, in milliseconds (trace): a step that ran long
on the chip itself."""

from benchmark import host_reduce


def read(ctx):
    steps = host_reduce.of(ctx)["step_ms"]
    return max(steps) if steps else None
