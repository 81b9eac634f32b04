"""Median milliseconds of an execution of module ``jit_hvd_train_step``
on the first chip inside the window (trace, its "XLA Modules" line):
the step as the chip saw it, beside the host's ``step_ms_median``."""

import statistics

from benchmark import host_reduce


def read(ctx):
    steps = host_reduce.of(ctx)["step_ms"]
    return statistics.median(steps) if steps else None
