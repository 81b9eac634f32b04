"""Seconds tracing the step to a jaxpr and lowering it to MLIR: the
``trace`` and ``lower`` spans the start-up log files under
``hvd_train_step`` (the spans of functions traced inside them lie
inside them and are not added). None where the program keeps no such
log."""

from benchmark import startup_reduce


def read(ctx):
    return startup_reduce.seconds(ctx, ("trace", "lower"),
                                  owner=startup_reduce.STEP)
