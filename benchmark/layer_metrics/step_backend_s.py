"""Seconds in the backend for the step: the ``backend_compile`` span
the start-up log files under ``hvd_train_step``: XLA's compilation on a
miss of the persistent cache; the cache load and the load onto the
chips on a hit. None where the program keeps no such log."""

from benchmark import startup_reduce


def read(ctx):
    return startup_reduce.seconds(ctx, ("backend_compile",),
                                  owner=startup_reduce.STEP)
