"""Seconds in the compile pipeline for every program that is not the
step, outside the step's own spans: the union of the ``trace``,
``lower`` and ``backend_compile`` spans of every other owner in the
start-up log (here the benchmark's own programs: weights, batches, the
norms ``correct`` rests on; in a job a user's evaluation and
initialisation programs). None where the program keeps no such log."""

from benchmark import startup_reduce, trace_reduce


def read(ctx):
    spans = startup_reduce.before_window(ctx)
    if spans is None:
        return None
    names, step = startup_reduce.PIPELINE, startup_reduce.STEP
    return trace_reduce.total(trace_reduce.subtract(
        startup_reduce.intervals(spans, names, other_than=step),
        startup_reduce.intervals(spans, names, owner=step)))
