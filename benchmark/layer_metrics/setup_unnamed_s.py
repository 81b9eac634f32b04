"""``setup_s`` less every second that has a name: less the union of the
start-up log's spans before the window (``before_program`` from the
harness's first clock read on, ``import``, ``init``, the compile
pipeline of the step and of every other program) and less the
harness's warm-up window. What is left: device runs before the window
(weights, the first steps ``correct`` rests on), the feed, and in a
traced run the profiler's start. None where the program keeps no such
log."""

from benchmark import startup_reduce, trace_reduce


def read(ctx):
    spans = startup_reduce.before_window(ctx)
    if spans is None:
        return None
    setup_s = ctx["end_to_end"]["setup_s"]
    # The harness reads its clock a few ms into the process and
    # ``setup_s`` ends a first batch before the window starts.
    t0 = ctx["seen"]["start"] - setup_s
    named = trace_reduce.union(
        [max(start, t0), end] for _, _, start, end in spans if end > t0)
    return (setup_s - trace_reduce.total(named)
            - ctx["spans"]["window"][0])
