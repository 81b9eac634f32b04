"""Milliseconds of the window under ``host_pause`` spans of the
program's log (``compile_cache.spans()``, docs/tracing.md "The host
while the step runs"): wake-ups of the pulse that came late by more than
its threshold, so the time in which no thread of the process ran.
``seen["start"]`` to ``seen["end"]``, the log's own clock. 0 barring a
stall; None where the program has no pulse."""

from benchmark import host_reduce


def read(ctx):
    return host_reduce.window_ms_under(ctx, "host_pause")
