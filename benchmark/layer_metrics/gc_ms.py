"""Milliseconds of the window under ``gc`` spans of the program's log
(``compile_cache.spans()``): collections of a millisecond or more, a
span inside another once. ``seen["start"]`` to ``seen["end"]``, the
log's own clock. None where the program has no collector's hook."""

from benchmark import host_reduce


def read(ctx):
    return host_reduce.window_ms_under(ctx, "gc")
