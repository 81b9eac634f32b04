"""Executables compiled and written to the persistent cache before the
window: 0 on the second run of a cell in one checkout (the program's
compile log)."""

from benchmark import scope_reduce


def read(ctx):
    entries = scope_reduce.compile_events(ctx, "cache_miss")
    return None if entries is None else len(entries)
