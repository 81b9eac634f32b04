"""Seconds spent reading executables back from the persistent cache
before the window (the program's compile log)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.compile_seconds(ctx, "cache_load")
