"""Seconds to lower and compile the step in this process, or to load it
from the persistent cache (host clock)."""


def read(ctx):
    return sum(ctx["spans"]["compile"])
