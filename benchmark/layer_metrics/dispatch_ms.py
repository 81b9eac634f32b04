"""Median host milliseconds for ``step(...)`` to return (host clock)."""

import statistics


def read(ctx):
    calls = ctx["spans"].get("dispatch")
    return 1e3 * statistics.median(calls) if calls else None
