"""Median milliseconds between the completions of consecutive steps in
the window (host clock): the steadier statistic beside ``mfu`` and the
rate, which are taken over the whole window and feel a stall."""

import statistics


def read(ctx):
    done = ctx["seen"]["done"]
    if len(done) < 2:
        return None
    return 1e3 * statistics.median(b - a for a, b in zip(done, done[1:]))
