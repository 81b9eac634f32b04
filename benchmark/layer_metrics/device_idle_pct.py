"""1 - union of the intervals in which an operation ran on the device /
the traced window, averaged over the chips (trace)."""


def read(ctx):
    trace = ctx["trace"]
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
