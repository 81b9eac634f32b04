"""Device milliseconds per step in the Mosaic kernels under scope
``hvd_dsa/attend``: the flash kernels with the selected set as a mask,
forward and backward (trace, first chip). None where the program has no
such scope."""

from benchmark import scope_sum

SCOPES = ("hvd_dsa", "attend")


def read(ctx):
    found = [ns for parts, kernel, ns in scope_sum.events(ctx) or ()
             if kernel and all(scope in parts for scope in SCOPES)]
    return sum(found) / 1e6 / ctx.steps if found else None
