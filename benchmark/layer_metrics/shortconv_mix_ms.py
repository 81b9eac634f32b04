"""Device milliseconds per step under ``hvd_shortconv`` / ``mix``: what
the trace files under the two gates and the taps between the gated short
convolutions' products, forward and backward (trace, first chip).
``scope_reduce`` files a fusion under its root instruction's scope, so a
gate that XLA fuses into a product's fusion shows in ``shortconv_ms``
only: a time, and for that reason no share. None where the program has
no such scope."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_shortconv", "mix")
