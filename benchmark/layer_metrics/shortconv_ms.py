"""Device milliseconds per step under scope ``hvd_shortconv``: what the
trace files under the gated short convolutions, forward, made again
under recomputation and backward (trace, first chip). A trace files a
fusion under its root's scope, so this is the mixers' gates, taps and
those of their products whose fusion ends inside the mixer; the products
that XLA fuses into a norm's backward pass, into the residual add or
into AdamW's update are filed there (``shortconv_roofline`` times every
operation that holds any of the mixers' work). None where the program
has no such scope."""

from benchmark import scope_sum


def read(ctx):
    return scope_sum.scope_ms(ctx, "hvd_shortconv")
