"""Device milliseconds per step in the flash kernels under scope
``hvd_attn_window``: the plain attention layers that see a window of
keys, all of them together, forward, forward again under recomputation,
and backward (trace, first chip). A layer's share of it beside
``swa_full_ms`` is what a window costs, against the share of the work
it keeps. None where the program has no such scope."""

from benchmark import harness

ROOFLINE = "benchmark/layer_metrics/swa_flash_roofline.py"


def read(ctx):
    return harness.load_module(ctx["root"], ROOFLINE).kernels_ms(
        ctx, "hvd_attn_window")
