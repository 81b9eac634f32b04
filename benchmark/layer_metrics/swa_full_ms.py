"""Device milliseconds per step in the flash kernels under scope
``hvd_attn_full``: the plain attention layers that see every key before
the query, forward, forward again under recomputation, and backward
(trace, first chip). None where the program has no such scope."""

from benchmark import harness

ROOFLINE = "benchmark/layer_metrics/swa_flash_roofline.py"


def read(ctx):
    return harness.load_module(ctx["root"], ROOFLINE).kernels_ms(
        ctx, "hvd_attn_full")
