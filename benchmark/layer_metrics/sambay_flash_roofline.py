"""The least time the chip could take for the attention a step of a
``sambay`` model requires (``attention_work`` of the reference: over the
attention layers, each of a head pair's two score maps against the keys
a query sees, the window's only in the windowed layer, and against the
pair's value of twice the width; q, k, v, the maps and their gradients
across HBM once) as a share of the time of the flash kernels. Where
``flash_roofline`` counts one full causal call a layer of equal widths,
this counts what each layer's mask and widths leave. A forward kernel
that recomputation runs a second time counts in the time and not in the
requirement."""

from benchmark import scope_reduce, scope_sum


def read(ctx):
    ms = scope_reduce.kernel_ms(ctx, *scope_reduce.KERNELS)
    attention_work = getattr(ctx["reference"], "attention_work", None)
    if not ms or attention_work is None:
        return None
    cell = ctx["cell"]
    operations, moved = attention_work(cell["cfg"], cell["traffic_params"])
    rows = cell["traffic_params"]["rows_per_chip"]
    return 100.0 * rows * scope_sum.least_seconds(
        ctx, operations, moved) / (ms / 1e3)
