"""The least time the chip could take for the attention a step of a
``smallthinker`` model requires (``attention_work`` of the reference:
28 query heads of 128 over 4 K/V heads; every key under the diagonal in
the full layer, the window's only in the windowed layers; forward and
backward; q, k, v, the output and their gradients across HBM once) as a
share of the time of the flash kernels under the plain attention
layers' scopes (``hvd_attn_full``, ``hvd_attn_window``). FLOP-bound at
seq 16384. A forward kernel that recomputation runs a second time counts
in the time and not in the requirement. None where the program has no
such scope: the kernels of another family's layers are not this
metric's."""

from benchmark import scope_sum

SCOPES = ("hvd_attn_full", "hvd_attn_window")


def kernels_ms(ctx, scope):
    """Milliseconds a step in the Mosaic kernels under ``scope``; None
    where the trace has none there."""
    found = [ns for parts, kernel, ns in scope_sum.events(ctx) or ()
             if kernel and scope in parts]
    return sum(found) / 1e6 / ctx.steps if found else None


def read(ctx):
    attention_work = getattr(ctx["reference"], "attention_work", None)
    ms = sum(kernels_ms(ctx, scope) or 0.0 for scope in SCOPES)
    if attention_work is None or not ms:
        return None
    cell = ctx["cell"]
    operations, moved = attention_work(cell["cfg"], cell["traffic_params"])
    rows = cell["traffic_params"]["rows_per_chip"]
    return 100.0 * rows * scope_sum.least_seconds(
        ctx, operations, moved) / (ms / 1e3)
