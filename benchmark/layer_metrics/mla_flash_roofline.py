"""The least time the chip could take for the attention a step requires
at the latent-attention shape (causal half, 2 products forward and 4
backward of s x s x 256 per head; q, k, v, o and their gradients across
HBM once), over every attention layer a step runs (``attention_layers``
of the reference: the main model's and the MTP module's), as a share of
the time of the three flash kernels. FLOP-bound at seq 4096."""

from benchmark import flops, scope_reduce, scope_sum


def read(ctx):
    ms = scope_reduce.kernel_ms(ctx, *scope_reduce.KERNELS)
    reference = ctx["reference"]
    if not ms or not hasattr(reference, "attention_layers"):
        return None
    cfg = ctx["cell"]["cfg"]
    shape = reference.attention_shape(cfg, ctx["cell"]["traffic_params"])
    least = scope_sum.least_seconds(
        ctx, sum(flops.attention_flops(*shape, causal=True)),
        sum(flops.attention_bytes(*shape)))
    return 100.0 * reference.attention_layers(cfg) * least / (ms / 1e3)
