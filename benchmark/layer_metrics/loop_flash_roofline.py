"""The least time the chip could take for the attention a step of a
looped model requires (causal half, 2 products forward and 4 backward of
s x s x head_dim per head; q, k, v, o and their gradients across HBM
once) over every attention call of the step, ``attention_layers`` of the
reference: layers x passes, where ``flash_roofline`` counts one call a
layer. As a share of the time of the flash kernels. FLOP-bound at seq
4096. A forward kernel that recomputation runs a second time counts in
the time and not in the requirement, so the share falls by what it
costs."""

from benchmark import flops, scope_reduce, scope_sum


def read(ctx):
    ms = scope_reduce.kernel_ms(ctx, *scope_reduce.KERNELS)
    reference = ctx["reference"]
    cfg = ctx["cell"]["cfg"]
    if not ms or not hasattr(reference, "attention_layers") or (
            "total_ut_steps" not in cfg):
        return None
    shape = reference.attention_shape(cfg, ctx["cell"]["traffic_params"])
    least = scope_sum.least_seconds(
        ctx, sum(flops.attention_flops(*shape, causal=True)),
        sum(flops.attention_bytes(*shape)))
    return 100.0 * reference.attention_layers(cfg) * least / (ms / 1e3)
