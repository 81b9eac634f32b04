"""The least time the chip could take for the attention a step requires
(causal half; forward 2 and backward 4 matrix products per head; q, k,
v, o and their gradients across HBM once), as a share of the time the
flash kernels took. Compute-bound at the cells' shapes: the FLOP bound
is 8.5 times the byte bound at seq 8192 and 2.1 times at seq 2048. The
shape is the configuration's own: ``attention_shape`` of its reference."""

from benchmark import flops, peaks


def read(ctx):
    device = next(iter(ctx["trace"]["devices"].values()))
    ns = device["by_class"]["kernel"]
    attention_shape = getattr(ctx["reference"], "attention_shape", None)
    if not ns or attention_shape is None:
        return None
    cfg = ctx["cell"]["cfg"]
    shape = attention_shape(cfg, ctx["cell"]["traffic_params"])
    kind = ctx["device_kind"]
    need = sum(flops.attention_flops(*shape, causal=True)) / peaks.peak(
        kind, "bf16_flops_per_s")
    move = sum(flops.attention_bytes(*shape)) / peaks.peak(
        kind, "hbm_bytes_per_s")
    least = cfg["num_hidden_layers"] * max(need, move)
    return 100.0 * least / (ns / 1e9 / ctx.steps)
