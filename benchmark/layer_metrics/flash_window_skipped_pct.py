"""Share of the sub-tiles under the diagonal that the window hides from
the forward flash kernel in a windowed layer of the cell: ``window`` over
``interior + masked + window`` of the program's own
``subtile_counts`` at the cell's sequence, the model's blocks and the
configuration's ``sliding_window`` (program counter: shapes alone, what
``hvd_flash_fwd_subtiles{kind}`` publishes for that call). None where
the configuration has no window or the program's kernels know none."""

import inspect


def read(ctx):
    cfg = ctx["cell"]["cfg"]
    window = cfg.get("sliding_window")
    if not window or "flash_tile" not in cfg:
        return None
    from horovod_tpu.ops import flash_attention
    counts = getattr(flash_attention, "subtile_counts", None)
    if counts is None or "window" not in inspect.signature(
            counts).parameters:
        return None
    seq, tile = ctx["cell"]["traffic_params"]["seq_len"], cfg["flash_tile"]
    head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
    kinds = counts("fwd", seq, seq, tile, tile, True, head_dim=head_dim,
                   window=window)
    seen = kinds["interior"] + kinds["masked"]
    hidden = kinds.get("window", 0)
    return 100.0 * hidden / (seen + hidden)
