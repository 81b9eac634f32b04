"""Share of the sub-tiles under the diagonal that the window hides from
the forward flash kernel in a windowed layer of the cell: ``window`` over
``interior + masked + window`` of the program's own ``subtile_counts``
at the cell's sequence, the model's blocks, the configuration's own
``head_dim`` and its ``sliding_window_size`` (program counter: shapes
alone, what ``hvd_flash_fwd_subtiles{kind}`` publishes for that call).
Where ``flash_window_skipped_pct`` takes the head dimension as ``hidden
/ heads``, this reads the key a configuration states it under. None
where the configuration states no such window or head dimension, or the
program's kernels know no window."""

import inspect


def read(ctx):
    cfg = ctx["cell"]["cfg"]
    window, head_dim = cfg.get("sliding_window_size"), cfg.get("head_dim")
    if not window or not head_dim or "flash_tile" not in cfg:
        return None
    from horovod_tpu.ops import flash_attention
    counts = getattr(flash_attention, "subtile_counts", None)
    if counts is None or "window" not in inspect.signature(
            counts).parameters:
        return None
    seq, tile = ctx["cell"]["traffic_params"]["seq_len"], cfg["flash_tile"]
    kinds = counts("fwd", seq, seq, tile, tile, True, head_dim=head_dim,
                   window=window)
    seen = kinds["interior"] + kinds["masked"]
    hidden = kinds.get("window", 0)
    return 100.0 * hidden / (seen + hidden)
