"""The least time the chip could take for the Mamba-2 recurrence a step
requires (``ssd_work`` of the reference: the recurrence's own multiply-
adds, whatever form computes it, and ``u``, ``dt``, ``B``, ``C``, ``y``
and their gradients across HBM once) as a share of the time under
``hvd_ssd/scan``. A forward pass that recomputation runs a second time
counts in the time and not in the requirement. Over 100% the count is
wrong, not the chip."""

from benchmark import harness, scope_sum


def read(ctx):
    ssd_work = getattr(ctx["reference"], "ssd_work", None)
    reader = harness.load_module(
        ctx["root"], "benchmark/layer_metrics/ssd_scan_ms.py")
    ms = reader.read(ctx) if ssd_work else None
    if not ms:
        return None
    cell = ctx["cell"]
    operations, moved = ssd_work(cell["cfg"], cell["traffic_params"])
    rows = cell["traffic_params"]["rows_per_chip"]
    return 100.0 * rows * scope_sum.least_seconds(
        ctx, operations, moved) / (ms / 1e3)
