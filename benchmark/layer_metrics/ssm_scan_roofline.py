"""The least time the chip could take for the selective scans a step
requires (``scan_work`` of the reference: x, dt, B, C, y and their
gradients across HBM once in float32; the recurrence is element-wise, so
of the benchmark's two peaks the bytes bound it) as a share of the time
of the scan kernels under ``hvd_ssm/scan``. A forward kernel that
recomputation runs a second time counts in the time and not in the
requirement."""

from benchmark import harness, scope_sum


def read(ctx):
    scan_work = getattr(ctx["reference"], "scan_work", None)
    reader = harness.load_module(
        ctx["root"], "benchmark/layer_metrics/ssm_scan_ms.py")
    ms = reader.read(ctx) if scan_work else None
    if not ms:
        return None
    cell = ctx["cell"]
    operations, moved = scan_work(cell["cfg"], cell["traffic_params"])
    rows = cell["traffic_params"]["rows_per_chip"]
    return 100.0 * rows * scope_sum.least_seconds(
        ctx, operations, moved) / (ms / 1e3)
