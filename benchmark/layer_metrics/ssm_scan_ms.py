"""Device milliseconds per step in the Mosaic kernels under
``hvd_ssm/scan``: ``hvd_ssm_fwd`` and ``hvd_ssm_bwd``, the selective
scan forward (a second time where recomputation runs it again) and
backward (trace, first chip). The XLA operations beside them under the
same scope (pads, reshapes, casts) are ``ssm_ms``'s. None where the
program has no such scope."""

from benchmark import scope_sum


def read(ctx):
    scan = ("hvd_ssm", "scan")
    whole = scope_sum.scope_ms(ctx, *scan)
    if whole is None:
        return None
    return whole - scope_sum.scope_ms(ctx, *scan, kernels=False)
