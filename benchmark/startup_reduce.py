"""The program's start-up log (``horovod_tpu.utils.compile_cache.
spans()``: ``(name, owner, start, end)`` on ``time.perf_counter()``,
docs/tracing.md "From the process's start to the first step") as the
readers of ``setup_s``'s parts see it: the spans that ended before the
window, and seconds under a choice of them."""

from benchmark import trace_reduce

STEP = "hvd_train_step"         # horovod_tpu.jax.STEP_NAME, as a literal
PIPELINE = ("trace", "lower", "backend_compile")


def before_window(ctx):
    """The spans that ended before the window; None where the program
    keeps no such log."""
    from horovod_tpu.utils import compile_cache
    read = getattr(compile_cache, "spans", None)
    if read is None:
        return None
    return [s for s in read() if s[3] < ctx["seen"]["start"]]


def intervals(spans, names, owner=None, other_than=None):
    """The merged intervals of the spans named in ``names``, of one
    owner or of every owner but one."""
    return trace_reduce.union(
        [start, end] for name, whose, start, end in spans
        if name in names and owner in (None, whose)
        and whose != other_than)


def seconds(ctx, names, owner=None):
    """Seconds under the spans named in ``names`` (of ``owner``) before
    the window, a span inside another counted once; None without a log
    or without such a span."""
    spans = before_window(ctx)
    if spans is None:
        return None
    merged = intervals(spans, names, owner)
    return trace_reduce.total(merged) if merged else None
