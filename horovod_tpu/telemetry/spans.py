"""Span API: one context manager that feeds BOTH planes.

The coordinator used to bracket work with ad-hoc ``timeline.begin`` /
``timeline.end`` pairs; metrics would have added a second pair of
``perf_counter`` reads next to each. A span is the single instrument:
entering emits the timeline begin event, exiting emits the end event and
feeds the elapsed seconds into a histogram. Either sink may be absent —
with neither, the shared ``NULL_SPAN`` is returned so a disabled hot
path allocates nothing. While the timeline has its ``jax.profiler``
trace running, the span is also written into that trace as
``hvd:<activity>``, so the coordinator's spans lie on the device
trace's clock (docs/tracing.md).
"""

import time

from .core import NULL

PROFILER_PREFIX = "hvd:"    # a span's name in a jax.profiler trace


class Span:
    __slots__ = ("_names", "_activity", "_timeline", "_histogram", "_t0",
                 "_annotation")

    def __init__(self, names, activity, timeline=None, histogram=None):
        self._names = names
        self._activity = activity
        self._timeline = timeline
        self._histogram = histogram
        self._t0 = 0.0
        self._annotation = None

    def __enter__(self):
        if self._timeline is not None:
            self._timeline.begin(self._names, self._activity)
            # Any sink with begin/end is a timeline; only the real one
            # knows of a profiler trace.
            if getattr(self._timeline, "profiling", False):
                import jax
                self._annotation = jax.profiler.TraceAnnotation(
                    PROFILER_PREFIX + self._activity)
                self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        elapsed = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        if self._histogram is not None:
            self._histogram.observe(elapsed)
        if self._timeline is not None and exc_type is None:
            # Failure paths leave the timeline event open, matching the
            # previous begin/end behavior (the error is what matters).
            self._timeline.end(self._names, self._activity)
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NULL_SPAN = _NullSpan()


def span(names, activity, timeline=None, histogram=None):
    """Build a span over ``names``; no-op when both sinks are absent."""
    if histogram is None or histogram is NULL:
        if timeline is None:
            return NULL_SPAN
        histogram = None
    return Span(names, activity, timeline=timeline, histogram=histogram)
