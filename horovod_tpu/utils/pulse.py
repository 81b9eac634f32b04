"""The host while the step runs: a pulse, and the collector's pauses
(docs/tracing.md "The host while the step runs").

A window that stalls for a second says nothing by itself: the span
round the wait for the loss covers the whole stall whether the process
stood still, the collector ran, the next launch came late or the chip
ran long. Two things inside the process tell the first two apart, and
``hvd.init()`` starts both (``hvd.shutdown()`` stops them; an elastic
reset keeps them: ``kept()``):

- **The pulse**: one daemon thread that sleeps ``PERIOD`` and at each
  wake-up reads ``time.perf_counter_ns()`` and writes one
  ``jax.profiler.TraceAnnotation("hvd:pulse", perf_counter_ns=...)``.
  Without a profiler session that is a flag test. Inside one, every
  pulse is a pair (profiler clock, ``perf_counter``): a span of the log
  is placed on the device trace's clock by the nearest pair, and a gap
  between two pulses is a pause of the host on that clock. A wake-up
  later than it was due by more than ``LATE`` is a span ``host_pause``,
  owner ``pulse``, of ``compile_cache``'s log, from the wake-up that
  was due to the one that came.
- **The collector**: one ``gc.callbacks`` entry that adds each
  collection's time to a total by generation and keeps those of
  ``GC_SPAN`` or more for the pulse, which records them as spans
  ``gc``, owner ``gen0`` / ``gen1`` / ``gen2``. A generation-2
  collection is also a span ``hvd:gc`` of the thread it ran on in a
  profiler trace. The callback takes no lock and calls nothing that
  does (a collection can start on any line of any thread, one that
  holds the log's lock among them): the log, the counters and the
  WARNING are the pulse's, one wake-up later.

The sinks are the log's: ``Timeline`` writes both spans under category
``hvd_host`` as they arrive, and with ``HOROVOD_TPU_METRICS`` on the
pulse moves ``hvd_host_pauses_total``, ``hvd_host_pause_seconds_total``,
``hvd_host_pause_longest_seconds`` and
``hvd_gc_seconds_total{generation}`` (docs/metrics.md). A pause of
``WARN`` seconds or more logs one WARNING, at most one every
``WARN_EVERY`` seconds.
"""

import collections
import contextlib
import gc
import threading
import time

import jax

from .. import telemetry
from ..telemetry.spans import PROFILER_PREFIX
from . import compile_cache
from .logging_util import get_logger

PERIOD = 0.020          # seconds between two wake-ups
LATE = 0.050            # a wake-up this much after it was due is a pause:
#                         a tenth of the shortest stall on record
GC_SPAN = 0.001         # a collection this long is a span of the log
WARN = 1.0              # a pause this long is a WARNING
WARN_EVERY = 60.0       # seconds between two WARNINGs at the least

PULSE_EVENT = PROFILER_PREFIX + "pulse"
GC_EVENT = PROFILER_PREFIX + "gc"
GENERATIONS = ("gen0", "gen1", "gen2")


class Collector:
    """The ``gc.callbacks`` entry. Generation 0 runs tens of thousands
    of times while a step is traced, so a call is two clock reads and
    an addition."""

    def __init__(self, clock=time.perf_counter_ns):
        self.ns = [0, 0, 0]     # in collections so far, by generation
        # (generation, start_ns, end_ns) of the long ones, until the
        # pulse takes them; bounded for a pulse that has stopped.
        self.long = collections.deque(maxlen=compile_cache.STEADY_KEPT)
        self._clock = clock
        self._start = None
        self._annotation = None

    def __call__(self, phase, info):
        generation = info["generation"]
        if phase == "start":
            if generation == 2:
                self._annotation = jax.profiler.TraceAnnotation(GC_EVENT)
                self._annotation.__enter__()
            self._start = self._clock()
        elif self._start is not None:   # registered inside a collection
            end = self._clock()
            self.ns[generation] += end - self._start
            if end - self._start >= GC_SPAN * 1e9:
                self.long.append((generation, self._start, end))
            self._start = None
            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
                self._annotation = None


class Pulse:
    """The thread and the collector's hook. ``clock`` gives nanoseconds
    on ``perf_counter``; ``sleep(seconds)`` returns true when the pulse
    is to end (tests hand both in)."""

    def __init__(self, clock=time.perf_counter_ns, sleep=None):
        self.collector = Collector(clock)
        self._clock = clock
        self._stopped = threading.Event()
        self._sleep = sleep or self._stopped.wait
        self._thread = None
        self._gc_seen = [0, 0, 0]
        self._longest = 0.0
        self._warned = None
        self._log = get_logger()
        self._pauses = telemetry.counter(
            "hvd_host_pauses_total",
            "Wake-ups of the pulse that came late: pauses of the host")
        self._pause_seconds = telemetry.counter(
            "hvd_host_pause_seconds_total", "Seconds in pauses of the host")
        self._pause_longest = telemetry.gauge(
            "hvd_host_pause_longest_seconds", "The longest pause of the host")
        gc_seconds = telemetry.counter(
            "hvd_gc_seconds_total", "Seconds in the collector",
            ("generation",))
        self._gc_seconds = [gc_seconds.labels(generation=g)
                            for g in GENERATIONS]

    def start(self):
        gc.callbacks.append(self.collector)
        self._thread = threading.Thread(target=self.run, name="hvd-tpu-pulse",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stopped.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if self.collector in gc.callbacks:
            gc.callbacks.remove(self.collector)
        self.collected()

    def run(self):
        due = self._clock() + int(PERIOD * 1e9)
        while not self._sleep(PERIOD):
            due = self.beat(due)

    def beat(self, due):
        """One wake-up that was due at ``due``; returns when the next
        is."""
        now = self._clock()
        with jax.profiler.TraceAnnotation(PULSE_EVENT, perf_counter_ns=now):
            pass
        inside = self.collected()
        if now - due > LATE * 1e9:
            self._paused(due / 1e9, now / 1e9, inside)
        return now + int(PERIOD * 1e9)

    def collected(self):
        """Hand the collector's long collections to the log and its
        totals to the counters; returns the seconds by generation since
        the last call."""
        long = self.collector.long
        while long:
            generation, start, end = long.popleft()
            compile_cache.record("gc", GENERATIONS[generation],
                                 start / 1e9, end / 1e9)
        inside = []
        for generation, total in enumerate(self.collector.ns):
            seconds = (total - self._gc_seen[generation]) / 1e9
            self._gc_seen[generation] = total
            if seconds:
                self._gc_seconds[generation].inc(seconds)
            inside.append(seconds)
        return inside

    def _paused(self, start, end, collecting):
        seconds = end - start
        compile_cache.record("host_pause", "pulse", start, end)
        self._pauses.inc()
        self._pause_seconds.inc(seconds)
        if seconds > self._longest:
            self._longest = seconds
            self._pause_longest.set(seconds)
        if seconds < WARN or (self._warned is not None
                              and end - self._warned < WARN_EVERY):
            return
        self._warned = end
        inside = ", ".join(
            f"{name} {s:.2f} s" for name, s in zip(GENERATIONS, collecting)
            if s >= GC_SPAN)
        self._log.warning(
            "host paused %.2f s: the pulse came late; collector: %s",
            seconds, inside + " inside it" if inside else "none")


# One a process, as the log it writes to.
_lock = threading.Lock()
_pulse = None
_keeping = 0


def start():
    """Start the process's pulse; a second call starts no second one."""
    global _pulse
    with _lock:
        if _pulse is None:
            _pulse = Pulse().start()
        return _pulse


def stop():
    """Stop it and take the collector's hook off; nothing inside
    ``kept()``."""
    global _pulse
    with _lock:
        if _pulse is not None and not _keeping:
            _pulse.stop()
            _pulse = None


@contextlib.contextmanager
def kept():
    """A ``hvd.shutdown()`` inside leaves the pulse running: an elastic
    reset is a pause of the job, and the pulse is what times it."""
    global _keeping
    with _lock:
        _keeping += 1
    try:
        yield
    finally:
        with _lock:
            _keeping -= 1


def running():
    """The process's pulse, or None."""
    return _pulse
