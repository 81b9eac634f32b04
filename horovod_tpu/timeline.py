"""Chrome-tracing timeline (reference: horovod/common/timeline.cc/.h).

The reference feeds a lock-free SPSC queue drained by a writer thread
(reference: timeline.h:48-100); events move through NEGOTIATING → TOP_LEVEL →
ACTIVITY states. Here the coordinator emits begin/end activity events into a
thread-safe queue and a writer thread streams Chrome ``trace_event`` JSON.
Runtime start/stop mirrors hvd.start_timeline/stop_timeline
(reference: horovod/common/basics.py:156, operations.cc:1032-1064).
"""

import json
import os
import queue
import threading
import time

from .utils import compile_cache, envparse


class Timeline:
    def __init__(self, path, jax_profiler_dir=None, mark_cycles=False):
        self.path = path
        # Actual file of the CURRENT session (path, version-suffixed in
        # elastic runs — see _shard_path); set by start().
        self.shard_path = path
        # When set, the coordinator drops an instant event per negotiation
        # cycle (reference: --timeline-mark-cycles / MarkCycle events).
        self.mark_cycles = bool(mark_cycles)
        self._queue = queue.Queue()
        self._thread = None
        self._running = False
        self._file = None
        # Optional device-side story: a jax.profiler trace alongside the
        # host timeline (the SURVEY-stated TPU equivalent of NVTX ranges,
        # reference: nvtx_op_range.cc — on TPU the profiler's TraceMe/xplane
        # capture is the per-op device view).
        self._jax_profiler_dir = jax_profiler_dir
        self._jax_profiling = False

    @property
    def profiling(self):
        """True while this timeline's ``jax.profiler`` trace runs
        (telemetry/spans.py then writes its spans into it too)."""
        return self._jax_profiling

    # -- producer side (coordinator) --------------------------------------
    def begin(self, names, activity):
        if self._running:
            self._queue.put(("B", tuple(names), activity,
                             time.perf_counter_ns() // 1000))

    def end(self, names, activity):
        if self._running:
            self._queue.put(("E", tuple(names), activity,
                             time.perf_counter_ns() // 1000))

    def marker(self, name, ts_us=None):
        """Instant event; ``ts_us`` lets a caller stamp a time captured
        earlier (the native cycle marker records the cycle's START but is
        emitted after the cycle ran, once it knows work happened)."""
        if self._running:
            self._queue.put(("I", (name,), name,
                             ts_us if ts_us is not None
                             else time.perf_counter_ns() // 1000))

    def span(self, name, owner, start, end):
        """A span that has ended, in seconds on this file's clock: the
        program's log's (``compile_cache.follow``), one row an owner."""
        if self._running:
            self._queue.put(("X", (owner,), name, int(start * 1e6),
                             int((end - start) * 1e6)))

    def _shard_path(self):
        """Elastic runs restart the timeline after every reset with the
        SAME configured path (basics.init reads one env knob), which
        used to truncate the pre-reset trace. Suffix the shard with the
        membership version joined (``trace.json`` → ``trace.v3.json``)
        so each cohort's timeline survives; non-elastic runs keep the
        plain path."""
        ver = envparse.get_env(envparse.ELASTIC_VERSION)
        if ver is None:
            return self.path
        root, ext = os.path.splitext(self.path)
        return f"{root}.v{ver}{ext or '.json'}"

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._running:
            return
        self.shard_path = self._shard_path()
        self._file = open(self.shard_path, "w")
        self._file.write("[\n")
        # Fresh queue per session, and the writer gets its file
        # explicitly: a start() after a stop() whose join timed out must
        # not let the OLD writer steal this session's events/sentinel or
        # race its close against the NEW file (the straggler finishes
        # draining its own queue into its own file and exits).
        self._queue = queue.Queue()
        self._running = True
        self._thread = threading.Thread(target=self._writer,
                                        args=(self._file, self._queue),
                                        name="hvd-tpu-timeline", daemon=True)
        self._thread.start()
        # The spans already in the start-up log, and later ones as they
        # arrive: the trace begins at the process's start.
        compile_cache.follow(self.span)
        if self._jax_profiler_dir:
            try:
                import jax
                jax.profiler.start_trace(self._jax_profiler_dir)
                self._jax_profiling = True
            except Exception:  # noqa: BLE001 — host timeline still works
                self._jax_profiling = False

    def stop(self):
        if not self._running:
            return
        compile_cache.unfollow(self.span)
        self._running = False
        if self._jax_profiling:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001
                pass
            self._jax_profiling = False
        self._queue.put(None)
        # The WRITER owns closing the file: if this join times out the
        # thread is still draining, and closing here would race its
        # writes (ValueError on a closed file). It closes after the
        # sentinel whether or not we are still waiting.
        self._thread.join(timeout=5)

    # -- writer thread -----------------------------------------------------
    # ``first`` is a writer-local [bool] (is the next event the file's
    # first?) and ``pids`` a writer-local name->tid map — NOT instance
    # state: a straggler writer from a previous session draining its
    # own queue must not corrupt this session's JSON comma placement,
    # and two writers sharing one tid dict would race its inserts
    # (the HVD301-shaped handoff bug this file used to have).
    def _emit(self, file, event, first):
        if not first[0]:
            file.write(",\n")
        first[0] = False
        file.write(json.dumps(event))

    def _emit_item(self, file, item, first, pids):
        phase, names, activity, ts_us, *dur_us = item
        for name in names:
            tid = pids.setdefault(name, len(pids) + 1)
            if phase == "X":
                category = ("hvd_host" if activity in compile_cache.STEADY
                            else "hvd_startup")
                self._emit(file, {"name": activity, "cat": category,
                                  "ph": "X", "ts": ts_us, "dur": dur_us[0],
                                  "pid": 0, "tid": tid,
                                  "args": {"owner": name}}, first)
            elif phase == "I":
                self._emit(file, {"name": activity, "ph": "i",
                                  "ts": ts_us, "pid": 0, "tid": tid,
                                  "s": "g"}, first)
            else:
                self._emit(file, {"name": activity, "cat": "hvd",
                                  "ph": phase, "ts": ts_us, "pid": 0,
                                  "tid": tid, "args": {"tensor": name}},
                           first)

    def _writer(self, file, q):
        """Drain-then-flush loop: one blocking get, then everything the
        producers queued meanwhile, then ONE flush for the whole drain —
        a busy cycle emitting hundreds of events pays one syscall, not
        one per event. Ends (and closes the file) at the stop sentinel.
        Everything mutable here (file, queue, first, pids) is owned by
        THIS writer: start() hands the new writer its own file+queue,
        so a timed-out predecessor can finish without sharing state."""
        first = [True]
        pids = {}
        try:
            stop = False
            while not stop:
                item = q.get()
                if item is None:
                    break
                self._emit_item(file, item, first, pids)
                while True:
                    try:
                        item = q.get_nowait()
                    except queue.Empty:
                        break
                    if item is None:
                        stop = True
                        break
                    self._emit_item(file, item, first, pids)
                file.flush()
        finally:
            try:
                file.write("\n]\n")
                file.close()
            except (OSError, ValueError):
                pass
