"""From a profiler trace to what the host and the chip each saw of a
step: the program's own host events, its pulse, and the executions of
the compiled step on the first chip.

``trace_reduce.load_xplane`` keeps the device's operations and the
``bench:*`` spans. This one keeps, as plain data (so a small one can be
a test fixture):

    {"host": [[name, start_ns, dur_ns], ...],
     "pulses": [[trace_ns, perf_counter_ns], ...],
     "steps": [[start_ns, dur_ns], ...]}

``host`` holds the events of every host thread named ``hvd:*`` (the
program's: ``hvd:pulse``, ``hvd:gc``; docs/tracing.md "The host while
the step runs") or ``bench:*`` (the harness's); ``pulses`` the pulse's
wake-ups, each a pair of the profiler's clock and the
``time.perf_counter_ns()`` the event carries, ascending; ``steps`` the
executions of module ``jit_hvd_train_step`` on the first chip (its
"XLA Modules" line), ascending. A program without a pulse leaves
``pulses`` empty.
"""

import glob
import importlib
import os

from benchmark import trace_reduce

PROGRAM_PREFIX = "hvd:"     # horovod_tpu.telemetry.spans.PROFILER_PREFIX
PULSE, GC = PROGRAM_PREFIX + "pulse", PROGRAM_PREFIX + "gc"
PULSE_STAT = "perf_counter_ns"
MODULES_LINE = "XLA Modules"
STEP_MODULE = "jit_hvd_train_step"      # "jit_" + horovod_tpu.jax.STEP_NAME
FIRST_CHIP = "/device:TPU:0"
# horovod_tpu/utils/pulse.py: PERIOD and LATE, as literals: two pulses
# farther apart than their sum are a pause of the host.
PERIOD_NS, LATE_NS = 20_000_000, 50_000_000


def planes(trace_dir):
    """The planes of the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1]).planes


def load_xplane(trace_dir):
    """The newest trace under ``trace_dir`` as the plain data above."""
    trace = {"host": [], "pulses": [], "steps": []}
    for plane in planes(trace_dir):
        for line in plane.lines:
            if plane.name == FIRST_CHIP:
                if line.name == MODULES_LINE:
                    trace["steps"] += [
                        [int(e.start_ns), int(e.duration_ns)]
                        for e in line.events
                        if e.name.startswith(STEP_MODULE)]
            elif not plane.name.startswith("/device:"):
                for e in line.events:
                    if not e.name.startswith((PROGRAM_PREFIX,
                                              trace_reduce.HOST_PREFIX)):
                        continue
                    trace["host"].append(
                        [e.name, int(e.start_ns), int(e.duration_ns)])
                    if e.name == PULSE:
                        stamp = dict(e.stats).get(PULSE_STAT)
                        if stamp is not None:
                            trace["pulses"].append(
                                [int(e.start_ns), int(stamp)])
    trace["pulses"].sort()
    trace["steps"].sort()
    return trace


def place(pulses, seconds):
    """A reading of ``time.perf_counter()`` on the profiler's clock, in
    ns, by the pulse nearest to it; None without a pulse."""
    if not pulses:
        return None
    at = seconds * 1e9
    trace_ns, stamp = min(pulses, key=lambda pair: abs(pair[1] - at))
    return trace_ns + (at - stamp)


def host_pauses(trace):
    """The intervals in which the host stood still, on the profiler's
    clock, merged: from the pulse that was due to the one that came,
    wherever two lie farther apart than the period and the threshold,
    and the ``hvd:gc`` events."""
    stamps = [trace_ns for trace_ns, _ in trace["pulses"]]
    late = [[a + PERIOD_NS, b] for a, b in zip(stamps, stamps[1:])
            if b - a > PERIOD_NS + LATE_NS]
    collecting = [[start, start + dur] for name, start, dur
                  in trace["host"] if name == GC]
    return trace_reduce.union(late + collecting)


def reduce(trace, busy=()):
    """The numbers of the window (``bench:window``, or else the extent
    of the steps), times in ms. ``busy`` is the merged intervals in
    which the first chip ran an operation (``trace_reduce``'s), for the
    idle share."""
    out = {"step_ms": [], "gap_ms": [], "idle_in_host_pause_pct": None}
    names = {e[0] for e in trace["host"]}
    if not trace["steps"] and trace_reduce.HOST_PREFIX + "window" not in names:
        return out
    lo, hi = window = trace_reduce.window_of({
        "host": trace["host"], "devices": {"0": [
            ["step", start, dur] for start, dur in trace["steps"]]}})
    steps = [(start, start + dur) for start, dur in trace["steps"]
             if lo <= start and start + dur <= hi]
    out["step_ms"] = [(b - a) / 1e6 for a, b in steps]
    out["gap_ms"] = [(nxt[0] - cur[1]) / 1e6
                     for cur, nxt in zip(steps, steps[1:])]
    if trace["pulses"]:
        idle = trace_reduce.subtract([list(window)], busy)
        outside = trace_reduce.subtract(idle, host_pauses(trace))
        idle_ns = trace_reduce.total(idle)
        out["idle_in_host_pause_pct"] = (
            100.0 * (idle_ns - trace_reduce.total(outside)) / idle_ns
            if idle_ns else 0.0)
    return out


def of(ctx):
    """The reduction of a run's trace, made once a context."""
    if "host_trace" not in ctx:
        first = next(iter(ctx["trace"]["devices"].values()))
        ctx["host_trace"] = reduce(load_xplane(ctx["trace_dir"]),
                                   first["busy"])
    return ctx["host_trace"]


def under(spans, name, lo, hi):
    """Seconds of ``[lo, hi]`` under the spans called ``name`` of a log
    ``(name, owner, start, end)``, a span inside another once."""
    merged = trace_reduce.union(
        [max(start, lo), min(end, hi)] for what, _, start, end in spans
        if what == name and min(end, hi) > max(start, lo))
    return trace_reduce.total(merged)


def window_ms_under(ctx, name):
    """ms of the window (``seen["start"]`` to ``seen["end"]``, the
    log's own clock) under the log's spans called ``name``; None where
    the program has no pulse to write them."""
    from horovod_tpu.utils import compile_cache
    try:
        importlib.import_module("horovod_tpu.utils.pulse")
    except ImportError:
        return None
    seen = ctx["seen"]
    return 1e3 * under(compile_cache.spans(), name, seen["start"],
                       seen["end"])
