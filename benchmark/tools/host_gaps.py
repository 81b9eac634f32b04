"""The ten longest idle gaps of the first chip in a traced window, each
with the host events of any name that were open at its middle, the
innermost first: what the host was doing while the chip waited.

    python3 benchmark/tools/host_gaps.py chiprun_out/bench_trace/<cell>

``trace_reduce.idle_gaps`` names a gap for the harness's own span
(``bench:*``) and calls it ``none`` where none was open; the profiler
keeps the runtime's own host events too (``python_tracer_level`` 0
leaves them in), and this reads those. Also printed: the pulse's
wake-ups in the trace and the widest distance between two
(docs/tracing.md "The host while the step runs").
"""

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import host_reduce, trace_reduce  # noqa: E402

TOP = 10


def host_events(trace_dir):
    """Every event of every host thread: ``[name, start_ns, dur_ns,
    thread]``."""
    return [[e.name, int(e.start_ns), int(e.duration_ns), line.name]
            for plane in host_reduce.planes(trace_dir)
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def open_at(events, at):
    """The events open at ``at``, the window's own span left out, the
    innermost (shortest) first."""
    return sorted((e for e in events if e[1] <= at < e[1] + e[2]
                   and e[0] != trace_reduce.HOST_PREFIX + "window"),
                  key=lambda e: e[2])


def gaps(trace, events, top=TOP):
    """``[offset_ms, length_ms, events open at its middle]`` of the
    longest idle gaps of the first chip inside the window, longest
    first."""
    window = trace_reduce.window_of(trace)
    first = min(trace["devices"], key=int)
    busy = trace_reduce.reduce_device(trace["devices"][first],
                                      window)["busy"]
    idle = trace_reduce.subtract([list(window)], busy)
    return [[(a - window[0]) / 1e6, (b - a) / 1e6,
             open_at(events, (a + b) / 2)]
            for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:top]]


def main():
    trace_dir, = sys.argv[1:]
    trace = trace_reduce.load_xplane(trace_dir)
    events = host_events(trace_dir)
    for offset, length, open_ in gaps(trace, events):
        names = " < ".join(
            f"{re.sub(r'[ \t\n]+', ' ', name)[:60]} ({dur / 1e6:.3f} ms, "
            f"{thread})" for name, _, dur, thread in open_[:4])
        print(f"{length:10.4f} ms idle at {offset:10.3f} ms of the window: "
              f"{names or 'none'}")
    stamps = sorted(e[1] for e in events if e[0] == host_reduce.PULSE)
    widest = max((b - a for a, b in zip(stamps, stamps[1:])), default=0)
    print(f"{len(stamps)} pulses, the widest distance between two "
          f"{widest / 1e6:.3f} ms")


if __name__ == "__main__":
    main()
