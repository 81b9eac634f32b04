"""From a profiler trace to numbers: device busy time, time by class of
operation, exposed collective time, idle gaps and what the host was
doing in them.

A trace, once loaded, is plain data (and so a small one can be kept as a
test fixture):

    {"devices": {"0": [[name, start_ns, dur_ns], ...], ...},
     "host": [[name, start_ns, dur_ns], ...]}

``devices`` holds the operations that ran on each chip (the "XLA Ops"
line of its plane); ``host`` the spans the benchmark's loop wrote with
``jax.profiler.TraceAnnotation`` (names start with ``bench:``).
"""

import glob
import os
import re

HOST_PREFIX = "bench:"
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
# Pallas kernels reach the device as Mosaic custom calls. The program
# gives them no name of their own yet (PERF.md, Open questions).
KERNEL = "custom-call:tpu_custom_call"


def short_name(text):
    """The trace names a device operation by its whole HLO line. Keep
    the instruction's name and its opcode: ``attn.102
    custom-call:tpu_custom_call``, ``fusion.7 fusion``."""
    lhs, eq, rest = text.partition(" = ")
    if not eq:
        return text
    found = re.search(r" ([a-z][a-z0-9_\-]*)\(", " " + rest)
    op = found.group(1) if found else "unknown"
    if op == "custom-call":
        target = re.search(r'custom_call_target="([^"]+)"', rest)
        op += ":" + (target.group(1) if target else "")
    return f"{lhs.lstrip('%')} {op}"


def family(name):
    """``attn.102 custom-call:...`` -> ``attn custom-call:...``: the
    operations a step repeats under numbered names, as one."""
    lhs, _, op = name.partition(" ")
    return (re.sub(r"\.\d+$", "", lhs) + " " + op).strip()


def load_xplane(trace_dir):
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    trace = {"devices": {}, "host": []}
    for plane in data.planes:
        match = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if match and line.name in (OPS_LINE, ASYNC_LINE):
                # Of the asynchronous line only collectives in flight
                # are kept: the rest (copies, slices) ride along with
                # operations of the main line.
                events = [[short_name(e.name), int(e.start_ns),
                           int(e.duration_ns)] for e in line.events]
                if line.name == ASYNC_LINE:
                    events = [e for e in events
                              if classify(e[0]) == "collective"]
                trace["devices"].setdefault(match.group(1), []).extend(
                    events)
            elif not match:
                trace["host"].extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX))
    return trace


def classify(name):
    lhs, _, op = name.partition(" ")
    op = op or lhs.lstrip("%")
    if op.startswith(COLLECTIVES):
        return "collective"
    return "kernel" if op == KERNEL else "xla"


def window_of(trace):
    """(start, end) of the measured window: the ``bench:window`` span,
    or else the extent of the device operations."""
    spans = [e for e in trace["host"] if e[0] == HOST_PREFIX + "window"]
    if spans:
        name, start, dur = max(spans, key=lambda e: e[2])
        return start, start + dur
    events = [e for ops in trace["devices"].values() for e in ops]
    return (min(e[1] for e in events), max(e[1] + e[2] for e in events))


def clip(events, window):
    lo, hi = window
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


def union(intervals):
    """Merged, sorted (start, end) pairs."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """The part of ``intervals`` (merged) not covered by ``holes``
    (merged)."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append([cur, holes[k][0]])
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def self_times(events):
    """(name, self_ns) per event: its duration less that of the events
    nested inside it, so that an enclosing ``while`` or ``call`` does
    not count its body twice."""
    out, stack = [], []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            stack[-1][2][1] -= min(dur, stack[-1][1] - start)
        entry = [name, dur]
        out.append(entry)
        stack.append((name, start + dur, entry))
    return [(name, max(ns, 0)) for name, ns in out]


def collective_intervals(events):
    """Intervals in which a collective is in flight: a synchronous
    operation's own extent, or from an ``-start`` to its ``-done``."""
    starts, out = {}, []
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        if classify(name) != "collective":
            continue
        base = name.partition(" ")[0].lstrip("%")
        pair = re.fullmatch(r"(.+)-(start|done)(\.\d+)?", base)
        if pair and pair.group(2) == "start":
            starts[(pair.group(1), pair.group(3))] = start
            out.append([start, start + dur])
        elif pair and (pair.group(1), pair.group(3)) in starts:
            out.append([starts.pop((pair.group(1), pair.group(3))),
                        start + dur])
        else:
            out.append([start, start + dur])
    return union(out)


def reduce_device(events, window):
    """Numbers of one chip inside ``window`` (all in nanoseconds)."""
    events = clip(events, window)
    busy = union([e[1], e[1] + e[2]] for e in events)
    by_class = {"kernel": 0, "collective": 0, "xla": 0}
    by_name = {}
    for name, ns in self_times(events):
        by_class[classify(name)] += ns
        by_name[family(name)] = by_name.get(family(name), 0) + ns
    flight = collective_intervals(events)
    others = union([e[1], e[1] + e[2]] for e in events
                   if classify(e[0]) != "collective")
    return {"busy_ns": total(busy), "busy": busy, "by_class": by_class,
            "by_name": by_name, "collective_ns": total(flight),
            "collective_exposed_ns": total(subtract(flight, others))}


def idle_gaps(busy, window, host, top=10):
    """The longest idle gaps of a chip, each named for the benchmark's
    host span that was open at its middle."""
    gaps = subtract([list(window)], busy)
    spans = [e for e in host if e[0] != HOST_PREFIX + "window"]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        open_ = [e for e in spans if e[1] <= mid < e[1] + e[2]]
        name = (min(open_, key=lambda e: e[2])[0][len(HOST_PREFIX):]
                if open_ else "none")
        out.append([name, (b - a) / 1e9])
    return out


def reduce(trace):
    """All of the above for every chip of a trace."""
    window = window_of(trace)
    devices = {d: reduce_device(ops, window)
               for d, ops in sorted(trace["devices"].items(),
                                    key=lambda kv: int(kv[0]))}
    if not devices:
        raise ValueError("the trace holds no device operations")
    first = next(iter(devices.values()))
    names = sorted(first["by_name"].items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(d["busy_ns"] for d in devices.values())
        / len(devices) / 1e9,
        "devices": devices,
        "device_ops": [[n, ns / 1e9] for n, ns in names],
        "idle_gaps": idle_gaps(first["busy"], window, trace["host"]),
    }
