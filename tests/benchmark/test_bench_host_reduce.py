"""``benchmark/host_reduce.py`` and the six readers of layer ``host`` on
a hand-made trace (plain data, as ``load_xplane`` gives it) and a
hand-made log; ``benchmark/tools/host_gaps.py`` on the same."""

import json
import os
import sys

import pytest

from benchmark import harness, host_reduce, layers
from benchmark.tools import host_gaps, stall_hunt

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000
# The profiler's clock starts near 0; ``perf_counter`` read 5000 s then
# and runs 1 us a pulse ahead of it.
AHEAD = 5000 * 1000 * MS
DRIFT = 1000

READERS = ["host_pause_ms", "gc_ms", "step_device_ms", "step_device_ms_max",
           "step_gap_ms_max", "idle_in_host_pause_pct"]


def ms(*values):
    return [int(v * MS) for v in values]


# A window of 2 s: two steps back to back, a third launched 301 ms late
# while the host stood still (no pulse from 1000 to 1250 ms, the
# collector inside it), a fourth that ends after the window.
STEPS = [ms(-600, 500), ms(3, 497), ms(500.02, 498.98), ms(1300, 500),
         ms(1800.01, 300)]
BUSY = [ms(3, 500), ms(500.02, 999), ms(1300, 1800), ms(1800.01, 2000)]
WAKEUPS = [20 * k for k in range(51)] + [1250 + 20 * k for k in range(38)]
PULSES = [[at * MS, at * MS + AHEAD + DRIFT * k]
          for k, at in enumerate(WAKEUPS)]
TRACE = {
    "host": [["bench:window", 0, 2000 * MS],
             ["bench:wait_loss", *ms(999, 301)],
             ["hvd:gc", *ms(1240, 50)]]
    + [["hvd:pulse", at, 2000] for at, _ in PULSES],
    "pulses": PULSES,
    "steps": STEPS,
}
IDLE_MS = 3 + 0.02 + 301 + 0.01


def perf(at_ms, k):
    """``perf_counter`` seconds at ``at_ms`` of the trace, by pulse
    ``k``'s offset."""
    return (at_ms * MS + AHEAD + DRIFT * k) / 1e9


def test_the_pairs_place_a_log_span_on_the_traces_clock():
    # The collection of 1240-1290 ms as the log holds it, stamped near
    # pulse 50 (1000 ms) and pulse 51 (1250 ms): each end lands within
    # the drift between two pulses of where the profiler's own
    # ``hvd:gc`` event lies.
    start, end = perf(1240, 51), perf(1290, 53)
    (_, at, dur), = [e for e in TRACE["host"] if e[0] == "hvd:gc"]
    assert host_reduce.place(PULSES, start) == pytest.approx(at, abs=1)
    assert host_reduce.place(PULSES, end) == pytest.approx(at + dur, abs=1)
    # Placed by the nearest pair, not by the first: 88 us of drift lie
    # between the trace's two ends.
    assert host_reduce.place(PULSES[:1], end) - (at + dur) == pytest.approx(
        53 * DRIFT, abs=1)
    assert host_reduce.place([], start) is None


def test_the_steps_inside_the_window_give_median_longest_and_longest_gap():
    reduced = host_reduce.reduce(TRACE, BUSY)
    assert reduced["step_ms"] == pytest.approx([497, 498.98, 500])
    assert reduced["gap_ms"] == pytest.approx([0.02, 301])


def test_idle_under_a_gap_between_pulses_or_a_collection():
    # Due at 1020, came at 1250; the collector ran on to 1290.
    assert host_reduce.host_pauses(TRACE) == [ms(1020, 1290)]
    reduced = host_reduce.reduce(TRACE, BUSY)
    assert reduced["idle_in_host_pause_pct"] == pytest.approx(
        100 * 270 / IDLE_MS)
    # Pulses on time and no collection: nothing overlaps.
    quiet = dict(TRACE, host=TRACE["host"][:2], pulses=[
        [20 * k * MS, 20 * k * MS + AHEAD] for k in range(101)])
    assert host_reduce.reduce(quiet, BUSY)["idle_in_host_pause_pct"] == 0
    # A gap of period + threshold exactly is no pause.
    edge = dict(quiet, pulses=[[0, AHEAD], [70 * MS, AHEAD + 70 * MS]])
    assert host_reduce.host_pauses(edge) == []


def test_a_trace_without_a_window_or_a_step_reads_nothing():
    empty = host_reduce.reduce({"host": [], "pulses": [], "steps": []})
    assert empty == {"step_ms": [], "gap_ms": [],
                     "idle_in_host_pause_pct": None}
    # Without the harness's span the window is the steps' extent.
    bare = host_reduce.reduce({"host": [], "pulses": [], "steps": STEPS})
    assert len(bare["step_ms"]) == 5 and len(bare["gap_ms"]) == 4


# The log on ``perf_counter`` seconds; the window is 5000.0 to 5002.0.
LOG = [
    ("host_pause", "pulse", 4990.0, 4990.2),            # before the window
    ("gc", "gen2", 4999.99, 5000.01),                   # across its start
    ("import", "horovod_tpu", 5000.5, 5001.5),          # neither name
    ("host_pause", "pulse", 5001.02, 5001.25),
    ("gc", "gen1", 5001.25, 5001.26),                   # inside the next
    ("gc", "gen2", 5001.24, 5001.29),
    ("host_pause", "pulse", 5001.9, 5002.3),            # across its end
]
EXPECTED = {
    "host_pause_ms": 230 + 100,
    "gc_ms": 10 + 50,
    "step_device_ms": 498.98,
    "step_device_ms_max": 500,
    "step_gap_ms_max": 301,
    "idle_in_host_pause_pct": 100 * 270 / IDLE_MS,
}


def reader(name):
    return harness.load_module(REPO, f"benchmark/layer_metrics/{name}.py")


@pytest.fixture
def ctx(monkeypatch):
    from horovod_tpu.utils import compile_cache
    monkeypatch.setattr(compile_cache, "spans", lambda: list(LOG))
    loads = []
    monkeypatch.setattr(host_reduce, "load_xplane",
                        lambda trace_dir: loads.append(trace_dir) or TRACE)
    context = layers.Context({
        "seen": {"start": 5000.0, "end": 5002.0}, "trace_dir": "somewhere",
        "trace": {"devices": {"0": {"busy": BUSY}, "1": {"busy": []}}}})
    context.loads = loads
    return context


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_hand_made_trace_and_log(ctx, name):
    assert reader(name).read(ctx) == pytest.approx(EXPECTED[name])


def test_the_readers_share_one_reduction(ctx):
    for name in READERS:
        reader(name).read(ctx)
    assert ctx.loads == ["somewhere"]


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_program_without_a_pulse(ctx, monkeypatch, name):
    # The parent's program: no ``utils/pulse.py``, no ``hvd:pulse`` in
    # the trace. The chip's side reads as before.
    monkeypatch.setitem(sys.modules, "horovod_tpu.utils.pulse", None)
    monkeypatch.setattr(host_reduce, "load_xplane", lambda trace_dir: dict(
        TRACE, host=TRACE["host"][:2], pulses=[]))
    value = reader(name).read(ctx)
    if name.startswith("step_"):
        assert value == pytest.approx(EXPECTED[name])
    else:
        assert value is None


def test_host_gaps_names_a_gap_for_the_innermost_event_of_any_name():
    trace = {"devices": {"0": [["fusion.1 fusion", a, b - a]
                               for a, b in BUSY]},
             "host": TRACE["host"][:2]}
    events = [
        ["bench:window", 0, 2000 * MS, "python3"],
        ["AllocateRawBuffer", *ms(0.5, 2.4), "main"],
        ["DeferredTpuAllocator::Allocate", *ms(1, 1), "main"],
        ["bench:wait_loss", *ms(999, 301), "python3"],
        ["ReadSyncFlag", *ms(1100, 100), "futex"],
    ]
    found = host_gaps.gaps(trace, events, top=3)
    assert [(round(offset, 2), round(length, 2), [e[0] for e in open_])
            for offset, length, open_ in found] == [
        (999, 301, ["ReadSyncFlag", "bench:wait_loss"]),
        (0, 3, ["DeferredTpuAllocator::Allocate", "AllocateRawBuffer"]),
        (500, 0.02, [])]


def test_stall_hunt_places_the_window_on_the_timelines_clock(
        tmp_path, monkeypatch):
    # The process started at 100 s of ``perf_counter``; ``setup_s`` 40.
    def event(name, cat, owner, start, seconds):
        return {"name": name, "cat": cat, "ph": "X", "ts": int(start * 1e6),
                "dur": int(seconds * 1e6), "pid": 0, "tid": 1,
                "args": {"owner": owner}}
    timeline = tmp_path / "timeline.json"
    timeline.write_text(json.dumps([
        event("before_program", "hvd_startup", "horovod_tpu", 100.0, 8.5),
        event("gc", "hvd_host", "gen2", 108.75, 0.5),        # half alone
        event("trace", "hvd_startup", "hvd_train_step", 109.0, 20.0),
        event("gc", "hvd_host", "gen2", 120.0, 0.5),         # inside it
        event("host_pause", "hvd_host", "pulse", 150.0, 1.75),
        event("gc", "hvd_host", "gen2", 150.25, 0.5),
        event("host_pause", "hvd_host", "pulse", 171.0, 0.25),  # after it
        {"name": "cycle", "ph": "i", "ts": 1, "pid": 0, "tid": 1, "s": "g"},
    ]))
    result = {"correct": True, "metrics": {"setup_s": {"value": 40.0}}}
    monkeypatch.setattr(stall_hunt.run_sets, "one", lambda *run: {
        "seed": run[1], "rc": 0, "result": result,
        "notes": ["compared ...", "window 30.012 s, 150 steps completed"]})
    record = stall_hunt.hunt("cell", 7, 30, str(timeline))
    assert os.environ.pop("HOROVOD_TIMELINE") == str(timeline)
    assert not timeline.exists() and record["window_s"] == 30.012
    assert [e[:2] + [round(e[2], 3), e[3]] for e in stall_hunt.inside(
        record)] == [["host_pause", "pulse", 10.0, 1.75],
                     ["gc", "gen2", 10.25, 0.5]]
    assert len(record["host_events"]) == 5
    # What ``setup_unnamed_s`` loses: 108.75-109, under no other span.
    assert record["host_alone_before_s"] == pytest.approx(0.25)
