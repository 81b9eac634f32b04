"""The host while the step runs (``utils/pulse.py``; docs/tracing.md):
the pulse with its clock and its sleep handed in, the collector's hook,
the bound on what the log keeps of both, and the sinks that read the
log. No test here rests on how long a real sleep takes."""

import gc
import json
import logging
import threading
import time

import pytest

import horovod_tpu as hvd
from horovod_tpu import telemetry
from horovod_tpu.timeline import Timeline
from horovod_tpu.utils import compile_cache, pulse
from horovod_tpu.utils.logging_util import get_logger

MS = 1_000_000      # the pulse's clock counts nanoseconds


@pytest.fixture
def metrics(monkeypatch):
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    telemetry.reset()
    yield lambda: {
        name: {tuple(s["labels"].values()): s["value"]
               for s in family["samples"]}
        for name, family in telemetry.snapshot()["families"].items()}
    monkeypatch.delenv("HOROVOD_TPU_METRICS")
    telemetry.reset()


@pytest.fixture
def warnings():
    said = []
    handler = logging.Handler(level=logging.WARNING)
    handler.emit = lambda record: said.append(record.getMessage())
    get_logger().addHandler(handler)
    yield said
    get_logger().removeHandler(handler)


def scripted(wakeups):
    """A clock and a sleep for ``Pulse``: the clock reads ``wakeups``
    (ns) one after another, the first when the pulse starts, and the
    sleep ends the pulse when none is left."""
    readings = iter(wakeups)
    left = [len(wakeups) - 1]

    def sleep(seconds):
        assert seconds == pulse.PERIOD
        left[0] -= 1
        return left[0] < 0
    return (lambda: next(readings)), sleep


def steady():
    return [s for s in compile_cache.spans() if s[0] in compile_cache.STEADY]


def test_a_late_wakeup_over_the_threshold_is_one_span_from_due_to_came(
        fresh_log):
    # Started at 1 s; wake-ups 1 ms late, then 40 ms late (under the
    # threshold), then 300 ms late, then on time.
    clock, sleep = scripted([1000 * MS, 1021 * MS, 1081 * MS, 1401 * MS,
                             1421 * MS])
    pulse.Pulse(clock, sleep).run()
    (name, owner, start, end), = steady()
    assert (name, owner) == ("host_pause", "pulse")
    # Due a period after the wake-up before it; came at 1.401.
    assert start == pytest.approx(1.081 + pulse.PERIOD)
    assert end == pytest.approx(1.401)


def test_a_wakeup_at_the_threshold_is_no_span(fresh_log):
    late = int((pulse.PERIOD + pulse.LATE) * 1e9)
    clock, sleep = scripted([0, late, 2 * late, 3 * late])
    pulse.Pulse(clock, sleep).run()
    assert steady() == []


def test_a_clock_that_jumps_leaves_one_span_one_warning_and_the_counters(
        fresh_log, metrics, warnings):
    clock, sleep = scripted([0, 20 * MS, 1770 * MS, 1790 * MS,
                             3790 * MS, 3810 * MS])
    sleeps = []

    def collecting(seconds):    # a collection inside the second sleep
        sleeps.append(seconds)
        if len(sleeps) == 2:
            beating.collector.ns[2] += 410 * MS
        return sleep(seconds)
    beating = pulse.Pulse(clock, collecting)
    beating.run()
    first, second = steady()
    assert first == ("host_pause", "pulse", pytest.approx(0.040),
                     pytest.approx(1.770))
    assert second[3] - second[2] == pytest.approx(1.980)
    # One WARNING a minute, and it says whether the collector ran.
    line, = warnings
    assert line == ("host paused 1.73 s: the pulse came late; collector: "
                    "gen2 0.41 s inside it")
    families = metrics()
    assert families["hvd_host_pauses_total"] == {(): 2}
    assert families["hvd_host_pause_seconds_total"][()] == pytest.approx(
        1.73 + 1.98)
    assert families["hvd_host_pause_longest_seconds"][()] == pytest.approx(
        1.98)
    assert families["hvd_gc_seconds_total"] == {
        ("gen0",): 0, ("gen1",): 0, ("gen2",): pytest.approx(0.41)}


def test_the_warning_says_none_and_comes_again_after_its_interval(
        fresh_log, warnings, monkeypatch):
    monkeypatch.setattr(pulse, "WARN_EVERY", 2.0)
    clock, sleep = scripted([0, 1500 * MS, 3000 * MS, 4500 * MS])
    pulse.Pulse(clock, sleep).run()
    assert len(steady()) == 3       # the second within 2 s of the first
    assert warnings == [
        "host paused 1.48 s: the pulse came late; collector: none",
        "host paused 1.48 s: the pulse came late; collector: none"]


def test_the_log_is_silent_about_a_pause_with_metrics_off(fresh_log):
    telemetry.reset()
    clock, sleep = scripted([0, 200 * MS])
    pulse.Pulse(clock, sleep).run()
    assert len(steady()) == 1
    assert telemetry.registry().families() == {}


def test_a_thread_that_keeps_the_gil_is_a_pause(fresh_log):
    """The real thread and the real clock: the main thread holds the
    interpreter in one C call for longer than period and threshold."""
    beating = pulse.Pulse().start()
    try:
        started = time.perf_counter()
        sum(range(20_000_000))      # releases nothing until it ends
    finally:
        beating.stop()              # joins: the wake-up has come
    pauses = [s for s in steady() if s[0] == "host_pause"]
    assert pauses and all(s[1] == "pulse" for s in pauses)
    assert all(started <= s[2] < s[3] <= time.perf_counter() for s in pauses)
    assert max(s[3] - s[2] for s in pauses) > pulse.LATE


def test_init_starts_one_pulse_and_shutdown_ends_it():
    def threads():
        return [t for t in threading.enumerate()
                if t.name == "hvd-tpu-pulse"]
    was = hvd.is_initialized()
    hvd.shutdown()
    try:
        assert threads() == [] and pulse.running() is None
        assert not any(isinstance(c, pulse.Collector) for c in gc.callbacks)
        hvd.init()
        first = pulse.running()
        hvd.init()          # idempotent: no second runtime, no second pulse
        assert pulse.running() is first and len(threads()) == 1
        assert threads()[0].daemon
        assert gc.callbacks.count(first.collector) == 1
        # An elastic reset keeps the one it has.
        with pulse.kept():
            hvd.shutdown()
        hvd.init()
        assert pulse.running() is first and len(threads()) == 1
        hvd.shutdown()
        assert threads() == [] and pulse.running() is None
        assert first.collector not in gc.callbacks
    finally:
        if was:
            hvd.init()


def test_an_elastic_reset_goes_through_kept(monkeypatch):
    from horovod_tpu import basics, elastic
    inside = []
    monkeypatch.setattr(basics, "shutdown",
                        lambda: inside.append(pulse._keeping))
    monkeypatch.setattr(basics, "init", lambda: inside.append(pulse._keeping))
    elastic._reset()
    assert inside == [1, 0] and pulse._keeping == 0


def collector_at(readings):
    times = iter(readings)
    return pulse.Collector(lambda: next(times))


def test_the_collector_adds_seconds_by_generation_and_keeps_the_long_ones():
    hook = collector_at([0, MS // 2, 10 * MS, 13 * MS, 20 * MS, 21 * MS])
    for generation in (0, 2, 1):
        hook("start", {"generation": generation})
        hook("stop", {"generation": generation, "collected": 0,
                      "uncollectable": 0})
    assert hook.ns == [MS // 2, MS, 3 * MS]
    # Half a millisecond is no span; one at the threshold is.
    assert list(hook.long) == [(2, 10 * MS, 13 * MS), (1, 20 * MS, 21 * MS)]
    # A stop without its start (registered inside a collection) is none.
    hook("stop", {"generation": 0})
    assert hook.ns == [MS // 2, MS, 3 * MS]


def test_gc_collect_moves_the_total_and_a_span_only_over_the_threshold(
        fresh_log, monkeypatch):
    beating = pulse.Pulse(sleep=lambda seconds: True)
    gc.callbacks.append(beating.collector)
    try:
        monkeypatch.setattr(pulse, "GC_SPAN", 3600.0)
        gc.collect()
        first = beating.collector.ns[2]
        assert first > 0
        gc.collect()
        assert beating.collector.ns[2] > first
        assert beating.collected()[2] == pytest.approx(
            beating.collector.ns[2] / 1e9)
        assert [s for s in steady() if s[0] == "gc"] == []
        monkeypatch.setattr(pulse, "GC_SPAN", 0.0)
        before = time.perf_counter()
        gc.collect()
        # The span waits for the pulse's next wake-up.
        assert [s for s in steady() if s[0] == "gc"] == []
        beating.collected()
    finally:
        gc.callbacks.remove(beating.collector)
    spans = [s for s in steady() if s[:2] == ("gc", "gen2")]
    assert spans and all(
        before <= start <= end <= time.perf_counter()
        for _, _, start, end in spans)


def test_the_log_keeps_the_newest_of_the_steady_spans_in_their_places(
        fresh_log):
    kept = compile_cache.STEADY_KEPT
    compile_cache.record("import", "first", 0.0, 0.5)
    for k in range(kept + 10):
        compile_cache.record("gc" if k % 2 else "host_pause", "pulse",
                             1.0 + k, 1.5 + k)
        if k == kept:
            compile_cache.record("trace", "between", 0.0, 0.25, 0.25)
    compile_cache.record("import", "last", 5000.0, 5000.5)
    spans = compile_cache.spans()
    assert len(spans) == kept + 3
    assert spans[0][1] == "first" and spans[-1][1] == "last"
    # The oldest ten went; the rest in arrival order round the others.
    starts = [s[2] for s in spans if s[0] in compile_cache.STEADY]
    assert starts == [1.0 + k for k in range(10, kept + 10)]
    assert spans.index(("trace", "between", 0.0, 0.25)) == 1 + kept - 9
    # The compile pipeline's view holds none of them.
    assert [e[0] for e in compile_cache.events()] == ["trace"]
    # A follower that comes later is handed the same list.
    seen = []
    compile_cache.follow(lambda *span: seen.append(span))
    assert seen == spans


def test_the_timeline_writes_both_names_under_hvd_host(tmp_path, fresh_log):
    compile_cache.record("import", "horovod_tpu", 0.5, 1.0)
    compile_cache.record("host_pause", "pulse", 2.0, 2.25)    # before it
    timeline = Timeline(str(tmp_path / "trace.json"))
    timeline.start()
    compile_cache.record("gc", "gen2", 3.0, 3.5)              # as it arrives
    timeline.stop()
    with open(timeline.shard_path) as f:
        written = [e for e in json.load(f) if e["ph"] == "X"]
    assert [(e["name"], e["cat"], e["args"]["owner"], e["ts"], e["dur"])
            for e in written] == [
        ("import", "hvd_startup", "horovod_tpu", 500_000, 500_000),
        ("host_pause", "hvd_host", "pulse", 2_000_000, 250_000),
        ("gc", "hvd_host", "gen2", 3_000_000, 500_000)]
    assert len({e["tid"] for e in written}) == 3
