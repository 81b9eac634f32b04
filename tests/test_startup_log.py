"""The program's log from the process's start (``utils/compile_cache.py``;
docs/tracing.md "From the process's start to the first step"): spans
with owners, the older ``events()`` view of the same list, the gauge
and the timeline that read it."""

import json
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import horovod_tpu as hvd
import horovod_tpu.jax as hvd_jax
from horovod_tpu import telemetry
from horovod_tpu.timeline import Timeline
from horovod_tpu.utils import compile_cache

NAMES = {"before_program", "import", "init", "trace", "lower",
         "backend_compile", "cache_load", "cache_hit", "cache_miss",
         *compile_cache.STEADY}


def startup_spans():
    """The log without what the steady state leaves (``host_pause``,
    ``gc``: a loaded test worker does pause; tests/test_pulse.py)."""
    return [s for s in compile_cache.spans()
            if s[0] not in compile_cache.STEADY]


def _step(offset):
    """A new function jitted under the step's name."""
    def body(v):
        return jnp.tanh(v) * 3.0 + offset
    body.__name__ = body.__qualname__ = hvd_jax.STEP_NAME
    return jax.jit(body)


def test_a_program_owns_the_spans_of_its_compilation():
    compile_cache.listen()
    step, x = _step(0.375), jnp.arange(6.0)
    n, before = len(startup_spans()), time.perf_counter()
    step(x).block_until_ready()
    after = time.perf_counter()
    new = startup_spans()[n:]
    mine = {name: (start, end) for name, owner, start, end in new
            if owner == compile_cache.STEP_NAME}
    assert sorted(mine) == ["backend_compile", "lower", "trace"]
    # One after another, each start before its end, on this clock.
    flat = [t for name in ("trace", "lower", "backend_compile")
            for t in mine[name]]
    assert flat == sorted(flat) and before <= flat[0] and flat[-1] <= after
    # The functions traced inside the step's trace are spans of their
    # own owners, inside it.
    inner = [(owner, start, end) for name, owner, start, end in new
             if name == "trace" and owner != compile_cache.STEP_NAME
             and start >= mine["trace"][0]]
    assert "tanh" in {owner for owner, _, _ in inner}
    assert all(end <= mine["trace"][1] for _, _, end in inner)
    # Arrival order is the order the spans ended in.
    ends = [end for _, _, _, end in new]
    assert ends == sorted(ends)
    assert {name for name, _, _, _ in compile_cache.spans()} <= NAMES
    # A second call compiles nothing and leaves nothing.
    n = len(startup_spans())
    step(x).block_until_ready()
    assert startup_spans()[n:] == []
    # A copy: the caller cannot edit the log.
    compile_cache.spans().clear()
    assert len(startup_spans()) == n


def test_events_is_a_view_of_the_same_log():
    compile_cache.listen()
    n, m = len(startup_spans()), len(compile_cache.events())
    _step(0.625)(jnp.arange(3.0)).block_until_ready()
    spans, events = startup_spans()[n:], compile_cache.events()[m:]
    # What it gave: (phase, seconds, arrival), the arrival the span's
    # end and the seconds what JAX sent, the span's length.
    assert [(name, end) for name, _, _, end in spans] == [
        (phase, at) for phase, _, at in events]
    for (_, _, start, end), (_, seconds, _) in zip(spans, events):
        assert seconds >= 0 and end - start == pytest.approx(seconds)


def test_the_cache_entries_take_the_owner_of_the_compilation_around_them(
        fresh_log):
    compile_cache.record("backend_compile", "first", 1.0, 2.0, 1.0)
    compile_cache._on_event(compile_cache._HIT)
    compile_cache._on_seconds(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    assert [owner for _, owner, _, _ in startup_spans()] == [
        "first", None, None]
    at = time.perf_counter()
    compile_cache._on_seconds(
        "/jax/core/compile/backend_compile_duration", at - 2.5,
        fun_name="jit(hvd_train_step)")
    assert [(name, owner) for name, owner, _, _ in startup_spans()] == [
        ("backend_compile", "first"), ("cache_hit", "hvd_train_step"),
        ("cache_load", "hvd_train_step"),
        ("backend_compile", "hvd_train_step")]
    assert [phase for phase, _, _ in compile_cache.events()] == [
        "backend_compile", "cache_hit", "cache_load", "backend_compile"]
    # Inside a backend_compile they add nothing to its stage.
    seconds = compile_cache.startup_seconds()
    assert seconds["other_programs"] == 1.0
    assert seconds["step_backend"] == pytest.approx(at - 2.5)


def test_importing_the_package_left_its_spans():
    spans = compile_cache.spans()
    imports = {owner: (start, end) for name, owner, start, end in spans
               if name == "import"}
    assert {"horovod_tpu", "horovod_tpu.ops", "horovod_tpu.jax"} <= set(
        imports)
    first = min(start for start, _ in imports.values())
    assert first == imports["horovod_tpu"][0]
    # The packages imported while the package was lie inside its span.
    assert imports["horovod_tpu"][0] <= imports["horovod_tpu.ops"][0]
    assert imports["horovod_tpu.ops"][1] <= imports["horovod_tpu"][1]
    before = [s for s in spans if s[0] == "before_program"]
    if sys.platform.startswith("linux"):
        (_, owner, start, end), = before
        assert owner == "horovod_tpu" and start < end == first
    else:
        assert len(before) <= 1


def test_init_is_one_span_a_runtime_and_the_first_says_the_split(fresh_log):
    import logging
    from horovod_tpu.utils.logging_util import get_logger
    said = []
    handler = logging.Handler(level=logging.INFO)
    handler.emit = lambda record: said.append(record.getMessage())
    logger, level = get_logger(), get_logger().level
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    was = hvd.is_initialized()
    hvd.shutdown()
    compile_cache.record("import", "horovod_tpu", 1.0, 3.5)
    try:
        hvd.init()
        hvd.init()      # idempotent: no second runtime, no second span
        (_, owner, start, end), = [
            s for s in compile_cache.spans() if s[0] == "init"]
        assert owner == "horovod_tpu" and start < end
        hvd.shutdown()
        hvd.init()      # an elastic reset's: a span, no second line
        assert [s[0] for s in compile_cache.spans()].count("init") == 2
        line, = [text for text in said if "start-up so far" in text]
        assert "import 2.50 s, init 0." in line
    finally:
        logger.removeHandler(handler)
        logger.setLevel(level)
        if not was:
            hvd.shutdown()


def test_other_programs_are_counted_outside_the_steps_spans(fresh_log):
    record, step = compile_cache.record, compile_cache.STEP_NAME
    record("backend_compile", "weights", 0.0, 1.0, 1.0)
    record("trace", "tanh", 2.0, 2.5, 0.5)      # inside the step's trace
    record("trace", "norm", 1.75, 3.0, 1.25)    # begun before it
    record("trace", step, 2.0, 4.0, 2.0)
    record("trace", "kernel", 4.25, 4.5, 0.25)  # inside its lowering
    record("lower", step, 4.0, 5.0, 1.0)
    record("import", "horovod_tpu.models", 5.0, 5.5)
    assert compile_cache.startup_seconds() == {
        "before_program": 0.0, "import": 0.5, "init": 0.0,
        "step_trace": 2.0, "step_lower": 1.0, "step_backend": 0.0,
        "other_programs": 1.25}


def test_union_counts_a_span_inside_another_once():
    union = compile_cache._Union()
    union.add(1.0, 2.0)         # inside the next, arrives first
    union.add(0.5, 3.0)
    union.add(4.0, 5.0)
    union.add(4.5, 5.5)         # overlaps the last
    assert union.parts == [(0.5, 3.0), (4.0, 4.5), (4.5, 5.5)]
    assert union.seconds == pytest.approx(4.0)
    union.add(4.75, 5.25)       # another thread's, ended before the last
    assert union.seconds == pytest.approx(4.0)
    union.cut(4.25)
    assert union.seconds == pytest.approx(2.75)
    union.cut(0.0)
    assert union.parts == [] and union.seconds == pytest.approx(0.0)


def test_startup_seconds_are_in_the_registry_with_metrics_on(monkeypatch):
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    telemetry.reset()
    try:
        compile_cache.listen()
        _step(0.875)(jnp.arange(5.0)).block_until_ready()
        family = telemetry.snapshot()["families"]["hvd_startup_seconds"]
        seen = {s["labels"]["stage"]: s["value"] for s in family["samples"]}
        # The stages that moved since the registry was made, at what
        # the log's own account gives.
        assert set(seen) == {"step_trace", "step_lower", "step_backend",
                             "other_programs"} < set(compile_cache.STAGES)
        seconds = compile_cache.startup_seconds()
        assert seen == {stage: seconds[stage] for stage in seen}
        assert all(seen[stage] > 0 for stage in seen
                   if stage.startswith("step_"))
        assert seconds["import"] > 0 and seconds["init"] >= 0
    finally:
        monkeypatch.delenv("HOROVOD_TPU_METRICS")
        telemetry.reset()


def test_the_log_is_silent_with_metrics_off():
    telemetry.reset()
    compile_cache.listen()
    _step(1.125)(jnp.arange(2.0)).block_until_ready()
    compile_cache.imported("horovod_tpu.test", time.perf_counter())
    assert telemetry.registry().families() == {}


def test_a_timeline_started_later_holds_the_spans(tmp_path):
    compile_cache.listen()
    _step(1.375)(jnp.arange(7.0)).block_until_ready()
    n = len(startup_spans())
    timeline = Timeline(str(tmp_path / "trace.json"))
    timeline.start()
    _step(1.625)(jnp.arange(7.0)).block_until_ready()
    timeline.stop()
    after_stop = len(startup_spans())
    _step(1.875)(jnp.arange(7.0)).block_until_ready()   # not followed
    with open(timeline.shard_path) as f:
        written = [e for e in json.load(f)
                   if e["ph"] == "X" and e["cat"] == "hvd_startup"]
    spans = [s for s in startup_spans()[:after_stop]
             if s[1] is not None]
    assert len(written) == len(spans) > n
    for event, (name, owner, start, end) in zip(written, spans):
        assert (event["name"], event["args"]["owner"]) == (name, owner)
        assert event["ts"] == int(start * 1e6)
        assert event["dur"] == int((end - start) * 1e6)
    # One row an owner, and the first span the process's start.
    rows = {e["args"]["owner"]: e["tid"] for e in written}
    assert all(rows[e["args"]["owner"]] == e["tid"] for e in written)
    if sys.platform.startswith("linux"):
        assert min(written, key=lambda e: e["ts"])["name"] == "before_program"
