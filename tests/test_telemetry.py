"""telemetry/: metrics core, spans, exposition, aggregation, /metrics
route, hvd-metrics CLI, and the timeline flush/stop fixes.

The disabled path is a load-bearing contract (near-zero cost, nothing
accumulates), so it gets its own guard tests against the session
runtime; the enabled path runs end-to-end in a fresh subprocess (the
session fixture initializes without HOROVOD_TPU_METRICS).
"""

import json
import os
import subprocess
import sys
import threading
import time
import types
import urllib.error
import urllib.request

import jax  # noqa: F401  (backend pinned to the CPU mesh by conftest)
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import clean_spawn_env
from horovod_tpu import telemetry
from horovod_tpu.telemetry import aggregate, core, exposition
from horovod_tpu.runner.http_server import AUTH_HEADER, KVStoreServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def metrics_on(monkeypatch):
    """Force-enable the metrics plane for one test, on a fresh registry;
    restore the disabled default (and a clean registry) afterwards."""
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    telemetry.reset()
    assert telemetry.enabled()
    yield telemetry
    monkeypatch.delenv("HOROVOD_TPU_METRICS", raising=False)
    telemetry.reset()


# ==========================================================================
# Core: histogram bucket edges, label plumbing, registry semantics
# ==========================================================================
class TestHistogramBuckets:
    def test_bucket_boundary_edges(self, metrics_on):
        h = telemetry.histogram("hvd_test_edges", buckets=[1.0, 2.0, 4.0])
        child = h.labels()
        for v in (0.0, 0.5, 1.0):     # le="1" is inclusive
            child.observe(v)
        child.observe(1.0000001)      # first value past an edge
        child.observe(4.0)            # exactly the last finite bound
        child.observe(4.1)            # overflows into +Inf
        buckets = dict(child.bucket_counts())
        assert buckets[1.0] == 3
        assert buckets[2.0] == 4      # cumulative
        assert buckets[4.0] == 5
        assert buckets[float("inf")] == 6
        assert child.count == 6
        assert child.sum == pytest.approx(0.5 + 1.0 + 1.0000001 + 4.0
                                          + 4.1)

    def test_log_buckets_cover_range(self):
        bounds = core.log_buckets(1e-5, 80.0)
        assert bounds[0] == 1e-5
        assert bounds[-1] >= 80.0
        ratios = [b / a for a, b in zip(bounds, bounds[1:])]
        assert all(r == pytest.approx(2.0) for r in ratios)

    def test_labels_and_registry_reuse(self, metrics_on):
        c1 = telemetry.counter("hvd_test_ops", labelnames=("kind",))
        c2 = telemetry.counter("hvd_test_ops", labelnames=("kind",))
        assert c1 is c2  # get-or-create across modules
        c1.labels(kind="a").inc(2)
        c1.labels(kind="b").inc()
        sample_values = {s["labels"]["kind"]: s["value"]
                         for s in c1.samples()}
        assert sample_values == {"a": 2, "b": 1}
        with pytest.raises(ValueError):
            telemetry.counter("hvd_test_ops", labelnames=("other",))
        with pytest.raises(ValueError):
            c1.labels(wrong="x")


# ==========================================================================
# Exposition: Prometheus v0.0.4 golden text
# ==========================================================================
GOLDEN = """\
# HELP hvd_test_depth Depth
# TYPE hvd_test_depth gauge
hvd_test_depth 2.5
# HELP hvd_test_lat_seconds Lat
# TYPE hvd_test_lat_seconds histogram
hvd_test_lat_seconds_bucket{le="0.1"} 1
hvd_test_lat_seconds_bucket{le="1"} 1
hvd_test_lat_seconds_bucket{le="+Inf"} 2
hvd_test_lat_seconds_sum 5.05
hvd_test_lat_seconds_count 2
# HELP hvd_test_ops_total Ops
# TYPE hvd_test_ops_total counter
hvd_test_ops_total{kind="allreduce"} 3
"""


def test_prometheus_exposition_golden():
    reg = core.Registry()
    reg.gauge("hvd_test_depth", "Depth").set(2.5)
    h = reg.histogram("hvd_test_lat_seconds", "Lat", buckets=[0.1, 1.0])
    h.observe(0.05)
    h.observe(5.0)
    reg.counter("hvd_test_ops_total", "Ops",
                labelnames=("kind",)).labels(kind="allreduce").inc(3)
    assert exposition.render_prometheus(reg.snapshot()) == GOLDEN


def test_prometheus_label_escaping_and_parse():
    reg = core.Registry()
    g = reg.gauge("hvd_test_esc", labelnames=("path",))
    g.labels(path='a"b\\c\nd').set(1)
    text = exposition.render_prometheus(reg.snapshot())
    assert '{path="a\\"b\\\\c\\nd"}' in text
    parsed = exposition.parse_prometheus(text)
    assert list(parsed) == ["hvd_test_esc"]
    assert list(parsed["hvd_test_esc"].values()) == [1.0]


# ==========================================================================
# Spans
# ==========================================================================
class _FakeTimeline:
    def __init__(self):
        self.events = []

    def begin(self, names, activity):
        self.events.append(("B", tuple(names), activity))

    def end(self, names, activity):
        self.events.append(("E", tuple(names), activity))


def test_span_feeds_histogram_and_timeline():
    reg = core.Registry()
    hist = reg.histogram("hvd_test_span_seconds", buckets=[10.0])
    tl = _FakeTimeline()
    with telemetry.span(["x", "y"], "ACT", timeline=tl, histogram=hist):
        pass
    assert tl.events == [("B", ("x", "y"), "ACT"),
                         ("E", ("x", "y"), "ACT")]
    assert hist.labels().count == 1


def test_span_failure_leaves_timeline_open_but_observes():
    reg = core.Registry()
    hist = reg.histogram("hvd_test_span_fail_seconds", buckets=[10.0])
    tl = _FakeTimeline()
    with pytest.raises(RuntimeError):
        with telemetry.span(["x"], "ACT", timeline=tl, histogram=hist):
            raise RuntimeError("boom")
    assert tl.events == [("B", ("x",), "ACT")]  # no end on failure
    assert hist.labels().count == 1

def test_span_null_when_both_sinks_absent():
    assert telemetry.span(["a"], "X") is telemetry.NULL_SPAN
    assert telemetry.span(["a"], "X",
                          histogram=telemetry.NULL) is telemetry.NULL_SPAN
    assert telemetry.span(
        ["a"], "X", timeline=_FakeTimeline()) is not telemetry.NULL_SPAN


# ==========================================================================
# Disabled mode: the no-op guard (acceptance criterion)
# ==========================================================================
class TestDisabledGuard:
    def test_factories_return_shared_null(self, hvd):
        assert not telemetry.enabled()
        c = telemetry.counter("hvd_guard_should_not_exist")
        assert c is telemetry.NULL
        assert c.labels(kind="x") is telemetry.NULL
        c.inc()
        c.observe(1.0)
        c.set(2.0)
        assert telemetry.registry().families() == {}

    def test_hot_path_accumulates_nothing(self, hvd, n_devices):
        import horovod_tpu.basics as basics
        coord = basics.runtime().coordinator
        assert coord._m_cycle_s is telemetry.NULL
        assert coord._metrics_on is False
        out = hvd.allreduce(jnp.ones((n_devices, 2)), op=hvd.Sum,
                            name="telemetry.guard.allreduce")
        assert np.asarray(out).shape == (n_devices, 2)
        assert telemetry.registry().families() == {}
        snap = hvd.metrics_snapshot()
        assert snap["families"] == {}
        assert snap["rank"] == hvd.rank()


# ==========================================================================
# Cluster aggregation
# ==========================================================================
def _counter_snap(value):
    return {"ts": 0.0, "families": {"hvd_x_total": {
        "type": "counter", "help": "x", "labelnames": [],
        "samples": [{"labels": {}, "value": value}]}}}


def test_quantile_from_buckets():
    buckets = [(1.0, 50), (2.0, 90), (4.0, 100), (float("inf"), 100)]
    assert aggregate.quantile_from_buckets(buckets, 0.50) == 1.0
    assert aggregate.quantile_from_buckets(buckets, 0.95) == 4.0
    assert aggregate.quantile_from_buckets(buckets, 0.99) == 4.0
    assert aggregate.quantile_from_buckets([], 0.99) == 0.0


def test_scalar_rollup_min_max_mean():
    rolled = aggregate.aggregate({0: _counter_snap(1.0),
                                  1: _counter_snap(3.0)})
    fam = rolled["families"]["hvd_x_total_cluster"]
    stats = {s["labels"]["stat"]: s["value"] for s in fam["samples"]}
    assert stats == {"min": 1.0, "max": 3.0, "mean": 2.0, "sum": 4.0}
    text = exposition.render_prometheus(rolled)
    assert 'hvd_x_total_cluster{stat="mean"} 2' in text


def test_histogram_rollup_merges_buckets():
    def snap(cum):
        return {"ts": 0.0, "families": {"hvd_h_seconds": {
            "type": "histogram", "help": "", "labelnames": [],
            "samples": [{"labels": {}, "sum": 1.0, "count": cum[-1][1],
                         "buckets": cum}]}}}
    rolled = aggregate.aggregate({
        0: snap([[1.0, 90], [float("inf"), 100]]),
        1: snap([[1.0, 100], [float("inf"), 100]])})
    fam = rolled["families"]["hvd_h_seconds_cluster"]
    stats = {s["labels"]["stat"]: s["value"] for s in fam["samples"]}
    assert stats["count"] == 200
    assert stats["p50"] == 1.0
    # p99 target (198 of 200) falls in +Inf: reported as the last
    # finite bound, not infinity.
    assert stats["p99"] == pytest.approx(1.0)


def test_push_and_scrape_store(metrics_on):
    telemetry.counter("hvd_push_total").inc(7)
    srv = KVStoreServer(job_token="tok")
    port = srv.start()
    try:
        aggregate.push_snapshot("127.0.0.1", port, "tok", 3)
        snaps = aggregate.store_snapshots(srv)
        assert 3 in snaps
        value = snaps[3]["families"]["hvd_push_total"]["samples"][0]
        assert value["value"] == 7
    finally:
        srv.stop()


# ==========================================================================
# /metrics route (auth + content)
# ==========================================================================
def _get(port, path, token=None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
    if token:
        req.add_header(AUTH_HEADER, token)
    return urllib.request.urlopen(req, timeout=10)


class TestMetricsRoute:
    def test_token_required(self):
        srv = KVStoreServer(job_token="s3cret")
        port = srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(port, "/metrics")
            assert err.value.code == 403
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(port, "/metrics.json", token="wrong")
            assert err.value.code == 403
        finally:
            srv.stop()

    def test_route_serves_prometheus_and_rollup(self, metrics_on):
        telemetry.counter("hvd_route_total").inc(5)
        srv = KVStoreServer(job_token="tok")
        port = srv.start()
        try:
            with _get(port, "/metrics", token="tok") as resp:
                assert resp.headers["Content-Type"].startswith(
                    "text/plain; version=0.0.4")
                text = resp.read().decode()
            parsed = exposition.parse_prometheus(text)
            assert parsed["hvd_route_total"] == {(): 5.0}
            # pushed rank snapshots appear as the cluster roll-up
            aggregate.push_snapshot("127.0.0.1", port, "tok", 0)
            aggregate.push_snapshot("127.0.0.1", port, "tok", 1)
            with _get(port, "/metrics", token="tok") as resp:
                text = resp.read().decode()
            assert 'hvd_route_total_cluster{stat="mean"}' in text
            with _get(port, "/metrics.json", token="tok") as resp:
                payload = json.loads(resp.read())
            assert sorted(payload["ranks"]) == ["0", "1"]
            assert "hvd_route_total" in payload["local"]["families"]
        finally:
            srv.stop()


# ==========================================================================
# End-to-end: coordinator/backend/elastic/autotune families on the CPU
# backend, snapshot + exposition, HVDTPU_METRICS_DUMP (fresh process —
# the session runtime initialized with metrics off)
# ==========================================================================
E2E_SCRIPT = """
import json, sys
import horovod_tpu as hvd
import jax, jax.numpy as jnp
hvd.init()
n = len(jax.devices())
for i in range(4):
    hvd.allreduce(jnp.ones((n, 8)), op=hvd.Sum, name=f"m.{i}")
hvd.allgather(jnp.ones((n, 2)), name="m.ag")
hvd.broadcast(jnp.ones((n, 2)), root_rank=0, name="m.bc")
import horovod_tpu.elastic as elastic
state = elastic.ObjectState(step=1)
state.commit()
snap = hvd.metrics_snapshot()
from horovod_tpu import telemetry
text = telemetry.render_prometheus(snap)
assert telemetry.parse_prometheus(text), "unparseable exposition"
print("FAMILIES=" + json.dumps(sorted(snap["families"])))
hvd.shutdown()
print("E2E-OK")
"""


def test_e2e_counters_cpu_backend(tmp_path):
    dump = tmp_path / "metrics.json"
    env = clean_spawn_env(
        PYTHONPATH=REPO,
        HOROVOD_TPU_METRICS="1",
        HVDTPU_AUTOTUNE="1",
        HVDTPU_METRICS_DUMP=str(dump),
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    proc = subprocess.run([sys.executable, "-c", E2E_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "E2E-OK" in proc.stdout
    families = json.loads(
        proc.stdout.split("FAMILIES=")[1].splitlines()[0])
    # Every instrumented layer reports (acceptance criterion).
    for needle in ("hvd_coordinator_ops_total",
                   "hvd_coordinator_cycle_seconds",
                   "hvd_coordinator_fused_bytes_total",
                   "hvd_backend_collective_seconds",
                   "hvd_backend_collective_bytes_total",
                   "hvd_elastic_commits_total",
                   "hvd_autotune_fusion_threshold_bytes",
                   "hvd_autotune_cycle_time_ms"):
        assert needle in families, (needle, families)
    # Shutdown wrote the HVDTPU_METRICS_DUMP snapshot.
    dumped = json.loads(dump.read_text())
    assert "hvd_coordinator_ops_total" in dumped["families"]
    ops = {s["labels"]["kind"]: s["value"]
           for s in dumped["families"]
           ["hvd_coordinator_ops_total"]["samples"]}
    assert ops.get("allreduce", 0) >= 4
    assert ops.get("allgather", 0) >= 1
    assert ops.get("broadcast", 0) >= 1
    eff = dumped["families"]["hvd_coordinator_fusion_efficiency"]
    assert 0.0 < eff["samples"][0]["value"] <= 1.0


# ==========================================================================
# hvd-metrics CLI
# ==========================================================================
def _run_cli(*args):
    env = clean_spawn_env(PYTHONPATH=REPO)
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.telemetry.cli", *args],
        env=env, capture_output=True, text=True, timeout=120)


def test_cli_dump_and_diff(tmp_path):
    before = tmp_path / "a.json"
    after = tmp_path / "b.json"
    before.write_text(json.dumps(_counter_snap(3.0)))
    after.write_text(json.dumps(_counter_snap(10.0)))
    dump = _run_cli("dump", str(before))
    assert dump.returncode == 0, dump.stderr
    assert "hvd_x_total 3" in dump.stdout
    dump_json = _run_cli("dump", str(before), "--format", "json")
    assert json.loads(dump_json.stdout)["families"]
    diff = _run_cli("diff", str(before), str(after))
    assert diff.returncode == 0, diff.stderr
    assert "(+7)" in diff.stdout
    assert "1 series changed" in diff.stdout


def test_cli_usage_errors():
    assert _run_cli("dump").returncode == 2
    assert _run_cli("dump", "/nonexistent.json").returncode == 2


# ==========================================================================
# Timeline satellites: flush once per drain, race-free stop
# ==========================================================================
def _own_events(path):
    """The events the test wrote: the program's log's spans, which every
    timeline begins with and follows (categories ``hvd_startup`` and
    ``hvd_host``, docs/tracing.md), are ``tests/test_startup_log.py``'s
    and ``tests/test_pulse.py``'s."""
    return [e for e in json.loads(path.read_text())
            if e.get("cat") not in ("hvd_startup", "hvd_host")]


def test_timeline_flushes_once_per_drain(tmp_path):
    from horovod_tpu.timeline import Timeline
    path = tmp_path / "trace.json"
    tl = Timeline(str(path))
    hold = threading.Event()
    first = [True]
    orig = tl._emit_item

    def gated(file, item, *rest):
        if first[0]:
            first[0] = False
            hold.wait(10)
        orig(file, item, *rest)

    tl._emit_item = gated
    tl.start()
    flushes = [0]
    orig_flush = tl._file.flush

    def counting_flush():
        flushes[0] += 1
        orig_flush()

    tl._file.flush = counting_flush
    for i in range(100):
        tl.marker(f"m{i}")
    hold.set()
    tl.stop()
    events = _own_events(path)
    assert len(events) == 100
    # One drain (plus at most a straggler) — not one flush per event.
    assert flushes[0] <= 3, flushes[0]


def test_timeline_stop_race_free_when_join_times_out(tmp_path):
    """stop() must NOT close the file while the writer is still
    draining (the pre-fix ValueError-on-closed-file race); the writer
    closes it after the sentinel."""
    from horovod_tpu.timeline import Timeline
    path = tmp_path / "trace.json"
    tl = Timeline(str(path))
    hold = threading.Event()
    orig = tl._emit_item

    def blocked(file, item, *rest):
        hold.wait(10)
        orig(file, item, *rest)

    tl._emit_item = blocked
    tl.start()
    tl.marker("m0")
    time.sleep(0.05)  # writer is now blocked inside _emit_item
    real_thread = tl._thread
    tl._thread = types.SimpleNamespace(join=lambda timeout=None: None)
    tl.stop()  # simulated join timeout: returns with the writer alive
    assert not tl._file.closed
    hold.set()
    real_thread.join(5)
    assert tl._file.closed
    events = _own_events(path)
    assert [e["name"] for e in events] == ["m0"]


def test_timeline_restart_while_old_writer_straggles(tmp_path):
    """A start() after a timed-out stop() gets a FRESH queue and file:
    the straggling writer keeps its own queue/file (finishing cleanly)
    and cannot steal the new session's events, sentinel, or comma
    placement."""
    from horovod_tpu.timeline import Timeline
    old_path = tmp_path / "old.json"
    tl = Timeline(str(old_path))
    hold = threading.Event()
    orig = tl._emit_item

    def blocked(file, item, *rest):
        hold.wait(10)
        orig(file, item, *rest)

    tl._emit_item = blocked
    tl.start()
    tl.marker("old0")
    time.sleep(0.05)
    old_thread = tl._thread
    tl._thread = types.SimpleNamespace(join=lambda timeout=None: None)
    tl.stop()  # old writer still blocked; its sentinel is queued

    tl.path = str(tmp_path / "new.json")
    tl._emit_item = orig  # new session writes unblocked
    tl.start()
    for i in range(3):
        tl.marker(f"new{i}")
    hold.set()           # let the straggler finish its own session
    old_thread.join(5)
    tl.stop()
    old_events = _own_events(old_path)
    assert [e["name"] for e in old_events] == ["old0"]
    new_events = _own_events(tmp_path / "new.json")
    assert [e["name"] for e in new_events] == ["new0", "new1", "new2"]
