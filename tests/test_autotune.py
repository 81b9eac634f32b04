"""Autotune tests (ISSUE 12; docs/autotune.md).

Unit-level knob sweep on a fake runtime + a whole-job SPMD run
observing convergence and cross-rank winner agreement (VERDICT round-1
item 8), extended for the trace-driven tuner package: per-arm
successive halving over every perf plane, the trace-derived steps/sec
score source, the persistent warm-start store (hit before the first
scored window, corrupt/stale degradation, elastic re-validation), the
cross-rank determinism pin under divergent rank-local scores, the
disabled-mode guard, the overlay, and the `hvd-autotune` CLI.
"""

import json
import logging
import os
import types

import numpy as np
import pytest

from test_spmd import launch

HERE = os.path.dirname(os.path.abspath(__file__))

MIB = 1024 * 1024


class _LogSpy(logging.Handler):
    """The horovod_tpu logger doesn't propagate (rank-prefixed handler
    of its own), so 'loud' contracts are pinned with a direct spy."""

    def __init__(self):
        super().__init__()
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())

    def grep(self, needle):
        return [m for m in self.messages if needle in m]


@pytest.fixture
def logspy():
    from horovod_tpu.utils.logging_util import get_logger
    log = get_logger()
    spy = _LogSpy()
    old_level = log.level
    log.addHandler(spy)
    log.setLevel(logging.INFO)
    yield spy
    log.removeHandler(spy)
    log.setLevel(old_level)


@pytest.fixture(autouse=True)
def _clean_overlay():
    """The overlay is process-global on purpose (construction-time
    readers); tests must not leak tuned values into each other."""
    from horovod_tpu.autotune import overlay
    overlay.clear()
    yield
    overlay.clear()


@pytest.fixture
def metrics_on(monkeypatch):
    from horovod_tpu.telemetry import core as telemetry
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    telemetry.reset()
    assert telemetry.enabled()
    yield telemetry
    monkeypatch.delenv("HOROVOD_TPU_METRICS", raising=False)
    telemetry.reset()


def _metric(name, labels=None):
    from horovod_tpu.telemetry import core as telemetry
    fam = (telemetry.snapshot().get("families") or {}).get(name)
    if not fam:
        return None
    for s in fam.get("samples") or []:
        if labels is None or (s.get("labels") or {}) == labels:
            return s.get("value")
    return None


class _FakeCore:
    def __init__(self):
        self.thresholds = []

    def set_fusion_threshold(self, v):
        self.thresholds.append(v)


def _fake_runtime():
    from horovod_tpu import basics
    coord = types.SimpleNamespace(bytes_processed=0, fusion_threshold=0,
                                  cycle_time_s=0.001)
    backend = types.SimpleNamespace(core=_FakeCore())
    rt = types.SimpleNamespace(mode=basics.MODE_SINGLE, coordinator=coord,
                               backend=backend, topology=None)
    return rt


def _drive(pm, rt, rates, max_cycles=2000):
    """Feed synthetic per-candidate byte rates until convergence; the
    currently applied candidate's rate drives the score."""
    observed = []
    for _ in range(max_cycles):
        cand = None
        if pm._pos >= 0:
            cand = pm._active[pm._pos]
        rt.coordinator.bytes_processed += rates.get(cand, 5)
        pm.record_cycle()
        observed.append((rt.coordinator.fusion_threshold,
                         rt.coordinator.cycle_time_s))
        if not pm.enabled:
            return observed
    raise AssertionError("did not converge")


def test_parameter_manager_halving_and_convergence(monkeypatch, tmp_path):
    monkeypatch.setenv("HVDTPU_AUTOTUNE_FUSION_CANDIDATES_MIB", "1,2")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CYCLE_CANDIDATES_MS", "0.5,1.0")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_WARMUP_CYCLES", "2")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CYCLES_PER_CANDIDATE", "8")
    log = tmp_path / "tune.log"
    monkeypatch.setenv("HVDTPU_AUTOTUNE_LOG", str(log))

    from horovod_tpu.autotune import ParameterManager
    rt = _fake_runtime()
    pm = ParameterManager(rt)
    assert len(pm._grid) == 4          # 2 fusion x 2 cycle x 1 bucket
    # 4 candidates -> 2 halving rounds; first-round budget 8 >> 1 = 4.
    assert pm._budget == 4

    # Candidate 2 (fusion=2MiB cycle=0.5ms) is the clear winner.
    observed = _drive(pm, rt, rates={0: 10, 1: 20, 2: 99, 3: 30})

    assert not pm.enabled, "did not converge"
    assert pm.best == (2 * 1024 * 1024, 0.5, None)
    # The sweep walked multiple candidates before converging.
    assert len(set(observed)) >= 3, set(observed)
    # Winner pushed into the native controller.
    assert rt.backend.core.thresholds[-1] == 2 * 1024 * 1024
    # Log has both rounds' scores with the starred winner; the loser half
    # appears only in round 0 (the successive-halving shape).
    content = log.read_text()
    assert "*" in content
    assert content.count("r0,") == 4
    assert content.count("r1,") == 2


def test_parameter_manager_tunes_delegated_bucket(monkeypatch):
    """With a delegated backend, the bucket knob joins the space and a
    small-tensor flood picks a non-default winner that is pushed to the
    backend (VERDICT r2 item 6)."""
    monkeypatch.setenv("HVDTPU_AUTOTUNE_FUSION_CANDIDATES_MIB", "1")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CYCLE_CANDIDATES_MS", "0.5")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_BUCKET_CANDIDATES", "256,65536")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_WARMUP_CYCLES", "1")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CYCLES_PER_CANDIDATE", "4")

    from horovod_tpu.autotune import ParameterManager
    rt = _fake_runtime()
    buckets = []
    rt.backend.set_min_bucket = buckets.append
    pm = ParameterManager(rt)
    assert len(pm._grid) == 2

    # The big-bucket candidate (index 1) wins the synthetic flood: fewer,
    # fuller launches -> higher bytes/sec.
    _drive(pm, rt, rates={0: 10, 1: 80})
    assert pm.best == (1024 * 1024, 0.5, 65536)
    assert buckets[-1] == 65536


def test_autotune_spmd_convergence():
    pytest.importorskip("jax")
    extra = {
        "HVDTPU_AUTOTUNE": "1",
        "HVDTPU_AUTOTUNE_FUSION_CANDIDATES_MIB": "1,4",
        "HVDTPU_AUTOTUNE_CYCLE_CANDIDATES_MS": "0.2,1.0",
        "HVDTPU_AUTOTUNE_WARMUP_CYCLES": "3",
        "HVDTPU_AUTOTUNE_CYCLES_PER_CANDIDATE": "4",
    }
    codes, outs = launch(2, script=os.path.join(HERE, "autotune_worker.py"),
                         extra_env=extra, timeout=300)
    for rank, (code, out) in enumerate(zip(codes, outs)):
        assert code == 0, f"rank {rank} failed (exit {code}):\n{out[-4000:]}"
        assert "AUTOTUNE OK" in out


# ==========================================================================
# Disabled-mode guard (the telemetry/chaos/guardian contract)
# ==========================================================================

def test_disabled_mode_guard(hvd, monkeypatch):
    """HVDTPU_AUTOTUNE unset: init never built a ParameterManager and
    the coordinator's per-cycle cost is the one None check. The
    sentinel half proves the call site is live (a dead guard would
    also 'pass'), the bomb half proves nothing constructs a tuner on
    the hot path."""
    from horovod_tpu import basics
    from horovod_tpu import autotune as autotune_mod
    import jax.numpy as jnp

    rt = basics.runtime()
    assert rt.autotuner is None, \
        "HVDTPU_AUTOTUNE unset must leave runtime.autotuner None"

    class _Bomb:
        def __init__(self, *a, **k):
            raise AssertionError("ParameterManager constructed with "
                                 "HVDTPU_AUTOTUNE unset")

    monkeypatch.setattr(autotune_mod, "ParameterManager", _Bomb)
    out = hvd.allreduce(jnp.ones(8), op=hvd.Sum, name="autotune.guard")
    np.testing.assert_allclose(np.asarray(out)[0], float(hvd.size()))

    calls = []
    sentinel = types.SimpleNamespace(
        record_cycle=lambda: calls.append(1), enabled=True)
    monkeypatch.setattr(rt, "autotuner", sentinel)
    out = hvd.allreduce(jnp.ones(8), op=hvd.Sum, name="autotune.guard2")
    np.testing.assert_allclose(np.asarray(out)[0], float(hvd.size()))
    assert calls, "record_cycle call site is dead — the guard test is vacuous"


# ==========================================================================
# Score sources (autotune/score.py)
# ==========================================================================

def _ring_runtime(events):
    flight = types.SimpleNamespace(snapshot=lambda: list(events))
    tracer = types.SimpleNamespace(_flight=flight)
    return types.SimpleNamespace(tracer=tracer)


def _step_events(n_steps, names=("grad.0", "grad.1"), t0=1.0, step_s=1.0,
                 flight_s=0.25):
    """n_steps complete occurrence groups: every name submits at the
    step start and finishes flight_s later."""
    events = []
    for occ in range(n_steps):
        base = t0 + occ * step_s
        for i, n in enumerate(names):
            events.append({"e": "sub", "n": n, "o": occ,
                           "t": base + 0.01 * i})
            events.append({"e": "fin", "n": n, "o": occ,
                           "t": base + 0.01 * i + flight_s})
    return events


def test_window_stats_counts_complete_steps():
    from horovod_tpu.autotune import score
    stats = score.window_stats(_step_events(3), 0.0, 100.0)
    assert stats["steps"] == 3
    # span = first submit (1.0) -> last finish (3.0 + 0.01 + 0.25).
    assert stats["span_s"] == pytest.approx(2.26, abs=1e-6)
    assert stats["mean_step_s"] == pytest.approx(0.26, abs=1e-6)
    # Two collectives per step in flight together: union 0.26 of 0.50
    # total in-flight seconds -> 48% of collective time was overlapped.
    assert stats["overlap_fraction"] == pytest.approx(0.48, abs=1e-6)


def test_window_stats_excludes_dirty_and_incomplete_groups():
    from horovod_tpu.autotune import score
    events = _step_events(2)
    # occurrence 2: a finish whose submit predates the window (fell off
    # the ring) FOLLOWED by a clean in-window pair of the same
    # occurrence -> the whole occurrence is dirty; it must not be
    # counted as a (shorter) step off the late pair alone.
    events.append({"e": "fin", "n": "grad.0", "o": 2, "t": 50.0})
    events.append({"e": "sub", "n": "grad.1", "o": 2, "t": 60.0})
    events.append({"e": "fin", "n": "grad.1", "o": 2, "t": 60.2})
    # occurrence 3: submitted but never finished -> open, excluded.
    events.append({"e": "sub", "n": "grad.0", "o": 3, "t": 70.0})
    # occurrence 4: completed but err-flagged -> a fast-FAILING
    # collective must not score as a fast step.
    events.append({"e": "sub", "n": "grad.0", "o": 4, "t": 80.0})
    events.append({"e": "fin", "n": "grad.0", "o": 4, "t": 80.01,
                   "err": 1})
    stats = score.window_stats(events, 0.0, 100.0)
    assert stats["steps"] == 2

    # Fewer than MIN_STEPS complete groups -> no step structure.
    assert score.window_stats(_step_events(1), 0.0, 100.0) is None
    assert score.window_stats([], 0.0, 100.0) is None


def test_trace_score_steps_per_sec_and_bytes_fallback(logspy):
    from horovod_tpu.autotune import score
    events = _step_events(4)
    ts = score.make_source(_ring_runtime(events), "auto")
    ts.open_window()
    ts._t0 = 0.0   # window covers the synthetic timestamps
    window = ts.close_window([7.0, 9.0])
    assert window["steps"] == pytest.approx(4 / 3.26, rel=1e-6)
    # The bytes rate always rides along: mixed-unit rounds decide on it.
    assert window["bytes"] == 8.0

    # No ring -> bytes-only window, quietly under auto.
    bs = score.make_source(types.SimpleNamespace(), "auto")
    bs.open_window()
    window = bs.close_window([7.0, 9.0])
    assert window == {"bytes": 8.0, "steps": None}
    assert not logspy.grep("falls back")

    # strict (=steps) falls back too, but loudly and only once.
    ss = score.make_source(types.SimpleNamespace(), "steps")
    for _ in range(2):
        ss.open_window()
        window = ss.close_window([1.0])
        assert window["steps"] is None
    assert len(logspy.grep("falls back")) == 1


def test_trace_score_straggler_delay_stretches_span(metrics_on):
    from horovod_tpu.autotune import score
    events = _step_events(4)
    gauge = metrics_on.gauge("hvd_straggler_delay_seconds",
                             "test", labelnames=("rank",))
    gauge.labels(rank="0").set(0.0)
    ts = score.TraceScore(_ring_runtime(events), rank=0)
    ts.open_window()
    ts._t0 = 0.0
    base = ts.close_window([])["steps"]
    # A live analyzer attributes 2s of new straggler delay to this
    # rank mid-window: the same local throughput must score worse.
    gauge.labels(rank="0").set(0.0)
    ts.open_window()
    ts._t0 = 0.0
    gauge.labels(rank="0").set(2.0)
    delayed = ts.close_window([])["steps"]
    assert delayed == pytest.approx(4 / (3.26 + 2.0), rel=1e-6)
    assert delayed < base
    # Window gauges published for /metrics debuggability.
    assert _metric("hvd_autotune_step_seconds") == pytest.approx(0.26,
                                                                 abs=1e-6)
    assert _metric("hvd_autotune_window_overlap_fraction") \
        == pytest.approx(0.48, abs=1e-6)


def test_make_source_rejects_unknown_mode():
    from horovod_tpu.autotune import score
    with pytest.raises(ValueError, match="HVDTPU_AUTOTUNE_SCORE"):
        score.make_source(types.SimpleNamespace(), "bayesian")


# ==========================================================================
# Warm-start store (autotune/store.py)
# ==========================================================================

def _entry(fusion=3 * MIB, cycle=2.0, score=42.0, version="0", **cfg):
    from horovod_tpu.autotune import store
    config = {k: None for k in store.CONFIG_KEYS}
    config.update(fusion_threshold=fusion, cycle_time_ms=cycle, **cfg)
    return store.make_entry(config, score, "steps", "sig", 1, "int8",
                            version, [("host", 0, "x", score)])


def test_store_roundtrip_and_clear(tmp_path):
    from horovod_tpu.autotune import store
    path = str(tmp_path / "cache.json")
    assert store.load(path) == {}           # first run is not an error
    store.save_entry(path, "k1", _entry())
    store.save_entry(path, "k2", _entry(fusion=MIB))
    entries = store.load(path)
    assert set(entries) == {"k1", "k2"}
    assert store.validate_entry(entries["k1"]) is None
    assert entries["k1"]["config"]["fusion_threshold"] == 3 * MIB
    assert store.clear(path, key="k1") == 1
    assert set(store.load(path)) == {"k2"}
    assert store.clear(path, key="nope") == 0
    assert store.clear(path) == 1
    assert not os.path.exists(path)
    assert store.clear(path) == 0


def test_store_rejects_corrupt_and_stale_files(tmp_path):
    from horovod_tpu.autotune import store
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(store.StoreError, match="cannot parse"):
        store.load(str(bad))
    bad.write_text(json.dumps({"entries": {}, "format": 99}))
    with pytest.raises(store.StoreError, match="format"):
        store.load(str(bad))
    bad.write_text(json.dumps({"format": store.FORMAT}))
    with pytest.raises(store.StoreError, match="entries"):
        store.load(str(bad))
    # save_entry over a corrupt file IS the repair.
    bad.write_text("{not json")
    store.save_entry(str(bad), "k", _entry())
    assert set(store.load(str(bad))) == {"k"}


def test_validate_entry_reasons():
    from horovod_tpu.autotune import store
    assert store.validate_entry([]) == "entry is not an object"
    assert store.validate_entry({}) == "no config object"
    assert "missing" in store.validate_entry({"config": {}})
    e = _entry()
    e["config"]["cycle_time_ms"] = "fast"
    assert "not numeric" in store.validate_entry(e)


def test_model_signature_and_key():
    from horovod_tpu.autotune import store
    sig = store.model_signature(["grad.1", "grad.0", "grad.1",
                                 "hvdlint.order", None])
    assert sig.startswith("m")
    # Order/duplicate independent; guard-internal ops excluded.
    assert sig == store.model_signature(["grad.0", "grad.1"])
    assert sig != store.model_signature(["grad.0"])
    assert store.model_signature([]) == "default"
    assert store.make_key(sig, 8, "int8+q") == f"{sig}|w8|int8+q"


# ==========================================================================
# Overlay (autotune/overlay.py)
# ==========================================================================

def test_overlay_set_get_generation():
    from horovod_tpu.autotune import overlay
    from horovod_tpu.utils import envparse
    g0 = overlay.generation()
    assert overlay.get_int(envparse.ZERO_BUCKET_BYTES) is None
    assert overlay.get_int(envparse.ZERO_BUCKET_BYTES, 7) == 7
    overlay.set_int(envparse.ZERO_BUCKET_BYTES, 4 * MIB)
    assert overlay.get_int(envparse.ZERO_BUCKET_BYTES, 7) == 4 * MIB
    assert overlay.generation() == g0 + 1
    assert overlay.snapshot() == {envparse.ZERO_BUCKET_BYTES: 4 * MIB}
    overlay.clear()
    assert overlay.get_int(envparse.ZERO_BUCKET_BYTES) is None
    assert overlay.generation() == g0 + 2


# ==========================================================================
# ParameterManager: arms, warm start, re-validation, determinism
# ==========================================================================

def _rt(mode=None, rank=0, size=1, overlap=False, compression=False,
        min_bucket=None):
    """Fake runtime rich enough for every arm; see _fake_runtime for
    the minimal legacy shape."""
    from horovod_tpu import basics
    coord = types.SimpleNamespace(bytes_processed=0, fusion_threshold=0,
                                  cycle_time_s=0.001)
    if overlap:
        coord._overlap = True
        coord._bucket_bytes = 4 * MIB
    if compression:
        coord._compression = types.SimpleNamespace(
            policy=types.SimpleNamespace(rules=[], threshold=1024),
            _delegated=False)
    backend = types.SimpleNamespace(core=_FakeCore())
    if min_bucket is not None:
        backend.min_bucket = min_bucket
        backend._buckets = []
        backend.set_min_bucket = backend._buckets.append
    topology = types.SimpleNamespace(rank=rank, size=size)
    return types.SimpleNamespace(
        mode=mode if mode is not None else basics.MODE_SINGLE,
        coordinator=coord, backend=backend, topology=topology, size=size)


def _drive_fn(pm, rt, rate_fn, max_cycles=4000):
    """Feed synthetic byte deltas from rate_fn(pm) until convergence."""
    for _ in range(max_cycles):
        rt.coordinator.bytes_processed += rate_fn(pm)
        pm.record_cycle()
        if not pm.enabled:
            return
    raise AssertionError(f"did not converge (phase={pm._phase})")


def _tiny_grid(monkeypatch, warmup=1, budget=2):
    monkeypatch.setenv("HVDTPU_AUTOTUNE_FUSION_CANDIDATES_MIB", "1,2")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CYCLE_CANDIDATES_MS", "0.5")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_WARMUP_CYCLES", str(warmup))
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CYCLES_PER_CANDIDATE", str(budget))


def test_warm_start_hit_applies_before_first_scored_window(
        monkeypatch, tmp_path, metrics_on, logspy):
    """A populated cache + unchanged elastic version: the stored winner
    is applied at the end of warmup — before any scoring window opens —
    and the sweep never runs."""
    from horovod_tpu.autotune import ParameterManager, store
    _tiny_grid(monkeypatch)
    cache = str(tmp_path / "cache.json")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CACHE", cache)
    monkeypatch.setenv("HVDTPU_AUTOTUNE_SIGNATURE", "sig-a")
    rt = _rt()
    key = store.make_key("sig-a", 1, store.codec_signature(rt))
    store.save_entry(cache, key, _entry(fusion=3 * MIB, cycle=2.0))

    pm = ParameterManager(rt)
    assert pm.enabled
    rt.coordinator.bytes_processed += 10
    pm.record_cycle()           # warmup cycle 1 of 1 -> warm decision

    assert not pm.enabled, "cache hit must skip the sweep entirely"
    assert pm._round_scores == {} and pm._history == [], \
        "no scored window may precede a warm start"
    assert rt.coordinator.fusion_threshold == 3 * MIB
    assert rt.coordinator.cycle_time_s == pytest.approx(0.002)
    assert pm.best == (3 * MIB, 2.0, None)
    assert pm.best_config["fusion_threshold"] == 3 * MIB
    assert pm.applied == [("host", f"{3 * MIB}/2.0/None")]
    assert _metric("hvd_autotune_warm_start_total",
                   {"outcome": "hit"}) == 1
    assert _metric("hvd_autotune_converged") == 1
    assert logspy.grep("warm start")


def test_warm_start_miss_and_unset_cache_sweep(monkeypatch, tmp_path,
                                               metrics_on):
    """No cache entry for the key (and separately: no cache path at
    all) -> the full sweep runs as before."""
    from horovod_tpu.autotune import ParameterManager
    _tiny_grid(monkeypatch)
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CACHE", str(tmp_path / "c.json"))
    monkeypatch.setenv("HVDTPU_AUTOTUNE_SIGNATURE", "sig-miss")
    rt = _rt()
    pm = ParameterManager(rt)
    _drive_fn(pm, rt, lambda p: 10)
    assert pm.best is not None
    assert _metric("hvd_autotune_warm_start_total",
                   {"outcome": "miss"}) == 1
    # Convergence persisted the winner for the NEXT run.
    from horovod_tpu.autotune import store
    key = store.make_key("sig-miss", 1, store.codec_signature(rt))
    assert store.load(str(tmp_path / "c.json"))[key]["config"][
        "fusion_threshold"] == pm.best[0]


def test_corrupt_cache_degrades_to_fresh_sweep_loudly(
        monkeypatch, tmp_path, metrics_on, logspy):
    from horovod_tpu.autotune import ParameterManager, store
    _tiny_grid(monkeypatch)
    cache = tmp_path / "cache.json"
    cache.write_text("{definitely not json")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CACHE", str(cache))
    monkeypatch.setenv("HVDTPU_AUTOTUNE_SIGNATURE", "sig-c")
    rt = _rt()
    pm = ParameterManager(rt)
    assert pm._store_corrupt
    assert logspy.grep("warm-start cache unusable")
    assert _metric("hvd_autotune_warm_start_total",
                   {"outcome": "corrupt"}) == 1
    _drive_fn(pm, rt, lambda p: 10)
    assert pm.best is not None
    # Convergence rewrote the corrupt file atomically (save = repair).
    key = store.make_key("sig-c", 1, store.codec_signature(rt))
    assert key in store.load(str(cache))


def test_stale_entry_degrades_to_fresh_sweep_loudly(
        monkeypatch, tmp_path, metrics_on, logspy):
    """A schema-valid file whose entry fails validation (missing config
    keys) is stale, not fatal: loud warning + full sweep."""
    from horovod_tpu.autotune import ParameterManager, store
    _tiny_grid(monkeypatch)
    cache = str(tmp_path / "cache.json")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CACHE", cache)
    monkeypatch.setenv("HVDTPU_AUTOTUNE_SIGNATURE", "sig-s")
    rt = _rt()
    key = store.make_key("sig-s", 1, store.codec_signature(rt))
    store.save_entry(cache, key, {"config": {"fusion_threshold": 1}})
    pm = ParameterManager(rt)
    rt.coordinator.bytes_processed += 10
    pm.record_cycle()
    assert pm.enabled and pm._phase == "sweep"
    assert logspy.grep("is stale")
    assert _metric("hvd_autotune_warm_start_total",
                   {"outcome": "stale"}) == 1


def test_elastic_bump_revalidates_and_keeps_winner(
        monkeypatch, tmp_path, metrics_on, logspy):
    """Entry cached under elastic version 0, job now at version 2:
    one baseline window + one warm window; the warm config keeps its
    crown on a tie (noise must not trigger a re-sweep) and the store
    is rewritten under the new version."""
    from horovod_tpu.autotune import ParameterManager, store
    _tiny_grid(monkeypatch)
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CONFIRM_CYCLES", "2")
    monkeypatch.setenv("HVDTPU_ELASTIC_VERSION", "2")
    cache = str(tmp_path / "cache.json")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CACHE", cache)
    monkeypatch.setenv("HVDTPU_AUTOTUNE_SIGNATURE", "sig-r")
    rt = _rt()
    key = store.make_key("sig-r", 1, store.codec_signature(rt))
    store.save_entry(cache, key, _entry(fusion=3 * MIB, cycle=2.0,
                                        version="0"))
    pm = ParameterManager(rt)
    phases = []

    def rate(p):
        phases.append(p._phase)
        return 10   # identical rate either side: a tie

    _drive_fn(pm, rt, rate)
    assert "confirm_base" in phases and "confirm_warm" in phases
    assert "sweep" not in phases, "a tie must not trigger a re-sweep"
    assert pm.best == (3 * MIB, 2.0, None)
    assert _metric("hvd_autotune_warm_start_total",
                   {"outcome": "revalidate"}) == 1
    assert _metric("hvd_autotune_warm_start_total",
                   {"outcome": "revalidated"}) == 1
    assert logspy.grep("re-validated")
    updated = store.load(cache)[key]
    assert updated["elastic_version"] == "2"
    # The original converged sweep's history survives the rewrite —
    # this session ran confirm windows, not a sweep.
    assert updated["history"] == [["host", 0, "x", 42.0]], updated


def test_elastic_bump_regression_triggers_full_resweep(
        monkeypatch, tmp_path, metrics_on, logspy):
    """The stored winner scores far below the baseline window under the
    new cohort -> loud regression + the full sweep re-runs (and its
    winner, not the stale one, is persisted)."""
    from horovod_tpu.autotune import ParameterManager, store
    _tiny_grid(monkeypatch)
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CONFIRM_CYCLES", "2")
    monkeypatch.setenv("HVDTPU_ELASTIC_VERSION", "3")
    cache = str(tmp_path / "cache.json")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CACHE", cache)
    monkeypatch.setenv("HVDTPU_AUTOTUNE_SIGNATURE", "sig-g")
    rt = _rt()
    key = store.make_key("sig-g", 1, store.codec_signature(rt))
    store.save_entry(cache, key, _entry(fusion=3 * MIB, cycle=2.0,
                                        version="0"))
    pm = ParameterManager(rt)

    def rate(p):
        if p._phase == "confirm_warm":
            return 1          # the stored winner tanks
        return 100

    _drive_fn(pm, rt, rate)
    assert logspy.grep("REGRESSED")
    assert _metric("hvd_autotune_warm_start_total",
                   {"outcome": "regressed"}) == 1
    # The sweep ran after the failed confirmation and its winner stuck
    # (grid fusion values are 1/2 MiB — never the stale 3 MiB).
    assert pm._history, "full re-sweep must have scored candidates"
    assert pm.best[0] in (MIB, 2 * MIB)
    assert store.load(cache)[key]["config"]["fusion_threshold"] \
        == pm.best[0]
    assert store.load(cache)[key]["elastic_version"] == "3"


def test_min_bucket_gauge_seeded_from_backend_reality(
        monkeypatch, metrics_on):
    """Satellite fix: a scrape before the first bucket candidate must
    show the backend's CURRENT min bucket (and every other seeded
    plane gauge), not 0."""
    from horovod_tpu.autotune import ParameterManager
    _tiny_grid(monkeypatch)
    rt = _rt(overlap=True, compression=True, min_bucket=4096)
    rt.coordinator.fusion_threshold = 7 * MIB
    rt.coordinator.cycle_time_s = 0.004
    ParameterManager(rt)
    assert _metric("hvd_autotune_min_bucket") == 4096
    assert _metric("hvd_autotune_fusion_threshold_bytes") == 7 * MIB
    assert _metric("hvd_autotune_cycle_time_ms") == pytest.approx(4.0)
    assert _metric("hvd_autotune_bucket_bytes") == 4 * MIB
    assert _metric("hvd_autotune_compression_codec",
                   {"codec": "none"}) == 1
    assert _metric("hvd_autotune_compression_threshold") == 1024


def test_multi_arm_sweep_tunes_every_plane(monkeypatch, tmp_path):
    """host -> overlap -> compression -> zero coordinate descent: each
    arm converges on the candidate its synthetic rates favor, winners
    land on the live objects / the overlay, and the history log names
    every arm."""
    from horovod_tpu import basics
    from horovod_tpu.autotune import ParameterManager, overlay
    from horovod_tpu.utils import envparse
    monkeypatch.setenv("HVDTPU_AUTOTUNE_FUSION_CANDIDATES_MIB", "1")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CYCLE_CANDIDATES_MS", "0.5")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_WARMUP_CYCLES", "1")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CYCLES_PER_CANDIDATE", "2")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_BUCKET_BYTES_CANDIDATES_MIB",
                       "1,4")
    # Space after the comma on purpose: grid parsing strips items.
    monkeypatch.setenv("HVDTPU_AUTOTUNE_COMPRESSION_CANDIDATES",
                       "none, int8")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_ZERO_BUCKET_CANDIDATES_MIB",
                       "1,4")
    monkeypatch.setenv("HVDTPU_ZERO", "1")
    log = tmp_path / "tune.log"
    monkeypatch.setenv("HVDTPU_AUTOTUNE_LOG", str(log))
    rt = _rt(mode=basics.MODE_SINGLE, overlap=True, compression=True)
    pm = ParameterManager(rt)
    assert [a.name for a in pm._arms] == ["host", "overlap",
                                          "compression", "zero"]

    # Favor: overlap idx 1 (4 MiB), compression idx 1 (int8@1024),
    # zero idx 1 (4 MiB). Single-candidate arms converge on their own.
    wins = {"overlap": 1, "compression": 1, "zero": 1}

    def rate(p):
        if p._pos < 0:
            return 5
        arm = p._arms[p._arm_idx]
        return 90 if p._active[p._pos] == wins.get(arm.name, 0) else 10

    _drive_fn(pm, rt, rate)
    assert set(pm._winners) == {"host", "overlap", "compression", "zero"}
    assert rt.coordinator._bucket_bytes == 4 * MIB
    assert overlay.get_int(envparse.ZERO_BUCKET_BYTES) == 4 * MIB
    assert pm._winners["compression"] == ("int8", 1024)
    assert rt.coordinator._compression.policy.rules == [("*", "int8")]
    assert pm.best_config["bucket_bytes"] == 4 * MIB
    assert pm.best_config["compression"] == "int8"
    assert pm.best_config["zero_bucket_bytes"] == 4 * MIB
    planes = {p for p, _ in pm.applied}
    assert planes == {"host", "overlap", "compression", "zero"}
    content = log.read_text()
    for arm in ("overlap", "compression", "zero"):
        assert f"{arm}=" in content, content


def _stub_source(pm, steps_by_cand):
    """Score-source stub: per-candidate steps value (None = the window
    saw no step structure), bytes riding along from the cycle rates."""
    class _Stub:
        name = "steps"

        def open_window(self):
            pass

        def close_window(self, rates):
            cand = pm._active[pm._pos]
            return {"bytes": sum(rates) / len(rates),
                    "steps": steps_by_cand.get(cand)}
    pm._source = _Stub()


def test_mixed_unit_round_decides_on_bytes(monkeypatch):
    """One candidate's windows fell back to bytes/sec: the round must
    compare EVERY candidate in bytes (which all windows carry) — a raw
    comparison would let any ~1e2 bytes rate beat any ~1e-3 steps rate
    regardless of actual step pacing."""
    from horovod_tpu.autotune import ParameterManager
    _tiny_grid(monkeypatch, warmup=1, budget=2)
    rt = _rt()
    pm = ParameterManager(rt)
    assert len(pm._grid) == 2
    # cand 0: no step structure, modest bytes. cand 1: tiny steps value
    # but DOUBLE the bytes rate.
    _stub_source(pm, {0: None, 1: 0.001})
    _drive_fn(pm, rt, lambda p: (100 if (p._pos >= 0
                                         and p._active[p._pos] == 1)
                                 else 50))
    assert pm._score_label == "bytes"
    assert pm.best == (2 * MIB, 0.5, None), \
        "mixed-unit round must decide on the common bytes unit"


def test_all_steps_round_decides_on_steps(monkeypatch):
    """Every window has step structure: steps/sec decides, even when
    the bytes rates disagree (the whole point of the trace score — a
    config that moves more bytes but finishes fewer steps loses)."""
    from horovod_tpu.autotune import ParameterManager
    _tiny_grid(monkeypatch, warmup=1, budget=2)
    rt = _rt()
    pm = ParameterManager(rt)
    # cand 0: more bytes, fewer steps. cand 1: fewer bytes, more steps.
    _stub_source(pm, {0: 5.0, 1: 9.0})
    _drive_fn(pm, rt, lambda p: (100 if (p._pos >= 0
                                         and p._active[p._pos] == 0)
                                 else 50))
    assert pm._score_label == "steps"
    assert pm.best == (2 * MIB, 0.5, None), \
        "steps/sec must out-vote the bytes proxy when available"


def test_apply_config_zero_overlay_respects_spmd_gate(monkeypatch):
    """A cached zero_bucket_bytes must obey the same single-controller
    gate the zero ARM does: in SPMD the per-process step loops would
    observe the overlay bump at different step indices and re-plan
    onto divergent shard geometries."""
    from horovod_tpu import basics
    from horovod_tpu.autotune import ParameterManager, overlay
    from horovod_tpu.utils import envparse
    _tiny_grid(monkeypatch)
    monkeypatch.setenv("HVDTPU_ZERO", "1")
    cfg = {"zero_bucket_bytes": 2 * MIB}

    rt0, rt1 = _spmd_pair()
    pm = ParameterManager(rt0)
    pm._apply_config(cfg)
    assert overlay.get_int(envparse.ZERO_BUCKET_BYTES) is None, \
        "SPMD warm start must not move the ZeRO overlay"

    pm = ParameterManager(_rt(mode=basics.MODE_SINGLE))
    pm._apply_config(cfg)
    assert overlay.get_int(envparse.ZERO_BUCKET_BYTES) == 2 * MIB


def test_apply_config_keeps_zero_compression_threshold(monkeypatch):
    """Threshold 0 (= compress everything) is a legitimate tuned value;
    the warm-start apply must not 'or' it away to the live plane's."""
    from horovod_tpu.autotune import ParameterManager
    _tiny_grid(monkeypatch)
    rt = _rt(compression=True)
    pm = ParameterManager(rt)
    pm._apply_config({"compression": "int8", "compression_threshold": 0})
    assert rt.coordinator._compression.policy.threshold == 0
    assert pm._current["compression_threshold"] == 0


def test_overlay_resolve_int_precedence(monkeypatch):
    """resolve_int: overlay > raw env > default — the one resolution
    every construction-time reader goes through."""
    from horovod_tpu.autotune import overlay
    from horovod_tpu.utils import envparse
    assert overlay.resolve_int(envparse.ZERO_BUCKET_BYTES, 7) == 7
    monkeypatch.setenv("HVDTPU_ZERO_BUCKET_BYTES", str(2 * MIB))
    assert overlay.resolve_int(envparse.ZERO_BUCKET_BYTES, 7) == 2 * MIB
    overlay.set_int(envparse.ZERO_BUCKET_BYTES, 4 * MIB)
    assert overlay.resolve_int(envparse.ZERO_BUCKET_BYTES, 7) == 4 * MIB


def test_compression_arm_dedupes_none_thresholds(monkeypatch):
    """'none' ignores the threshold; crossing it with every threshold
    would burn a scoring window per identical duplicate."""
    from horovod_tpu.autotune import ParameterManager
    _tiny_grid(monkeypatch)
    monkeypatch.setenv("HVDTPU_AUTOTUNE_COMPRESSION_CANDIDATES",
                       "none,int8")
    monkeypatch.setenv(
        "HVDTPU_AUTOTUNE_COMPRESSION_THRESHOLD_CANDIDATES",
        "1024,16384")
    rt = _rt(compression=True)
    pm = ParameterManager(rt)
    comp = {a.name: a for a in pm._arms}["compression"]
    assert comp.candidates == [("none", 1024), ("int8", 1024),
                               ("int8", 16384)]


def test_cli_clear_unwritable_path_exits_2(tmp_path, capsys,
                                           monkeypatch):
    """An unwritable store (OSError from remove/rename) is the
    documented exit-2 failure, not a traceback. Simulated via
    monkeypatch: the test process runs as root, where chmod can't
    produce a real EACCES."""
    from horovod_tpu.autotune import cli, store
    cache = str(tmp_path / "cache.json")
    store.save_entry(cache, "k", _entry())

    def boom(path, key=None):
        raise OSError(30, "Read-only file system", path)

    monkeypatch.setattr(cli.store, "clear", boom)
    assert _cli(["clear", "--cache", cache]) == 2
    assert "hvd-autotune:" in capsys.readouterr().err


def test_compression_arm_never_overwrites_per_glob_rules(monkeypatch):
    """A user policy with per-glob rules is not the tuner's to rewrite:
    no compression arm is built over it."""
    from horovod_tpu.autotune import ParameterManager
    _tiny_grid(monkeypatch)
    rt = _rt(compression=True)
    rt.coordinator._compression.policy.rules = [("emb.*", "int8"),
                                                ("*", "none")]
    pm = ParameterManager(rt)
    assert [a.name for a in pm._arms] == ["host"]


# ==========================================================================
# Cross-rank determinism (the acceptance pin)
# ==========================================================================

class _Chan:
    """Rank 0 -> rank 1 broadcast FIFO; lockstep driving keeps the
    send/receive order aligned the way the real data plane's
    negotiated cycles do."""

    def __init__(self):
        self.fifo = []

    def bind(self, rt, rank):
        def broadcast(tensors, root, process_set):
            assert root == 0
            if rank == 0:
                self.fifo.append([np.array(t, copy=True)
                                  for t in tensors])
                return tensors
            assert self.fifo, \
                "rank 1 reached a broadcast before rank 0 (lockstep broken)"
            return self.fifo.pop(0)
        rt.backend.broadcast = broadcast


def _spmd_pair(**kw):
    from horovod_tpu import basics
    chan = _Chan()
    rts = []
    for rank in (0, 1):
        rt = _rt(mode=basics.MODE_SPMD, rank=rank, size=2, **kw)
        chan.bind(rt, rank)
        rts.append(rt)
    return rts


def test_divergent_rank_local_scores_identical_applied_sequence(
        monkeypatch):
    """THE determinism pin: rank 1's local scores prefer the opposite
    corner of the grid, yet both ranks apply the identical knob
    sequence and converge on rank 0's winner (survivors broadcast at
    every round boundary)."""
    from horovod_tpu.autotune import ParameterManager
    monkeypatch.setenv("HVDTPU_AUTOTUNE_FUSION_CANDIDATES_MIB", "1,2")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CYCLE_CANDIDATES_MS", "0.5,1.0")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_WARMUP_CYCLES", "2")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CYCLES_PER_CANDIDATE", "4")
    rt0, rt1 = _spmd_pair()
    pm0, pm1 = ParameterManager(rt0), ParameterManager(rt1)
    rates0 = {0: 10, 1: 20, 2: 99, 3: 30}     # rank 0 prefers cand 2
    rates1 = {0: 99, 1: 30, 2: 10, 3: 20}     # rank 1 prefers cand 0

    for _ in range(2000):
        for pm, rt, rates in ((pm0, rt0, rates0), (pm1, rt1, rates1)):
            cand = pm._active[pm._pos] if pm._pos >= 0 else None
            rt.coordinator.bytes_processed += rates.get(cand, 5)
            pm.record_cycle()
        if not pm0.enabled and not pm1.enabled:
            break
    assert not pm0.enabled and not pm1.enabled, "did not converge"

    assert pm0.applied == pm1.applied, \
        "ranks diverged on the applied-knob sequence"
    assert len(pm0.applied) >= 4
    assert pm0.best == pm1.best == (2 * MIB, 0.5, None), \
        "rank 0's preference must win on both ranks"
    assert (rt1.coordinator.fusion_threshold
            == rt0.coordinator.fusion_threshold == 2 * MIB)


def test_divergent_cache_files_follow_rank0_warm_decision(
        monkeypatch, tmp_path, logspy):
    """SPMD warm start with per-host cache drift: rank 0 has a valid
    entry, rank 1's file is empty. Rank 0's decision AND config
    broadcast — both ranks warm-start identically instead of rank 1
    forking into a sweep (divergent phases = divergent collective
    schedules)."""
    from horovod_tpu.autotune import ParameterManager, store
    _tiny_grid(monkeypatch)
    monkeypatch.setenv("HVDTPU_AUTOTUNE_SIGNATURE", "sig-d")
    rt0, rt1 = _spmd_pair()
    cache0 = str(tmp_path / "cache.rank0.json")
    cache1 = str(tmp_path / "cache.rank1.json")   # never populated
    key = store.make_key("sig-d", 2, store.codec_signature(rt0))
    store.save_entry(cache0, key, _entry(fusion=3 * MIB, cycle=2.0))

    monkeypatch.setenv("HVDTPU_AUTOTUNE_CACHE", cache0)
    pm0 = ParameterManager(rt0)
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CACHE", cache1)
    pm1 = ParameterManager(rt1)

    for rt, pm in ((rt0, pm0), (rt1, pm1)):
        rt.coordinator.bytes_processed += 10
        pm.record_cycle()

    assert not pm0.enabled and not pm1.enabled
    assert pm0.applied == pm1.applied
    assert pm1.best == (3 * MIB, 2.0, None)
    assert rt1.coordinator.fusion_threshold == 3 * MIB
    # Rank 1 warm-started on the broadcast: its rank-LOCAL miss must
    # not be logged/counted as the run's outcome.
    assert not logspy.grep("no cache entry")


# ==========================================================================
# Knob registry
# ==========================================================================

def test_autotune_knobs_registered():
    from horovod_tpu.utils import envparse
    for name in ("AUTOTUNE_CACHE", "AUTOTUNE_SIGNATURE",
                 "AUTOTUNE_SCORE", "AUTOTUNE_CONFIRM_CYCLES",
                 "AUTOTUNE_BUCKET_BYTES_CANDIDATES_MIB",
                 "AUTOTUNE_COMPRESSION_CANDIDATES",
                 "AUTOTUNE_COMPRESSION_THRESHOLD_CANDIDATES",
                 "AUTOTUNE_ZERO_BUCKET_CANDIDATES_MIB"):
        assert name in envparse.KNOBS, name


# ==========================================================================
# hvd-autotune CLI
# ==========================================================================

def _cli(argv):
    from horovod_tpu.autotune import cli
    return cli.main(argv)


def test_cli_show_history_diff_clear(tmp_path, capsys):
    from horovod_tpu.autotune import store
    cache = str(tmp_path / "cache.json")
    old = str(tmp_path / "old.json")
    store.save_entry(old, "k1", _entry(fusion=MIB, score=10.0))
    store.save_entry(cache, "k1", _entry(fusion=3 * MIB, score=42.0))
    store.save_entry(cache, "k2", _entry(fusion=MIB))

    assert _cli(["show", "--cache", cache]) == 0
    out = capsys.readouterr().out
    assert "k1" in out and f"fusion_threshold={3 * MIB}" in out

    assert _cli(["show", "--cache", cache, "--json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert set(data) == {"k1", "k2"}

    assert _cli(["history", "--cache", cache, "--key", "k1"]) == 0
    out = capsys.readouterr().out
    assert "host" in out and "42.0" in out

    # Two entries and no --key: refuse rather than guess.
    with pytest.raises(SystemExit) as exc:
        _cli(["history", "--cache", cache])
    assert exc.value.code == 1

    assert _cli(["diff", old, cache]) == 0
    out = capsys.readouterr().out
    assert "+ k2" in out
    assert f"fusion_threshold: {MIB} -> {3 * MIB}" in out
    assert "score: 10.0 -> 42.0" in out

    assert _cli(["clear", "--cache", cache, "--key", "k2"]) == 0
    capsys.readouterr()
    assert set(store.load(cache)) == {"k1"}
    assert _cli(["clear", "--cache", cache]) == 0
    capsys.readouterr()
    assert not os.path.exists(cache)


def test_cli_empty_corrupt_and_missing_path(tmp_path, capsys,
                                            monkeypatch):
    monkeypatch.delenv("HVDTPU_AUTOTUNE_CACHE", raising=False)
    with pytest.raises(SystemExit) as exc:
        _cli(["show"])
    assert exc.value.code == 1

    empty = str(tmp_path / "missing.json")
    assert _cli(["show", "--cache", empty]) == 0
    assert "(empty store)" in capsys.readouterr().out

    bad = tmp_path / "bad.json"
    bad.write_text("{nope")
    with pytest.raises(SystemExit) as exc:
        _cli(["show", "--cache", str(bad)])
    assert exc.value.code == 2
