"""hvd-perf: the calibrated α–β cost model (analysis/costmodel.py) —
fit roundtrip, prediction shapes, HVD6xx rule fixtures, SARIF/baseline
interplay, CLI plumbing, the one-parse contract, and autotune
warm-start priors.
"""

import ast
import json
import math
import os
import subprocess
import sys
import types

import pytest

from conftest import clean_spawn_env
from horovod_tpu.analysis import (ast_lint, baseline as baseline_mod,
                                  cli, costmodel, sarif as sarif_mod,
                                  schedule)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF_FIXTURES = os.path.join(REPO, "tests", "lint_fixtures", "perf")
FIXTURE_TABLE = os.path.join(PERF_FIXTURES, "costmodel_table.json")
RANKS = (8, 64, 256, 1024)


def _table():
    return costmodel.load_table(FIXTURE_TABLE)


def _perf(path, table=None, ranks=RANKS):
    v = schedule.Verifier()
    v.add_path(path)
    return costmodel.perf_diagnostics(
        v, table=table or _table(), target_ranks=list(ranks))


def _pins(diags, rule):
    return [(os.path.basename(d.file), d.line) for d in diags
            if d.rule == rule]


# ==========================================================================
# Model math
# ==========================================================================
def test_canonical_kind_mapping():
    cm = costmodel.canonical_kind
    assert cm("allreduce_async") == "allreduce"
    assert cm("psum") == "allreduce"
    assert cm("grouped_allreduce") == "allreduce"
    assert cm("sparse_allreduce") == "allgather"
    assert cm("reduce_scatter") == "reducescatter"
    assert cm("ppermute") == "alltoall"
    assert cm("broadcast_") == "broadcast"
    assert cm("join") == "barrier"
    assert cm("definitely_not_a_collective") == "allreduce"


def test_collective_time_monotone_in_payload_and_world():
    t = costmodel.collective_time
    for kind in costmodel.MODEL_KINDS:
        if kind == "barrier":
            continue
        assert t(kind, 1 << 20, 8) < t(kind, 1 << 24, 8) \
            < t(kind, 1 << 28, 8), kind
    # Latency term grows with the cohort for every kind, barrier
    # included (dissemination rounds).
    for kind in costmodel.MODEL_KINDS:
        assert t(kind, 1 << 20, 8) < t(kind, 1 << 20, 64) \
            < t(kind, 1 << 20, 1024), kind


def test_bucket_optimum_formula_and_clamps():
    table = _table()
    total = table["step_bytes"]
    opt = costmodel.bucket_optimum(total, 1024, table)
    lat, bw = costmodel._terms("allreduce", 1024)
    expect = math.sqrt(total * (1e-6 * lat) / (1e-11 * bw))
    assert opt == int(expect)
    # Tiny totals clamp to the total itself, never below 64 KiB.
    assert costmodel.bucket_optimum(1024, 1024, table) == 1024
    assert costmodel.bucket_optimum(10 << 20, 2, table) >= 64 * 1024


def test_predict_step_async_hides_under_compute():
    table = dict(_table())     # compute_s = 5 ms, serial 1.0
    ev_sync = types.SimpleNamespace(kind="allreduce")
    ev_async = types.SimpleNamespace(kind="allreduce_async")
    sync = costmodel.predict_step([ev_sync], 64, table)
    asyn = costmodel.predict_step([ev_async], 64, table)
    # Same payload, same kind: the async submit hides under the 5 ms
    # compute baseline, the sync one serializes on top of it.
    assert asyn["step_s"] < sync["step_s"]
    assert sync["blocking"] == 1 and asyn["blocking"] == 0
    # fixed_s rides on the critical path for BOTH.
    bumped = dict(table, fixed_s=0.5)
    assert costmodel.predict_step([ev_async], 64, bumped)["step_s"] \
        == pytest.approx(asyn["step_s"] + 0.5)


# ==========================================================================
# Calibration: fit roundtrip on synthetic shards
# ==========================================================================
ALPHA_TRUE = 2e-5
BYTE_S_TRUE = 3e-10


def _write_shard(dirpath, world=8, alpha=ALPHA_TRUE,
                 byte_s=BYTE_S_TRUE,
                 payloads=(1 << 20, 1 << 22, 1 << 24, 1 << 26)):
    """One rank-0 shard whose spans sit exactly on the α–β plane."""
    lat, bw = costmodel._terms("allreduce", world)
    recs = [{"e": "meta", "rank": 0, "size": world, "ver": 0,
             "off": 0.0, "t": 0.0}]
    t = 1.0
    for occ, nbytes in enumerate(payloads):
        dur = alpha * lat + nbytes * byte_s * bw
        recs.append({"e": "sub", "t": t, "n": "grad", "o": occ,
                     "k": "allreduce", "b": nbytes})
        recs.append({"e": "fin", "t": t + dur, "n": "grad", "o": occ,
                     "k": "allreduce"})
        t += dur + 0.01
    path = os.path.join(dirpath, "shard.r0.v0.jsonl")
    with open(path, "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in recs)
    return path


def test_fit_recovers_known_coefficients(tmp_path):
    # Two run groups, uniform payload within each (like two bench
    # invocations at different model sizes): the span-level 2x2 fit
    # recovers alpha/byte_s exactly, and the step-level regression sees
    # two points sitting ON the line wall == 1.0 x model (+ 0 fixed).
    for name, nbytes in (("run_a", 1 << 20), ("run_b", 1 << 26)):
        d = str(tmp_path / name)
        os.makedirs(d)
        _write_shard(d, payloads=(nbytes,) * 3)
    table = costmodel.fit_paths(
        [str(tmp_path / "run_a"), str(tmp_path / "run_b")])
    row = table["kinds"]["allreduce"]
    assert row["alpha_s"] == pytest.approx(ALPHA_TRUE, rel=1e-6)
    assert row["byte_s"] == pytest.approx(BYTE_S_TRUE, rel=1e-6)
    assert table["source"] == "calibrated"
    assert table["worlds"] == [8]
    assert table["spans"] == 6
    assert table["serial_fraction"] == pytest.approx(1.0, rel=0.02)
    assert table["fixed_s"] == pytest.approx(0.0, abs=1e-9)


def test_fit_over_two_worlds_predicts_its_own_steps(tmp_path):
    # One run group per world, as a calibration over n=2 and n=4 runs
    # records them: the table fitted over both must give back the step
    # each shard was written with (one allreduce of `nbytes` a step).
    from horovod_tpu.tracing import merge
    runs = {2: 1 << 22, 4: 1 << 24}
    dirs = [str(tmp_path / f"n{world}") for world in runs]
    for d, (world, nbytes) in zip(dirs, runs.items()):
        os.makedirs(d)
        _write_shard(d, world=world, payloads=(nbytes,) * 3)
    table = costmodel.fit_shards(
        merge.load_paths(dirs, kinds=(merge.SHARD_PREFIX,)))
    assert table["source"] == "calibrated"
    assert table["worlds"] == [2, 4]
    assert table["spans"] == 6
    for world, nbytes in runs.items():
        lat, bw = costmodel._terms("allreduce", world)
        written = ALPHA_TRUE * lat + nbytes * BYTE_S_TRUE * bw
        for kind in ("allreduce", "allreduce_async"):
            pred = costmodel.predict_step(
                [types.SimpleNamespace(kind=kind)], world, table,
                step_bytes=nbytes)
            assert pred["step_s"] == pytest.approx(written, rel=1e-6), \
                (world, kind)


def test_fit_paths_raises_when_no_spans(tmp_path):
    with pytest.raises(ValueError, match="no usable collective spans"):
        costmodel.fit_paths([str(tmp_path)])


def test_load_paths_warns_and_skips_unreadable_shard(tmp_path):
    import logging

    from horovod_tpu.tracing import merge
    _write_shard(str(tmp_path))
    # A directory matching the shard glob: open() raises IsADirectoryError
    # (an OSError) — must be skipped with a warning, not fatal. The
    # hvd-tpu logger does not propagate, so hook a handler onto it.
    os.makedirs(str(tmp_path / "shard.r1.v0.jsonl"))
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logger = logging.getLogger("horovod_tpu")
    logger.addHandler(handler)
    try:
        shards = merge.load_paths([str(tmp_path)])
    finally:
        logger.removeHandler(handler)
    assert len(shards) == 1
    assert any("skipping unreadable shard" in r.getMessage()
               for r in records)


def test_save_and_load_table_roundtrip(tmp_path):
    table = costmodel.fit_shards([])
    table["compute_s"] = 0.0125
    out = str(tmp_path / "model.json")
    costmodel.save_table(table, out)
    loaded = costmodel.load_table(out)
    assert loaded["compute_s"] == 0.0125
    assert loaded["kinds"]["allreduce"] == table["kinds"]["allreduce"]


# ==========================================================================
# HVD6xx rules over the fixture corpus
# ==========================================================================
def test_hvd601_fixture_pins_three_findings():
    diags = _perf(os.path.join(PERF_FIXTURES, "bad_bucket_knob.py"))
    assert _pins(diags, "HVD601") == [("bad_bucket_knob.py", 12),
                                      ("bad_bucket_knob.py", 15),
                                      ("bad_bucket_knob.py", 17)]
    assert all(d.severity == "warning" for d in diags)


def test_hvd601_silent_without_collectives_or_literals():
    # The clean twin: knob within 2x of optimum + a computed export.
    diags = _perf(os.path.join(PERF_FIXTURES, "good_perf_clean.py"))
    assert _pins(diags, "HVD601") == []


def test_hvd602_fixture_pins_three_findings():
    diags = _perf(os.path.join(PERF_FIXTURES, "bad_step_barrier.py"))
    assert _pins(diags, "HVD602") == [("bad_step_barrier.py", 15),
                                      ("bad_step_barrier.py", 23),
                                      ("bad_step_barrier.py", 31)]
    # two_metric_reductions (two sync sites, below threshold) is clean.
    msgs = [d.message for d in diags if d.rule == "HVD602"]
    assert not any("two_metric_reductions" in m for m in msgs)


def test_hvd602_needs_no_table():
    # Serialization points are schedule-structural: the rule fires
    # identically under the uncalibrated default table.
    diags = _perf(os.path.join(PERF_FIXTURES, "bad_step_barrier.py"),
                  table=dict(costmodel.DEFAULT_TABLE))
    assert len(_pins(diags, "HVD602")) == 3


def test_hvd603_fixture_pins_and_default_table_silence():
    path = os.path.join(PERF_FIXTURES, "bad_scale_cliff.py")
    diags = _perf(path)
    assert _pins(diags, "HVD603") == [("bad_scale_cliff.py", 16),
                                      ("bad_scale_cliff.py", 24),
                                      ("bad_scale_cliff.py", 37)]
    # No calibrated compute baseline -> a 50% claim would be fiction.
    assert _perf(path, table=dict(costmodel.DEFAULT_TABLE)) == []


def test_hvd6xx_good_fixture_fully_silent_under_both_tables():
    path = os.path.join(PERF_FIXTURES, "good_perf_clean.py")
    assert _perf(path) == []
    assert _perf(path, table=dict(costmodel.DEFAULT_TABLE)) == []


def test_hvd6xx_suppression_comments_respected():
    path = os.path.join(PERF_FIXTURES, "good_perf_suppressed.py")
    assert _perf(path) == []


# ==========================================================================
# Report + SARIF + baseline interplay
# ==========================================================================
def test_analyze_corpus_and_render_report():
    v = schedule.Verifier()
    v.add_path(os.path.join(PERF_FIXTURES, "bad_scale_cliff.py"))
    report = costmodel.analyze_corpus(v, table=_table(),
                                      target_ranks=list(RANKS))
    fns = {row["function"].split(".")[-1]: row
           for row in report["functions"]}
    assert {"cliff_early", "cliff_late", "cliff_async"} <= set(fns)
    row = fns["cliff_early"]
    assert sorted(row["curve"]) == sorted(RANKS)
    # comm fraction is monotone in the cohort for a sync loop
    fracs = [row["curve"][n]["comm_fraction"] for n in RANKS]
    assert fracs == sorted(fracs)
    text = costmodel.render_report(report)
    assert "predicted scaling" in text
    assert "cliff_early" in text


def test_perf_sarif_golden_file():
    diags = _perf(os.path.join(PERF_FIXTURES, "bad_bucket_knob.py"))
    doc = sarif_mod.to_sarif(diags)
    doc["runs"][0]["tool"]["driver"]["version"] = "GOLDEN"
    for result in doc["runs"][0]["results"]:
        uri = result["locations"][0]["physicalLocation"]
        uri["artifactLocation"]["uri"] = \
            "tests/lint_fixtures/perf/bad_bucket_knob.py"
    with open(os.path.join(PERF_FIXTURES, "golden_perf.sarif")) as f:
        golden = json.load(f)
    assert doc == golden


def test_hvd6xx_baseline_suppresses_known_findings(tmp_path):
    diags = _perf(os.path.join(PERF_FIXTURES, "bad_step_barrier.py"))
    path = str(tmp_path / "perf-baseline.json")
    baseline_mod.write_baseline(diags, path)
    doc = baseline_mod.load_baseline(path)
    new, suppressed = baseline_mod.filter_new(diags, doc)
    assert new == [] and len(suppressed) == len(diags)


# ==========================================================================
# CLI plumbing
# ==========================================================================
def _run_cli(*args):
    env = clean_spawn_env(
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis.cli", *args],
        env=env, capture_output=True, text=True, timeout=120)


def test_cli_perf_reports_and_exit_codes():
    proc = _run_cli("perf", PERF_FIXTURES, "--table", FIXTURE_TABLE,
                    "--fail-on", "never")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for rule in ("HVD601", "HVD602", "HVD603"):
        assert rule in proc.stdout
    proc = _run_cli("perf", PERF_FIXTURES, "--table", FIXTURE_TABLE,
                    "--fail-on", "warning")
    assert proc.returncode == 1


def test_cli_perf_prints_predicted_scaling_report():
    proc = _run_cli("perf",
                    os.path.join(PERF_FIXTURES, "good_perf_clean.py"),
                    "--target-ranks", "4,16")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "predicted scaling" in proc.stdout
    assert "n = 4/16" in proc.stdout


def test_cli_calibrate_writes_table(tmp_path):
    _write_shard(str(tmp_path))
    out = str(tmp_path / "model.json")
    proc = _run_cli("perf", "--calibrate", str(tmp_path),
                    "--write-table", out)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "calibrated 4 span(s)" in proc.stdout
    table = costmodel.load_table(out)
    assert table["kinds"]["allreduce"]["alpha_s"] == pytest.approx(
        ALPHA_TRUE, rel=1e-6)


def test_cli_calibrate_empty_dir_fails(tmp_path):
    proc = _run_cli("perf", "--calibrate", str(tmp_path))
    assert proc.returncode == 2
    assert "no usable collective spans" in proc.stderr


def test_cli_rejects_garbage_table(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("not json{")
    proc = _run_cli("perf", PERF_FIXTURES, "--table", str(bad))
    assert proc.returncode == 2


def test_cli_env_table_fallback_warns(tmp_path, monkeypatch):
    # HVDTPU_COSTMODEL_TABLE pointing nowhere must not kill the run.
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis.cli", "perf",
         os.path.join(PERF_FIXTURES, "good_perf_clean.py")],
        env=clean_spawn_env(
            PYTHONPATH=REPO + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
            HVDTPU_COSTMODEL_TABLE=str(tmp_path / "nope.json")),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ==========================================================================
# One-parse contract: the perf leg rides the shared corpus
# ==========================================================================
def test_self_sweep_parses_each_file_once(monkeypatch):
    """--self now runs AST + verify + simulate + perf off ONE parsed
    corpus: no file may be fed to ast.parse twice in one invocation."""
    ast_lint._PARSE_CACHE.clear()
    counts = {}
    real_parse = ast.parse

    def counting_parse(src, filename="<unknown>", *a, **kw):
        if str(filename).endswith(".py"):
            counts[filename] = counts.get(filename, 0) + 1
        return real_parse(src, filename, *a, **kw)

    monkeypatch.setattr(ast_lint.ast, "parse", counting_parse)
    rc = cli.main(["--self", "--fail-on", "warning"])
    assert rc == 0
    repeats = {f: n for f, n in counts.items() if n > 1}
    assert not repeats, f"files parsed more than once: {repeats}"
    assert counts, "self sweep parsed nothing?"


# ==========================================================================
# Autotune warm-start priors
# ==========================================================================
def test_rank_candidates_orders_by_predicted_cost():
    table = _table()
    candidates = [1 << 18, 1 << 22, 1 << 26]   # overlap arm buckets
    order = costmodel.rank_candidates("overlap", candidates, 64, table)
    assert sorted(order) == [0, 1, 2]
    costs = [costmodel.predicted_cost("overlap", candidates[i], 64,
                                      table) for i in order]
    assert costs == sorted(costs)
    # Deterministic: same inputs, same order — every rank agrees.
    assert order == costmodel.rank_candidates("overlap", candidates,
                                              64, table)


def test_prior_cost_compression_prefers_smaller_wires():
    table = _table()
    none_cost = costmodel.predicted_cost(
        "compression", ("none", 1024), 256, table)
    fp16_cost = costmodel.predicted_cost(
        "compression", ("fp16", 1024), 256, table)
    int8_cost = costmodel.predicted_cost(
        "compression", ("int8", 1024), 256, table)
    assert int8_cost < fp16_cost < none_cost


def _fake_runtime(rank=0, size=4):
    from horovod_tpu import basics
    coord = types.SimpleNamespace(bytes_processed=0, fusion_threshold=0,
                                  cycle_time_s=0.001)
    backend = types.SimpleNamespace(core=types.SimpleNamespace(
        set_fusion_threshold=lambda v: None))
    topology = types.SimpleNamespace(rank=rank, size=size)
    return types.SimpleNamespace(mode=basics.MODE_SINGLE,
                                 coordinator=coord, backend=backend,
                                 topology=topology, size=size)


def _tiny_grid(monkeypatch):
    monkeypatch.setenv("HVDTPU_AUTOTUNE_FUSION_CANDIDATES_MIB",
                       "64,1,16")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CYCLE_CANDIDATES_MS", "0.5")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_WARMUP_CYCLES", "1")
    monkeypatch.setenv("HVDTPU_AUTOTUNE_CYCLES_PER_CANDIDATE", "2")
    monkeypatch.delenv("HVDTPU_AUTOTUNE_CACHE", raising=False)


def test_disabled_mode_constructs_no_model(monkeypatch):
    """HVDTPU_COSTMODEL off (the default): ParameterManager start-up
    must not touch the cost model at all — the knob check is the whole
    cost."""
    from horovod_tpu.autotune import ParameterManager
    _tiny_grid(monkeypatch)
    monkeypatch.delenv("HVDTPU_COSTMODEL", raising=False)

    def bomb(*a, **k):
        raise AssertionError("cost model touched in disabled mode")

    monkeypatch.setattr(costmodel, "resolve_table", bomb)
    monkeypatch.setattr(costmodel, "rank_candidates", bomb)
    monkeypatch.setattr(costmodel, "predicted_cost", bomb)
    pm = ParameterManager(_fake_runtime())
    assert pm._prior_table is None
    assert pm._active == list(range(len(pm._arms[0].candidates)))


def test_prior_seeding_reorders_identically_on_every_rank(monkeypatch):
    """Knob on: the sweep's probe order is seeded from the model
    ranking, identically for every rank (the applied sequence stays
    byte-identical — broadcast determinism intact)."""
    from horovod_tpu.autotune import ParameterManager
    _tiny_grid(monkeypatch)
    monkeypatch.setenv("HVDTPU_COSTMODEL", "1")
    monkeypatch.setenv("HVDTPU_COSTMODEL_TABLE", FIXTURE_TABLE)
    pms = [ParameterManager(_fake_runtime(rank=r)) for r in (0, 1, 3)]
    orders = [pm._active for pm in pms]
    assert orders[0] == orders[1] == orders[2]
    arm = pms[0]._arms[0]
    ranked = costmodel.rank_candidates(
        arm.name, arm.candidates, 4, _table())
    assert orders[0] == ranked
    # The grid was written host-order 64,1,16 MiB — the prior must
    # actually reorder it (otherwise this test pins nothing).
    assert orders[0] != list(range(len(arm.candidates)))


def test_store_entry_predicted_field():
    from horovod_tpu.autotune import store
    cfg = {k: None for k in store.CONFIG_KEYS}
    cfg.update(fusion_threshold=1 << 20, cycle_time_ms=2.0)
    entry = store.make_entry(cfg, 1.5, "steps_per_s", "sig", 4, "int8",
                             "0", [], predicted={"host": 0.003})
    assert entry["predicted"] == {"host": 0.003}
    assert store.validate_entry(entry) is None
    bare = store.make_entry(cfg, 1.5, "steps_per_s", "sig", 4, "int8",
                            "0", [])
    assert "predicted" not in bare
