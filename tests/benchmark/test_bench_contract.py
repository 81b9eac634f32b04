"""BENCHMARK.json against the rules a reader of it relies on: names and
units, files under ``paths``, every cell reporting what its metrics
name, one reader a per-layer metric."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in bench[key]]
    assert all(NAME.fullmatch(n) for n in names)
    for key in ("configs", "workloads"):
        listed = [x["name"] for x in bench[key]]
        assert len(set(listed)) == len(listed)
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_cells(bench):
    cells = bench["workloads"]
    configs = {c["name"] for c in bench["configs"]}
    assert {w["config"] for w in cells} == configs
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.fullmatch(w["traffic"])
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "traffic", w["traffic"] + ".json"))
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        for key in ("builder", "reference"):
            assert os.path.exists(os.path.join(REPO, cfg[key]))
        assert set(cfg["limits"]) == {"loss_gap", "grad_norm_gap",
                                      "update_norm_gap"}


def test_every_cell_reports_what_its_metrics_name(bench):
    end = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in end and "workloads" not in end["setup_s"]
    for w in bench["workloads"]:
        mine = [m for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= 2
        layer = [m for m in bench["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert layer
        for m in layer:
            moved = end[m["moves"]]
            assert w["name"] in moved.get("workloads", [w["name"]]), (
                m["name"], w["name"])


def test_one_reader_a_per_layer_metric(bench):
    for m in bench["per_layer"]:
        path = os.path.join(REPO, "benchmark", "layer_metrics",
                            m["name"] + ".py")
        with open(path) as f:
            assert "def read(ctx):" in f.read()
    # A share of a roofline or of a peak is named so, in %.
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
