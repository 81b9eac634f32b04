"""The six readers that split ``setup_s`` (``benchmark/layer_metrics/``
over ``benchmark/startup_reduce.py``) on a hand-made start-up log, and
on a program that keeps none."""

import json
import os

import pytest

from benchmark import harness, layers

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

STEP = "hvd_train_step"
READERS = ["before_program_s", "import_s", "step_trace_lower_s",
           "step_backend_s", "other_programs_s", "setup_unnamed_s"]

# The window starts at 40; the harness read its clock at 0.5, so
# ``setup_s`` is 39.5 less the first batch (0.25).
LOG = [
    ("before_program", "horovod_tpu", 0.0, 3.0),
    ("import", "horovod_tpu.ops", 3.5, 3.75),       # inside the package's
    ("import", "horovod_tpu", 3.0, 5.0),
    ("import", "horovod_tpu.jax", 5.0, 5.5),
    ("init", "horovod_tpu", 6.0, 6.25),
    ("trace", "init_params", 7.0, 8.0),
    ("lower", "init_params", 8.0, 8.5),
    ("cache_hit", "init_params", 8.75, 8.75),
    ("cache_load", "init_params", 8.75, 9.0),
    ("backend_compile", "init_params", 8.5, 9.5),
    ("trace", "norm", 9.75, 10.5),                  # begun before the step's
    ("trace", "tanh", 10.5, 11.0),                  # traced inside the step's
    ("trace", STEP, 10.0, 14.0),
    ("trace", "kernel", 14.5, 15.0),                # inside its lowering
    ("lower", STEP, 14.0, 16.0),
    ("cache_miss", STEP, 24.0, 24.0),
    ("backend_compile", STEP, 16.0, 24.0),
    ("trace", "sqnorm", 25.0, 25.5),
    ("backend_compile", "sqnorm", 25.5, 26.0),
    ("trace", "late", 41.0, 42.0),                  # after the window began
    ("backend_compile", "across", 39.0, 40.5),      # ended inside it
]
EXPECTED = {
    "before_program_s": 3.0,
    "import_s": 2.5,                # 3-5 and 5-5.5, .ops not twice
    "step_trace_lower_s": 6.0,      # 10-16, tanh and kernel not added
    "step_backend_s": 8.0,
    # init_params 7-9.5, norm 9.75-10 outside the step's, sqnorm 25-26.
    "other_programs_s": 2.5 + 0.25 + 1.0,
    # 39.25 less the warm-up (1.5) and what has a name from 0.75 on:
    # 0.75-5.5, 6-6.25, 7-9.5, 9.75-24, 25-26.
    "setup_unnamed_s": 39.25 - 1.5 - (4.75 + 0.25 + 2.5 + 14.25 + 1.0),
}


def reader(name):
    return harness.load_module(REPO, f"benchmark/layer_metrics/{name}.py")


@pytest.fixture
def ctx(monkeypatch):
    from horovod_tpu.utils import compile_cache
    monkeypatch.setattr(compile_cache, "spans", lambda: list(LOG))
    return layers.Context({
        "seen": {"start": 40.0}, "end_to_end": {"setup_s": 39.25},
        "spans": {"window": [1.5, 30.0]}})


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_hand_made_log(ctx, name):
    assert reader(name).read(ctx) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_without_a_log(ctx, monkeypatch, name):
    # The parent's program: ``compile_cache`` has ``events()`` alone.
    from horovod_tpu.utils import compile_cache
    monkeypatch.delattr(compile_cache, "spans")
    assert reader(name).read(ctx) is None


def test_a_span_the_platform_does_not_give_reads_nothing(ctx, monkeypatch):
    from horovod_tpu.utils import compile_cache
    monkeypatch.setattr(compile_cache, "spans", lambda: LOG[1:])
    assert reader("before_program_s").read(ctx) is None
    assert reader("import_s").read(ctx) == EXPECTED["import_s"]
    assert reader("setup_unnamed_s").read(ctx) == pytest.approx(
        EXPECTED["setup_unnamed_s"] + 2.25)


def test_the_parts_and_the_unnamed_rest_make_up_setup_s(ctx):
    parts = {name: reader(name).read(ctx) for name in READERS}
    init_s, warm_up, before_t0 = 0.25, 1.5, 0.75
    assert sum(parts.values()) + init_s + warm_up - before_t0 == (
        pytest.approx(ctx["end_to_end"]["setup_s"]))


def test_every_cell_reports_the_six():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert sorted(mine) == sorted(READERS)
    for m in mine.values():
        assert m["workloads"] == cells and m["moves"] == "setup_s"
        assert (m["unit"], m["better"], m["source"]) == (
            "s", "lower", "program_counter")
