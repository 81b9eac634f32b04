"""The harness end to end on the CPU at tiny sizes: cells, configurations
and per-layer metrics found as new files; the last line's keys; refusal
without a TPU; ``correct`` false in lower precision and with the timed
path broken underneath.

The tiny cells reuse the committed configurations' limits, builders and
references; no device metric's name is printed from these runs outside
the objects the tests inspect."""

import json
import os
import subprocess
import sys
import time

import pytest
from bench_fixtures import bench_root, cpu_peak  # noqa: F401 (fixtures)

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run(root, cell, seed=5, seconds=0.3, trace=False):
    lines = []
    result = harness.run(root.path, cell, seed, seconds, trace,
                         time.perf_counter(), on_chip=False,
                         say=lines.append)
    return result, lines


def test_new_cell_config_and_metric_are_only_new_files(bench_root):
    cell = bench_root.add_tiny_lm()
    bench_root.add_file(
        "benchmark/layer_metrics/steps_completed.py",
        '"""Steps the window completed."""\n\n'
        "def read(ctx):\n    return ctx.steps\n")
    bench_root.bench["per_layer"].append({
        "name": "steps_completed", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "step_ms_p90", "workloads": [cell]})
    bench_root.write()
    loaded = harness.load_cell(bench_root.path, cell)
    assert loaded["cfg"]["hidden_size"] == 64
    assert loaded["traffic_params"]["seq_len"] == 128
    assert "steps_completed" in [m["name"] for m in loaded["per_layer"]]
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "tokens_per_s_per_chip", "mfu", "step_ms_p90", "setup_s"]
    reader = harness.load_module(
        bench_root.path, "benchmark/layer_metrics/steps_completed.py")
    from benchmark.layers import Context
    assert reader.read(Context(seen={"done": [1.0, 2.0, 3.0]})) == 3
    # Nothing that was committed changed, and the committed cells still
    # load from the same root.
    assert bench_root.snapshot() == bench_root.committed
    assert harness.load_cell(bench_root.path, "resnet50-b384-1chip")[
        "cfg"]["name"] == "resnet50"


def test_unknown_workload_is_refused(bench_root):
    with pytest.raises(SystemExit) as exit_:
        harness.load_cell(bench_root.path, "no-such-cell")
    assert exit_.value.code not in (0, None)


@pytest.mark.parametrize("family,chips", [("lm", 1), ("lm", 4),
                                          ("resnet", 1)])
def test_a_whole_run_on_the_cpu(bench_root, cpu_peak, family, chips):
    cell = (bench_root.add_tiny_lm(chips) if family == "lm"
            else bench_root.add_tiny_resnet())
    result, lines = run(bench_root, cell)
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == DEVICE_KEYS
    assert result["device"]["platform"] == "cpu"       # named as it is
    assert result["failed"] == 0 and result["attempted"] >= 2
    rate = ("tokens_per_s_per_chip" if family == "lm"
            else "images_per_s_per_chip")
    assert set(result["metrics"]) == {rate, "mfu", "step_ms_p90", "setup_s"}
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    # Every number compared is printed beside its limit.
    compared = [line for line in lines if line.startswith("compared ")]
    names = [line.split()[1].rstrip(":") for line in compared]
    assert names[:3] == ["loss_gap", "grad_norm_gap", "update_norm_gap"]
    assert ("replicas_differ" in names) == (chips > 1)
    assert all("(limit " in line for line in compared)
    json.dumps(result)


def test_a_new_family_is_only_new_files(bench_root, cpu_peak):
    """A configuration of a family the benchmark has never seen brings
    its builder, its reference and its own count of operations, and
    edits nothing: ``mfu`` is worked out from the reference's
    ``flops_per_row``, and the kernel's roofline finds nothing to read."""
    cell = bench_root.add_mlp_family()
    assert bench_root.snapshot() == bench_root.committed
    result, lines = run(bench_root, cell)
    assert result["correct"] is True, lines
    rate = result["metrics"]["images_per_s_per_chip"]["value"]
    flops_per_row = 2 * (2 * 32 * 64 + 3 * 64 * 10)
    assert result["metrics"]["mfu"]["value"] == pytest.approx(
        100 * rate * flops_per_row / 1e12)
    from benchmark.layers import Context
    session_reference = harness.load_module(
        bench_root.path, "benchmark/references/mlp.py")
    roofline = harness.load_module(
        bench_root.path, "benchmark/layer_metrics/flash_roofline.py")
    assert roofline.read(Context(
        reference=session_reference, cell=harness.load_cell(
            bench_root.path, cell),
        trace={"devices": {"0": {"by_class": {"kernel": 5e6}}}})) is None


def test_mfu_and_rate_are_over_the_whole_window():
    """A stall inside the window moves the rate and ``mfu`` (all the
    work over all the time) and not the median step."""
    import types
    cell = {"cfg": {}, "chips": 1, "traffic_params": {
        "rows_per_chip": 4, "units_per_row": 10, "row_unit": "tokens"}}
    reference = types.SimpleNamespace(flops_per_row=lambda cfg, t: 1e9)
    steady = {"start": 0.0, "done": [1.0 + k for k in range(10)],
              "end": 10.0}
    stalled = {"start": 0.0, "end": 12.0,
               "done": [1.0 + k + (2.0 if k >= 5 else 0) for k in range(10)]}
    from benchmark import peaks
    peaks.PEAKS["test-kind"] = {"bf16_flops_per_s": 1e10}
    try:
        a, na = harness.end_to_end(cell, reference, steady, 1.0, "test-kind")
        b, nb = harness.end_to_end(cell, reference, stalled, 1.0,
                                   "test-kind")
    finally:
        del peaks.PEAKS["test-kind"]
    assert a["tokens_per_s_per_chip"] == pytest.approx(40.0)
    assert a["mfu"] == pytest.approx(100 * 4e9 / 1e10)
    assert b["mfu"] == pytest.approx(a["mfu"] * 10 / 12)
    assert b["tokens_per_s_per_chip"] == pytest.approx(40.0 * 10 / 12)
    assert na["step_ms_median"] == nb["step_ms_median"] == 1000.0
    assert b["step_ms_p90"] > a["step_ms_p90"]


def test_same_seed_same_inputs_other_seed_other_inputs(bench_root):
    cell = bench_root.add_tiny_lm()
    session = harness.Session(bench_root.path, cell, on_chip=False)
    big = 2**31 + 12345                     # more than 32 signed bits hold
    a, b, c = session.feed(big), session.feed(big), session.feed(big + 1)
    assert (a.global_batch(3)[0] == b.global_batch(3)[0]).all()
    assert (a.global_batch(3)[0] != a.global_batch(4)[0]).any()
    assert (a.global_batch(3)[0] != c.global_batch(3)[0]).any()
    assert a.global_batch(0)[0].shape == (2, 128)
    p, q = session.make_params(big), session.make_params(big + 1)
    import jax
    assert all((x == y).all() for x, y in zip(
        jax.tree.leaves(p), jax.tree.leaves(session.make_params(big))))
    assert any((x != y).any() for x, y in zip(
        jax.tree.leaves(p), jax.tree.leaves(q)))


@pytest.mark.parametrize("mode", ["frozen", "half_batch"])
def test_broken_timed_path_is_not_correct(bench_root, cpu_peak, mode):
    builder = bench_root.add_broken_builder(
        mode, "benchmark/builders/transformer_lm.py")
    cell = bench_root.add_tiny_lm(builder=builder, name="lmbroken")
    result, lines = run(bench_root, cell)
    assert result["correct"] is False
    assert any("NOT OK" in line for line in lines)


def test_lower_precision_is_not_correct(bench_root):
    """The control: the reference in the program's place, in the
    precision below the one the configuration states."""
    from benchmark import control
    cell = bench_root.add_tiny_lm()
    session = harness.Session(bench_root.path, cell, on_chip=False)
    lower = session.cfg["control_precision"]
    out = control.readings(session, 7, [lower])
    assert out["program"][0] is True
    assert out[lower][0] is False


def test_refusal_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "lm365m-seq8192-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr
    assert not [line for line in done.stdout.splitlines()
                if line.startswith("{")]


def test_unknown_device_kind_has_no_peak():
    from benchmark import peaks
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(RuntimeError):
        peaks.peak("cpu", "bf16_flops_per_s")


def test_percentile():
    assert harness.percentile([1, 2, 3, 4, 5], 50) == 3
    assert harness.percentile(list(range(11)), 90) == 9
    assert harness.percentile([10.0, 20.0], 90) == pytest.approx(19.0)
