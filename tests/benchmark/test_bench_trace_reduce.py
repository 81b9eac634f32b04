"""The reduction from a profiler trace to numbers: on a hand-written
list of events, and on a small trace recorded on the chip (two steps of
``lm365m-seq8192-1chip`` cut from this PR's first traced run)."""

import gzip
import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL = "custom-call:tpu_custom_call"

HAND = {
    "devices": {"0": [
        ["fusion.1 fusion", 0, 100],
        [f"attn.1 {KERNEL}", 100, 200],
        # idle 300..400
        ["all-reduce-start.1 all-reduce-start", 400, 10],
        ["fusion.2 fusion", 410, 90],          # hides part of the exchange
        ["all-reduce-done.1 all-reduce-done", 500, 60],
        # idle 560..600
        ["while.1 while", 600, 200],
        ["fusion.3 fusion", 650, 50],          # nested in the while
        # idle 800..1000
    ]},
    "host": [
        ["bench:window", 0, 1000],
        ["bench:next_batch", 290, 60],
        ["bench:dispatch", 350, 70],
        ["bench:wait_loss", 560, 440],
    ],
}


def test_interval_arithmetic():
    assert tr.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert tr.total([[0, 3], [5, 8]]) == 6
    assert tr.subtract([[0, 10]], [[2, 3], [5, 7]]) == [
        [0, 2], [3, 5], [7, 10]]
    assert tr.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert tr.subtract([[0, 4]], []) == [[0, 4]]


def test_names_from_hlo_lines():
    kernel = ('%attn.102 = (bf16[32,8192,64]{2,1,0:T(8,128)(2,1)}, '
              'bf16[32,8192,64]{2,1,0:T(8,128)(2,1)S(1)}) custom-call('
              's32[3]{0:T(128)S(1)} %copy-done.2302, bf16[32,8192,64] '
              '%pad_maximum_fusion.40), custom_call_target='
              '"tpu_custom_call", operand_layout_constraints={}')
    assert tr.short_name(kernel) == f"attn.102 {KERNEL}"
    assert tr.classify(tr.short_name(kernel)) == "kernel"
    # An operand that is a custom call does not make its user a kernel.
    user = ('%fusion.9 = f32[8]{0:T(128)} fusion(f32[8]{0} '
            '%custom-call.460), kind=kLoop, calls=%fused.1')
    assert tr.short_name(user) == "fusion.9 fusion"
    assert tr.classify(tr.short_name(user)) == "xla"
    ar = ('%all-reduce-start.3 = (f32[1024]{0}, f32[1024]{0}) '
          'all-reduce-start(f32[1024]{0} %x), replica_groups={}')
    assert tr.classify(tr.short_name(ar)) == "collective"
    assert tr.family("attn.102 " + KERNEL) == "attn " + KERNEL
    assert tr.short_name("bench:window") == "bench:window"


def test_hand_written_trace():
    out = tr.reduce(HAND)
    dev = out["devices"]["0"]
    assert out["window_s"] == pytest.approx(1000e-9)
    assert dev["busy_ns"] == 300 + 160 + 200
    assert out["busy_s"] == pytest.approx(660e-9)
    idle_share = 1 - out["busy_s"] / out["window_s"]
    assert idle_share == pytest.approx(0.34)
    # Self times: the while counts 150 of its 200, its body the other 50.
    assert dev["by_class"] == {"kernel": 200, "collective": 70,
                               "xla": 100 + 90 + 150 + 50}
    assert sum(dev["by_class"].values()) == dev["busy_ns"]
    # In flight from the start's begin to the done's end; exposed where
    # nothing else runs: the start itself and the wait in the done.
    assert dev["collective_ns"] == 160
    assert dev["collective_exposed_ns"] == 10 + 60
    assert out["idle_gaps"] == [
        ["wait_loss", pytest.approx(200e-9)],
        ["dispatch", pytest.approx(100e-9)],
        ["wait_loss", pytest.approx(40e-9)]]
    assert out["device_ops"][0] == ["fusion fusion", pytest.approx(240e-9)]


def test_synchronous_collective_is_all_exposed():
    trace = {"devices": {"0": [["fusion.1 fusion", 0, 50],
                               ["all-reduce.7 all-reduce", 50, 30],
                               ["fusion.2 fusion", 80, 20]]},
             "host": []}
    dev = tr.reduce(trace)["devices"]["0"]
    assert (dev["collective_ns"], dev["collective_exposed_ns"]) == (30, 30)
    assert dev["busy_ns"] == 100      # window: the extent of the ops


def test_busy_is_averaged_over_chips():
    trace = {"devices": {"0": [["fusion.1 fusion", 0, 100]],
                         "1": [["fusion.1 fusion", 0, 50]]},
             "host": [["bench:window", 0, 100]]}
    assert tr.reduce(trace)["busy_s"] == pytest.approx(75e-9)


def test_a_trace_with_no_device_operation_is_refused():
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "host": [["bench:window", 0, 10]]})


def test_recorded_trace():
    path = os.path.join(HERE, "data",
                        "trace_lm365m_seq8192_2steps.json.gz")
    with gzip.open(path) as f:
        trace = json.load(f)
    out = tr.reduce(trace)
    dev = out["devices"]["0"]
    ops = trace["devices"]["0"]
    # Two steps of 24 layers, three Mosaic kernels a layer.
    assert sum(tr.classify(e[0]) == "kernel" for e in ops) == 2 * 72
    assert out["window_s"] == pytest.approx(1.646357611)
    assert out["busy_s"] == pytest.approx(1.646191634)
    assert dev["by_class"] == {"kernel": 950181768, "collective": 0,
                               "xla": 696009866}
    assert sum(dev["by_class"].values()) == dev["busy_ns"]
    assert dev["collective_ns"] == dev["collective_exposed_ns"] == 0
    assert out["device_ops"][0][0] == "attn " + KERNEL
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) <= 10
    # One 0.82 s program per dispatch: the chip idles only while the
    # host waits for a loss, some tens of microseconds a step.
    assert out["idle_gaps"][0][0] == "wait_loss"
    assert 1 - out["busy_s"] / out["window_s"] < 0.001
