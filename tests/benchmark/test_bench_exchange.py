"""The readers of the exchange's per-layer metrics (PR 35):
``exchange_exposed_ms`` from a hand-written trace, ``exchange_async_pct``
and ``exchange_allreduces`` from a hand-written scheduled HLO, and what
each returns on one chip and over a program without the counter."""

import os
import sys

import pytest

from benchmark import harness, layers, trace_reduce as tr

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Two steps: a synchronous all-reduce with nothing beside it, and a
# pair whose start and done are fusions with a product between them.
TRACE = {
    "devices": {"0": [
        ["fusion.1 fusion", 0, 100],
        ["all-reduce.7 all-reduce", 100, 30],
        ["async-collective-start.3 fusion", 130, 5],
        ["convolution_add_fusion.2 fusion", 135, 200],
        ["async-collective-done.3 fusion", 335, 40],
        ["async-collective-start fusion", 375, 5],
        ["fusion.9 fusion", 380, 100],
        ["async-collective-done fusion", 480, 20],
    ]},
    "host": [["bench:window", 0, 500]],
}

# The shape of a TPU schedule: the pair's all-reduce sits in the called
# computation of the start fusion; the loss's scalar is synchronous.
HLO = """HloModule jit_hvd_train_step, is_scheduled=true

%region_75.76 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(%a, %b)
}

%async_collective_fusion.611 (param_0.2792: f32[16,64,1024]) -> f32[16,64,1024] {
  %param_0.2792 = f32[16,64,1024]{2,1,0} parameter(0)
  ROOT %all-reduce.91 = f32[16,64,1024]{2,1,0:T(8,128)S(1)} all-reduce(%param_0.2792), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_75.76
}

%async_collective_fusion.612 (param_0.1: f32[16,64,1024]) -> f32[16,64,1024] {
  %param_0.1 = f32[16,64,1024]{2,1,0} parameter(0)
  ROOT %all-reduce.92 = f32[16,64,1024]{2,1,0:T(8,128)S(1)} all-reduce(%param_0.1), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_75.76
}

ENTRY %main.101_spmd (param.1: f32[16,64,1024], param.2: f32[]) -> f32[16,64,1024] {
  %param.1 = f32[16,64,1024]{2,1,0} parameter(0)
  %param.2 = f32[] parameter(1)
  %async-collective-start.1 = (f32[16,64,1024]{2,1,0}, f32[16,64,1024]{2,1,0}, s32[2]{0}) fusion(%param.1), kind=kCustom, calls=%async_collective_fusion.611
  %fusion.5 = f32[1024,1024]{1,0} fusion(%param.1), kind=kOutput, calls=%region_75.76, metadata={op_name="jit(hvd_train_step)/shard_map/hvd_grad/transpose(jvp(mlp))/dot_general"}
  %async-collective-done.1 = f32[16,64,1024]{2,1,0} fusion(%async-collective-start.1), kind=kCustom, calls=%async_collective_fusion.612
  %all-reduce.3 = f32[]{:T(128)} all-reduce(%param.2), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%region_75.76
  ROOT %copy = f32[16,64,1024]{2,1,0} copy(%async-collective-done.1)
}
"""


def reader(name):
    return harness.load_module(REPO, f"benchmark/layer_metrics/{name}.py")


def context(chips=4, **more):
    return layers.Context({"cell": {"chips": chips},
                           "seen": {"done": [0.0, 1.0]}, **more})


def test_exposed_exchange_is_the_synchronous_part_and_the_pairs_own_time():
    ctx = context(trace=tr.reduce(TRACE))
    # 30 synchronous and exposed; the starts 5 + 5 and the dones 40 + 20,
    # over two steps. The products between them are not the exchange's.
    assert reader("exchange_exposed_ms").read(ctx) == pytest.approx(
        (30 + 10 + 60) / 1e6 / 2)
    assert reader("collective_exposed_ms").read(ctx) == pytest.approx(
        30 / 1e6 / 2)


def test_exposed_exchange_without_pairs_is_the_collectives_exposed_part():
    trace = {"devices": {"0": [e for e in TRACE["devices"]["0"]
                               if "async" not in e[0]]},
             "host": TRACE["host"]}
    ctx = context(trace=tr.reduce(trace))
    assert reader("exchange_exposed_ms").read(ctx) == reader(
        "collective_exposed_ms").read(ctx) == pytest.approx(30 / 1e6 / 2)


def test_counters_read_the_entry_computation():
    ctx = context(hlo=HLO)
    leaf = 16 * 64 * 1024 * 4
    assert reader("exchange_async_pct").read(ctx) == pytest.approx(
        100.0 * leaf / (leaf + 4))
    # One pair and one scalar, where the text says ``all-reduce(`` thrice.
    assert reader("exchange_allreduces").read(ctx) == 2
    assert reader("allreduce_ops").read(ctx) == 3


@pytest.mark.parametrize("name", ["exchange_exposed_ms",
                                  "exchange_async_pct",
                                  "exchange_allreduces"])
def test_on_one_chip_there_is_nothing_to_read(name):
    assert reader(name).read(context(chips=1)) is None


@pytest.mark.parametrize("name", ["exchange_async_pct",
                                  "exchange_allreduces"])
def test_a_program_without_the_counter_reports_nothing(name, monkeypatch):
    # The parent's horovod_tpu.jax has no exchange_schedule.
    import horovod_tpu.jax as hvd_jax
    monkeypatch.delattr(hvd_jax, "exchange_schedule")
    assert "horovod_tpu.jax" in sys.modules
    assert reader(name).read(context(hlo=HLO)) is None
