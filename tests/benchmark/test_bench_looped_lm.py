"""The ``ouro26b`` configuration's benchmark files on the CPU: what the
configuration file states against what its plain reference builds and
counts, a whole run of a tiny cell through the harness with the new
builder, the control in lower precision, the new per-layer readers on
made-up events, and the flash kernels at the cell's shape compiled for a
described TPU v5e. (The layer tests proper are
``tests/test_looped_lm.py``.)"""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest
from bench_fixtures import bench_root, cpu_peak  # noqa: F401 (fixtures)
from jax.sharding import SingleDeviceSharding

from benchmark import flops, harness
from benchmark.layers import Context

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ouro26b-seq4096-1chip"
TRAFFIC = {"rows_per_chip": 1, "seq_len": 4096}
TINY = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=2,
    num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
    total_ut_steps=3, vocab_size=64, attention_impl="einsum",
    # This tiny size's own limits, read on the CPU as PERF.md reads the
    # cell's on the chip: the program's largest over seeds 1-7 is
    # 5.9e-4 / 6.4e-3 / 3.9e-3, the int8 control's smallest over seeds
    # 5-7 1.3e-3 / 9.9e-3 / 4.6e-3.
    limits={"loss_gap": 9e-4, "grad_norm_gap": 8e-3,
            "update_norm_gap": 4.3e-3})


def load(name):
    return harness.load_module(REPO, f"benchmark/{name}/looped_lm.py")


def reader(name):
    return harness.load_module(REPO, f"benchmark/layer_metrics/{name}.py")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark/configs/ouro26b.json")) as f:
        return json.load(f)


def add_tiny_cell(root):
    root.add_config("ourotiny", "ouro26b", **TINY)
    root.add_traffic(
        "seq32x2", "seq4096x1", rows_per_chip=2, seq_len=32,
        units_per_row=32,
        fields=[{"dist": "randint", "high": "vocab_size", "shape": [33],
                 "dtype": "int32", "next_token": True}])
    root.add_cell("ourotiny-1chip", "ourotiny", "seq32x2", 1, CELL)
    return "ourotiny-1chip"


def test_the_file_states_what_the_reference_builds_and_counts(cfg):
    reference = load("references")
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == cfg["parameters"]
    # ISSUE 30's table: 8 blocks, the embedding and the head, N_f, gate.
    block = 16_777_216 + 34_603_008
    assert reference.block_params(cfg) == block == 51_380_224
    assert cfg["parameters"] == (8 * (block + 4 * 2048)
                                 + 2 * 49_152 * 2048 + 2048 + 2049)
    # ... and its count of required FLOPs: 32 block applications and 4
    # uses of the head a token, 32 causal attention calls at seq 4096.
    products = 6 * (32 * block + 4 * 100_663_296)
    attention = 32 * 6 * 16 * 4096 * 128
    assert reference.flops_per_row(cfg, TRAFFIC) == 4096 * (products
                                                            + attention)
    assert round((products + attention) / 1e9, 2) == 13.89
    assert round(4096 * (products + attention) / 1e12, 1) == 56.9
    assert reference.attention_shape(cfg, TRAFFIC) == (1, 16, 4096, 128)
    assert reference.attention_layers(cfg) == 32
    assert attention == 32 * sum(flops.attention_flops(
        1, 16, 4096, 128, causal=True)) // 4096


def test_reduced_keys_and_published_values_stand_side_by_side(cfg):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}["ouro26b"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    assert entry["source"] == cfg["source"]
    for key in cfg["reduced"]:
        assert key in cfg["changed"] and key + "_published" in cfg
    assert (cfg["num_hidden_layers"],
            cfg["num_hidden_layers_published"]) == (8, 48)
    # Every width as published.
    published = dict(hidden_size=2048, num_attention_heads=16,
                     num_key_value_heads=16, head_dim=128,
                     intermediate_size=5632, vocab_size=49152,
                     total_ut_steps=4, rope_theta=1000000,
                     rms_norm_eps=1e-6, tie_word_embeddings=False)
    assert {k: cfg[k] for k in published} == published
    assert cfg["layer_types"] == ["full_attention"] * 48
    assert set(cfg["limits"]) == {"loss_gap", "grad_norm_gap",
                                  "update_norm_gap"}


def test_the_builder_runs_the_stack_as_the_file_says(cfg):
    model = load("builders").model_config(cfg, {"seq_len": 4096})
    assert (model.passes, model.sandwich_norm, model.exit_gate) == (
        4, True, True)
    assert (model.layers, model.hidden // model.heads, model.mlp_width) == (
        8, 128, 5632)
    assert model.remat == cfg["remat"] and model.attention_impl == "flash"
    assert (model.norm, model.bias, model.rope_theta) == ("rmsnorm", False,
                                                          1e6)


def test_a_whole_run_of_a_tiny_cell_on_the_cpu(bench_root, cpu_peak):
    cell = add_tiny_cell(bench_root)
    assert bench_root.snapshot() == bench_root.committed
    lines = []
    result = harness.run(bench_root.path, cell, 5, 0.3, False,
                         time.perf_counter(), on_chip=False,
                         say=lines.append)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"tokens_per_s_per_chip", "mfu",
                                      "step_ms_p90", "setup_s"}


def test_lower_precision_is_not_correct(bench_root):
    from benchmark import control
    cell = add_tiny_cell(bench_root)
    session = harness.Session(bench_root.path, cell, on_chip=False)
    lower = session.cfg["control_precision"]
    out = control.readings(session, 7, [lower])
    assert out["program"][0] is True, out["program"][1]
    assert out[lower][0] is False


# ---- the new readers, on made-up events ------------------------------------

LOOP = ("jit(hvd_train_step)", "hvd_grad", "TransformerLM", "backbone",
        "hvd_loop", "while", "body", "closed_call", "backbone.step")
REMAT = LOOP + ("backbone.step", "checkpoint", "rematted_computation")
EXIT = ("jit(hvd_train_step)", "hvd_grad", "TransformerLM", "hvd_exit",
        "TransformerLM._exits", "while", "body", "closed_call")
EVENTS = [
    (LOOP + ("block_0", "mlp_in", "dot_general"), False, 8e6),
    (LOOP + ("block_0", "attn", "hvd_flash", "hvd_flash_fwd"), True, 2e6),
    (REMAT + ("block_0", "mlp_in", "dot_general"), False, 4e6),
    (REMAT + ("block_0", "attn", "hvd_flash", "hvd_flash_fwd"), True, 2e6),
    (LOOP + ("ln_f", "mul"), False, 1e6),
    (EXIT + ("checkpoint", "TransformerLM.one", "lm_head", "dot_general"),
     False, 5e6),
    (EXIT + ("checkpoint", "rematted_computation", "TransformerLM.one",
             "lm_head", "dot_general"), False, 3e6),
    (("jit(hvd_train_step)", "hvd_grad", "hvd_exit", "mul"), False, 1e6),
    (("jit(hvd_train_step)", "hvd_grad", "TransformerLM", "backbone",
      "tok_embed", "gather"), False, 7e6),
]


@pytest.fixture
def ctx(cfg):
    return Context(scope_events=EVENTS, seen={"done": [0.0, 1.0]},
                   reference=load("references"), device_kind="TPU v5 lite",
                   cell={"cfg": cfg, "traffic_params": TRAFFIC})


@pytest.mark.parametrize("name,ms", [
    ("loop_ms", 8.5), ("exit_ms", 4.5), ("remat_ms", 4.5)])
def test_scope_readers_sum_their_scopes(ctx, name, ms):
    assert reader(name).read(ctx) == pytest.approx(ms)


@pytest.mark.parametrize("name", ["loop_ms", "exit_ms", "remat_ms",
                                  "loop_flash_roofline"])
def test_readers_find_nothing_where_the_program_has_no_such_scope(name):
    """As on the parent commit, or in a cell of another configuration:
    None, and no error."""
    class Reference:
        attention_shape = staticmethod(lambda cfg, traffic: (1, 2, 64, 32))
    ctx = Context(scope_events=[EVENTS[-1]], scopes={"by_kernel": {}},
                  seen={"done": [0.0, 1.0]}, reference=Reference,
                  device_kind="TPU v5 lite",
                  cell={"cfg": {}, "traffic_params": {}})
    assert reader(name).read(ctx) is None
    untraced = Context(trace_dir=None, seen={"done": [0.0]},
                       reference=Reference,
                       cell={"cfg": {}, "traffic_params": {}})
    assert reader(name).read(untraced) is None


def test_loop_flash_roofline_counts_layers_times_passes(cfg):
    reference = load("references")
    one = sum(flops.attention_flops(1, 16, 4096, 128, causal=True)) / 197e12
    need = 8 * 4 * one
    # Two kernels; a forward kernel run again under recomputation is in
    # the time and not in the requirement.
    kernels = {"hvd_flash_fwd": 0.5 * need * 1e9,
               "hvd_flash_bwd_dkdv": 1.5 * need * 1e9}
    ctx = Context(scopes={"by_kernel": kernels}, seen={"done": [0.0]},
                  reference=reference, device_kind="TPU v5 lite",
                  cell={"cfg": cfg, "traffic_params": TRAFFIC})
    assert reader("loop_flash_roofline").read(ctx) == pytest.approx(50.0)
    # Another configuration's cell, whose reference counts its layers
    # too, is not this reader's to read.
    other = Context(ctx, cell={"cfg": {"num_hidden_layers": 5},
                               "traffic_params": TRAFFIC})
    assert reader("loop_flash_roofline").read(other) is None


# ---- the flash kernels at the cell's shape, for a described v5e ------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops it, skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_flash_gradient_compiles_for_v5e_at_head_dim_128(
        one_chip, monkeypatch, cfg):
    # The kernel asks the default backend whether to interpret; here
    # that is the CPU, and the compile is for the TPU.
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    shape = load("references").attention_shape(cfg, TRAFFIC)
    assert shape == (1, 16, 4096, 128)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True,
                                 block_q=cfg["flash_tile"],
                                 block_k=cfg["flash_tile"])
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    # Forward and the one backward kernel: the two Mosaic calls a layer
    # makes.
    assert compiled.as_text().count("tpu_custom_call") == 2
