"""The ``lfm2moe24b`` configuration's benchmark files on the CPU: what the
configuration file states against the catalog's published numbers and
against what its plain reference builds and counts, a whole run of a
tiny cell through the harness with the new builder, the control in lower
precision, the three new per-layer readers on made-up events, and the
cell's flash call, its expert layer and its whole step compiled for a
described TPU v5e. (The layer tests proper are
``tests/test_lfm2_moe.py``.)"""

import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_fixtures import bench_root, cpu_peak  # noqa: F401 (fixtures)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark import harness
from benchmark.layers import Context

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "lfm2moe24b-seq8192-1chip"
TRAFFIC = {"rows_per_chip": 2, "seq_len": 8192}
LAYER_TYPES = ["conv", "conv", "full_attention", "conv"] * 10
# The catalog's ``config`` for the model (the model-configs guide's
# architectures.jsonl), every key of it.
PUBLISHED = dict(
    conv_L_cache=3, conv_bias=False, hidden_size=2048,
    intermediate_size=11776, layer_types=LAYER_TYPES,
    max_position_embeddings=128000, model_type="lfm2_moe",
    moe_intermediate_size=1536, norm_eps=1e-5, norm_topk_prob=True,
    num_attention_heads=32, num_dense_layers=2, num_experts=64,
    num_experts_per_tok=4, num_hidden_layers=40, num_key_value_heads=8,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536)
TINY = dict(
    hidden_size=128, num_attention_heads=8, num_key_value_heads=2,
    intermediate_size=192, moe_intermediate_size=64, num_experts=4,
    num_experts_published=16, experts_held=[4, 8], vocab_size=96,
    embedding_fan_in=128, attention_impl="einsum",
    # This tiny size's own limits (hidden 128, 2 x 64 tokens), read on
    # the CPU as PERF.md reads the cell's on the chip: the program's
    # largest over seeds 1-8 is 1.58e-3 / 0.0213 / 0.0052, the int8
    # control's smallest 1.16e-3 / 0.0310 / 0.0056. The gradient's limit
    # tells the control apart on every seed, the update's on every seed
    # by a hair, the loss's on none (a token that picks another expert
    # than the reference moves a tiny batch's loss as far as int8
    # operands do).
    limits={"loss_gap": 2e-3, "grad_norm_gap": 0.026,
            "update_norm_gap": 0.0054})


def load(name):
    return harness.load_module(REPO, f"benchmark/{name}/lfm2_moe.py")


def reader(name):
    return harness.load_module(REPO, f"benchmark/layer_metrics/{name}.py")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark/configs/lfm2moe24b.json")) as f:
        return json.load(f)


def add_tiny_cell(root):
    root.add_config("lfm2tiny", "lfm2moe24b", **TINY)
    root.add_traffic(
        "seq64x2", "seq8192x2", rows_per_chip=2, seq_len=64,
        units_per_row=64,
        fields=[{"dist": "randint", "high": "vocab_size", "shape": [65],
                 "dtype": "int32", "next_token": True}])
    root.add_cell("lfm2tiny-1chip", "lfm2tiny", "seq64x2", 1, CELL)
    return "lfm2tiny-1chip"


def test_every_published_key_is_kept_or_listed_as_reduced(cfg):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["lfm2moe24b"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts",
        "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert "8 of 64 experts, layers 1-5 of 40, 1/8 vocabulary" in entry[
        "why"] and "one of 8 expert-parallel chips" in entry["why"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key + "_published"] == value and key in cfg["changed"]
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"], cfg["experts_held"],
            cfg["layers_held"]) == (5, 1, 8, 8192, [0, 8], [1, 6])
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert "8 chips share each layer" in cfg["deployment"]
    for item in ("tied_head", "in_proj_order", "conv_alignment", "qk_norm",
                 "rope_pairing", "router_bias", "router_epsilon",
                 "router_loss", "optimizer", "initializer"):
        assert len(cfg["assumed"][item]) > 40, item
        assert "TO BE" not in cfg["assumed"][item], item
    assert set(cfg["limits"]) == {"loss_gap", "grad_norm_gap",
                                  "update_norm_gap"}
    assert cfg["fit"].startswith("rule:") and "TO BE" not in cfg["fit"]
    assert "seeds" in cfg["limits_set_from"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2moe24b", "seq8192x2", 1)
    assert len(bench["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m["workloads"]}
    assert {"shortconv_ms", "shortconv_mix_ms", "shortconv_roofline",
            "swa_flash_roofline", "swa_full_ms", "moe_draw_imbalance",
            "moe_ms", "moe_route_ms", "moe_experts_roofline",
            "moe_held_pairs", "moe_sized_pct", "flash_fwd_ms",
            "flash_dkdv_ms", "flash_glue_ms", "hbm_gb", "init_s",
            "setup_unnamed_s", "xla_ms", "optimizer_ms"} <= mine
    assert len(mine) == 19 + 11 + 3
    # Readers of another call, of a window or of another family's scopes
    # do not list the cell.
    assert not mine & {"flash_dq_ms", "flash_ms", "flash_roofline",
                       "flash_fwd_roofline", "flash_bwd_roofline",
                       "swa_window_ms", "swa_blocks_skipped_pct",
                       "flash_window_skipped_pct", "mla_ms", "mtp_ms",
                       "ssm_ms", "gmu_ms", "diff_ms", "loop_ms", "exit_ms",
                       "remat_ms", "exchange_ms"}
    for name in ("shortconv_ms", "shortconv_mix_ms", "shortconv_roofline"):
        metric = {m["name"]: m for m in bench["per_layer"]}[name]
        assert (metric["layer"], metric["moves"], metric["source"],
                metric["workloads"]) == (
            "short convolution", "tokens_per_s_per_chip", "device_trace",
            [CELL])
    assert [m["name"] for m in bench["per_layer"][-3:]] == [
        "shortconv_ms", "shortconv_mix_ms", "shortconv_roofline"]


def test_the_file_states_what_the_reference_builds_and_counts(cfg):
    reference = load("references")
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    built = sum(x.size for x in jax.tree.leaves(shapes))
    assert built == cfg["parameters"] == 469_284_992
    # ISSUE 40's table, by hand.
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    norms, dense = 2 * 2048, 3 * 2048 * 11776
    experts = 2048 * 64 + 8 * 3 * 2048 * 1536
    assert (conv, attention, dense, experts) == (
        16_783_360, 10_485_888, 72_351_744, 75_628_544)
    assert conv + norms + dense == 89_139_200               # layer 1
    assert attention + norms + experts == 86_118_528        # layer 2
    assert conv + norms + experts == 92_416_000             # layers 3-5
    table = 8192 * 2048
    assert built == (89_139_200 + 86_118_528 + 3 * 92_416_000 + table
                     + 2048)
    assert built + table == 486_062_208                     # untied
    # The same equations over all 40 layers, 64 experts and the whole
    # vocabulary give the published 24B.
    whole_experts = 2048 * 64 + 64 * 3 * 2048 * 1536
    whole = (30 * conv + 10 * attention + 40 * norms + 2 * dense
             + 38 * whole_experts + 65_536 * 2048 + 2048)
    assert round(whole / 1e9, 1) == 23.8
    assert reference.kinds(cfg) == ["conv", "full_rope", "conv", "conv",
                                    "conv"]
    assert reference.layers(cfg) == [1, 2, 3, 4, 5]
    assert (reference.attention_layers(cfg), reference.conv_layers(cfg),
            reference.expert_layers(cfg)) == (1, 4, 4)
    assert [reference.is_expert(cfg, i) for i in range(5)] == [
        False, True, True, True, True]
    assert reference.expert_params(cfg) == (0.5 * 3 * 2048 * 1536, 0)
    # The conv mixers: two products, 16,777,216 multiply-adds a token a
    # layer, three times over; six tensor-widths of 2048 with their
    # gradients in bfloat16.
    ops, moved = reference.conv_work(cfg, TRAFFIC)
    assert ops == 4 * 8192 * 3 * 2 * 16_777_216
    assert moved == 4 * 8192 * 2 * 2 * 6 * 2048
    assert ops / 197e12 > moved / 819e9                     # FLOP-bound
    # Attention at seq 8192: 32 heads, two products 64 wide over the
    # keys at or before a query, three times; one layer.
    seen = 8192 * 8193 // 2
    operations = 3 * 2 * 2 * 32 * 64 * seen
    assert reference.attention_work(cfg, TRAFFIC)[0] == operations
    q, kv = 2048, 1024
    assert reference.attention_work(cfg, TRAFFIC)[1] == 2 * 8192 * (
        (2 * q + kv) + (3 * q + kv) + (q + kv))
    products = (4 * 16_777_216 + (2 * 2048 * 2048 + 2 * 2048 * 512)
                + dense + 4 * (2048 * 64 + 0.5 * 3 * 2048 * 1536) + table)
    row = reference.flops_per_row(cfg, TRAFFIC)
    assert row == 6 * 8192 * products + operations
    assert round(row / 8192 / 3 / 1e6, 1) == 405.8          # forward
    assert round(2 * row / 1e12, 2) == 19.95                # a step
    shares = {"conv": ops, "dense": 6 * 8192 * dense,
              "head": 6 * 8192 * table, "attention": operations}
    assert {k: round(100 * v / row, 1) for k, v in shares.items()} == {
        "conv": 33.1, "dense": 35.7, "head": 8.3, "attention": 8.3}
    flops, moved = reference.expert_products(cfg, TRAFFIC)
    assert flops == 4 * 6 * 16384 * 0.5 * 3 * 2048 * 1536
    assert round(100 * flops / (2 * row), 1) == 9.3
    assert moved == 4 * (3 * 4 * 8 * 3 * 2048 * 1536
                         + 4 * 2 * 16384 * 2048)


def test_the_builder_runs_the_stack_as_the_file_says(cfg):
    model = load("builders").model_config(cfg, {"seq_len": 8192})
    assert model.mixers == ("conv", "full_rope", "conv", "conv", "conv")
    assert (model.hidden, model.heads, model.kv_heads, model.head_width,
            model.vocab_size, model.layers, model.mlp_width,
            model.conv_taps) == (2048, 32, 8, 64, 8192, 5, 11776, 3)
    assert model.rope_theta == 1e6 and model.norm_eps == 1e-5
    assert model.qk_norm and model.tie_embeddings and model.mlp == "swiglu"
    assert not (model.use_rope or model.positions or model.bias)
    assert model.norm == "rmsnorm" and model.mla is None
    assert model.remat == cfg["remat"] and model.attention_impl == "flash"
    moe = model.moe
    assert (moe.experts, moe.per_token, moe.width, moe.held, moe.shared,
            moe.first_dense, moe.scale) == (64, 4, 1536, (0, 8), 0, 1, 1.0)
    assert (moe.scoring, moe.gate, moe.router_reads) == (
        "sigmoid", "silu", "ffn")
    from horovod_tpu.parallel.moe import sized_rows
    assert sized_rows(16384 * 4, 8, 64) == 16_384


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
    # The step kept its newest draw for the readers.
    ctx = Context(cell=harness.load_cell(bench_root.path, cell),
                  root=bench_root.path)
    assert reader("moe_held_pairs").read(ctx) > 0
    assert reader("moe_draw_imbalance").read(ctx) >= 1.0
    assert reader("moe_sized_pct").read(ctx) in (0.0, 25.0, 50.0, 75.0,
                                                 100.0)


def test_lower_precision_is_not_correct(bench_root):
    from benchmark import control
    cell = add_tiny_cell(bench_root)
    session = harness.Session(bench_root.path, cell, on_chip=False)
    lower = session.cfg["control_precision"]
    out = control.readings(session, 6, [lower])
    assert out["program"][0] is True, out["program"][1]
    assert out[lower][0] is False


# ---- the new readers, on made-up events ------------------------------------

GRAD = ("jit(hvd_train_step)", "hvd_grad", "TransformerLM", "backbone")
CONV = GRAD + ("block_0", "conv", "hvd_shortconv")
EVENTS = [
    (CONV + ("in_proj", "dot_general"), False, 40e6),
    (CONV + ("mix", "mul"), False, 6e6),
    (GRAD + ("block_3", "conv", "hvd_shortconv", "mix", "add"), False, 4e6),
    (GRAD + ("block_3", "conv", "hvd_shortconv", "out_proj", "dot_general"),
     False, 50e6),
    (GRAD + ("block_1", "attn", "hvd_attn_full", "hvd_flash",
             "hvd_flash_fwd"), True, 16e6),
    (GRAD + ("block_1", "attn", "hvd_attn_full", "hvd_flash",
             "hvd_flash_bwd_dkdv"), True, 32e6),
    (GRAD + ("block_1", "attn", "q_norm", "mul"), False, 2e6),
    (GRAD + ("block_2", "moe", "hvd_moe", "experts", "ragged-dot-none"),
     True, 5e6),
    (GRAD + ("block_0", "mlp_in", "dot_general"), False, 30e6),
    (GRAD + ("tok_embed", "gather"), False, 7e6),
]


OP = "jit(hvd_train_step)/hvd_grad/transpose(jvp(TransformerLM))/backbone"
# A compiled step in small: the product back to a mixer's input fused
# into the norm's backward pass, a weight's gradient fused into AdamW's
# update (inside a nested fusion), the gates under their own name, and
# two operations that hold none of the mixers' work.
HLO = f"""HloModule jit_hvd_train_step

%fused_norm (p0: bf16[8,4]) -> bf16[8,4] {{
  %p0 = bf16[8,4]{{1,0}} parameter(0)
  %dot.1 = bf16[8,4]{{1,0}} dot(%p0, %p0), metadata={{op_name="{OP}/block_0/conv/hvd_shortconv/in_proj/dot_general"}}
  ROOT %mul.1 = bf16[8,4]{{1,0}} multiply(%dot.1, %p0), metadata={{op_name="{OP}/block_0/ln1/mul"}}
}}

%inner (p0: bf16[8,4]) -> bf16[8,4] {{
  %p0 = bf16[8,4]{{1,0}} parameter(0)
  ROOT %dot.2 = bf16[8,4]{{1,0}} dot(%p0, %p0), metadata={{op_name="{OP}/block_3/conv/hvd_shortconv/out_proj/transpose"}}
}}

%fused_update (p0: bf16[8,4]) -> bf16[8,4] {{
  %p0 = bf16[8,4]{{1,0}} parameter(0)
  %fusion.9 = bf16[8,4]{{1,0}} fusion(%p0), kind=kOutput, calls=%inner
  ROOT %add.1 = bf16[8,4]{{1,0}} add(%fusion.9, %p0), metadata={{op_name="jit(hvd_train_step)/hvd_optimizer/add"}}
}}

%fused_dense (p0: bf16[8,4]) -> bf16[8,4] {{
  %p0 = bf16[8,4]{{1,0}} parameter(0)
  ROOT %dot.3 = bf16[8,4]{{1,0}} dot(%p0, %p0), metadata={{op_name="{OP}/block_0/mlp_in/dot_general"}}
}}

ENTRY %main (a: bf16[8,4]) -> bf16[8,4] {{
  %a = bf16[8,4]{{1,0}} parameter(0)
  %fusion.1 = bf16[8,4]{{1,0}} fusion(%a), kind=kOutput, calls=%fused_norm, metadata={{op_name="{OP}/block_0/ln1/mul"}}
  %fusion.2 = bf16[8,4]{{1,0}} fusion(%fusion.1), kind=kOutput, calls=%fused_update, metadata={{op_name="jit(hvd_train_step)/hvd_optimizer/add"}}
  %fusion.3 = bf16[8,4]{{1,0}} fusion(%fusion.2), kind=kOutput, calls=%fused_dense, metadata={{op_name="{OP}/block_0/mlp_in/dot_general"}}
  %multiply.4 = bf16[8,4]{{1,0}} multiply(%fusion.3, %a), metadata={{op_name="{OP}/block_0/conv/hvd_shortconv/mix/mul"}}
  ROOT %copy.5 = bf16[8,4]{{1,0}} copy(%multiply.4)
}}
"""
# Two steps' self time by instruction, ns.
INSTRUCTION_NS = [("fusion.1", 30e6), ("fusion.2", 60e6), ("fusion.3", 70e6),
                  ("multiply.4", 10e6), ("copy.5", 5e6)]


@pytest.fixture
def ctx(cfg):
    return Context(scope_events=EVENTS, instruction_ns=INSTRUCTION_NS,
                   hlo=HLO, seen={"done": [0.0, 1.0]},
                   reference=load("references"), device_kind="TPU v5 lite",
                   root=REPO, cell={"cfg": cfg, "traffic_params": TRAFFIC})


@pytest.mark.parametrize("name,ms", [("shortconv_ms", 50.0),
                                     ("shortconv_mix_ms", 5.0),
                                     ("swa_full_ms", 24.0)])
def test_scope_readers_sum_what_lies_under_their_scope(ctx, name, ms):
    """Two steps: the whole mixer, products and all; the gates and taps
    alone; the attention layer's kernels and not the norm beside them."""
    assert reader(name).read(ctx) == pytest.approx(ms)


def test_holders_are_the_operations_with_any_of_the_mixers_work_inside():
    """By its own name, by an instruction of its fused computation, or
    by one of a fusion nested in that; the scope as a whole part of the
    path, whatever transformation wrapped it."""
    holders = reader("shortconv_roofline").holders
    assert holders(HLO) == {"dot.1", "fusion.1", "dot.2", "fusion.9",
                            "fusion.2", "multiply.4"}
    assert holders(HLO, "mix") == {"multiply.4"}
    assert holders(HLO, "hvd_short") == set()
    assert holders(HLO, "hvd_optimizer") == {"add.1", "fusion.2"}


def test_shortconv_roofline_is_the_products_need_over_all_that_holds_them(
        ctx, cfg):
    """The time is that of every operation that holds any of the mixers'
    work (50 ms a step here, where the scope by roots reads 5 and would
    give 670%), so what XLA fuses the products into cannot push the
    share past 100."""
    operations, moved = load("references").conv_work(cfg, TRAFFIC)
    need = 2 * max(operations / 197e12, moved / 819e9)
    assert need == pytest.approx(2 * operations / 197e12)
    assert need == pytest.approx(33.5e-3, rel=2e-3)     # ISSUE 40's count
    module = reader("shortconv_roofline")
    assert module.held_ms(ctx) == pytest.approx(50.0)
    got = module.read(ctx)
    assert got == pytest.approx(100.0 * need / 50e-3)
    assert 0 < got < 100
    # The cell's attention call against its own requirement.
    ops, moved = load("references").attention_work(cfg, TRAFFIC)
    assert reader("swa_flash_roofline").read(ctx) == pytest.approx(
        100.0 * 2 * max(ops / 197e12, moved / 819e9) / 24e-3)
    assert 0 < reader("swa_flash_roofline").read(ctx) < 100


@pytest.mark.parametrize("name", ["shortconv_ms", "shortconv_mix_ms",
                                  "shortconv_roofline"])
def test_readers_find_nothing_where_the_program_has_no_such_scope(name):
    """As on the parent commit, or in a cell of another configuration:
    None, and no error; so too with a reference that counts no
    ``conv_work``, and in a run that took no trace."""
    class Reference:
        conv_work = staticmethod(lambda cfg, traffic: (1e12, 1e9))
    ctx = Context(scope_events=EVENTS[4:], seen={"done": [0.0, 1.0]},
                  instruction_ns=INSTRUCTION_NS,
                  hlo=HLO.replace("hvd_shortconv", "hvd_gmu"),
                  reference=Reference, device_kind="TPU v5 lite", root=REPO,
                  cell={"cfg": {}, "traffic_params": TRAFFIC})
    assert reader(name).read(ctx) is None
    untraced = Context(trace_dir=None, seen={"done": [0.0]},
                       reference=Reference, root=REPO,
                       cell={"cfg": {}, "traffic_params": TRAFFIC})
    assert reader(name).read(untraced) is None
    if name == "shortconv_roofline":
        other = Context(scope_events=EVENTS, seen={"done": [0.0, 1.0]},
                        instruction_ns=INSTRUCTION_NS, hlo=HLO,
                        reference=object(), device_kind="TPU v5 lite",
                        root=REPO,
                        cell={"cfg": {}, "traffic_params": TRAFFIC})
        assert reader(name).read(other) is None


# ---- the cell's kernels, expert layer and step, for a described v5e --------

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
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_flash_call_compiles_for_v5e_at_the_cells_shape(one_chip,
                                                            monkeypatch):
    """32 query heads of 64 in groups of 4 over 8 K/V heads at 8,192
    positions, two rows, forward and backward: one Mosaic call each way,
    the group's dk and dv summed outside it."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    at = SingleDeviceSharding(one_chip)
    q = jax.ShapeDtypeStruct((2, 32, 8192, 64), jnp.bfloat16, sharding=at)
    kv = jax.ShapeDtypeStruct((2, 8, 8192, 64), jnp.bfloat16, sharding=at)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, block_q=1024,
                                 block_k=1024)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_expert_layer_compiles_for_v5e_at_the_cells_shape(one_chip):
    """65,536 pairs with 8 of 64 held, sigmoid scores, no shared expert:
    both buffer sizes inside a conditional each way, and the sized rows
    twice ``glm47flash``'s at the same widths."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.parallel import moe
    at = SingleDeviceSharding(one_chip)

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=at)

    params = {"router": shape((2048, 64)),
              "w_gate": shape((8, 2048, 1536)),
              "w_up": shape((8, 2048, 1536)),
              "w_down": shape((8, 1536, 2048))}
    tokens = shape((16384, 2048), jnp.bfloat16)

    def loss(x, params, bias, weigh):
        y, _ = moe.moe_apply(x, params, bias, k=4, scoring="sigmoid")
        return jnp.sum((y * weigh).astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        tokens, params, shape((64,)), tokens).compile()
    text = compiled.as_text()
    assert len(re.findall(r" conditional\(", text)) == 2
    rows = {int(n) for n in re.findall(
        r"ragged-dot-none[.\d]* = bf16\[(\d+),(?:2048|1536)\]", text)}
    assert rows == {16_384, 65_536}
    # One branch at a time: the fallback's backward pass works on
    # 65,536-row buffers of 2048 and 1536 in bfloat16; 1.6 GB when this
    # was written.
    assert compiled.memory_analysis().temp_size_in_bytes < 2.0e9


def test_the_step_compiles_for_v5e_and_fits(one_chip, monkeypatch, cfg):
    """The whole train step at the published widths: the flash kernels
    through Mosaic under the full layer's scope, the conv mixers under
    theirs with the gates and taps inside ``mix``, the router's product
    under ``hvd_moe/route``, and the device's 15.75 GiB enough under the
    file's ``remat`` with a quarter of the chip well passed."""
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.ops import flash_attention
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    reference = load("references")
    traffic = dict(harness.load_cell(REPO, CELL)["traffic_params"])
    mesh = Mesh(np.array([one_chip]), ("hvd",))
    program = load("builders").build(cfg, traffic, mesh, hvd_jax)

    def placed(tree, spec=P()):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

    params = placed(jax.eval_shape(
        lambda k: reference.init_params(cfg, k), jax.random.PRNGKey(0)))
    aux = placed(jax.eval_shape(lambda: reference.init_aux(cfg)))
    opt_state = placed(jax.eval_shape(
        lambda p: program.init_state(p, {})[2], params))
    tokens = placed(jax.ShapeDtypeStruct((2, 8192), jnp.int32), P("hvd"))
    compiled = program.step.lower(params, aux, opt_state,
                                  (tokens, tokens)).compile()
    text = compiled.as_text()
    assert cfg["remat"] is False
    for kernel in ("hvd_flash_fwd", "hvd_flash_bwd_dkdv"):
        named = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and f"/{kernel}" in line]
        assert len(named) == 1, kernel      # one attention layer
        assert "hvd_attn_full" in named[0] and "block_1" in named[0]
    names = re.findall(r'op_name="([^"]+)"', text)
    for scope in ("hvd_shortconv", "hvd_shortconv/mix", "hvd_moe/route",
                  "hvd_moe/experts"):
        assert any(scope in n for n in names), scope
    assert not any("block_1" in n and "hvd_shortconv" in n for n in names)
    assert "32,8192,8192" not in text       # no score matrix anywhere
    assert harness.hbm_bytes(compiled) < 15.75 * 2 ** 30
    assert harness.hbm_bytes(compiled) > 0.5 * 16.9e9
