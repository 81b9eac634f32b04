"""The ``nemotron3nano30b`` configuration's benchmark files on the CPU:
what the configuration file states against the catalog's published
numbers and against what its plain reference builds and counts, a whole
run of a tiny cell through the harness with the new builder, the control
in lower precision, the three new per-layer readers on made-up events,
and the cell's flash call, recurrence, expert layer and whole step
compiled for a described TPU v5e. (The layer tests proper are
``tests/test_nemotron_h.py``.)"""

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
CELL = "nemotron3nano30b-seq16384-1chip"
TRAFFIC = {"rows_per_chip": 1, "seq_len": 16384}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# The catalog's ``config`` for the model (the model-configs guide's
# architectures.jsonl), every key of it.
PUBLISHED = dict(
    attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
    head_dim=128, hidden_size=2688, hybrid_override_pattern=PATTERN,
    intermediate_size=1856, layer_norm_epsilon=1e-5, mamba_head_dim=64,
    mamba_hidden_act="silu", mamba_num_heads=64, mamba_proj_bias=False,
    max_position_embeddings=262144, mlp_bias=False, mlp_hidden_act="relu2",
    model_type="nemotron_h", moe_intermediate_size=1856,
    moe_shared_expert_intermediate_size=3712, n_group=1, n_groups=8,
    n_routed_experts=128, n_shared_experts=1, norm_eps=1e-5,
    norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=6,
    num_hidden_layers=52, num_key_value_heads=2, num_logits_to_keep=1,
    partial_rotary_factor=1, rescale_prenorm_residual=True,
    residual_in_fp32=False, rope_theta=10000, routed_scaling_factor=2.5,
    sliding_window=None, ssm_state_size=128, tie_word_embeddings=False,
    time_step_floor=0.0001, time_step_max=0.1, time_step_min=0.001,
    topk_group=1, use_bias=False, use_conv_bias=True,
    use_mamba_kernels=True, vocab_size=131072)
TINY = dict(
    hidden_size=128, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, mamba_num_heads=8, mamba_head_dim=16, n_groups=2,
    ssm_state_size=16, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, n_routed_experts=4,
    n_routed_experts_published=16, experts_held=[4, 8], vocab_size=96,
    attention_impl="einsum", remat=False,
    # This tiny size's own limits (hidden 128, 2 x 64 tokens), read on
    # the CPU as PERF.md reads the cell's on the chip, over seeds 1-8.
    # On seeds 1, 2 and 7 the program's gradient gap is 3.6e-3 to 5.0e-3
    # and the int8 control's 7.9e-3 to 8.7e-3, and the limit between
    # them tells the two apart; on the other five a token whose sixth
    # and seventh scores lie within bfloat16's rounding picks another
    # expert than the reference and moves a router's leaf by 0.012 to
    # 0.075, program and control alike (128 tokens, 16 experts 48 wide:
    # one token is a fifth of an expert's draw), so at this size the
    # limit holds on three seeds of eight. The loss (1.0e-4 to 4.2e-4
    # against 1.6e-4 to 1.1e-3) and the update (2.8e-3 to 1.2e-2 against
    # 6.8e-3 to 1.5e-2) tell nothing here.
    limits={"loss_gap": 1e-3, "grad_norm_gap": 6e-3,
            "update_norm_gap": 0.02})


def load(name):
    return harness.load_module(REPO, f"benchmark/{name}/nemotron_h.py")


def reader(name):
    return harness.load_module(REPO, f"benchmark/layer_metrics/{name}.py")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(
            REPO, "benchmark/configs/nemotron3nano30b.json")) as f:
        return json.load(f)


def add_tiny_cell(root):
    root.add_config("nemotrontiny", "nemotron3nano30b", **TINY)
    root.add_traffic(
        "seq64x2", "seq8192x2", rows_per_chip=2, seq_len=64,
        units_per_row=64,
        fields=[{"dist": "randint", "high": "vocab_size", "shape": [65],
                 "dtype": "int32", "next_token": True}])
    root.add_cell("nemotrontiny-1chip", "nemotrontiny", "seq64x2", 1, CELL)
    return "nemotrontiny-1chip"


def test_every_published_key_is_kept_or_listed_as_reduced(cfg):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["nemotron3nano30b"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/nemotron3nano30b.json"
    assert "8 of 128 experts, layers 0-8 of 52" in entry["why"]
    assert "one of 16 expert-parallel chips" in entry["why"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key + "_published"] == value and key in cfg["changed"]
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["experts_held"], cfg["layers_held"]) == (
        9, 8, 16384, [0, 8], [0, 9])
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert "16 chips share each layer" in cfg["deployment"]
    # No width is cut.
    assert (cfg["hidden_size"], cfg["mamba_num_heads"],
            cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["n_groups"],
            cfg["conv_kernel"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["n_routed_experts_published"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"]) == (
        2688, 64, 64, 128, 8, 4, 32, 2, 128, 1856, 3712, 128, 6, 2.5)
    for item in ("attention_positions", "in_proj_order", "gated_norm",
                 "state_init", "router_bias", "rescale_prenorm_residual",
                 "router_epsilon", "router_loss", "optimizer",
                 "initializer"):
        assert len(cfg["assumed"][item]) > 40, item
        assert "TO BE" not in cfg["assumed"][item], item
    assert set(cfg["limits"]) == {"loss_gap", "grad_norm_gap",
                                  "update_norm_gap"}
    assert cfg["fit"].startswith("rule:") and "TO BE" not in cfg["fit"]
    assert "seeds" in cfg["limits_set_from"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron3nano30b", "seq16384x1", 1)
    assert len(bench["workloads"]) >= 12
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= len(
        bench["workloads"]) // 4
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m["workloads"]}
    assert {"ssd_ms", "ssd_scan_ms", "ssd_scan_roofline",
            "swa_flash_roofline", "swa_full_ms", "moe_draw_imbalance",
            "moe_ms", "moe_route_ms", "moe_experts_roofline",
            "moe_held_pairs", "moe_sized_pct", "flash_fwd_ms",
            "flash_dkdv_ms", "flash_glue_ms", "fwd_ms", "bwd_ms", "hbm_gb",
            "init_s", "setup_unnamed_s", "xla_ms", "optimizer_ms",
            "remat_ms"} <= mine
    # Readers of another call, of a window or of another family's scopes
    # do not list the cell.
    assert not mine & {"flash_dq_ms", "flash_ms", "flash_roofline",
                       "flash_fwd_roofline", "flash_bwd_roofline",
                       "swa_window_ms", "swa_blocks_skipped_pct",
                       "flash_window_skipped_pct", "mla_ms", "mtp_ms",
                       "ssm_ms", "ssm_scan_ms", "ssm_scan_roofline",
                       "gmu_ms", "diff_ms", "loop_ms", "exit_ms",
                       "shortconv_ms", "dsa_ms", "exchange_ms"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, layer in (("ssd_ms", "state-space layer"),
                        ("ssd_scan_ms", "kernel"),
                        ("ssd_scan_roofline", "kernel")):
        metric = by_name[name]
        assert (metric["layer"], metric["moves"], metric["source"]) == (
            layer, "tokens_per_s_per_chip", "device_trace")
        assert CELL in metric["workloads"]
    assert by_name["ssd_scan_roofline"]["unit"] == "%"
    assert by_name["ssd_ms"]["layer"] == by_name["ssm_ms"]["layer"]
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}[
        "tokens_per_s_per_chip"]["workloads"]


def test_the_file_states_what_the_reference_builds_and_counts(cfg):
    reference = load("references")
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    built = sum(x.size for x in jax.tree.leaves(shapes))
    assert built == cfg["parameters"] == 666_962_944
    # ISSUE 48's table, by hand.
    norm = 2688
    mixer = (2688 * 10304 + 4 * 6144 + 6144 + 3 * 64 + 4096 + 4096 * 2688)
    attention = 2688 * 36 * 128 + 32 * 128 * 2688
    shared, expert = 2 * 2688 * 3712, 2 * 2688 * 1856
    experts = 2688 * 128 + shared + 8 * expert
    assert (mixer + norm, attention + norm, experts + norm) == (
        38_744_896, 23_399_040, 100_125_312)
    assert (2688 * 128, shared, expert) == (344_064, 19_955_712, 9_977_856)
    table = 16384 * 2688
    assert 2 * table == 88_080_384
    assert built == (4 * 38_744_896 + 23_399_040 + 4 * 100_125_312 + norm
                     + 2 * table)
    assert round(built * 16 / 1e9, 2) == 10.67
    # The same equations over all 52 layers, 128 experts and the whole
    # vocabulary give the published 31.6B.
    whole = (23 * (mixer + norm) + 6 * (attention + norm)
             + 23 * (2688 * 128 + shared + 128 * expert + norm)
             + 2 * 131_072 * 2688 + norm)
    assert round(whole / 1e9, 1) == 31.6
    assert reference.pattern(cfg) == "MEMEM*EME"
    assert reference.layers(cfg) == list(range(9))
    assert reference.kinds(cfg) == ["mamba2", "none", "mamba2", "none",
                                    "mamba2", "full", "none", "mamba2",
                                    "none"]
    assert reference.ffns(cfg) == ["none", "expert", "none", "expert",
                                   "none", "none", "expert", "none",
                                   "expert"]
    assert [reference.count(cfg, k) for k in "ME*"] == [4, 4, 1]
    assert reference.mamba_dims(cfg) == (64, 64, 8, 128, 6144, 10304)
    assert reference.expert_params(cfg) == (6 * 8 / 128 * expert, shared)
    # The recurrence, counted as the recurrence: 2.10 MFLOP a token a
    # layer forward, three times over; 10,304 numbers a token a layer
    # each way, dt's 64 in float32.
    ops, moved = reference.ssd_work(cfg, TRAFFIC)
    assert 4 * 64 * 64 * 128 == 2_097_152
    assert ops == 4 * 16384 * 3 * 2_097_152
    assert 2 * 4096 + 2 * 1024 + 64 == 10_304
    assert moved == 4 * 16384 * 2 * (2 * 10_240 + 4 * 64)
    assert ops / 197e12 < moved / 819e9                     # bytes-bound
    # Attention at seq 16384: 32 heads, two products 128 wide over the
    # keys at or before a query, three times; one layer.
    seen = 16384 * 16385 // 2
    operations = 3 * 2 * 2 * 32 * 128 * seen
    assert reference.attention_work(cfg, TRAFFIC)[0] == operations
    q, kv = 4096, 512
    assert reference.attention_work(cfg, TRAFFIC)[1] == 2 * 16384 * (
        (2 * q + kv) + (3 * q + kv) + (q + kv))
    products = (4 * (2688 * 10304 + 4096 * 2688) + attention
                + 4 * (2688 * 128 + 0.375 * expert + shared) + table)
    row = reference.flops_per_row(cfg, TRAFFIC)
    assert row == 6 * 16384 * products + ops + operations
    # ISSUE 48's arithmetic, 779.7 MFLOP a token forward and 38.32 TFLOP
    # a step, counts the convolutions' taps (2 x 4 x 6144 FLOP a token a
    # layer, 0.2 MFLOP over the four); they are element-wise work, which
    # no cell's count holds: 779.5 and 38.31. The shares of the parts are
    # the issue's.
    assert round(row / 16384 / 3 / 1e6, 1) == 779.5
    assert round((row / 16384 / 3 + 4 * 2 * 4 * 6144) / 1e6, 1) == 779.7
    assert round(row / 1e12, 2) == 38.31
    shares = {"mixer products": 6 * 16384 * 4 * (2688 * 10304 + 4096 * 2688),
              "recurrence": ops, "attention layer": 6 * 16384 * attention
              + operations, "scores and values": operations,
              "expert layers": 6 * 16384 * 4 * (
                  2688 * 128 + 0.375 * expert + shared),
              "shared expert": 6 * 16384 * 4 * shared,
              "held routed": 6 * 16384 * 4 * 0.375 * expert,
              "head": 6 * 16384 * table}
    assert {k: round(100 * v / row, 1) for k, v in shares.items()} == {
        "mixer products": 39.7, "recurrence": 1.1, "attention layer": 23.2,
        "scores and values": 17.2, "expert layers": 24.7,
        "shared expert": 20.5, "held routed": 3.8, "head": 11.3}
    flops, moved = reference.expert_products(cfg, TRAFFIC)
    # The held experts' grouped products alone: the shared expert's are
    # not all under the scope the share is read on (the reference says).
    assert flops == 4 * 6 * 16384 * 0.375 * expert
    assert moved == 4 * (3 * 4 * 8 * expert + 4 * 2 * 6144 * 2688)
    assert flops / 197e12 > moved / 819e9                   # FLOP-bound


def test_the_builder_runs_the_stack_as_the_file_says(cfg):
    model = load("builders").model_config(cfg, {"seq_len": 16384})
    assert model.mixers == ("mamba2", "none", "mamba2", "none", "mamba2",
                            "full", "none", "mamba2", "none")
    assert model.ffns == ("none", "expert", "none", "expert", "none", "none",
                          "expert", "none", "expert")
    assert (model.hidden, model.heads, model.kv_heads, model.head_width,
            model.vocab_size, model.layers) == (2688, 32, 2, 128, 16384, 9)
    assert model.norm_eps == 1e-5 and model.norm == "rmsnorm"
    assert not (model.use_rope or model.positions or model.bias
                or model.tie_embeddings or model.qk_norm)
    assert model.mla is None and model.indexer is None
    assert model.remat == cfg["remat"] and model.attention_impl == "flash"
    m = model.ssm
    assert (m.d_inner, m.heads, m.head_dim, m.groups, m.d_state,
            m.d_conv) == (4096, 64, 64, 8, 128, 4)
    moe = model.moe
    assert (moe.experts, moe.per_token, moe.width, moe.held, moe.shared,
            moe.scale) == (128, 6, 1856, (0, 8), 2, 2.5)
    assert (moe.scoring, moe.gate, moe.router_reads) == (
        "sigmoid", "relu2", "ffn")
    from horovod_tpu.ops import ssd
    from horovod_tpu.parallel.moe import kept_bytes, sized_rows
    assert sized_rows(16384 * 6, 8, 128) == 12_288
    assert kept_bytes(12_288, 1856, "relu2") == 12_288 * (2 * 1856 + 4)
    assert ssd.CHUNK == cfg["ssd_chunk"] == 128
    assert ssd.state_bytes(1, 16384, 64, 64, 128) == 268_435_456


def test_a_whole_run_of_a_tiny_cell_on_the_cpu(bench_root, cpu_peak):
    cell = add_tiny_cell(bench_root)
    assert bench_root.snapshot() == bench_root.committed
    lines = []
    result = harness.run(bench_root.path, cell, 1, 0.3, False,
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
    """On seed 1 the int8 control's gradient gap is 8.0e-3 against the
    program's 3.6e-3 and a limit of 6e-3; at this size it is not told
    apart on every seed (the note beside ``TINY``'s limits)."""
    from benchmark import control
    cell = add_tiny_cell(bench_root)
    session = harness.Session(bench_root.path, cell, on_chip=False)
    lower = session.cfg["control_precision"]
    out = control.readings(session, 1, [lower])
    assert out["program"][0] is True, out["program"][1]
    assert out[lower][0] is False
    failed = {row["name"] for row in out[lower][1] if not row["ok"]}
    assert "grad_norm_gap" in failed


# ---- the new readers, on made-up events ------------------------------------

GRAD = ("jit(hvd_train_step)", "hvd_grad", "TransformerLM", "backbone")
MIXER = GRAD + ("block_0", "mamba2", "hvd_ssd")
EVENTS = [
    (MIXER + ("in_proj", "dot_general"), False, 30e6),
    (MIXER + ("scan", "jit(_fwd_call)", "bcgrls,bcsgrp->bclgrp",
              "dot_general"), False, 10e6),
    (MIXER + ("scan", "jit(_fwd_call)", "while", "body", "mul"), False, 2e6),
    (GRAD + ("block_2", "mamba2", "hvd_ssd", "scan", "jit(_bwd_call)",
             "exp"), False, 28e6),
    (GRAD + ("rematted_computation", "block_2", "mamba2", "hvd_ssd", "scan",
             "jit(_fwd_call)", "exp"), False, 8e6),
    (GRAD + ("block_4", "mamba2", "hvd_ssd", "out_proj", "dot_general"),
     False, 22e6),
    (GRAD + ("block_5", "attn", "hvd_attn_full", "hvd_flash",
             "hvd_flash_fwd"), True, 16e6),
    (GRAD + ("block_5", "attn", "hvd_attn_full", "hvd_flash",
             "hvd_flash_bwd_dkdv"), True, 32e6),
    (GRAD + ("block_1", "moe", "hvd_moe", "experts", "ragged-dot-none"),
     True, 5e6),
    (GRAD + ("block_1", "moe", "hvd_moe", "experts", "dot_general"), False,
     9e6),
    (GRAD + ("tok_embed", "gather"), False, 7e6),
]


@pytest.fixture
def ctx(cfg):
    return Context(scope_events=EVENTS, seen={"done": [0.0, 1.0]},
                   reference=load("references"), device_kind="TPU v5 lite",
                   root=REPO, cell={"cfg": cfg, "traffic_params": TRAFFIC})


@pytest.mark.parametrize("name,ms", [("ssd_ms", 50.0), ("ssd_scan_ms", 24.0),
                                     ("swa_full_ms", 24.0),
                                     ("remat_ms", 4.0)])
def test_scope_readers_sum_what_lies_under_their_scope(ctx, name, ms):
    """Two steps: the whole mixer, products and all; the recurrence
    alone, forward, made again and backward, whatever operations it is
    made of; the attention layer's kernels; what recomputation ran."""
    assert reader(name).read(ctx) == pytest.approx(ms)


def test_ssd_scan_roofline_is_the_recurrences_need_over_the_scopes_time(
        ctx, cfg):
    operations, moved = load("references").ssd_work(cfg, TRAFFIC)
    need = max(operations / 197e12, moved / 819e9)
    assert need == pytest.approx(moved / 819e9)
    assert need == pytest.approx(3.32e-3, rel=2e-3)
    got = reader("ssd_scan_roofline").read(ctx)
    assert got == pytest.approx(100.0 * need / 24e-3)
    assert 0 < got < 100
    # The cell's attention call and its experts' products against their
    # own requirements.
    ops, moved = load("references").attention_work(cfg, TRAFFIC)
    assert reader("swa_flash_roofline").read(ctx) == pytest.approx(
        100.0 * max(ops / 197e12, moved / 819e9) / 24e-3)
    flops, moved = load("references").expert_products(cfg, TRAFFIC)
    assert reader("moe_experts_roofline").read(ctx) == pytest.approx(
        100.0 * flops / 197e12 / 7e-3)


@pytest.mark.parametrize("name", ["ssd_ms", "ssd_scan_ms",
                                  "ssd_scan_roofline"])
def test_readers_find_nothing_where_the_program_has_no_such_scope(name):
    """As on the parent commit, or in a cell of another configuration:
    None, and no error; so too with a reference that counts no
    ``ssd_work``, and in a run that took no trace."""
    class Reference:
        ssd_work = staticmethod(lambda cfg, traffic: (1e12, 1e9))
    ctx = Context(scope_events=EVENTS[6:], seen={"done": [0.0, 1.0]},
                  reference=Reference, device_kind="TPU v5 lite", root=REPO,
                  cell={"cfg": {}, "traffic_params": TRAFFIC})
    assert reader(name).read(ctx) is None
    untraced = Context(trace_dir=None, seen={"done": [0.0]},
                       reference=Reference, root=REPO,
                       cell={"cfg": {}, "traffic_params": TRAFFIC})
    assert reader(name).read(untraced) is None
    if name == "ssd_scan_roofline":
        other = Context(scope_events=EVENTS, seen={"done": [0.0, 1.0]},
                        reference=object(), device_kind="TPU v5 lite",
                        root=REPO,
                        cell={"cfg": {}, "traffic_params": TRAFFIC})
        assert reader(name).read(other) is None


# ---- the cell's kernels, layers and step, for a described v5e --------------

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
    """32 query heads of 128 in groups of 16 over 2 K/V heads at 16,384
    positions, one row, forward and backward: one Mosaic call each way,
    the group's dk and dv summed outside it."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    at = SingleDeviceSharding(one_chip)
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=at)
    kv = jax.ShapeDtypeStruct((1, 16384, 2, 128), jnp.bfloat16, sharding=at)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, block_q=1024,
                                 block_k=1024, layout="bshd")
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_the_recurrence_compiles_for_v5e_at_the_cells_shape(one_chip):
    """64 heads of 64 over 8 groups of 128 at 16,384 positions in chunks
    of 128, forward and backward: no Mosaic kernel (the form that ships
    is XLA's), one loop over the chunk boundaries each way, and what the
    way back holds at once stays under 1.5 GB (1.18 when this was
    written: the L x L decay matrices are fused, not kept)."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.ops import ssd
    at = SingleDeviceSharding(one_chip)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=at)

    args = (shape((1, 16384, 64, 64)), shape((1, 16384, 64), jnp.float32),
            shape((64,), jnp.float32), shape((1, 16384, 8, 128)),
            shape((1, 16384, 8, 128)))

    def loss(u, dt, a, b, c, weigh):
        return jnp.sum(ssd.ssd(u, dt, a, b, c).astype(jnp.float32) * weigh)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args, shape((1, 16384, 64, 64))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert len(re.findall(r" while\(", text)) == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("path", ["as_on_the_chip", "as_off_it"])
def test_expert_layer_compiles_for_v5e_at_the_cells_shape(one_chip, path,
                                                          monkeypatch):
    """98,304 pairs with 8 of 128 held, experts 1856 wide (14.5 lane
    tiles) on a hidden size of 2688 (21), no gate matrix, the shared
    expert 3712 wide: both buffer sizes inside a conditional each way,
    the sized rows 12,288. On the chip the sized rows' six products are
    the kernels of ``ops/grouped_product.py`` (``hvd_moe_gmm`` four
    times, ``hvd_moe_tgmm`` twice) and XLA's grouped kernel is left the
    fallback's 98,304 rows; steered off it, as every CPU test runs,
    XLA's has both sizes."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel import moe
    from moe_fixtures import clear_traces
    at = SingleDeviceSharding(one_chip)
    monkeypatch.setattr(flash_attention, "_interpret",
                        lambda: path == "as_off_it")
    clear_traces()

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=at)

    params = {"router": shape((2688, 128)),
              "w_up": shape((8, 2688, 1856)),
              "w_down": shape((8, 1856, 2688)),
              "shared_up": shape((2688, 3712)),
              "shared_down": shape((3712, 2688))}
    tokens = shape((16384, 2688), jnp.bfloat16)

    def loss(x, params, bias, weigh):
        y, _ = moe.moe_apply(x, params, bias, k=6, scale=2.5,
                             scoring="sigmoid", gate="relu2")
        return jnp.sum((y * weigh).astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        tokens, params, shape((128,)), tokens).compile()
    clear_traces()
    text = compiled.as_text()
    assert len(re.findall(r" conditional\(", text)) == 2
    rows = {int(n) for n in re.findall(
        r"ragged-dot-none[.\d]* = bf16\[(\d+),(?:2688|1856)\]", text)}
    kernels = [re.search(r'op_name="([^"]+)"', line).group(1)
               for line in text.splitlines()
               if "tpu_custom_call" in line and "/pallas_call" in line]
    grouped = [name for name in kernels if "gmm/" in name]
    if path == "as_off_it":
        assert rows == {12_288, 98_304} and not grouped
    else:
        assert rows == {98_304}
        assert all("hvd_moe" in name and "/experts/" in name
                   for name in grouped)
        assert sum("/hvd_moe_gmm/" in name for name in grouped) == 4
        assert sum("/hvd_moe_tgmm/" in name for name in grouped) == 2
        assert sum("hvd_moe_rows" in name for name in kernels) == 2
    # One branch at a time: the fallback's backward pass works on
    # 98,304-row buffers of 2688 and 1856 in bfloat16.
    assert compiled.memory_analysis().temp_size_in_bytes < 3.0e9


def test_the_step_compiles_for_v5e_and_fits(one_chip, monkeypatch, cfg):
    """The whole train step at the published widths: the flash kernels
    through Mosaic under the full layer's scope, the Mamba-2 mixers
    under theirs with the recurrence inside ``scan``, the router's
    product under ``hvd_moe/route``, and the device's 15.75 GiB enough
    under the file's ``remat`` with a quarter of the chip well passed."""
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.ops import flash_attention
    from moe_fixtures import clear_traces
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    clear_traces()      # the expert layer's two ways are traced once
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
    tokens = placed(jax.ShapeDtypeStruct((1, 16384), jnp.int32), P("hvd"))
    compiled = program.step.lower(params, aux, opt_state,
                                  (tokens, tokens)).compile()
    clear_traces()
    text = compiled.as_text()
    assert cfg["remat"] == "dots"
    for kernel, calls in (("hvd_flash_fwd", 2), ("hvd_flash_bwd_dkdv", 1)):
        named = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and f"/{kernel}" in line]
        # One attention layer; "dots" makes its forward kernel again.
        assert len(named) == calls, kernel
        assert all("hvd_attn_full" in n and "block_5" in n for n in named)
    names = re.findall(r'op_name="([^"]+)"', text)
    for scope in ("hvd_ssd", "hvd_ssd/scan", "hvd_moe/route",
                  "hvd_moe/experts", "rematted_computation"):
        assert any(scope in n for n in names), scope
    # Four expert layers, each made again under "dots": the sized rows'
    # products are the Pallas grouped kernels, under the experts' scope.
    grouped = [n for n in names if n.endswith("/pallas_call")
               and ("/hvd_moe_gmm/" in n or "/hvd_moe_tgmm/" in n)]
    assert grouped and all("hvd_moe" in n and "/experts/" in n
                           for n in grouped)
    assert not any("block_5" in n and "hvd_ssd" in n for n in names)
    assert not any("block_1/" in n and ("ln1" in n or "hvd_ssd" in n)
                   for n in names)          # an E layer has no mixer
    assert "32,16384,16384" not in text     # no score matrix anywhere
    assert harness.hbm_bytes(compiled) < 15.75 * 2 ** 30
    assert harness.hbm_bytes(compiled) > 0.5 * 16.9e9
