"""The ``smallthinker21b`` configuration's benchmark files on the CPU:
what the configuration file states against the catalog's published
numbers and against what its plain reference builds and counts, a whole
run of a tiny cell through the harness with the new builder, the control
in lower precision, the new per-layer readers on made-up events, and the
cell's kernels, its expert layer and its whole step compiled for a
described TPU v5e. (The layer tests proper are
``tests/test_smallthinker.py``.)"""

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
CELL = "smallthinker21b-seq16384-1chip"
TRAFFIC = {"rows_per_chip": 1, "seq_len": 16384}
LAYOUT = [0, 1, 1, 1] * 13
# The catalog's ``config`` for the model (the model-configs guide's
# architectures.jsonl), every key of it.
PUBLISHED = dict(
    head_dim=128, hidden_size=2560, max_position_embeddings=16384,
    model_name="smallthinker_21b_instruct", moe_ffn_hidden_size=768,
    moe_num_active_primary_experts=6, moe_num_primary_experts=64,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    num_attention_heads=28, num_hidden_layers=52, num_key_value_heads=4,
    rms_norm_eps=1e-6, rope_layout=LAYOUT, rope_scaling=None,
    rope_theta=1500000, sliding_window_layout=LAYOUT,
    sliding_window_size=4096, tie_word_embeddings=False, vocab_size=151936)
TINY = dict(
    hidden_size=128, num_attention_heads=14, num_key_value_heads=2,
    head_dim=16, moe_ffn_hidden_size=64, moe_num_primary_experts=4,
    moe_num_primary_experts_published=16, experts_held=[4, 8],
    vocab_size=96, sliding_window_size=16, attention_impl="einsum",
    # This tiny size's own limits (hidden 128, 2 x 64 tokens, a window
    # of 16), read on the CPU as PERF.md reads the cell's on the chip:
    # the program's largest over seeds 1-8 is 6.4e-4 / 0.010 / 0.0017,
    # the int8 control's smallest 2.2e-4 / 0.0089 / 0.0017. At this size
    # no one limit tells the control apart on every seed (a token that
    # picks another expert than the reference moves a leaf as far as
    # int8 operands do); one or another of the three does on each of
    # the eight.
    limits={"loss_gap": 6.9e-4, "grad_norm_gap": 0.0125,
            "update_norm_gap": 0.0021})


def load(name):
    return harness.load_module(REPO, f"benchmark/{name}/smallthinker.py")


def reader(name):
    return harness.load_module(REPO, f"benchmark/layer_metrics/{name}.py")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO,
                           "benchmark/configs/smallthinker21b.json")) as f:
        return json.load(f)


def add_tiny_cell(root):
    root.add_config("thinkertiny", "smallthinker21b", **TINY)
    root.add_traffic(
        "seq64x2", "seq16384x1", rows_per_chip=2, seq_len=64,
        units_per_row=64,
        fields=[{"dist": "randint", "high": "vocab_size", "shape": [65],
                 "dtype": "int32", "next_token": True}])
    root.add_cell("thinkertiny-1chip", "thinkertiny", "seq64x2", 1, CELL)
    return "thinkertiny-1chip"


def test_every_published_key_is_kept_or_listed_as_reduced(cfg):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["smallthinker21b"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key + "_published"] == value and key in cfg["changed"]
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"], cfg["experts_held"]) == (4, 16, 18992,
                                                        [0, 16])
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert "4 chips share each layer" in cfg["deployment"]
    assert "over 8 chips" in cfg["deployment"]
    for item in ("router_input", "expert_activation", "attention", "window",
                 "rope_pairing", "router_loss", "secondary_experts",
                 "dense_ffn", "optimizer", "initializer"):
        assert len(cfg["assumed"][item]) > 40, item
    assert set(cfg["limits"]) == {"loss_gap", "grad_norm_gap",
                                  "update_norm_gap"}
    assert cfg["fit"].startswith("rule:") and "limits_set_from" in cfg
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker21b", "seq16384x1", 1)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m["workloads"]}
    assert {"swa_flash_roofline", "swa_full_ms", "swa_window_ms",
            "swa_blocks_skipped_pct", "moe_draw_imbalance", "moe_ms",
            "moe_route_ms", "moe_experts_roofline", "moe_held_pairs",
            "moe_sized_pct", "flash_fwd_ms", "flash_dkdv_ms",
            "flash_glue_ms", "hbm_gb"} <= mine
    # Readers that take the head dimension as hidden / heads, or one
    # full causal call a layer, do not list the cell.
    assert not mine & {"flash_dq_ms", "flash_roofline", "flash_fwd_roofline",
                       "flash_bwd_roofline", "flash_window_skipped_pct"}


def test_the_file_states_what_the_reference_builds_and_counts(cfg):
    reference = load("references")
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    built = sum(x.size for x in jax.tree.leaves(shapes))
    assert built == cfg["parameters"] == 559_290_880
    # ISSUE 38's table, by hand.
    attention = 2560 * 4608 + 3584 * 2560
    router, norms, experts = 2560 * 64, 2 * 2560, 16 * 3 * 2560 * 768
    assert (attention, router, experts) == (20_971_520, 163_840, 94_371_840)
    layer = attention + router + norms + experts
    assert layer == 115_512_320
    assert built == 4 * layer + 2 * 18_992 * 2560 + 2560
    # The same equations over all 52 layers, 64 experts and the whole
    # vocabulary give the published 21B.
    whole = (52 * (attention + router + norms + 4 * experts)
             + 2 * 151_936 * 2560 + 2560)
    assert round(whole / 1e9, 1) == 21.5
    assert reference.kinds(cfg) == ["full", "sliding_rope", "sliding_rope",
                                    "sliding_rope"]
    assert reference.attention_layers(cfg) == 4
    assert reference.expert_params(cfg) == (1.5 * 3 * 2560 * 768, 0)
    # Matrix parameters a token meets in a block: q, k, v and o, the
    # router, 1.5 held experts by expectation.
    block = attention + router + 1.5 * 3 * 2560 * 768
    assert reference.block_params(cfg) == block == 29_982_720
    # Attention at seq 16384: 28 heads, two products 128 wide over the
    # keys a query sees, three times.
    seen_full = 16384 * 16385 // 2
    seen_window = 4096 * 4097 // 2 + (16384 - 4096) * 4096
    assert reference.keys_seen(16384, None) == seen_full
    assert reference.keys_seen(16384, 4096) == seen_window
    assert seen_window / seen_full == pytest.approx(0.4375, abs=2e-4)
    operations = 3 * 2 * 2 * 28 * 128 * (seen_full + 3 * seen_window)
    assert reference.attention_work(cfg, TRAFFIC)[0] == operations
    assert reference.flops_per_row(cfg, TRAFFIC) == (
        6 * 16384 * (4 * block + 2560 * 18_992) + operations)
    per_token = reference.flops_per_row(cfg, TRAFFIC) / 16384
    assert round(per_token / 3 / 1e6, 1) == 608.7        # forward
    assert round(reference.flops_per_row(cfg, TRAFFIC) / 1e12, 1) == 29.9
    assert round(100 * operations / reference.flops_per_row(cfg, TRAFFIC),
                 1) == 44.6
    # Bytes: q and o 3584 wide, k and v 512 each, bfloat16: q, k, v in
    # and o out; q, k, v, o, do in; dq, dk, dv out; four layers.
    q, kv = 3584, 1024
    assert reference.attention_work(cfg, TRAFFIC)[1] == 4 * 2 * 16384 * (
        (2 * q + kv) + (3 * q + kv) + (q + kv))
    flops, moved = reference.expert_products(cfg, TRAFFIC)
    assert flops == 4 * 6 * 16384 * 1.5 * 3 * 2560 * 768
    assert round(100 * flops / reference.flops_per_row(cfg, TRAFFIC),
                 1) == 11.6
    assert moved == 4 * (3 * 4 * experts + 4 * 2 * 16384 * 2560)


def test_the_builder_runs_the_stack_as_the_file_says(cfg):
    model = load("builders").model_config(cfg, {"seq_len": 16384})
    assert model.mixers == ("full", "sliding_rope", "sliding_rope",
                            "sliding_rope")
    assert (model.hidden, model.heads, model.kv_heads, model.head_dim,
            model.window, model.vocab_size, model.layers) == (
        2560, 28, 4, 128, 4096, 18992, 4)
    assert model.rope_theta == 1.5e6 and model.norm_eps == 1e-6
    assert not (model.use_rope or model.positions or model.bias
                or model.tie_embeddings)
    assert model.norm == "rmsnorm" and model.mla is None
    assert model.remat == cfg["remat"] and model.attention_impl == "flash"
    moe = model.moe
    assert (moe.experts, moe.per_token, moe.width, moe.held, moe.shared,
            moe.first_dense, moe.scale) == (64, 6, 768, (0, 16), 0, 0, 1.0)
    assert (moe.scoring, moe.gate, moe.router_reads) == (
        "softmax", "relu", "attention")
    from horovod_tpu.parallel.moe import sized_rows
    assert sized_rows(16384 * 6, 16, 64) == 49_152


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
FULL = GRAD + ("block_0", "attn", "hvd_attn_full", "hvd_flash")
WINDOW = GRAD + ("block_2", "attn", "hvd_attn_window", "hvd_flash")
EVENTS = [
    (FULL + ("hvd_flash_fwd",), True, 32e6),
    (FULL + ("hvd_flash_bwd_dkdv",), True, 64e6),
    (FULL + ("pad",), False, 1e6),
    (WINDOW + ("hvd_flash_fwd",), True, 48e6),
    (WINDOW + ("hvd_flash_bwd_dkdv",), True, 96e6),
    (GRAD + ("block_2", "attn", "qkv", "dot_general"), False, 6e6),
    (GRAD + ("block_2", "moe", "hvd_moe", "experts", "ragged-dot-none"),
     True, 5e6),
    (GRAD + ("tok_embed", "gather"), False, 7e6),
]


@pytest.fixture
def ctx(cfg):
    return Context(scope_events=EVENTS, seen={"done": [0.0, 1.0]},
                   reference=load("references"), device_kind="TPU v5 lite",
                   root=REPO, cell={"cfg": cfg, "traffic_params": TRAFFIC})


@pytest.mark.parametrize("name,ms", [("swa_full_ms", 48.0),
                                     ("swa_window_ms", 72.0)])
def test_kernel_readers_sum_the_kernels_under_their_scope(ctx, name, ms):
    """The kernels only: neither the pad beside them nor another
    layer's grouped products."""
    assert reader(name).read(ctx) == pytest.approx(ms)


def test_flash_roofline_counts_what_masks_and_widths_leave(ctx, cfg):
    operations, moved = load("references").attention_work(cfg, TRAFFIC)
    need = max(operations / 197e12, moved / 819e9)
    assert need == operations / 197e12      # FLOP-bound at seq 16384
    assert reader("swa_flash_roofline").read(ctx) == pytest.approx(
        100.0 * need / 120e-3)
    assert 0 < reader("swa_flash_roofline").read(ctx) < 100


def test_blocks_skipped_is_the_programs_own_count_at_the_files_head_dim(
        ctx, cfg):
    from horovod_tpu.ops import flash_attention
    kinds = flash_attention.subtile_counts(
        "fwd", 16384, 16384, 1024, 1024, True, head_dim=128, window=4096)
    want = 100.0 * kinds["window"] / (kinds["interior"] + kinds["masked"]
                                      + kinds["window"])
    assert reader("swa_blocks_skipped_pct").read(ctx) == pytest.approx(want)
    # A window four blocks long in a sequence of sixteen hides over half
    # of what lies under the diagonal.
    assert 50 < want < 60
    other = Context(ctx, cell={"cfg": {"flash_tile": 1024,
                                       "sliding_window": 512,
                                       "hidden_size": 2560},
                               "traffic_params": TRAFFIC})
    assert reader("swa_blocks_skipped_pct").read(other) is None


def test_draw_imbalance_is_the_worst_layers_largest_over_mean(cfg):
    builder = load("builders")
    even = jnp.full((64,), 1536.0)
    skewed = even.at[3].set(3072.0).at[40].set(9000.0)  # 40 is not held
    builder.DRAW["aux"] = {"moe_state": {"backbone": {
        "block_0": {"moe": {"bias": even, "expert_tokens": even}},
        "block_1": {"moe": {"bias": even, "expert_tokens": skewed}}}}}
    try:
        got = reader("moe_draw_imbalance").read(Context(
            cell={"cfg": cfg}, root=REPO))
        assert got == pytest.approx(3072.0 / ((15 * 1536 + 3072) / 16))
        assert reader("moe_held_pairs").read(Context(
            cell={"cfg": cfg}, root=REPO)) == 31 * 1536 + 3072
    finally:
        builder.DRAW.clear()


@pytest.mark.parametrize("name", [
    "swa_flash_roofline", "swa_full_ms", "swa_window_ms",
    "swa_blocks_skipped_pct", "moe_draw_imbalance"])
def test_readers_find_nothing_where_the_program_has_no_such_scope(name):
    """As on the parent commit, or in a cell of another configuration:
    None, and no error."""
    class Reference:
        attention_work = staticmethod(lambda cfg, traffic: (1e12, 1e9))
    ctx = Context(scope_events=[EVENTS[-1], EVENTS[-2]],
                  scopes={"by_kernel": {}}, seen={"done": [0.0, 1.0]},
                  reference=Reference, device_kind="TPU v5 lite", root=REPO,
                  cell={"cfg": {}, "traffic_params": TRAFFIC})
    assert reader(name).read(ctx) is None
    untraced = Context(trace_dir=None, seen={"done": [0.0]},
                       reference=Reference, root=REPO,
                       cell={"cfg": {}, "traffic_params": TRAFFIC})
    assert reader(name).read(untraced) is None


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


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_flash_calls_compile_for_v5e_at_the_cells_shape(one_chip,
                                                        monkeypatch, window):
    """28 query heads of 128 over 4 K/V heads at 16,384 positions,
    forward and backward: one Mosaic call each way, the group's dk and dv
    summed outside it."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    at = SingleDeviceSharding(one_chip)
    q = jax.ShapeDtypeStruct((1, 28, 16384, 128), jnp.bfloat16, sharding=at)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16, sharding=at)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, block_q=1024,
                                 block_k=1024, window=window)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_expert_layer_compiles_for_v5e_at_the_cells_shape(one_chip):
    """98,304 pairs with 16 of 64 held: both buffer sizes inside a
    conditional each way, the routing from another tensor than the
    experts' input, and no full-size buffer kept for the way back."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.parallel import moe
    at = SingleDeviceSharding(one_chip)

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=at)

    params = {"router": shape((2560, 64)),
              "w_gate": shape((16, 2560, 768)),
              "w_up": shape((16, 2560, 768)),
              "w_down": shape((16, 768, 2560))}
    tokens = shape((16384, 2560), jnp.bfloat16)

    def loss(x, h, params, weigh):
        y, _ = moe.moe_apply(x, params, jnp.zeros((64,)), k=6,
                             scoring="softmax", gate="relu", scores_from=h)
        return jnp.sum((y * weigh).astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        tokens, tokens, params, tokens).compile()
    text = compiled.as_text()
    assert len(re.findall(r" conditional\(", text)) == 2
    rows = {int(n) for n in re.findall(
        r"ragged-dot-none[.\d]* = bf16\[(\d+),(?:2560|768)\]", text)}
    assert rows == {49_152, 98_304}
    # One branch at a time and nothing kept between the ways: the
    # fallback's backward pass alone works on four 98,304-row buffers
    # of 2560 in bfloat16 (the rows, the products and the cotangents of
    # both, 0.5 GB each); 2.21 GB when this was written.
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def test_the_step_compiles_for_v5e_and_fits(one_chip, monkeypatch, cfg):
    """The whole train step at the published widths: the flash kernels
    through Mosaic under each kind's scope, the router's product at
    ``highest`` under ``hvd_moe/route``, and the device's 15.75 GiB
    enough under the file's ``remat``."""
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
    tokens = placed(jax.ShapeDtypeStruct((1, 16384), jnp.int32), P("hvd"))
    compiled = program.step.lower(params, aux, opt_state,
                                  (tokens, tokens)).compile()
    text = compiled.as_text()
    for kernel, calls in (("hvd_flash_fwd", 4), ("hvd_flash_bwd_dkdv", 4)):
        named = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and f"/{kernel}" in line]
        again = 0 if cfg["remat"] in (False, "flash") or (
            kernel != "hvd_flash_fwd") else 4
        assert len(named) == calls + again, kernel
        assert sum("hvd_attn_full" in line for line in named) * 4 == len(
            named)
        assert sum("hvd_attn_window" in line for line in named) * 4 == 3 * len(
            named)
    assert "16384,16384" not in text        # no score matrix anywhere
    assert harness.hbm_bytes(compiled) < 15.75 * 2 ** 30
    assert harness.hbm_bytes(compiled) > 0.25 * 16.9e9
