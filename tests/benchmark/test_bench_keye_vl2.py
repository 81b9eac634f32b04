"""The ``keyevl30b`` configuration's benchmark files on the CPU: what the
configuration file states against the catalog's published numbers and
against what its plain reference builds and counts, a whole run of a
tiny cell through the harness with the new builder, the control in lower
precision, the new per-layer readers on made-up events, and the cell's
flash call under a mask and one layer of its step compiled for a
described TPU v5e. (The layer tests proper are
``tests/test_keye_vl2.py``.)"""

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
CELL = "keyevl30b-seq16384-1chip"
TRAFFIC = {"rows_per_chip": 1, "seq_len": 16384}
# The catalog's ``config`` for the model (the model-configs guide's
# architectures.jsonl), every key of it.
PUBLISHED = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=128,
    hidden_act="silu", hidden_size=2048, intermediate_size=6144,
    max_position_embeddings=262144, max_window_layers=48,
    mlp_only_layers=[], model_type="KeyeVL2", moe_intermediate_size=768,
    norm_topk_prob=True, num_attention_heads=32, num_experts=128,
    num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4,
    num_local_experts=128, rms_norm_eps=1e-06,
    rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                  "type": "default"},
    rope_theta=10000000,
    sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 2048},
    sliding_window=None, tie_word_embeddings=False,
    use_sliding_window=False, vocab_size=151936)
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, moe_intermediate_size=24, num_experts=2,
    num_local_experts=2, num_experts_published=8, num_experts_per_tok=2,
    experts_held=[2, 4], vocab_size=96, num_hidden_layers=2,
    rope_scaling={"mrope_section": [2, 3, 3]},
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 2,
               "indexer_num_kv_heads": 1, "topk": 8},
    row_layout=[["text", 16], ["image", 4, 4], ["text", 16]],
    # This tiny size's own limits (hidden 64, 2 x 48 tokens a step),
    # read on the CPU as PERF.md reads the cell's on the chip. One token
    # that picks another expert than the reference, or one query another
    # eighth key, moves a router's or an indexer's gradient by a tenth
    # here, so only some seeds tell the program from the control: over
    # seeds 4, 5 and 8 the program's largest is 3.9e-3 / 0.0218 / 0.0156
    # and the int8 control's smallest 3.3e-3 / 0.0243 / 0.0215. The
    # gradient's and the update's limits tell the control apart on those
    # seeds, the loss's on none.
    limits={"loss_gap": 4e-3, "grad_norm_gap": 0.023,
            "update_norm_gap": 0.018})


def load(name):
    return harness.load_module(REPO, f"benchmark/{name}/keye_vl2.py")


def reader(name):
    return harness.load_module(REPO, f"benchmark/layer_metrics/{name}.py")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark/configs/keyevl30b.json")) as f:
        return json.load(f)


def add_tiny_cell(root):
    root.add_config("keyetiny", "keyevl30b", **TINY)
    root.add_traffic(
        "seq48x2", "seq16384x1", rows_per_chip=2, seq_len=48,
        units_per_row=48,
        fields=[{"dist": "randint", "high": "vocab_size", "shape": [49],
                 "dtype": "int32", "next_token": True}])
    root.add_cell("keyetiny-1chip", "keyetiny", "seq48x2", 1, CELL)
    return "keyetiny-1chip"


def test_every_published_key_is_kept_or_listed_as_reduced(cfg):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["keyevl30b"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert "16 of 128 experts, 4 of 48 layers, 1/8 vocabulary" in entry[
        "why"] and "one of 8 expert-parallel chips" in entry["why"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key + "_published"] == value and key in cfg["changed"]
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["num_local_experts"], cfg["vocab_size"], cfg["experts_held"],
            cfg["layers_held"]) == (4, 16, 16, 18992, [0, 16], [0, 4])
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert "8 chips share each layer" in cfg["deployment"]
    for item in ("indexer_rope", "indexer_norm", "indexer_weights",
                 "indexer_input", "selection", "alignment_loss", "qk_norm",
                 "mrope", "row_layout", "router_loss", "dense_ffn",
                 "optimizer", "initializer"):
        assert len(cfg["assumed"][item]) > 40, item
        assert "TO BE" not in cfg["assumed"][item], item
    assert "NOT as selection by blocks" in cfg["assumed"]["selection"]
    assert set(cfg["limits"]) == {"loss_gap", "grad_norm_gap",
                                  "update_norm_gap"}
    assert cfg["fit"].startswith("rule:") and "TO BE" not in cfg["fit"]
    assert "seeds" in cfg["limits_set_from"]
    assert sum(n if kind == "text" else n * m[0] for kind, n, *m in
               cfg["row_layout"]) == 16384
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "keyevl30b", "seq16384x1", 1)
    assert len(cell["why"]) <= 200 and "1024 tokens" in cell["why"]
    assert "over their share" in cell["why"]
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m["workloads"]}
    assert {"dsa_ms", "dsa_index_ms", "dsa_select_ms", "dsa_attend_ms",
            "dsa_align_ms", "dsa_attend_roofline", "dsa_selected_keys",
            "moe_draw_imbalance", "moe_ms", "moe_route_ms",
            "moe_experts_roofline", "moe_held_pairs", "moe_sized_pct",
            "flash_fwd_ms", "flash_dkdv_ms", "flash_glue_ms", "hbm_gb",
            "init_s", "setup_unnamed_s", "xla_ms", "fwd_ms", "bwd_ms",
            "device_idle_pct", "optimizer_ms"} <= mine
    # Readers of another call, of a window or of another family's scopes
    # do not list the cell; nor do the flash rooflines, which take the
    # head dimension as hidden / heads.
    assert not mine & {"flash_dq_ms", "flash_ms", "flash_roofline",
                       "flash_fwd_roofline", "flash_bwd_roofline",
                       "swa_flash_roofline", "swa_full_ms", "swa_window_ms",
                       "swa_blocks_skipped_pct", "flash_window_skipped_pct",
                       "mla_ms", "mtp_ms", "ssm_ms", "gmu_ms", "diff_ms",
                       "loop_ms", "exit_ms", "remat_ms", "exchange_ms",
                       "shortconv_ms"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("dsa_ms", "dsa_index_ms", "dsa_select_ms", "dsa_attend_ms",
                 "dsa_align_ms", "dsa_attend_roofline", "dsa_selected_keys"):
        metric = by_name[name]
        assert (metric["moves"], metric["workloads"]) == (
            "tokens_per_s_per_chip", [CELL])
        assert metric["layer"] == ("kernel" if "attend" in name
                                   else "learned sparse attention")
        assert metric["source"] == ("program_counter" if name
                                    == "dsa_selected_keys"
                                    else "device_trace")
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "layer_metrics", name + ".py"))
    assert CELL in {e["name"]: e for e in bench["end_to_end"]}[
        "tokens_per_s_per_chip"]["workloads"]


def test_the_file_states_what_the_reference_builds_and_counts(cfg):
    reference = load("references")
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    built = sum(x.size for x in jax.tree.leaves(shapes))
    assert built == cfg["parameters"] == 465_391_104
    # ISSUE 45's table, by hand.
    attention = 2048 * 5120 + 4096 * 2048 + 2 * 128
    indexer = 2048 * 1024 + 2048 * 64 + 2048 * 16 + 128
    router, experts = 2048 * 128, 16 * 3 * 2048 * 768
    assert (attention, indexer, router, experts) == (
        18_874_624, 2_261_120, 262_144, 75_497_472)
    layer = attention + indexer + router + experts + 2 * 2048
    assert layer == 96_899_456
    table = 2 * 18_992 * 2048
    assert built == 4 * layer + 2048 + table
    block = shapes["params"]["backbone"]["block_0"]
    assert sum(x.size for x in jax.tree.leaves(
        block["attn"]["indexer"])) == indexer
    assert round(built * 16 / 1e9, 2) == 7.45
    # The same equations over all 48 layers, 128 experts and the whole
    # vocabulary give the published 30B.
    whole = (48 * (attention + indexer + router + 8 * experts + 4096)
             + 2 * 151_936 * 2048 + 2048)
    assert round(whole / 1e9, 1) == 30.6
    assert reference.kinds(cfg) == ["sparse_rope"] * 4
    assert reference.attention_layers(cfg) == 4
    assert reference.expert_params(cfg) == (3 * 2048 * 768, 0)
    assert reference.selected_pairs(cfg, TRAFFIC) == 31_458_304
    assert reference.causal_pairs(TRAFFIC) == 134_225_920
    assert round(31_458_304 / 134_225_920, 4) == 0.2344
    assert round(31_458_304 / 16384, 2) == 1920.06
    # Attention over the selected pairs: 32 heads, two products 128
    # wide, three times; four layers.
    operations, moved = reference.attention_work(cfg, TRAFFIC)
    assert operations == 4 * 3 * 2 * 2 * 32 * 128 * 31_458_304
    q, kv = 4096, 1024
    assert moved == 4 * 2 * 16384 * ((2 * q + kv) + (3 * q + kv) + (q + kv))
    assert operations / 197e12 > moved / 819e9              # FLOP-bound
    scores, _ = reference.index_work(cfg, TRAFFIC)
    assert scores == 4 * 3 * 2 * 16 * 64 * 134_225_920
    # A token, forward, in MFLOP (ISSUE 45's list).
    per_layer = {
        "qkvo": 2 * (2048 * 5120 + 4096 * 2048),
        "indexer": 2 * (2048 * 1024 + 2048 * 64 + 2048 * 16),
        "scores": scores / 4 / 3 / 16384,
        "attention": operations / 4 / 3 / 16384,
        "router": 2 * 2048 * 128, "experts": 2 * 3 * 2048 * 768}
    assert {k: round(v / 1e6, 2) for k, v in per_layer.items()} == {
        "qkvo": 37.75, "indexer": 4.52, "scores": 16.78,
        "attention": 31.46, "router": 0.52, "experts": 9.44}
    head = 2 * 2048 * 18_992
    forward = 4 * sum(per_layer.values()) + head
    assert round(forward / 1e6, 1) == 479.7
    row = reference.flops_per_row(cfg, TRAFFIC)
    assert row == pytest.approx(3 * 16384 * forward, rel=1e-12)
    assert round(row / 1e12, 1) == 23.6                     # a step
    assert round(1e3 * row / 197e12) == 120                 # ms at the peak
    shares = {"attention": operations, "scores": scores,
              "head": 3 * 16384 * head,
              "experts": 4 * 3 * 16384 * per_layer["experts"],
              "products": 4 * 3 * 16384 * (
                  per_layer["qkvo"] + per_layer["indexer"])}
    assert {k: round(100 * v / row) for k, v in shares.items()} == {
        "attention": 26, "scores": 14, "head": 16, "experts": 8,
        "products": 35}
    flops, moved = reference.expert_products(cfg, TRAFFIC)
    assert flops == 4 * 6 * 16384 * 3 * 2048 * 768
    assert moved == 4 * (3 * 4 * 16 * 3 * 2048 * 768
                         + 4 * 2 * 16384 * 2048)
    from horovod_tpu.parallel.moe import sized_rows
    assert sized_rows(16384 * 8, 16, 128) == 32_768


def test_the_builder_runs_the_stack_as_the_file_says(cfg):
    from horovod_tpu.models.transformer import IndexerConfig
    model = load("builders").model_config(cfg, {"seq_len": 16384})
    assert model.mixers == ("sparse_rope",) * 4
    assert (model.hidden, model.heads, model.kv_heads, model.head_width,
            model.vocab_size, model.layers) == (2048, 32, 4, 128, 18992, 4)
    assert model.rope_theta == 1e7 and model.norm_eps == 1e-6
    assert model.qk_norm and not model.tie_embeddings
    assert not (model.use_rope or model.positions or model.bias)
    assert model.norm == "rmsnorm" and model.mla is None
    assert model.remat == cfg["remat"] and model.attention_impl == "flash"
    assert model.indexer == IndexerConfig(heads=16, head_dim=64, topk=2048)
    assert model.rope_sections == (16, 24, 24)
    assert model.rope_layout == (("text", 3072), ("image", 32, 32)) * 4
    moe = model.moe
    assert (moe.experts, moe.per_token, moe.width, moe.held, moe.shared,
            moe.first_dense, moe.scale) == (128, 8, 768, (0, 16), 0, 0, 1.0)
    assert (moe.scoring, moe.gate, moe.router_reads) == (
        "softmax", "silu", "ffn")


def test_a_whole_run_of_a_tiny_cell_on_the_cpu(bench_root, cpu_peak):
    cell = add_tiny_cell(bench_root)
    assert bench_root.snapshot() == bench_root.committed
    lines = []
    result = harness.run(bench_root.path, cell, 8, 0.3, False,
                         time.perf_counter(), on_chip=False,
                         say=lines.append)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"tokens_per_s_per_chip", "mfu",
                                      "step_ms_p90", "setup_s"}
    # The step kept its newest state for the readers.
    ctx = Context(cell=harness.load_cell(bench_root.path, cell),
                  root=bench_root.path)
    assert reader("dsa_selected_keys").read(ctx) == pytest.approx(
        (36 + 40 * 8) / 48)
    assert reader("moe_held_pairs").read(ctx) > 0


def test_lower_precision_is_not_correct(bench_root):
    from benchmark import control
    cell = add_tiny_cell(bench_root)
    session = harness.Session(bench_root.path, cell, on_chip=False)
    lower = session.cfg["control_precision"]
    out = control.readings(session, 8, [lower])
    assert out["program"][0] is True, out["program"][1]
    assert out[lower][0] is False


# ---- the new readers, on made-up events ------------------------------------

GRAD = ("jit(hvd_train_step)", "hvd_grad", "TransformerLM", "backbone")
ATTN = GRAD + ("block_1", "attn")
EVENTS = [
    (ATTN + ("hvd_dsa", "index", "indexer", "q", "dot_general"), False, 8e6),
    (ATTN + ("hvd_dsa", "index", "while", "body", "dot_general"), False,
     40e6),
    (ATTN + ("hvd_dsa", "select", "while", "body", "while", "body",
             "reduce_sum"), False, 60e6),
    (ATTN + ("hvd_dsa", "attend", "hvd_flash", "hvd_flash_fwd"), True, 50e6),
    (ATTN + ("hvd_dsa", "attend", "hvd_flash", "hvd_flash_bwd_dkdv"), True,
     110e6),
    (ATTN + ("hvd_dsa", "attend", "hvd_flash", "reduce_sum"), False, 2e6),
    (ATTN + ("hvd_dsa", "align", "while", "body", "exp"), False, 90e6),
    (ATTN + ("qkv", "dot_general"), False, 30e6),
    (GRAD + ("block_1", "moe", "hvd_moe", "experts", "ragged-dot-none"),
     True, 5e6),
    (GRAD + ("tok_embed", "gather"), False, 7e6),
]


@pytest.fixture
def ctx(cfg):
    return Context(scope_events=EVENTS, seen={"done": [0.0, 1.0]},
                   reference=load("references"), device_kind="TPU v5 lite",
                   root=REPO, cell={"cfg": cfg, "traffic_params": TRAFFIC})


@pytest.mark.parametrize("name,ms", [
    ("dsa_ms", 180.0), ("dsa_index_ms", 24.0), ("dsa_select_ms", 30.0),
    ("dsa_attend_ms", 80.0), ("dsa_align_ms", 45.0)])
def test_scope_readers_sum_what_lies_under_their_scope(ctx, name, ms):
    """Two steps: the whole mechanism, kernels and all; each part alone;
    of ``attend`` the kernels and not the glue beside them; never the
    layer's own q, k, v product."""
    assert reader(name).read(ctx) == pytest.approx(ms)


def test_attend_roofline_is_the_selected_pairs_need_over_the_kernels_time(
        ctx, cfg):
    operations, moved = load("references").attention_work(cfg, TRAFFIC)
    need = operations / 197e12
    assert need == pytest.approx(31.4e-3, rel=2e-3)
    got = reader("dsa_attend_roofline").read(ctx)
    assert got == pytest.approx(100.0 * need / 80e-3)
    assert 0 < got < 100
    # Kernels that run every causal tile under the mask, at their best,
    # read the kept share of what they compute.
    dense = 4 * 3 * 2 * 2 * 32 * 128 * 134_225_920 / 197e12
    assert 100.0 * need / dense == pytest.approx(23.44, abs=0.01)


@pytest.mark.parametrize("name", [
    "dsa_ms", "dsa_index_ms", "dsa_select_ms", "dsa_attend_ms",
    "dsa_align_ms", "dsa_attend_roofline", "dsa_selected_keys"])
def test_readers_find_nothing_where_the_program_has_no_such_scope(name):
    """As on the parent commit, or in a cell of another configuration:
    None, and no error; so too with a reference that counts no
    ``attention_work``, and in a run that took no trace."""
    class Reference:
        attention_work = staticmethod(lambda cfg, traffic: (1e12, 1e9))
    ctx = Context(scope_events=EVENTS[7:], seen={"done": [0.0, 1.0]},
                  reference=Reference, device_kind="TPU v5 lite", root=REPO,
                  cell={"cfg": {}, "traffic_params": TRAFFIC})
    assert reader(name).read(ctx) is None
    untraced = Context(trace_dir=None, seen={"done": [0.0]},
                       reference=Reference, root=REPO,
                       cell={"cfg": {}, "traffic_params": TRAFFIC})
    assert reader(name).read(untraced) is None
    if name == "dsa_attend_roofline":
        other = Context(scope_events=EVENTS, seen={"done": [0.0, 1.0]},
                        reference=object(), device_kind="TPU v5 lite",
                        root=REPO,
                        cell={"cfg": {}, "traffic_params": TRAFFIC})
        assert reader(name).read(other) is None


def test_selected_keys_reads_the_state_of_another_builder_as_nothing(cfg):
    """A state without the leaf (another family's draw) reads None."""
    builder = load("builders")
    builder.DRAW["aux"] = {"moe_state": {"expert_tokens": jnp.ones((4,))}}
    ctx = Context(cell={"cfg": cfg}, root=REPO)
    assert reader("dsa_selected_keys").read(ctx) is None
    builder.DRAW["aux"] = {"dsa_state": {"a": {"selected_keys": jnp.asarray(
        1920.0)}, "b": {"selected_keys": jnp.asarray(1920.125)}}}
    assert reader("dsa_selected_keys").read(ctx) == pytest.approx(1920.0625)
    builder.DRAW.clear()


# ---- the cell's kernels and a layer of its step, for a described v5e -------

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


def test_the_flash_call_under_a_mask_compiles_for_v5e_at_the_cells_shape(
        one_chip, monkeypatch):
    """32 query heads of 128 in groups of 8 over 4 K/V heads at 16,384
    positions under an int8 mask of 16,384 x 16,384, forward and
    backward: one Mosaic call each way on the causal tiles, 136 a
    (batch, head), as the call without a mask."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    at = SingleDeviceSharding(one_chip)
    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16, sharding=at)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16, sharding=at)
    mask = jax.ShapeDtypeStruct((1, 16384, 16384), jnp.int8, sharding=at)

    def loss(q, k, v, mask):
        out, lse = fa.flash_attention(q, k, v, causal=True, block_q=1024,
                                      block_k=1024, mask=mask, with_lse=True)
        return jnp.sum(out.astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    grids = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids[eqn.params["name"]] = tuple(
                    eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(grad.trace(q, kv, kv, mask).jaxpr.jaxpr)
    assert grids == {fa.KERNEL_FWD: (32, 136), fa.KERNEL_BWD_DKDV: (32, 136)}
    compiled = grad.lower(q, kv, kv, mask).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_one_layer_of_the_step_compiles_for_v5e_and_the_file_states_the_fit(
        one_chip, monkeypatch, cfg):
    """The train step at the published widths and the cell's row with
    one of its four layers (the four compile for three minutes here; a
    layer is a layer): the flash kernels through Mosaic under
    ``hvd_dsa/attend``, the other three parts under their scopes, no
    score matrix of all the heads anywhere, and what the four layers
    need as the file's ``fit`` records it from the whole compile."""
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.ops import flash_attention
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    reference = load("references")
    one = dict(cfg, num_hidden_layers=1)
    traffic = dict(harness.load_cell(REPO, CELL)["traffic_params"])
    mesh = Mesh(np.array([one_chip]), ("hvd",))
    program = load("builders").build(one, traffic, mesh, hvd_jax)

    def placed(tree, spec=P()):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

    params = placed(jax.eval_shape(
        lambda k: reference.init_params(one, k), jax.random.PRNGKey(0)))
    aux = placed(jax.eval_shape(lambda: reference.init_aux(one)))
    opt_state = placed(jax.eval_shape(
        lambda p: program.init_state(p, {})[2], params))
    tokens = placed(jax.ShapeDtypeStruct((1, 16384), jnp.int32), P("hvd"))
    compiled = program.step.lower(params, aux, opt_state,
                                  (tokens, tokens)).compile()
    text = compiled.as_text()
    assert cfg["remat"] is False
    for kernel in ("hvd_flash_fwd", "hvd_flash_bwd_dkdv"):
        named = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and f"/{kernel}" in line]
        assert len(named) == 1, kernel
        assert "hvd_dsa/attend" in named[0] and "block_0" in named[0]
    names = re.findall(r'op_name="([^"]+)"', text)
    for scope in ("hvd_dsa/index", "hvd_dsa/select", "hvd_dsa/align",
                  "hvd_moe/route", "hvd_moe/experts"):
        assert any(scope in n for n in names), scope
    assert "32,16384,16384" not in text     # no score matrix of the heads
    assert "s8[1,16384,16384]" in text      # the mask, kept
    # A layer and the vocabulary: 2.8 GB of state, and its activations.
    assert 4.0e9 < harness.hbm_bytes(compiled) < 15.75 * 2 ** 30
    found = re.search(r"hbm_gb ([\d.]+) of the chip's 16\.91", cfg["fit"])
    assert found and 0.25 * 16.91 < float(found.group(1)) < 15.75 * 1.0737
