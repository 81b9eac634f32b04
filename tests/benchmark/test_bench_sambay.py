"""The ``phi4miniflash`` configuration's benchmark files on the CPU: what
the configuration file states against the catalog's published numbers
and against what its plain reference builds and counts, a whole run of a
tiny cell through the harness with the new builder, the control in lower
precision, the new per-layer readers on made-up events, and the cell's
whole step compiled for a described TPU v5e. (The layer tests proper are
``tests/test_sambay.py``.)"""

import json
import os
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
CELL = "phi4miniflash-seq8192-1chip"
TRAFFIC = {"rows_per_chip": 1, "seq_len": 8192}
# The catalog's ``config`` for the model (the model-configs guide's
# architectures.jsonl), every number of it.
PUBLISHED = dict(
    embd_pdrop=0, hidden_act="silu", hidden_size=2560,
    intermediate_size=10240, layer_norm_eps=1e-5,
    max_position_embeddings=262144, mb_per_layer=2, model_type="phi4flash",
    num_attention_heads=40, num_hidden_layers=32, num_key_value_heads=20,
    resid_pdrop=0, sliding_window=512, tie_word_embeddings=True,
    mlp_bias=False, lm_head_bias=False, vocab_size=200064)
TINY = dict(
    hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=192, vocab_size=96, sliding_window=8,
    attention_impl="einsum", remat="flash",
    assumed_sizes=dict(expand=2, d_state=4, d_conv=4, dt_rank=8),
    # This tiny size's own limits, read on the CPU as PERF.md reads the
    # cell's on the chip: the program's largest over seeds 1-6 is
    # 1.6e-3 / 0.025 / 0.23, the int8 control's smallest 2.7e-3 / 0.12 /
    # 0.12. The update's gap cannot tell the control apart here either
    # (PERF.md section 2: the key bias has no gradient but rounding's,
    # and AdamW makes a step of it): the loss and the gradient do.
    limits={"loss_gap": 2.1e-3, "grad_norm_gap": 0.05,
            "update_norm_gap": 0.3})


def load(name):
    return harness.load_module(REPO, f"benchmark/{name}/sambay.py")


def reader(name):
    return harness.load_module(REPO, f"benchmark/layer_metrics/{name}.py")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark/configs/phi4miniflash.json")) as f:
        return json.load(f)


def add_tiny_cell(root):
    root.add_config("phitiny", "phi4miniflash", **TINY)
    root.add_traffic(
        "seq32x2", "seq8192x1", rows_per_chip=2, seq_len=32,
        units_per_row=32,
        fields=[{"dist": "randint", "high": "vocab_size", "shape": [33],
                 "dtype": "int32", "next_token": True}])
    root.add_cell("phitiny-1chip", "phitiny", "seq32x2", 1, CELL)
    return "phitiny-1chip"


def test_every_published_number_is_kept_or_listed_as_reduced(cfg):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["phi4miniflash"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "vocab_size", "max_position_embeddings"]
    assert entry["source"] == cfg["source"]
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert cfg[key + "_published"] == value and key in cfg["changed"]
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (6, 25008, 8192)
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert cfg["layer_indices"] == [14, 15, 16, 17, 18, 19]
    assert len(cfg["layer_indices"]) == cfg["num_hidden_layers"]
    assert "eight chips" in cfg["deployment"]
    for item in ("mamba_sizes", "differential_attention", "placement",
                 "biases", "memory", "head_pairing", "optimizer",
                 "initializer"):
        assert len(cfg["assumed"][item]) > 40, item
    assert cfg["assumed_sizes"] == dict(expand=2, d_state=16, d_conv=4,
                                        dt_rank=160)
    assert set(cfg["limits"]) == {"loss_gap", "grad_norm_gap",
                                  "update_norm_gap"}
    assert cfg["fit"].startswith("rule:") and "limits_set_from" in cfg
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi4miniflash", "seq8192x1", 1)


def test_the_file_states_what_the_reference_builds_and_counts(cfg):
    reference = load("references")
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    built = sum(x.size for x in jax.tree.leaves(shapes))
    assert built == cfg["parameters"] == 697_094_272
    # ISSUE 32's table, by hand.
    mlp, norms = 3 * 2560 * 10240, 2 * 2 * 2560
    mamba = (2560 * 10240 + (4 * 5120 + 5120) + 5120 * 192
             + (160 * 5120 + 5120) + 5120 * 16 + 5120 + 5120 * 2560)
    attention = (2560 * 5120 + 5120) + (2560 * 2560 + 2560) + 4 * 64 + 128
    cross = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
    memory = 2 * 2560 * 5120
    assert (mamba, attention, cross, memory) == (
        41_241_600, 19_668_864, 13_112_704, 26_214_400)
    assert built == (2 * mamba + 2 * attention + cross + memory
                     + 6 * (mlp + norms) + 25_008 * 2560 + 2 * 2560)
    # The same equations over all 32 layers and the whole vocabulary give
    # the published 3.8B.
    whole = (9 * mamba + 9 * attention + 7 * cross + 7 * memory
             + 32 * (mlp + norms) + 200_064 * 2560 + 2 * 2560)
    assert whole == 3_852_562_944
    assert reference.kinds(cfg) == ["mamba", "window", "mamba", "attention",
                                    "gmu", "cross"]
    assert reference.attention_layers(cfg) == 3
    # Matrix parameters a token meets: the mixers' products and six
    # SwiGLUs; no convolution, no recurrence, no bias, no norm.
    products = (2 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
                + 2 * (2560 * 5120 + 2560 * 2560) + 2 * 2560 * 2560
                + 2 * 2560 * 5120 + 6 * mlp)
    assert reference.block_params(cfg) == products == 632_750_080
    # Attention at seq 8192: 40 score maps a layer, each a product 64
    # wide and one 128 wide over the keys a query sees, three times.
    seen_full = 8192 * 8193 // 2
    seen_window = 512 * 513 // 2 + (8192 - 512) * 512
    operations = 3 * 2 * 40 * 192 * (2 * seen_full + seen_window)
    assert reference.attention_work(cfg, TRAFFIC)[0] == operations
    assert reference.flops_per_row(cfg, TRAFFIC) == (
        6 * 8192 * (products + 2560 * 25_008) + operations)
    per_token = reference.flops_per_row(cfg, TRAFFIC) / 8192
    assert round(per_token / 1e9, 2) == 4.58
    assert round(reference.flops_per_row(cfg, TRAFFIC) / 1e12, 1) == 37.5
    # Bytes: q (2560), k and v (2 x 1280), the two maps (2 x 2560) and
    # the gradients of all of them, bfloat16, once each way.
    q, kv, out = 2560, 2560, 5120
    assert reference.attention_work(cfg, TRAFFIC)[1] == 3 * 2 * 8192 * (
        (q + kv + out) + (q + kv + 2 * out) + (q + kv))
    # The scan: 5120-wide x, dt, y and 16-wide B, C, float32, and their
    # gradients, in two Mamba layers.
    wide, narrow = 4 * 8192 * 5120, 4 * 8192 * 16
    assert reference.scan_work(cfg, TRAFFIC)[1] == 2 * (
        (3 * wide + 2 * narrow) + (5 * wide + 4 * narrow))


def test_the_builder_runs_the_stack_as_the_file_says(cfg):
    model = load("builders").model_config(cfg, {"seq_len": 8192})
    assert model.mixers == ("mamba", "window", "mamba", "attention", "gmu",
                            "cross")
    assert model.layer_indices == (14, 15, 16, 17, 18, 19)
    assert (model.hidden, model.heads, model.kv_heads, model.mlp_width,
            model.window, model.vocab_size) == (2560, 40, 20, 10240, 512,
                                                25008)
    assert (model.ssm.d_inner, model.ssm.d_state, model.ssm.d_conv,
            model.ssm.dt_rank) == (5120, 16, 4, 160)
    assert model.tie_embeddings
    assert not (model.use_rope or model.positions or model.mlp_bias)
    assert model.bias and model.norm == "layernorm"
    assert model.norm_eps == 1e-5 and model.mlp == "swiglu"
    assert model.remat == cfg["remat"] and model.attention_impl == "flash"
    from horovod_tpu.ops import selective_scan
    assert selective_scan.CHUNK == cfg["scan_chunk"]


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
    out = control.readings(session, 6, [lower])
    assert out["program"][0] is True, out["program"][1]
    assert out[lower][0] is False


# ---- the new readers, on made-up events ------------------------------------

GRAD = ("jit(hvd_train_step)", "hvd_grad", "TransformerLM", "backbone")
MAMBA = GRAD + ("block_0", "mamba", "hvd_ssm")
REMAT = GRAD + ("checkpoint", "rematted_computation", "block_0", "mamba",
                "hvd_ssm")
EVENTS = [
    (MAMBA + ("in_proj", "dot_general"), False, 6e6),
    (MAMBA + ("scan", "hvd_ssm_fwd"), True, 2e6),
    (MAMBA + ("scan", "pad"), False, 1e6),
    (REMAT + ("scan", "hvd_ssm_fwd"), True, 2e6),
    (MAMBA + ("hvd_ssm", "scan", "hvd_ssm_bwd"), True, 4e6),
    (GRAD + ("block_4", "gmu", "hvd_gmu", "in_proj", "dot_general"), False,
     3e6),
    (GRAD + ("block_3", "attn", "hvd_diff", "subln", "mul"), False, 5e5),
    (GRAD + ("block_3", "attn", "hvd_flash", "hvd_flash_fwd"), True, 7e6),
    (GRAD + ("tok_embed", "gather"), False, 7e6),
]


@pytest.fixture
def ctx(cfg):
    return Context(scope_events=EVENTS, seen={"done": [0.0, 1.0]},
                   reference=load("references"), device_kind="TPU v5 lite",
                   root=REPO, cell={"cfg": cfg, "traffic_params": TRAFFIC})


@pytest.mark.parametrize("name,ms", [
    ("ssm_ms", 7.5), ("ssm_scan_ms", 4.0), ("gmu_ms", 1.5),
    ("diff_ms", 0.25)])
def test_scope_readers_sum_their_scopes(ctx, name, ms):
    assert reader(name).read(ctx) == pytest.approx(ms)


def test_scan_roofline_is_the_bytes_over_the_kernels_time(ctx, cfg):
    moved = load("references").scan_work(cfg, TRAFFIC)[1]
    assert reader("ssm_scan_roofline").read(ctx) == pytest.approx(
        100.0 * (moved / 819e9) / 4e-3)


def test_flash_roofline_counts_what_masks_and_widths_leave(cfg):
    reference = load("references")
    operations, moved = reference.attention_work(cfg, TRAFFIC)
    need = max(operations / 197e12, moved / 819e9)
    assert need == operations / 197e12      # FLOP-bound at seq 8192
    kernels = {"hvd_flash_fwd": 0.5 * need * 1e9,
               "hvd_flash_bwd_dkdv": 1.5 * need * 1e9}
    ctx = Context(scopes={"by_kernel": kernels}, seen={"done": [0.0]},
                  reference=reference, device_kind="TPU v5 lite",
                  cell={"cfg": cfg, "traffic_params": TRAFFIC})
    assert reader("sambay_flash_roofline").read(ctx) == pytest.approx(50.0)


def test_window_skipped_share_is_the_programs_own_count(ctx, cfg):
    # 105 of the 136 sub-tiles of 512 on or under the diagonal.
    assert reader("flash_window_skipped_pct").read(ctx) == pytest.approx(
        100.0 * 105 / 136)
    other = Context(ctx, cell={"cfg": {"flash_tile": 1024,
                                       "sliding_window": None},
                               "traffic_params": TRAFFIC})
    assert reader("flash_window_skipped_pct").read(other) is None


@pytest.mark.parametrize("name", [
    "ssm_ms", "ssm_scan_ms", "ssm_scan_roofline", "sambay_flash_roofline",
    "gmu_ms", "diff_ms", "flash_window_skipped_pct"])
def test_readers_find_nothing_where_the_program_has_no_such_scope(name):
    """As on the parent commit, or in a cell of another configuration:
    None, and no error."""
    class Reference:
        attention_shape = staticmethod(lambda cfg, traffic: (1, 2, 64, 32))
    ctx = Context(scope_events=[EVENTS[-1]], scopes={"by_kernel": {}},
                  seen={"done": [0.0, 1.0]}, reference=Reference,
                  device_kind="TPU v5 lite", root=REPO,
                  cell={"cfg": {}, "traffic_params": TRAFFIC})
    assert reader(name).read(ctx) is None
    untraced = Context(trace_dir=None, seen={"done": [0.0]},
                       reference=Reference, root=REPO,
                       cell={"cfg": {}, "traffic_params": TRAFFIC})
    assert reader(name).read(untraced) is None


# ---- the cell's whole step, for a described v5e ----------------------------

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


def test_scan_kernels_compile_for_v5e_at_a_batch_of_two(one_chip,
                                                         monkeypatch):
    """A block of scalars in SMEM has to be a whole row of its array:
    with two sequences the chunks' rows are not the batch's."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.ops import selective_scan
    monkeypatch.setattr(selective_scan, "_interpret", lambda: False)
    at = SingleDeviceSharding(one_chip)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=at)

    wide, narrow = shape(2, 1000, 5120), shape(2, 1000, 16)
    compiled = jax.jit(jax.grad(
        lambda *args: jnp.sum(selective_scan.selective_scan(*args)),
        argnums=(0, 1, 2, 3, 4))).lower(
            wide, wide, shape(5120, 16), narrow, narrow).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_the_step_compiles_for_v5e_and_fits(one_chip, monkeypatch, cfg):
    """The whole train step at the published widths: both kernel pairs
    through Mosaic, no ``[seq, 5120, 16]`` operand anywhere, and the
    device's 15.75 GiB enough."""
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.ops import flash_attention, selective_scan
    # The kernels ask the default backend whether to interpret; here
    # that is the CPU, and the compile is for the TPU.
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    monkeypatch.setattr(selective_scan, "_interpret", lambda: False)
    reference = load("references")
    traffic = dict(harness.load_cell(REPO, CELL)["traffic_params"])
    mesh = Mesh(np.array([one_chip]), ("hvd",))
    program = load("builders").build(cfg, traffic, mesh, hvd_jax)

    def placed(tree, spec=P()):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

    params = placed(jax.eval_shape(
        lambda k: reference.init_params(cfg, k), jax.random.PRNGKey(0)))
    opt_state = placed(jax.eval_shape(
        lambda p: program.init_state(p, {})[1], params))
    tokens = placed(jax.ShapeDtypeStruct((1, 8192), jnp.int32), P("hvd"))
    compiled = program.step.lower(params, opt_state,
                                  (tokens, tokens)).compile()
    text = compiled.as_text()
    # Three attention layers of two score maps and two Mamba layers,
    # forward and backward; "dots" runs the forward kernels again.
    once = 2 * (3 * 2 + 2)
    again = {False: 0, "flash": 0}.get(cfg["remat"], 3 * 2 + 2)
    assert text.count("tpu_custom_call") == once + again
    assert "8192,5120,16" not in text and "5120,16,8192" not in text
    assert harness.hbm_bytes(compiled) < 15.75 * 2 ** 30
