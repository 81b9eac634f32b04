"""The ``glm47flash`` configuration's benchmark files on the CPU: its
plain reference against the program's model (logits), what the
configuration file states against what the reference builds and counts,
a whole run of a tiny cell through the harness with the new builder, the
control in lower precision, and the new per-layer readers on made-up
events. (The layer tests proper are ``tests/test_glm4_moe_lite.py``.)"""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest
from bench_fixtures import bench_root, cpu_peak  # noqa: F401 (fixtures)

from benchmark import flops, harness, scope_sum
from benchmark.layers import Context

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "glm47flash-seq4096-1chip"
TINY = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=48,
    num_attention_heads=2, n_routed_experts_published=8,
    experts_held=[2, 4], num_experts_per_tok=2, num_hidden_layers=3,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24,
    qk_rope_head_dim=8, v_head_dim=32, vocab_size=64,
    attention_impl="einsum",
    # This tiny size's own limits, read on the CPU as PERF.md reads the
    # cell's on the chip: the program's largest over seeds 1-7 is
    # 1.3e-3 / 0.046 / 0.010 (a token whose second and third scores lie
    # within bfloat16's rounding picks another expert than the
    # reference's, and at 64 tokens a step one flip shows), the int8
    # control's smallest over seeds 5-7 9.5e-4 / 0.073 / 0.0139.
    limits={"loss_gap": 3e-3, "grad_norm_gap": 0.06,
            "update_norm_gap": 0.012})


def load(name):
    return harness.load_module(REPO, f"benchmark/{name}/glm4_moe_lite.py")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REPO, "benchmark/configs/glm47flash.json")) as f:
        return json.load(f)


def add_tiny_cell(root):
    root.add_config("glmtiny", "glm47flash", **TINY)
    root.add_traffic(
        "seq32x2", "seq4096x2", seq_len=32, units_per_row=32,
        fields=[{"dist": "randint", "high": "vocab_size", "shape": [33],
                 "dtype": "int32", "next_token": True}])
    root.add_cell("glmtiny-1chip", "glmtiny", "seq32x2", 1, CELL)
    return "glmtiny-1chip"


def test_the_file_states_what_the_reference_builds_and_counts(cfg):
    reference = load("references")
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == cfg["parameters"]
    traffic = {"rows_per_chip": 2, "seq_len": 4096}
    # ISSUE 26's count: 352.6M active matrix parameters a token (routed
    # experts by expectation, 4 x 8/64 of one expert), plus causal
    # attention at 20 heads of 256 over six layers.
    mla = 21_757_952
    expert = mla + 2048 * 64 + 1.5 * 3 * 2048 * 1536
    matrix = (mla + 3 * 2048 * 10240 + 4 * expert + 2048 * 19360)
    mtp = 2 * 2048 * 2048 + expert + 2048 * 19360
    assert round((matrix + mtp) / 1e6, 1) == 352.6
    attention = 6 * sum(flops.attention_flops(1, 20, 4096, 256, causal=True))
    assert reference.flops_per_row(cfg, traffic) == pytest.approx(
        6 * (4096 * matrix + 4095 * mtp) + attention, rel=1e-12)
    assert reference.attention_shape(cfg, traffic) == (2, 20, 4096, 256)
    assert reference.attention_layers(cfg) == 6
    operations, moved = reference.expert_products(cfg, traffic)
    assert operations == 5 * 6 * 8192 * 1.5 * 3 * 2048 * 1536
    assert moved == 5 * (12 * 9 * 3 * 2048 * 1536 + 8 * 8192 * 2048)


def test_reduced_keys_and_published_values_stand_side_by_side(cfg):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}["glm47flash"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    held = cfg["experts_held"]
    assert cfg["n_routed_experts"] == held[1] - held[0] == 8
    assert cfg["n_routed_experts_published"] == 64
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    for key in cfg["reduced"]:
        assert key in cfg["changed"]


def test_reference_logits_match_model():
    reference, builder = load("references"), load("builders")
    from horovod_tpu.models import TransformerLM
    with open(os.path.join(REPO, "benchmark/configs/glm47flash.json")) as f:
        tiny = dict(json.load(f), **TINY)
    model = TransformerLM(dataclasses.replace(
        builder.model_config(tiny, {"seq_len": 32}), dtype=jnp.float32))
    params = reference.init_params(tiny, jax.random.PRNGKey(1))
    aux = reference.init_aux(tiny)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, 64)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: model.apply(
            {**p, **aux}, tokens[:, :-1], next_tokens=tokens[:, 1:]))(params)
    got = jax.jit(lambda p: reference.logits_fn(
        p, aux, tokens[:, :-1], tokens[:, 1:], tiny))(params)
    assert len(got) == len(want) == 2
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-4


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
    # The readers run after the harness has freed its state: the draw
    # of the last step is still there to read. 64 tokens x 2 choices x
    # 3 expert layers, of which a quarter is held by expectation.
    session_cell = harness.load_cell(bench_root.path, cell)
    pairs = reader("moe_held_pairs").read(Context(
        cell=session_cell, root=bench_root.path))
    assert pairs == int(pairs) and 0 < pairs < 64 * 2 * 3


def test_lower_precision_is_not_correct(bench_root):
    from benchmark import control
    cell = add_tiny_cell(bench_root)
    session = harness.Session(bench_root.path, cell, on_chip=False)
    lower = session.cfg["control_precision"]
    out = control.readings(session, 7, [lower])
    assert out["program"][0] is True, out["program"][1]
    assert out[lower][0] is False


# ---- the new readers, on made-up events ------------------------------------

MOE = ("TransformerLM", "backbone", "block_1", "moe", "hvd_moe")
MLA = ("TransformerLM", "backbone", "block_1", "attn", "hvd_mla")
EVENTS = [
    (MOE + ("route", "top_k"), False, 2e6),
    (MOE + ("checkpoint", "experts", "ragged_dot"), True, 6e6),
    (MOE + ("experts", "dot_general"), False, 4e6),
    (("TransformerLM", "backbone", "hvd_mtp", "mtp_0", "block", "moe",
      "hvd_moe", "experts", "ragged_dot"), True, 2e6),
    (MLA + ("q_b", "dot_general"), False, 3e6),
    (MLA + ("hvd_flash", "hvd_flash_fwd"), True, 5e6),
    (MLA + ("hvd_flash", "reshape"), False, 1e6),
    (("TransformerLM", "backbone", "block_0", "mlp_in"), False, 9e6),
]


def reader(name):
    return harness.load_module(REPO, f"benchmark/layer_metrics/{name}.py")


@pytest.fixture
def ctx(cfg):
    class Reference:
        expert_products = staticmethod(lambda cfg, traffic: (197e12 * 3e-3,
                                                             819e9 * 1e-3))
    return Context(scope_events=EVENTS, seen={"done": [0.0, 1.0]},
                   reference=Reference, device_kind="TPU v5 lite",
                   cell={"cfg": cfg, "traffic_params": {}})


@pytest.mark.parametrize("name,ms", [
    ("moe_ms", 7.0), ("moe_route_ms", 1.0), ("mla_ms", 2.0),
    ("mtp_ms", 1.0)])
def test_scope_readers_sum_their_scopes(ctx, name, ms):
    assert reader(name).read(ctx) == pytest.approx(ms)


def test_experts_roofline_is_least_time_over_scope_time(ctx):
    # 3 ms at the FLOP peak (1 ms at the byte peak) over the 6 ms a step
    # under hvd_moe/experts.
    assert reader("moe_experts_roofline").read(ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("name", ["moe_ms", "moe_route_ms", "mla_ms",
                                  "mtp_ms", "moe_experts_roofline",
                                  "mla_flash_roofline"])
def test_readers_find_nothing_where_the_program_has_no_such_scope(name):
    """As on the parent commit, or in a cell of another configuration:
    None, and no error."""
    class Reference:
        pass
    ctx = Context(scope_events=[EVENTS[-1]], scopes={"by_kernel": {}},
                  seen={"done": [0.0, 1.0]}, reference=Reference,
                  device_kind="TPU v5 lite", cell={"cfg": {}})
    assert reader(name).read(ctx) is None
    untraced = Context(trace_dir=None, seen={"done": [0.0]},
                       reference=Reference, cell={"cfg": {}})
    assert reader(name).read(untraced) is None


def test_mla_flash_roofline_counts_every_attention_layer(cfg):
    reference = load("references")
    traffic = {"rows_per_chip": 2, "seq_len": 4096}
    need = 6 * sum(flops.attention_flops(2, 20, 4096, 256,
                                         causal=True)) / 197e12
    kernels = {"hvd_flash_fwd": 0.5 * need * 1e9, "hvd_flash_bwd_dq": 0,
               "hvd_flash_bwd_dkdv": 1.5 * need * 1e9}
    ctx = Context(scopes={"by_kernel": kernels}, seen={"done": [0.0]},
                  reference=reference, device_kind="TPU v5 lite",
                  cell={"cfg": cfg, "traffic_params": traffic})
    assert reader("mla_flash_roofline").read(ctx) == pytest.approx(50.0)


def test_grouped_products_without_an_op_name_are_the_experts():
    # As the TPU compiler names them: for themselves.
    op_names = {"ragged-dot-none.3": ["ragged-dot-none", []],
                "copy.7": ["", []],
                "fusion.2": ["jit(f)/hvd_grad/jvp(M)/moe/hvd_moe/route/sort",
                             []]}
    assert scope_sum._scopes("ragged-dot-none.3 custom-call:tpu_custom_call",
                             op_names) == ("hvd_moe", "experts")
    assert scope_sum._scopes("copy.7 copy", op_names) == ()
    assert scope_sum._within(("hvd_moe", "route"), scope_sum._scopes(
        "fusion.2 fusion", op_names))


def test_scopes_match_in_order_and_anywhere():
    parts = ("a", "hvd_moe", "checkpoint", "experts", "dot")
    assert scope_sum._within(("hvd_moe", "experts"), parts)
    assert not scope_sum._within(("experts", "hvd_moe"), parts)
    assert not scope_sum._within(("hvd_mla",), parts)


@pytest.mark.parametrize("events,lost", [
    ([(MOE + ("experts", "dot_general"), False, 4e6), EVENTS[0]], True),
    ([(MOE + ("experts", "dot_general"), False, 4e6), EVENTS[1]], False),
    ([EVENTS[0], EVENTS[4]], False)])
def test_work_under_experts_without_a_kernel_has_lost_the_grouped_products(
        events, lost):
    """XLA names its grouped-product kernels for themselves; if it
    renames them they fall out of ``hvd_moe/experts`` and the readers
    would under-read. No expert layer in the trace is not that case."""
    assert scope_sum.grouped_lost(events) is lost


def test_scopes_of_an_op_name_at_any_depth():
    assert scope_sum.scopes_of(
        "jit(s)/hvd_grad/transpose(jvp(M))/a/b/c/d/hvd_moe/checkpoint/"
        "experts/ragged_dot") == (
            "jit(s)", "hvd_grad", "M", "a", "b", "c", "d", "hvd_moe",
            "checkpoint", "experts", "ragged_dot")
    assert scope_sum.scopes_of("") == ()


class _Stage:
    """A jitted, lowered or compiled step, as far as the harness uses
    one."""

    def __init__(self, form):
        self.form = form

    def lower(self, *args):
        return _Stage("lowered")

    def compile(self):
        return _Stage("compiled")

    def as_text(self):
        return self.form

    def __call__(self, params, aux, opt_state, batch):
        return params, {"n": aux["n"] + 1}, opt_state, 0.0


def test_the_step_keeps_its_newest_draw_in_every_form():
    builder = load("builders")
    compiled = builder._KeepsDraw(_Stage("jitted")).lower(0).compile()
    assert compiled.as_text() == "compiled"
    state = (0, {"n": 0}, 0)
    for _ in range(3):
        *state, loss = compiled(*state, None)
    assert builder.DRAW["aux"] == {"n": 3} and loss == 0.0


@pytest.mark.parametrize("cfg_keys", [{}, {"builder": "no/such.py"}])
def test_held_pairs_reads_nothing_without_an_expert_layer(cfg_keys):
    assert reader("moe_held_pairs").read(Context(
        cell={"cfg": cfg_keys}, root=REPO)) is None


def test_held_pairs_sums_the_held_experts_over_the_layers(cfg):
    builder = load("builders")
    first, end = cfg["experts_held"]
    drawn = jnp.arange(cfg["n_routed_experts_published"], dtype=jnp.float32)
    builder.DRAW["aux"] = {"moe_state": {"backbone": {
        "block_1": {"moe": {"bias": drawn, "expert_tokens": drawn}},
        "mtp_0": {"block": {"moe": {"expert_tokens": 2 * drawn}}}}}}
    try:
        assert reader("moe_held_pairs").read(Context(
            cell={"cfg": cfg}, root=REPO)) == 3 * sum(range(first, end))
    finally:
        builder.DRAW.clear()
