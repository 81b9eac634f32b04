"""The reduction from a device trace and the compiled step's text to
time by scope (``benchmark/scope_reduce.py``): on a hand-written trace
with a hand-written HLO text, and on two steps of
``lm365m-seq8192-1chip`` recorded on the chip with the scopes in the
program (cut from PR 24's first traced run)."""

import gzip
import json
import os
import time

import pytest

from benchmark import harness, layers, scope_reduce as sr, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
STEP = "jit(hvd_train_step)/shard_map/"
MODEL = "TransformerLM"

# What the compiler prints, cut to what the reduction reads: fused
# computations (one of them mixed, with a nested fusion and a tuple
# root), and an entry with instructions that have no op_name: a copy of
# a parameter, a while after a fusion, an iota after nothing.
HLO = f'''HloModule jit_hvd_train_step, is_scheduled=true

%fused_inner (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  ROOT %mul.1 = f32[8]{{0}} multiply(%p0, %p0), metadata={{op_name="{STEP}hvd_grad/jvp({MODEL})/backbone/block_0/mlp_in/mul"}}
}}

%fused_mixed (p1: f32[8], p2: f32[8]) -> (f32[8], f32[8]) {{
  %p1 = f32[8]{{0}} parameter(0)
  %p2 = f32[8]{{0}} parameter(1)
  %fusion.9 = f32[8]{{0}} fusion(%p1), kind=kLoop, calls=%fused_inner
  %dot.1 = f32[8]{{0}} multiply(%fusion.9, %p2), metadata={{op_name="{STEP}hvd_grad/transpose(jvp({MODEL}))/backbone/block_0/mlp_in/dot_general"}}
  %add.1 = f32[8]{{0}} add(%dot.1, %p2), metadata={{op_name="{STEP}hvd_optimizer/add"}}
  ROOT %tuple.1 = (f32[8]{{0}}, f32[8]{{0}}) tuple(%add.1, %dot.1)
}}

%fused_plain (p3: f32[8]) -> f32[8] {{
  %p3 = f32[8]{{0}} parameter(0)
  ROOT %neg.1 = f32[8]{{0}} negate(%p3), metadata={{op_name="{STEP}hvd_grad/jvp({MODEL})/backbone/block_1/attn/rope/neg"}}
}}

%fused_glue (p4: f32[8]) -> f32[8] {{
  %p4 = f32[8]{{0}} parameter(0)
  ROOT %reshape.1 = f32[8]{{0}} reshape(%p4), metadata={{op_name="{STEP}hvd_grad/jvp({MODEL})/backbone/block_0/attn/hvd_flash/reshape"}}
}}

%body (x: f32[8]) -> f32[8] {{
  %x = f32[8]{{0}} parameter(0)
  ROOT %fusion.3 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_plain
}}

ENTRY %main (a: f32[8], b: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0), metadata={{op_name="params[\\'w\\']"}}
  %b = f32[8]{{0}} parameter(1)
  %copy.1 = f32[8]{{0:T(8,128)S(1)}} copy(%a)
  %iota.1 = f32[8]{{0}} iota(), iota_dimension=0
  %hvd_flash_fwd.2 = f32[8]{{0}} custom-call(%copy.1), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}hvd_grad/jvp({MODEL})/backbone/block_0/attn/hvd_flash/hvd_flash_fwd/pallas_call"}}
  %fusion.2 = f32[8]{{0}} fusion(%hvd_flash_fwd.2), kind=kLoop, calls=%fused_glue, metadata={{op_name="{STEP}hvd_grad/jvp({MODEL})/backbone/block_0/attn/proj/dot_general"}}
  %while.1 = f32[8]{{0}} while(%fusion.2), condition=%cond, body=%body
  %hvd_flash_bwd_dkdv.2 = f32[8]{{0}} custom-call(%while.1), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}hvd_grad/transpose(hvd_grad)/jvp({MODEL})/backbone/block_0/attn/hvd_flash/hvd_flash/hvd_flash_bwd_dkdv/pallas_call"}}
  %hvd_flash_bwd_dq.2 = f32[8]{{0}} custom-call(%while.1), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}hvd_grad/transpose(hvd_grad)/jvp({MODEL})/backbone/block_0/attn/hvd_flash/hvd_flash/hvd_flash_bwd_dq/pallas_call"}}
  %all-reduce.1 = f32[8]{{0}} all-reduce(%hvd_flash_bwd_dq.2), to_apply=%sum, metadata={{op_name="{STEP}hvd_exchange/psum"}}
  ROOT %fusion.1 = (f32[8]{{0}}, f32[8]{{0}}) fusion(%all-reduce.1, %b), kind=kOutput, calls=%fused_mixed
}}
'''
KERNEL = "custom-call:tpu_custom_call"
TRACE = {
    "devices": {"0": [
        ["copy.1 copy", 0, 6],                       # a parameter's
        ["iota.1 iota", 6, 4],                       # nobody's
        [f"hvd_flash_fwd.2 {KERNEL}", 10, 100],
        ["fusion.2 fusion", 110, 20],                # glue, forward
        ["while.1 while", 130, 50],                  # its operand's
        ["fusion.3 fusion", 140, 30],                # nested in the while
        [f"hvd_flash_bwd_dkdv.2 {KERNEL}", 180, 200],
        [f"hvd_flash_bwd_dq.2 {KERNEL}", 380, 150],
        ["all-reduce.1 all-reduce", 530, 40],
        ["fusion.1 fusion", 570, 60],                # mixed, tuple root
        # idle 630..700
    ], "1": [["fusion.1 fusion", 0, 700]]},
    "host": [["bench:window", 0, 700]],
}


@pytest.mark.parametrize("op_name, expected", [
    (STEP + f"hvd_grad/jvp({MODEL})/backbone/block_3/attn/qkv/dot_general",
     ("fwd", None, f"{MODEL}/backbone/block_3/attn/qkv")),
    (STEP + f"hvd_grad/transpose(jvp({MODEL}))/backbone/ln_f/mul",
     ("bwd", None, f"{MODEL}/backbone/ln_f")),
    # The backward rule of a custom_vjp: transposed, not jvp-wrapped.
    ("jit(hvd_train_step)/hvd_grad/transpose(hvd_grad)/jvp(M)/attn/"
     "hvd_flash/hvd_flash/hvd_flash_bwd_dq/pallas_call",
     ("bwd", "hvd_flash_bwd_dq",
      "M/attn/hvd_flash/hvd_flash/hvd_flash_bwd_dq")),
    (STEP + "hvd_grad/jvp()/reduce_max", ("fwd", None, "")),
    (STEP + "hvd_exchange/psum", ("exchange", None, "")),
    (STEP + "hvd_optimizer/jit(_where)/select_n",
     ("optimizer", None, "jit(_where)")),
    # A collective of the model's own belongs to the model.
    (STEP + "hvd_grad/jvp(ResNet)/bn_init/pmean",
     ("fwd", None, "ResNet/bn_init")),
    # A renamed scope reads unscoped; so do the compiler's own names.
    (STEP + "grad/jvp(M)/mlp_in/dot_general", ("unscoped", None,
                                               "grad/M/mlp_in")),
    ("params['w']", ("unscoped", None, "")),
    ("", ("unscoped", None, "")),
])
def test_classify(op_name, expected):
    assert sr.classify(op_name) == expected


def test_op_names_of_a_hand_written_text():
    names = sr.op_names(HLO)
    # Without a name of its own an instruction is its first operand's
    # maker's: a parameter's (unscoped), a fusion's, nobody's.
    assert names["copy.1"] == ["params[\\'w\\']", []]
    assert names["while.1"] == [names["fusion.2"][0], []]
    assert names["iota.1"] == ["", []]
    assert names["hvd_flash_fwd.2"][0].endswith("hvd_flash_fwd/pallas_call")
    # A fusion is its root's, whatever name it carries itself.
    assert names["fusion.2"] == [names["reshape.1"][0], ["fwd"]]
    assert names["fusion.3"] == [names["neg.1"][0], ["fwd"]]
    # A tuple root has no name: the last named instruction's; the nested
    # fusion's forward multiply makes it a fusion of three phases.
    assert names["fusion.1"] == [STEP + "hvd_optimizer/add",
                                 ["bwd", "fwd", "optimizer"]]
    assert names["fusion.9"][1] == ["fwd"]


def test_hand_written_trace():
    out = sr.reduce(TRACE, HLO)
    assert out["device"] == "0"
    assert out["busy_ns"] == 630
    # The while counts 20 of its 50, its body the other 30.
    assert out["by_phase"] == {
        "fwd": 100 + 20 + 20 + 30, "bwd": 200 + 150, "exchange": 40,
        "optimizer": 60, "unscoped": 6 + 4}
    assert sum(out["by_phase"].values()) == out["busy_ns"]
    assert out["by_kernel"] == {"hvd_flash_fwd": 100,
                                "hvd_flash_bwd_dkdv": 200,
                                "hvd_flash_bwd_dq": 150}
    # The fusion under hvd_flash, and the while that is its operand's.
    assert out["flash_glue_ns"] == 20 + 20 and out["flash_seen"]
    assert out["mixed_ns"] == 60
    assert out["mixed"] == {"bwd+fwd+optimizer": 60}
    assert out["by_path"]["fwd"][
        f"{MODEL}/backbone/block_1/attn/rope"] == 30
    assert out["by_op"]["fusion fusion"] == {
        f"fwd {MODEL}/backbone/block_0/attn/hvd_flash": 20,
        f"fwd {MODEL}/backbone/block_1/attn/rope": 30,
        "optimizer ": 60}
    # What it read of the text is kept, and is enough to reduce again.
    assert sr.reduce(TRACE, out["op_names"]) == out


def test_a_program_without_the_scopes_reads_unscoped():
    text = HLO.replace("hvd_", "xyz_")
    out = sr.reduce(TRACE, text)
    assert out["by_phase"]["unscoped"] == out["busy_ns"] == 630
    assert out["by_kernel"] == {} and not out["flash_seen"]
    assert out["mixed_ns"] == 0


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "data",
                        "trace_scopes_lm365m_seq8192_2steps.json.gz")
    with gzip.open(path) as f:
        return json.load(f)


def context(scopes, steps=2):
    """What a reader sees of a traced run whose reduction is made."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.utils import compile_cache
    compile_cache.listen()
    jax.jit(lambda v: v * 0.5 - 2.0)(jnp.arange(4.0)).block_until_ready()
    cell = harness.load_cell(REPO, "lm365m-seq8192-1chip")
    return layers.Context({
        "cell": cell, "scopes": scopes, "device_kind": "TPU v5 lite",
        "seen": {"done": list(range(steps)),
                 "start": time.perf_counter()},
        "reference": harness.load_module(REPO, cell["cfg"]["reference"])})


NEW = ["fwd_ms", "bwd_ms", "optimizer_ms", "flash_fwd_ms", "flash_dkdv_ms",
       "flash_dq_ms", "flash_glue_ms", "flash_fwd_roofline",
       "flash_bwd_roofline", "trace_lower_s", "cache_load_s",
       "cache_misses"]


def reader(name):
    return harness.load_module(REPO, f"benchmark/layer_metrics/{name}.py")


def test_recorded_trace(recorded):
    out = sr.reduce(recorded, recorded["op_names"])
    plain = tr.reduce(recorded)["devices"]["0"]
    assert out["busy_ns"] == plain["busy_ns"]
    assert sum(out["by_phase"].values()) == out["busy_ns"]
    # Two steps of 24 layers: each kernel 48 times, and together what
    # ``flash_ms`` counts.
    assert sorted(out["by_kernel"]) == sorted(sr.KERNELS)
    assert sum(out["by_kernel"].values()) == plain["by_class"]["kernel"]
    assert out["by_phase"]["exchange"] == 0          # one chip
    assert out["by_phase"]["unscoped"] < 0.03 * out["busy_ns"]
    assert 0 < out["mixed_ns"] < out["busy_ns"]
    assert out["by_phase"]["bwd"] > out["by_phase"]["fwd"] > 0


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_reads_the_recorded_trace(recorded, name):
    ctx = context(sr.reduce(recorded, recorded["op_names"]))
    value = reader(name).read(ctx)
    assert isinstance(value, (int, float)) and value >= 0
    if name.endswith("roofline"):
        assert 0 < value < 100
    if name.endswith("_ms"):
        assert value > 0


def test_exchange_reads_nothing_on_one_chip_and_all_read_nothing_untraced(
        recorded):
    ctx = context(sr.reduce(recorded, recorded["op_names"]))
    assert reader("exchange_ms").read(ctx) is None
    four = dict(ctx, cell=dict(ctx["cell"], chips=4))
    four["scopes"] = sr.reduce(TRACE, HLO)
    assert reader("exchange_ms").read(layers.Context(four)) == 40 / 1e6 / 2
    # A parent commit's step has no scope and no kernel name: every
    # device reader finds nothing and raises nothing.
    bare = context(sr.reduce(TRACE, HLO.replace("hvd_", "xyz_")))
    for name in NEW[:9]:
        assert reader(name).read(bare) is None, name


def test_compile_readers_without_a_log(monkeypatch, recorded):
    from horovod_tpu.utils import compile_cache
    ctx = context(None)
    monkeypatch.delattr(compile_cache, "events")
    for name in NEW[9:]:
        assert reader(name).read(ctx) is None


def test_scope_table_prints(recorded, tmp_path, capsys, monkeypatch):
    out = sr.reduce(recorded, recorded["op_names"])
    out["steps"] = 2
    path = tmp_path / "scopes.json"
    path.write_text(json.dumps(out))
    table = harness.load_module(REPO, "benchmark/tools/scope_table.py")
    monkeypatch.setattr("sys.argv", ["scope_table.py", str(path), "5"])
    table.main()
    text = capsys.readouterr().out
    for word in ("by phase", "mixed", "hvd_flash_bwd_dkdv", "glue",
                 "block_N", "sum of the phases"):
        assert word in text
