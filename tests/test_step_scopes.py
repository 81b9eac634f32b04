"""The compiled step's tracing contract (docs/tracing.md "The compiled
step in a device trace"): the module name and the four scopes of
``make_train_step`` (plain, ``has_aux``, ZeRO), the two named flash
kernels, and the compile-event log of ``utils/compile_cache.py``."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import horovod_tpu.jax as hvd_jax
from horovod_tpu import telemetry
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.utils import compile_cache

CHIPS = 4


def _step_and_args(kind):
    mesh = Mesh(np.array(jax.devices()[:CHIPS]), ("hvd",))
    params = {"w": jnp.ones((8, 3)), "b": jnp.zeros((3,))}
    batch = (jnp.ones((2 * CHIPS, 8)), jnp.ones((2 * CHIPS, 3)))

    def loss_fn(p, b):
        return jnp.mean((b[0] @ p["w"] + p["b"] - b[1]) ** 2)

    def loss_aux(p, aux, b):
        return loss_fn(p, b), {"seen": aux["seen"] + jnp.mean(b[0])}

    opt = hvd_jax.DistributedOptimizer(optax.adam(1e-2),
                                       zero=(kind == "zero"))
    if kind == "has_aux":
        step = hvd_jax.make_train_step(loss_aux, opt, mesh=mesh,
                                       has_aux=True, donate=False)
        return step, (params, {"seen": jnp.zeros(())}, opt.init(params),
                      batch)
    step = hvd_jax.make_train_step(loss_fn, opt, mesh=mesh, donate=False)
    return step, (params, opt.init(params), batch)


@pytest.mark.parametrize("kind", ["plain", "has_aux", "zero"])
def test_compiled_step_names_its_parts(kind):
    step, args = _step_and_args(kind)
    # The ZeRO step is a Python wrapper that builds its jitted step on
    # the first call; seen through an outer jit it is one more level.
    jitted = step if hasattr(step, "lower") else jax.jit(step)
    text = jitted.lower(*args).compile().as_text()
    if jitted is step:
        assert text.startswith("HloModule jit_hvd_train_step")
    op_names = re.findall(r'op_name="([^"]+)"', text)
    for scope in ("jit(hvd_train_step)/", "hvd_grad/jvp(",
                  "hvd_grad/transpose(jvp(", "hvd_exchange/",
                  "hvd_optimizer/"):
        assert any(scope in n for n in op_names), scope
    # The step's collectives run under the exchange.
    collective = "reduce-scatter" if kind == "zero" else "all-reduce"
    lines = [x for x in text.splitlines()
             if re.search(rf" {collective}(-start)?\(", x)]
    assert lines and all("hvd_exchange/" in x for x in lines)
    if kind == "zero":
        gathers = [x for x in text.splitlines()
                   if re.search(r" all-gather(-start)?\(", x)]
        assert gathers and all("hvd_exchange/" in x for x in gathers)


def test_compiled_step_names_rope_forward_and_backward():
    # The rotation has its own backward (a custom_vjp): both directions
    # keep scope ``rope`` below the model's path, the backward one under
    # ``transpose(``, which is how the scope reduction tells them apart.
    from horovod_tpu.models import TransformerConfig, TransformerLM
    mesh = Mesh(np.array(jax.devices()[:CHIPS]), ("hvd",))
    model = TransformerLM(TransformerConfig(
        vocab_size=64, hidden=64, layers=1, heads=2, max_len=16))
    tokens = jnp.zeros((CHIPS, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)

    def loss_fn(p, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(p, batch[0]), batch[1]).mean()

    opt = hvd_jax.DistributedOptimizer(optax.adam(1e-2))
    step = hvd_jax.make_train_step(loss_fn, opt, mesh=mesh, donate=False)
    text = step.lower(params, opt.init(params),
                      (tokens, tokens)).compile().as_text()
    roped = [n for n in re.findall(r'op_name="([^"]+)"', text)
             if "/attn/rope/" in n]
    forward = [n for n in roped if "transpose(" not in n]
    backward = [n for n in roped if "transpose(" in n]
    # The lane shuffle is a product, each way.
    assert any(n.endswith("/rope/dot_general") for n in forward)
    assert any(n.endswith("/rope/dot_general") for n in backward)
    # And the benchmark's reduction by scope files them as it did.
    from benchmark import scope_reduce
    path = "TransformerLM/backbone/block_0/attn/rope"
    assert {scope_reduce.classify(n) for n in forward} == {
        ("fwd", None, path)}
    assert {scope_reduce.classify(n) for n in backward} == {
        ("bwd", None, path)}


def test_compiled_step_names_latent_attention_experts_and_mtp():
    # The scopes the expert layer, latent attention and the MTP module
    # add (docs/tracing.md), forward and backward, as the benchmark's
    # readers look for them: anywhere in the op_name, in this order.
    from benchmark import scope_reduce, scope_sum
    from horovod_tpu.models import TransformerConfig, TransformerLM
    from horovod_tpu.models.transformer import MLAConfig
    from horovod_tpu.parallel.moe import MoEConfig
    mesh = Mesh(np.array(jax.devices()[:CHIPS]), ("hvd",))
    model = TransformerLM(TransformerConfig(
        vocab_size=64, hidden=64, layers=2, heads=2, max_len=16,
        norm="rmsnorm", bias=False, mlp="swiglu", mlp_width=128,
        mla=MLAConfig(24, 16, 24, 8, 32), mtp_layers=1,
        moe=MoEConfig(experts=8, per_token=2, width=48, held=(0, 2))))
    tokens = jnp.zeros((CHIPS, 16), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens,
                           next_tokens=tokens)
    params = {"params": variables["params"]}
    aux = {"moe_state": variables["moe_state"]}

    def loss_fn(p, aux, batch):
        (main, mtp), aux = model.apply({**p, **aux}, batch[0],
                                       next_tokens=batch[1],
                                       mutable=list(aux))
        return main.mean() + mtp.mean(), aux

    opt = hvd_jax.DistributedOptimizer(optax.adam(1e-2))
    step = hvd_jax.make_train_step(loss_fn, opt, mesh=mesh, has_aux=True,
                                   donate=False)
    text = step.lower(params, aux, opt.init(params),
                      (tokens, tokens)).compile().as_text()
    parts = [scope_reduce._parts(n)
             for n in re.findall(r'op_name="([^"]+)"', text)]
    for scopes in (("hvd_mla",), ("hvd_mla", "rope"), ("hvd_moe", "route"),
                   ("hvd_moe", "experts"), ("hvd_mtp", "hvd_mla"),
                   ("hvd_mtp", "hvd_moe", "experts")):
        found = [p for p in parts if scope_sum._within(scopes, p)]
        assert found, scopes
    names = re.findall(r'op_name="([^"]+)"', text)
    for scope in ("hvd_mla", "hvd_moe", "hvd_mtp"):
        assert any(scope in n and "transpose(" in n for n in names), scope
        assert any(scope in n and "transpose(" not in n for n in names)


def test_each_expert_layers_two_sizes_carry_that_layers_names():
    # Where a share of the experts is held the routed part is a
    # conditional over two buffer sizes, traced and lowered once for
    # all the layers (``jax.jit``); every layer's copy, forward and
    # backward, still carries the names of the layer it runs in, so
    # ``mtp_ms`` and the scope table file it where it belongs.
    from horovod_tpu.models import TransformerConfig, TransformerLM
    from horovod_tpu.models.transformer import MLAConfig
    from horovod_tpu.parallel.moe import MoEConfig, sized_rows
    mesh = Mesh(np.array(jax.devices()[:CHIPS]), ("hvd",))
    model = TransformerLM(TransformerConfig(
        vocab_size=64, hidden=64, layers=3, heads=2, max_len=512,
        norm="rmsnorm", bias=False, mlp="swiglu", mlp_width=128,
        mla=MLAConfig(24, 16, 24, 8, 32), mtp_layers=1,
        moe=MoEConfig(experts=8, per_token=2, width=48, held=(0, 2))))
    assert sized_rows(512 * 2, 2, 8) == 512
    tokens = jnp.zeros((CHIPS, 512), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens,
                           next_tokens=tokens)
    params = {"params": variables["params"]}
    aux = {"moe_state": variables["moe_state"]}

    def loss_fn(p, aux, batch):
        (main, mtp), aux = model.apply({**p, **aux}, batch[0],
                                       next_tokens=batch[1],
                                       mutable=list(aux))
        return main.mean() + mtp.mean(), aux

    opt = hvd_jax.DistributedOptimizer(optax.adam(1e-2))
    step = hvd_jax.make_train_step(loss_fn, opt, mesh=mesh, has_aux=True,
                                   donate=False)
    text = step.lower(params, aux, opt.init(params),
                      (tokens, tokens)).compile().as_text()
    sites = {}
    for name in re.findall(r'op_name="([^"]+)"', text):
        site = re.search(r"block_\d/moe|hvd_mtp/mtp_0", name)
        # (A reduction's own small computation is named without a site.)
        if site and "hvd_moe/cond/" in name and "route" in name:
            way = "bwd" if "transpose(" in name else "fwd"
            sites.setdefault((site.group(0), way), set()).add(
                re.search(r"branch_\d", name).group(0))
    assert set(sites) == {
        (site, way) for site in ("block_1/moe", "block_2/moe",
                                 "hvd_mtp/mtp_0")
        for way in ("fwd", "bwd")}
    assert set(map(frozenset, sites.values())) == {
        frozenset({"branch_0", "branch_1"})}


def test_scope_names_are_the_documented_constants():
    from horovod_tpu.models import transformer
    from horovod_tpu.parallel import moe
    assert (transformer.SCOPE_MLA, transformer.SCOPE_MTP, moe.SCOPE,
            moe.SCOPE_ROUTE, moe.SCOPE_EXPERTS) == (
        "hvd_mla", "hvd_mtp", "hvd_moe", "route", "experts")
    assert (hvd_jax.STEP_NAME, hvd_jax.SCOPE_GRAD, hvd_jax.SCOPE_EXCHANGE,
            hvd_jax.SCOPE_OPTIMIZER) == (
        "hvd_train_step", "hvd_grad", "hvd_exchange", "hvd_optimizer")
    assert (fa.SCOPE, fa.KERNEL_FWD, fa.KERNEL_BWD_DKDV) == (
        "hvd_flash", "hvd_flash_fwd", "hvd_flash_bwd_dkdv")
    # One backward kernel makes dq too, under the name readers match.
    assert not hasattr(fa, "KERNEL_BWD_DQ")


def _pallas_calls(jaxpr, out, outer=""):
    """(kernel name, name stack) of every pallas_call; an equation
    inside a ``jit`` (both kernels' calls are) carries its own part of
    the stack, after the enclosing equation's."""
    for eqn in jaxpr.eqns:
        stack = "/".join(filter(None, (outer,
                                       str(eqn.source_info.name_stack))))
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"], stack))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, out, stack)
    return out


@pytest.mark.parametrize("variant", ["plain", "with_lse", "dropout"])
def test_flash_kernels_are_named(variant):
    x = jnp.ones((1, 2, 128, 64), jnp.bfloat16)
    kwargs = {"plain": {}, "with_lse": {"with_lse": True},
              "dropout": {"dropout_mask": jnp.ones((1, 2, 128, 128), bool),
                          "dropout_rate": 0.1}}[variant]

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, **kwargs)
        return sum(jnp.sum(o.astype(jnp.float32))
                   for o in jax.tree.leaves(out))

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        x, x, x)
    calls = _pallas_calls(jaxpr.jaxpr, [])
    assert [name for name, _ in calls] == [
        "hvd_flash_fwd", "hvd_flash_bwd_dkdv"]
    # Each call lies under its own name, and the backward kernel under
    # a plain ``hvd_flash`` as well as the transposed stack.
    for name, stack in calls:
        assert stack.endswith(name) and "hvd_flash" in stack
    assert all("transpose(" in stack and "/hvd_flash/" in stack
               for _, stack in calls[1:])


def _phases_since(n):
    return [phase for phase, _, _ in compile_cache.events()[n:]]


def test_compile_log_grows_when_something_compiles(monkeypatch, tmp_path):
    # With the variable set, enable() leaves JAX's configuration alone
    # and still registers the listeners, once.
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert compile_cache.enable() == str(tmp_path)
    x = jnp.arange(7.0)
    fn = jax.jit(lambda v: jnp.tanh(v) * 3.0 + 1.5)
    n = len(compile_cache.events())
    fn(x).block_until_ready()
    # One executable, so each listener is there once; the functions of
    # jax.numpy inside it are traced on their own.
    phases = _phases_since(n)
    assert phases.count("backend_compile") == 1
    assert phases.count("lower") == 1 and "trace" in phases
    stamps = [at for _, _, at in compile_cache.events()[n:]]
    assert stamps == sorted(stamps) and all(
        seconds >= 0 for _, seconds, _ in compile_cache.events()[n:])
    n = len(compile_cache.events())
    fn(x).block_until_ready()
    assert _phases_since(n) == []
    # A copy: the caller cannot edit the log.
    compile_cache.events().clear()
    assert len(compile_cache.events()) == n


def test_compile_log_feeds_the_metrics_registry(monkeypatch):
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    telemetry.reset()
    try:
        compile_cache.listen()
        n = len(compile_cache.events())
        jax.jit(lambda v: jnp.sin(v) - 0.25)(
            jnp.arange(5.0)).block_until_ready()
        logged = _phases_since(n)
        families = telemetry.snapshot()["families"]
        seen = {s["labels"]["phase"]: s["count"]
                for s in families["hvd_compile_seconds"]["samples"]}
        assert seen == {p: logged.count(p) for p in set(logged)}
        assert seen["backend_compile"] >= 1
    finally:
        monkeypatch.delenv("HOROVOD_TPU_METRICS")
        telemetry.reset()


def test_compile_log_is_silent_with_metrics_off():
    telemetry.reset()
    compile_cache.listen()
    jax.jit(lambda v: jnp.cos(v) + 0.125)(jnp.arange(3.0))
    assert telemetry.registry().families() == {}
