"""Write the lowered text of the train steps a checkout builds, so that two
checkouts can be compared text for text (`diff -r`): what a change that
claims to keep every program has to show (ROADMAP C9's guard; CHANGES.md,
PR 44). No chip is needed and nothing runs on one.

    python3 scripts/lowered_steps.py cells <checkout> <out> [cell ...]
    python3 scripts/lowered_steps.py toy   <checkout> <out>

`cells`: every cell of the checkout's BENCHMARK.json (or those named), its
step built as `benchmark/harness.py: Session` builds it, lowered for a
described TPU v5e (a `chips: 4` cell over v5e:2x2), one file a cell; the
first line holds the `compiler_options` its `jax.jit` was given. About six
minutes for the ten cells of PR 44.
`toy`: a small step on the 8-device CPU mesh under each feature of
`DistributedOptimizer` (ops, casts, wire codecs, scales, aggregation,
ZeRO, `has_aux`) and an `update` over a tree with a `SparseGradient` leaf.

Source locations are left out by `as_text()`; a Mosaic kernel's body
(base64 MLIR bytecode with locations inside) is replaced by its assembly
printed without debug info. Run it once a checkout (`git archive <commit>`
into a directory for the parent): one process imports one `horovod_tpu`.
"""

import base64
import json
import os
import re
import sys


def setup(checkout):
    """Point this process at one checkout, on the CPU with 8 devices,
    before anything imports jax."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.chdir(checkout)
    sys.path.insert(0, checkout)
    import jax
    import horovod_tpu.jax as hvd_jax
    if not hvd_jax.__file__.startswith(checkout):
        sys.exit(f"horovod_tpu came from {hvd_jax.__file__}")
    jax.config.update("jax_enable_compilation_cache", False)


_BODY = re.compile(r'(\\22body\\22: \\22|"body": ")([A-Za-z0-9+/=]+)')
_asm = {}


def without_locations(text):
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def asm(match):
        body = match.group(2)
        if body not in _asm:
            ctx = mlir.make_ir_context()
            ctx.allow_unregistered_dialects = True
            with ctx:
                _asm[body] = ir.Module.parse(
                    base64.b64decode(body)).operation.get_asm(
                        enable_debug_info=False)
        return match.group(1) + "<<" + _asm[body] + ">>"
    return _BODY.sub(asm, text)


def write(out, name, text, options=None):
    with open(os.path.join(out, name + ".txt"), "w") as f:
        f.write("compiler_options=" + json.dumps(options, sort_keys=True)
                + "\n" + without_locations(text))
    print(name, len(text), flush=True)


def spy_on_jit():
    """The `compiler_options` of the `jax.jit` calls that donate
    arguments, as `make_train_step`'s does."""
    import jax
    seen, real = [], jax.jit

    def jit(fn, **kwargs):
        if "donate_argnums" in kwargs:
            seen.append(kwargs.get("compiler_options"))
        return real(fn, **kwargs)
    jax.jit = jit
    return seen


def cells(checkout, out, names):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax
    from benchmark import harness
    from horovod_tpu.ops import flash_attention
    if not harness.__file__.startswith(checkout):
        sys.exit(f"benchmark came from {harness.__file__}")
    # The kernels ask the default backend whether to interpret; here that
    # is the CPU, and the lowering is for the TPU.
    flash_attention._interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    seen = spy_on_jit()
    hvd.init()
    with open("BENCHMARK.json") as f:
        names = names or [w["name"] for w in json.load(f)["workloads"]]
    for name in names:
        cell = harness.load_cell(checkout, name)
        cfg, traffic = cell["cfg"], cell["traffic_params"]
        mesh = Mesh(np.array(topo.devices[:cell["chips"]]), ("hvd",))
        reference = harness.load_module(checkout, cfg["reference"])
        del seen[:]
        program = harness.load_module(checkout, cfg["builder"]).build(
            cfg, traffic, mesh, hvd_jax)
        options = list(seen)

        def placed(tree, spec=P()):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)
        params = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        aux = jax.eval_shape(lambda: reference.init_aux(cfg))
        state = placed(jax.eval_shape(program.init_state, params, aux))
        rows = traffic["rows_per_chip"] * cell["chips"]
        batch = []
        for field in traffic["fields"]:
            shape, dtype = (rows, *field["shape"]), jnp.dtype(field["dtype"])
            if field.get("next_token"):     # tokens and targets
                shape = shape[:-1] + (shape[-1] - 1,)
                batch.append(jax.ShapeDtypeStruct(shape, dtype))
            batch.append(jax.ShapeDtypeStruct(shape, dtype))
        batch = placed(tuple(batch), P("hvd"))
        write(out, name, program.step.lower(*state, batch).as_text(),
              options)


def toy(out):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P
    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu import basics
    from horovod_tpu.ops import reduce_ops, sparse
    hvd.init()
    mesh = basics.runtime().mesh
    if mesh.devices.size != 8:
        sys.exit(f"expected the 8-device CPU mesh, got {mesh}")
    params = {"w": jnp.ones((16, 4)), "b": jnp.zeros((4,)),
              "big": jnp.ones((300, 256)),
              "half": jnp.ones((16,), jnp.bfloat16)}

    def loss_fn(p, batch):
        y = (batch @ p["w"] + p["b"] + jnp.sum(p["big"])
             + jnp.sum(p["half"]).astype(jnp.float32))
        return jnp.mean(y ** 2)

    def loss_aux(p, aux, batch):
        return loss_fn(p, batch), {"seen": aux["seen"] + 1.0}

    batch = jnp.arange(16 * 16, dtype=jnp.float32).reshape(16, 16) / 100
    c, scaled = hvd.Compression, {"prescale_factor": 0.5,
                                  "postscale_factor": 3.0}
    features = {
        "average": {}, "sum": {"op": reduce_ops.Sum},
        "adasum": {"op": reduce_ops.Adasum},
        "bf16": {"compression": c.bf16}, "fp16": {"compression": c.fp16},
        "int8": {"compression": c.int8}, "scaled": scaled,
        "int8_sum_scaled": {"compression": c.int8, "op": reduce_ops.Sum,
                            **scaled},
        "bf16_sum_scaled": {"compression": c.bf16, "op": reduce_ops.Sum,
                            **scaled},
        "k2": {"backward_passes_per_step": 2},
        "k2_int8": {"backward_passes_per_step": 2, "compression": c.int8},
        "zero": {"zero": True},
        "zero_int8": {"zero": True, "compression": c.int8}}
    for name, kwargs in features.items():
        for has_aux in (False, True):
            if has_aux and name not in ("average", "int8", "zero"):
                continue
            opt = hvd_jax.DistributedOptimizer(optax.adamw(1e-3), **kwargs)
            step = hvd_jax.make_train_step(
                loss_aux if has_aux else loss_fn, opt, has_aux=has_aux,
                donate=False)
            aux = ({"seen": jnp.zeros(())},) if has_aux else ()
            args = (params,) + aux + (opt.init(params), batch)
            if kwargs.get("zero"):
                # The ZeRO step is a Python wrapper that builds its jit
                # on the first call: run one, then take the jit it keeps.
                step(*args)
                step = next(c.cell_contents for c in step.__closure__
                            if isinstance(c.cell_contents, dict)
                            and "fn" in c.cell_contents)["fn"]
            write(out, name + ("_aux" if has_aux else ""),
                  step.lower(*args).as_text())

    # A tree with one SparseGradient leaf, through `update` on the axis.
    for name, kwargs in {"sparse": {},
                         "sparse_bf16_scaled": {"compression": c.bf16,
                                                **scaled},
                         "sparse_int8": {"compression": c.int8}}.items():
        opt = hvd_jax.DistributedOptimizer(optax.sgd(0.1), axis_name="hvd",
                                           **kwargs)
        held = {"emb": jnp.zeros((16, 4)), "w": jnp.ones((5,))}
        state = opt.init(held)

        def body(indices, values, w):
            grads = {"emb": sparse.SparseGradient(indices[0], values[0],
                                                  (16, 4)), "w": w[0]}
            updates, _ = opt.update(grads, state, held)
            return jax.tree.map(lambda x: x[None], updates)
        sharded = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("hvd"),) * 3, out_specs=P("hvd"),
            check_vma=False))
        write(out, name, sharded.lower(
            jnp.tile(jnp.arange(3, dtype=jnp.int32)[None], (8, 1)),
            jnp.ones((8, 3, 4)), jnp.ones((8, 5))).as_text())


def main():
    if len(sys.argv) < 4 or sys.argv[1] not in ("cells", "toy"):
        sys.exit(__doc__)
    checkout, out = os.path.abspath(sys.argv[2]), os.path.abspath(sys.argv[3])
    os.makedirs(out, exist_ok=True)
    setup(checkout)
    if sys.argv[1] == "cells":
        cells(checkout, out, sys.argv[4:])
    else:
        toy(out)


if __name__ == "__main__":
    main()
