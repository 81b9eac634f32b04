"""Parameter/activation sharding rules for the GSPMD (jit) path.

Megatron-style tensor parallelism expressed as PartitionSpecs: annotate the
parameter tree + batch, jit the step, and XLA's SPMD partitioner inserts the
tp collectives (the scaling-book recipe: pick a mesh, annotate shardings,
let XLA insert collectives). The manual shard_map compositions live in
ring_attention.py / ulysses.py / pipeline.py / moe.py; this module is the
annotation route, which is what most users want for tp/fsdp.

Rules are (path-regex → PartitionSpec) pairs matched against the flax param
path joined with '/'. First match wins; unmatched params replicate.
"""

import re

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def transformer_param_rules(tp_axis="tp", fsdp_axis=None):
    """Sharding rules for the models in horovod_tpu.models.

    Column-parallel (shard output features): qkv projections, mlp_in.
    Row-parallel (shard input features): attention out-proj, mlp_out.
    Embeddings/lm_head: shard the vocab dimension.
    With ``fsdp_axis``, the remaining major dimension is sharded ZeRO-3
    style and XLA all-gathers parameters at use.
    """
    f = fsdp_axis

    return [
        # DenseGeneral qkv kernel: (hidden, 3, heads, head_dim) — shard heads.
        (r".*attn/qkv/kernel", P(f, None, tp_axis, None)),
        (r".*attn/qkv/bias", P(None, tp_axis, None)),
        # DenseGeneral proj kernel: (heads, head_dim, hidden) — shard heads.
        (r".*attn/proj/kernel", P(tp_axis, None, f)),
        (r".*attn/proj/bias", P()),
        (r".*mlp_in/kernel", P(f, tp_axis)),
        (r".*mlp_in/bias", P(tp_axis)),
        (r".*mlp_out/kernel", P(tp_axis, f)),
        (r".*mlp_out/bias", P()),
        (r".*mlp_gate/kernel", P(f, tp_axis)),
        # Expert layer (parallel/moe.py): routed weights (experts, d, f)
        # and (experts, f, d), experts over the data axes (expert
        # parallelism), features over tp; the shared expert as an MLP.
        (r".*moe/w_(gate|up)", P(("dp",) if f is None else ("dp", f), None,
                                 tp_axis)),
        (r".*moe/w_down", P(("dp",) if f is None else ("dp", f), tp_axis,
                            None)),
        (r".*moe/shared_(gate|up)", P(f, tp_axis)),
        (r".*moe/shared_down", P(tp_axis, f)),
        (r".*moe/router", P()),
        (r".*embed/embedding", P(tp_axis, f)),
        (r".*lm_head/kernel", P(f, tp_axis)),
        (r".*mlm_head/kernel", P(f, tp_axis)),
    ]


def _path_str(path):
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _spec_fits(spec, shape, mesh):
    """A spec only applies if every named dimension divides evenly."""
    if len(spec) > len(shape):
        return False
    for dim, names in zip(shape, spec):
        if names is None:
            continue
        names = names if isinstance(names, tuple) else (names,)
        if any(n not in mesh.shape for n in names):
            return False  # mesh lacks this axis → fall back to replication
        k = int(np.prod([mesh.shape[n] for n in names]))
        if dim % k:
            return False
    return True


def make_param_specs(params, mesh, rules=None):
    """Map a param pytree to PartitionSpecs via the rules; params whose
    shapes don't divide the mesh axes fall back to replication."""
    if rules is None:
        rules = transformer_param_rules(
            fsdp_axis="fsdp" if mesh.shape.get("fsdp", 1) > 1 else None)
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def assign(path, leaf):
        name = _path_str(path)
        for pat, spec in compiled:
            if pat.fullmatch(name):
                if _spec_fits(spec, leaf.shape, mesh):
                    return spec
                return P()
        return P()

    return jax.tree_util.tree_map_with_path(assign, params)


def shard_params(params, mesh, specs=None):
    """device_put the param tree onto the mesh per the specs."""
    if specs is None:
        specs = make_param_specs(params, mesh)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)


def constrain(x, mesh, spec):
    """with_sharding_constraint under an explicit mesh."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_spec(extra_dims=0, data_axes=("dp", "fsdp")):
    """PartitionSpec for a batch-leading array: batch over the data axes."""
    return P(data_axes, *([None] * extra_dims))
