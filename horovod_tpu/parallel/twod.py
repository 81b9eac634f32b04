"""2D (data × tensor) training: ``sharding.py`` layouts + ZeRO legs.

One mesh, two axes: parameters shard over ``tp`` per the Megatron-
style rules of :mod:`horovod_tpu.parallel.sharding`, gradients reduce
over ``dp`` through the ZeRO-1 legs of ``ops/zero.py`` — each (dp, tp)
rank owns 1/dp of the optimizer state for ITS tensor slice, so state
memory scales 1/(dp·tp). The composition is exactly the two-stage
layout the redistribution planner speaks (a :class:`ZeroFlat` stage
over ``dp`` stacked on :class:`Sharded` tensor stages over ``tp``),
which is what makes the elastic transitions planner-emitted instead of
hand-rolled:

- :func:`reshard_2d` — dp cohort change (4→2, 2→4, …) at fixed or
  changed tp: one ``plan_redistribution`` over the composed specs,
  executed host-side from windowed shard reads.
- :meth:`TwoDZero.to_serving` — train→serve: tensor-sharded params to
  the serving plane's replicated / near-even rows layout.

Numerics follow the ZeRO contract (tests/test_twod.py): with plain
fp32 Sum/Average the sharded update is bit-identical to the same-mesh
data-parallel oracle (psum + replicated update), because psum_scatter
reduces per element exactly like psum and the parameter add stays
adjacent to the optimizer multiply (``ops/zero.py`` ``_run``). Wire
codecs do not compose with the 2D path yet — gradients ride fp32.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import reduce_ops
from ..ops.bucketing import _unpack
from ..ops.zero import (DEFAULT_ZERO_BUCKET_BYTES, _pack_padded,
                        _validate_elementwise_state, plan_zero)
from ..utils.logging_util import get_logger
from .sharding import make_param_specs, transformer_param_rules


def make_mesh_2d(dp, tp, devices=None):
    """A (dp, tp) mesh over the first ``dp*tp`` local devices."""
    devices = list(devices if devices is not None else jax.devices())
    n = int(dp) * int(tp)
    if len(devices) < n:
        raise ValueError(f"need {n} devices for a ({dp}, {tp}) mesh, "
                         f"have {len(devices)}")
    return Mesh(np.array(devices[:n]).reshape(int(dp), int(tp)),
                ("dp", "tp"))


class TwoDZero:
    """One bound instance of (inner optimizer × 2D mesh × shard plan).

    The ZeRO plan is derived from the TENSOR-LOCAL leaf shapes (every
    tp rank's slice is the same shape — even division is the
    ``sharding._spec_fits`` contract), so all (dp, tp) ranks agree on
    the identical pad-and-split geometry; state vector leaves live as
    global ``(dp·tp·shard_len,)`` arrays sharded ``P((dp, tp))`` —
    rank-major flat shards, the exact buffer layout the redistribution
    planner's ``("bucket", k)`` keys address."""

    def __init__(self, inner, mesh, dp_axis="dp", tp_axis="tp",
                 op=reduce_ops.Average,
                 bucket_bytes=DEFAULT_ZERO_BUCKET_BYTES, rules=None):
        if op not in (reduce_ops.Average, reduce_ops.Sum):
            raise ValueError(
                "2D ZeRO supports Average/Sum gradient reductions "
                f"only (got {reduce_ops.op_name(op)})")
        self.inner = inner
        self.mesh = mesh
        self.dp_axis = dp_axis
        self.tp_axis = tp_axis
        self.op = op
        self.dp = int(mesh.shape[dp_axis])
        self.tp = int(mesh.shape[tp_axis])
        self.bucket_bytes = int(bucket_bytes)
        self.rules = rules
        self.plan = None
        self.param_specs = None
        self.treedef = None

    # -- plan --------------------------------------------------------------
    def _local_shape(self, shape, spec):
        out = list(shape)
        for d, names in enumerate(tuple(spec)[:len(out)]):
            if names is None:
                continue
            names = names if isinstance(names, tuple) else (names,)
            k = int(np.prod([self.mesh.shape[n] for n in names]))
            out[d] //= k
        return tuple(out)

    def ensure_plan(self, params):
        leaves, treedef = jax.tree.flatten(params)
        if self.plan is None:
            self.param_specs = make_param_specs(
                params, self.mesh,
                self.rules if self.rules is not None
                else transformer_param_rules(tp_axis=self.tp_axis))
            spec_leaves = jax.tree.leaves(
                self.param_specs,
                is_leaf=lambda x: isinstance(x, P))
            local = [jax.ShapeDtypeStruct(
                self._local_shape(leaf.shape, spec), leaf.dtype)
                for leaf, spec in zip(leaves, spec_leaves)]
            self.plan = plan_zero(local, self.dp, self.bucket_bytes)
            self.treedef = treedef
            for b, s in zip(self.plan.buckets, self.plan.shards):
                _validate_elementwise_state(self.inner, s.shard_len,
                                            b.dtype)
        return self.plan

    def _spec_leaves(self):
        return jax.tree.leaves(self.param_specs,
                               is_leaf=lambda x: isinstance(x, P))

    # -- resharding specs --------------------------------------------------
    def tensor_layouts(self):
        """Per-leaf :class:`resharding.Sharded`/``Replicated`` tensor
        stages mirroring the param specs (first tp-named dim wins; the
        rules shard at most one dim over tp)."""
        from .. import resharding
        out = []
        for spec in self._spec_leaves():
            lay = resharding.Replicated()
            for d, names in enumerate(tuple(spec)):
                names = names if isinstance(names, tuple) else (names,)
                if self.tp_axis in names:
                    lay = resharding.Sharded(self.tp_axis, d)
                    break
            out.append(lay)
        return out

    def spec_2d(self, params):
        """The composed (ZeroFlat over dp) × (Sharded over tp) layout
        of this runtime's optimizer state, as a planner Spec."""
        from .. import resharding
        self.ensure_plan(params)
        return resharding.Spec(
            {self.dp_axis: self.dp, self.tp_axis: self.tp},
            self.tensor_layouts(),
            zero=resharding.ZeroFlat(self.dp_axis, self.plan))

    # -- state -------------------------------------------------------------
    def state_specs(self):
        specs = []
        for b, s in zip(self.plan.buckets, self.plan.shards):
            shape = jax.eval_shape(
                self.inner.init,
                jax.ShapeDtypeStruct((s.shard_len,), b.dtype))
            specs.append(jax.tree.map(
                lambda l: P((self.dp_axis, self.tp_axis))
                if l.ndim >= 1 else P(), shape))
        return (tuple(specs), (), ())

    def init_state(self, params):
        """Optimizer state born (dp × tp)-sharded — the replicated
        footprint never exists (same contract as
        ``ZeroRuntime.init_state``)."""
        plan = self.ensure_plan(params)

        def body(p):
            leaves = jax.tree.leaves(p)
            states = []
            for b, s in zip(plan.buckets, plan.shards):
                buf = _pack_padded(leaves, b, s.padded)
                p_shard = buf.reshape(self.dp, s.shard_len)[
                    lax.axis_index(self.dp_axis)]
                states.append(self.inner.init(p_shard))
            return tuple(states), (), ()

        return jax.jit(_shard_map(
            body, mesh=self.mesh, in_specs=(self.param_specs,),
            out_specs=self.state_specs(), check_vma=False))(params)

    # -- the 2D sharded update --------------------------------------------
    def _tp_replicated(self, spec):
        for names in tuple(spec):
            names = names if isinstance(names, tuple) else (names,)
            if self.tp_axis in names:
                return False
        return True

    def _update_body(self, grads, state, params):
        """tp-local, dp-replicated leaves in; ZeRO legs over dp.

        Gradients arrive as raw autodiff of the rank's LOCAL partial
        loss: tp-sharded leaves carry their exact slice gradient, but
        tp-REPLICATED leaves (norms, embeddings the rules leave whole)
        carry only this tp slice's contribution — sum those over tp
        first, or the shared parameter silently diverges across the
        tensor dimension."""
        plan = self.plan
        g_leaves = list(jax.tree.leaves(grads))
        if self.tp > 1:
            for idx, spec in enumerate(self._spec_leaves()):
                if self._tp_replicated(spec):
                    g_leaves[idx] = lax.psum(g_leaves[idx],
                                             self.tp_axis)
        p_leaves = jax.tree.leaves(params)
        bucket_states = state[0]
        out = [None] * len(g_leaves)
        new_states = []
        average = self.op == reduce_ops.Average
        for k, (b, s) in enumerate(zip(plan.buckets, plan.shards)):
            g = _pack_padded(g_leaves, b, s.padded)
            g_shard = lax.psum_scatter(g, self.dp_axis, tiled=True)
            if average:
                g_shard = g_shard / self.dp
            p = _pack_padded(p_leaves, b, s.padded)
            p_shard = p.reshape(self.dp, s.shard_len)[
                lax.axis_index(self.dp_axis)]
            u_shard, new_state_k = self.inner.update(
                g_shard, bucket_states[k], p_shard)
            new_states.append(new_state_k)
            new_p_shard = p_shard + u_shard.astype(p_shard.dtype)
            full = lax.all_gather(new_p_shard, self.dp_axis,
                                  tiled=True)
            if s.padded != s.size:
                full = lax.slice(full, (0,), (s.size,))
            _unpack(full, g_leaves, b, out)
        new_params = jax.tree.unflatten(self.treedef, out)
        return new_params, (tuple(new_states), (), ())

    def make_step(self, loss_fn):
        """Jitted 2D train step: ``step(params, state, batch) ->
        (new_params, new_state, loss)``. ``loss_fn(params, batch)``
        sees TENSOR-LOCAL params and the rank's dp batch shard and
        returns its local partial loss; the returned loss is the
        psum over both axes."""
        self_ref = self

        def body(p, s, b):
            loss, grads = jax.value_and_grad(loss_fn)(p, b)
            new_p, new_s = self_ref._update_body(grads, s, p)
            loss = lax.psum(lax.psum(loss, self_ref.dp_axis),
                            self_ref.tp_axis)
            return new_p, new_s, loss

        def step(params, state, batch):
            self_ref.ensure_plan(params)
            fn = jax.jit(_shard_map(
                body, mesh=self_ref.mesh,
                in_specs=(self_ref.param_specs,
                          self_ref.state_specs(),
                          P(self_ref.dp_axis)),
                out_specs=(self_ref.param_specs,
                           self_ref.state_specs(), P()),
                check_vma=False))
            return fn(params, state, batch)

        return step

    def apply_gradients(self, params, state, grads):
        """ZeRO-leg update from already-computed gradients (grads laid
        out exactly like params: tp-sharded, dp-replicated)."""
        self.ensure_plan(params)
        fn = jax.jit(_shard_map(
            lambda g, s, p: self._update_body(g, s, p),
            mesh=self.mesh,
            in_specs=(self.param_specs, self.state_specs(),
                      self.param_specs),
            out_specs=(self.param_specs, self.state_specs()),
            check_vma=False))
        return fn(grads, state, params)

    # -- train -> serve ----------------------------------------------------
    def to_serving(self, params, serving_world=1, serving_rank=0,
                   layout="replicated"):
        """Planner-emitted train→serve transform: the tensor-sharded
        params move to the serving plane's layout
        (``serving.state.REPLICATED`` / ``ROWS``) through a bounded-
        window program — never a full device_get of the tree."""
        from .. import resharding
        self.ensure_plan(params)
        meta = resharding.tree_meta_of(params)
        src = resharding.Spec(
            {self.dp_axis: self.dp, self.tp_axis: self.tp},
            self.tensor_layouts())
        if layout == "rows":
            dst = resharding.Spec(
                {"s": int(serving_world)},
                [resharding.Sharded("s", 0, even=False)
                 for _ in meta])
        elif layout == "replicated":
            dst = resharding.replicated_spec(len(meta),
                                             {"s": int(serving_world)})
        else:
            raise ValueError(f"unknown inference layout {layout!r}")
        program = resharding.plan_redistribution(src, dst, meta)
        program.verify_consistency()
        reader = _param_shard_reader(params, src, meta, self.mesh)
        results, _ = resharding.execute_host(
            program, reader, ranks=[int(serving_rank)])
        leaves = []
        for i, (shape, dtype) in enumerate(meta):
            flat = results[int(serving_rank)].get(
                ("leaf", i), np.zeros(0, np.dtype(dtype)))
            if layout == "rows" and len(shape) >= 1 and shape[0] >= 1:
                from ..serving.state import row_slice
                lo, hi = row_slice(shape[0], serving_world,
                                   serving_rank)
                out_shape = (hi - lo,) + tuple(shape[1:])
            else:
                out_shape = tuple(shape)
            leaves.append(flat.reshape(out_shape))
        return jax.tree.unflatten(jax.tree.structure(params), leaves)


def _param_shard_reader(params, spec, meta, mesh):
    """Windowed reads over tensor-sharded param leaves: resolve
    (rank, leaf) to the rank's addressable device shard, slice the
    window (one host-side shard cached at a time)."""
    devices = list(mesh.devices.flat)
    dev_rank = {id(d): r for r, d in enumerate(devices)}
    leaves = jax.tree.leaves(params)
    shard_by = []
    for leaf in leaves:
        if not getattr(leaf, "is_fully_addressable", True):
            raise RuntimeError(
                "twod: cannot read train-layout params in place — a "
                "leaf lives on non-addressable devices (multi-process "
                "global mesh). Checkpoint and load_from_shards on the "
                "serving hosts instead (docs/serving.md).")
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            shard_by.append(None)
        else:
            shard_by.append({dev_rank[id(sh.device)]: sh
                             for sh in shards
                             if id(sh.device) in dev_rank})
    cache = {}

    def read_window(rank, buf, start, length):
        _, i = buf
        key = (i, rank)
        if key not in cache:
            cache.clear()
            if shard_by[i] is None:
                cache[key] = np.asarray(leaves[i]).reshape(-1)
            else:
                cache[key] = np.asarray(
                    shard_by[i][rank].data).reshape(-1)
        return cache[key][start:start + length]

    return read_window


def reshard_2d(state, old, new, params):
    """Planner-emitted elastic reshard of the 2D optimizer state:
    ``old``/``new`` are :class:`TwoDZero` runtimes (dp and/or tp
    cohort sizes may differ; the new tp slicing must keep leaf shapes
    even). One redistribution program moves every moment slot; windows
    read from the old cohort's addressable shards. Mirrors
    ``ops.zero.reshard_state`` (residual-free state, pure data
    movement — moments survive bit-exactly)."""
    from .. import resharding
    old.ensure_plan(params)
    new_plan = new.ensure_plan(params)
    meta = [(tuple(leaf.shape), str(leaf.dtype))
            for leaf in jax.tree.leaves(params)]
    src_spec = old.spec_2d(params)
    dst_spec = new.spec_2d(params)
    program = resharding.plan_redistribution(src_spec, dst_spec, meta)
    program.verify_consistency()
    bucket_states = state[0]
    treedefs = [jax.tree.structure(bs) for bs in bucket_states]
    if any(td != treedefs[0] for td in treedefs[1:]):
        raise ValueError("per-bucket inner states diverge in structure")
    devices_old = list(old.mesh.devices.flat)
    dev_rank = {id(d): r for r, d in enumerate(devices_old)}
    new_devices = list(new.mesh.devices.flat)
    nw = new.dp * new.tp
    slot0 = jax.tree.leaves(bucket_states[0])
    nslots = len(slot0)
    new_flat = [[None] * nslots for _ in range(len(new_plan.buckets))]
    rep_sharding = NamedSharding(new.mesh, P())
    for j in range(nslots):
        if np.ndim(slot0[j]) == 0:
            scalar = np.asarray(slot0[j])
            for k in range(len(new_plan.buckets)):
                new_flat[k][j] = jax.device_put(scalar, rep_sharding)
            continue
        shard_by = {}
        for k, bs in enumerate(bucket_states):
            leaf = jax.tree.leaves(bs)[j]
            shard_by[k] = {dev_rank[id(sh.device)]: sh
                           for sh in leaf.addressable_shards
                           if id(sh.device) in dev_rank}
        cache = {}

        def read_window(rank, buf, start, length, _sb=shard_by,
                        _c=cache):
            _, k = buf
            key = (k, rank)
            if key not in _c:
                _c.clear()
                _c[key] = np.asarray(_sb[k][rank].data).reshape(-1)
            return _c[key][start:start + length]

        dtypes = {str(jax.tree.leaves(bs)[j].dtype)
                  for bs in bucket_states}
        override = dtypes.pop() if len(dtypes) == 1 else None
        results, _ = resharding.execute_host(program, read_window,
                                             dtype_override=override)
        for k, s in enumerate(new_plan.shards):
            vec_sharding = NamedSharding(
                new.mesh, P((new.dp_axis, new.tp_axis)))
            new_flat[k][j] = jax.make_array_from_single_device_arrays(
                (nw * s.shard_len,), vec_sharding,
                [jax.device_put(results[r][("bucket", k)], d)
                 for r, d in enumerate(new_devices)])
    get_logger().warning(
        "twod: optimizer state resharded (dp=%d, tp=%d) -> "
        "(dp=%d, tp=%d) via %s program (%d step(s), %d wire bytes)",
        old.dp, old.tp, new.dp, new.tp, program.strategy,
        len(program.steps), program.bytes_moved())
    return (tuple(jax.tree.unflatten(treedefs[0], flat)
                  for flat in new_flat), (), ())
