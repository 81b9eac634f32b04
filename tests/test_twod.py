"""2D (data × tensor) training (parallel/twod.py; docs/resharding.md).

Pins the ISSUE 17 acceptance contract: a composed dp × tp train step —
sharding.py tensor layouts + ZeRO legs over dp on ONE mesh — is
bit-exact against the same-mesh data-parallel oracle (psum +
replicated inner state), its elastic reshard (dp 4→2 on 8 devices) and
train→serve transform are both planner-emitted and bit-exact, the
moment bytes survive the transition exactly, and every emitted program
proves HVD501/HVD502-clean under hvd-sim.
"""

import numpy as np

import jax
import jax.numpy as jnp
import optax
import pytest
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import resharding
from horovod_tpu.parallel import twod

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices")


def _params(seed=1):
    rng = np.random.default_rng(seed)
    return {
        "mlp_in": {
            "kernel": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
            "bias": jnp.asarray(rng.normal(size=(4,)), jnp.float32)},
        "scale": jnp.asarray(rng.normal(size=(8,)), jnp.float32)}


def _batch(seed=2):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)


def _loss_fn(p, b):
    """Column-decomposable toy loss: with mlp_in.kernel tensor-sharded
    on its output dim, each tp rank's partial is exact for its slice
    and the partials sum to the full loss."""
    h = b * p["scale"]
    y = h @ p["mlp_in"]["kernel"] + p["mlp_in"]["bias"]
    return jnp.sum(y * y)


def _oracle(tz, inner):
    """Same-mesh data-parallel reference: tp-sharded params, psum'd
    gradients (tp-sum for replicated leaves first — the shared-param
    contract), one REPLICATED (unsharded) inner state per rank."""
    mesh, specs = tz.mesh, tz.param_specs
    params0 = _params()
    pspec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    local_shapes = [tz._local_shape(l.shape, sp) for l, sp in
                    zip(jax.tree.leaves(params0), pspec_leaves)]
    ostate_shape = jax.eval_shape(inner.init, jax.tree.map(
        lambda l, sp: jax.ShapeDtypeStruct(
            tz._local_shape(l.shape, sp), l.dtype),
        params0, specs, is_leaf=lambda x: hasattr(x, "shape")))
    flat_state, tdef = jax.tree_util.tree_flatten(ostate_shape)
    sspec_leaves = [P() if l.ndim == 0 else
                    pspec_leaves[local_shapes.index(tuple(l.shape))]
                    for l in flat_state]
    state_specs = jax.tree_util.tree_unflatten(tdef, sspec_leaves)

    def o_body(p, s, b):
        loss, grads = jax.value_and_grad(_loss_fn)(p, b)
        gl = list(jax.tree.leaves(grads))
        for i, sp in enumerate(pspec_leaves):
            if tz._tp_replicated(sp):
                gl[i] = lax.psum(gl[i], tz.tp_axis)
        grads = jax.tree.unflatten(jax.tree.structure(grads), gl)
        grads = jax.tree.map(
            lambda g: lax.psum(g, tz.dp_axis) / tz.dp, grads)
        updates, s2 = inner.update(grads, s, p)
        p2 = jax.tree.map(lambda q, u: q + u.astype(q.dtype), p,
                          updates)
        return p2, s2, lax.psum(lax.psum(loss, tz.dp_axis),
                                tz.tp_axis)

    o_init = jax.jit(_shard_map(
        lambda p: inner.init(p), mesh=mesh, in_specs=(specs,),
        out_specs=state_specs, check_vma=False))
    o_step = jax.jit(_shard_map(
        o_body, mesh=mesh,
        in_specs=(specs, state_specs, P(tz.dp_axis)),
        out_specs=(specs, state_specs, P()), check_vma=False))
    return o_init, o_step, state_specs


def _place(tree, mesh, specs):
    return jax.tree.map(
        lambda leaf, spec: jax.device_put(
            np.asarray(leaf), NamedSharding(mesh, spec)),
        tree, specs, is_leaf=lambda x: hasattr(x, "shape"))


def _moment_vecs(state, tz):
    """(bucket k, (dp, tp, shard_len) view) per vector state leaf."""
    out = []
    for k, bs in enumerate(state[0]):
        for leaf in jax.tree_util.tree_leaves(bs):
            if np.ndim(leaf) >= 1:
                out.append((k, np.asarray(leaf).reshape(
                    tz.dp, tz.tp, -1)))
    return out


class TestTwoDStep:
    def test_two_steps_bit_exact_vs_oracle(self):
        inner = optax.adam(1e-2)
        mesh = twod.make_mesh_2d(4, 2)
        tz = twod.TwoDZero(inner, mesh)
        params, batch = _params(), _batch()
        state = tz.init_state(params)
        step = tz.make_step(_loss_fn)
        o_init, o_step, _ = _oracle(tz, inner)
        op, ost = params, o_init(params)
        p, s = params, state
        for _ in range(2):
            p, s, loss = step(p, s, batch)
            op, ost, oloss = o_step(op, ost, batch)
            assert float(loss) == float(oloss)
            for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(op)):
                assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_state_is_born_sharded(self):
        inner = optax.adam(1e-2)
        tz = twod.TwoDZero(inner, twod.make_mesh_2d(4, 2))
        state = tz.init_state(_params())
        for k, bs in enumerate(state[0]):
            for leaf in jax.tree_util.tree_leaves(bs):
                if np.ndim(leaf) >= 1:
                    n = tz.plan.shards[k].shard_len
                    assert leaf.shape == (tz.dp * tz.tp * n,)

    def test_sum_op_supported_and_adasum_rejected(self):
        from horovod_tpu.ops import reduce_ops
        inner = optax.sgd(1e-2)
        mesh = twod.make_mesh_2d(2, 2)
        twod.TwoDZero(inner, mesh, op=reduce_ops.Sum)
        with pytest.raises(ValueError):
            twod.TwoDZero(inner, mesh, op=reduce_ops.Adasum)


class TestElasticReshard2D:
    def _train(self, steps=2):
        inner = optax.adam(1e-2)
        mesh4 = twod.make_mesh_2d(4, 2)
        tz4 = twod.TwoDZero(inner, mesh4)
        params, batch = _params(), _batch()
        state = tz4.init_state(params)
        step = tz4.make_step(_loss_fn)
        p, s = params, state
        for _ in range(steps):
            p, s, _ = step(p, s, batch)
        return inner, tz4, p, s, batch, step

    def test_reshard_4_to_2_then_step_bit_exact_vs_oracle(self):
        inner, tz4, p, s, batch, _ = self._train()
        o_init4, o_step4, _ = _oracle(tz4, inner)
        op, ost = _params(), o_init4(_params())
        for _ in range(2):
            op, ost, _ = o_step4(op, ost, batch)

        mesh2 = twod.make_mesh_2d(2, 2)
        tz2 = twod.TwoDZero(inner, mesh2)
        s2 = twod.reshard_2d(s, tz4, tz2, p)
        tz2.ensure_plan(p)
        p2 = _place(p, mesh2, tz2.param_specs)
        pa, sa, la = tz2.make_step(_loss_fn)(p2, s2, batch)

        _, o_step2, sspecs2 = _oracle(tz2, inner)
        opa, osta, ola = o_step2(
            _place(op, mesh2, tz2.param_specs),
            _place(ost, mesh2, sspecs2), batch)
        assert float(la) == float(ola)
        for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(opa)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_moments_survive_bit_exact_and_round_trip(self):
        inner, tz4, p, s, _, _ = self._train()
        tz2 = twod.TwoDZero(inner, twod.make_mesh_2d(2, 2))
        s2 = twod.reshard_2d(s, tz4, tz2, p)
        v4 = _moment_vecs(s, tz4)
        for (k, a), (_, b) in zip(v4, _moment_vecs(s2, tz2)):
            size = tz4.plan.shards[k].size
            for t in range(tz4.tp):
                assert np.array_equal(
                    a[:, t].reshape(-1)[:size],
                    b[:, t].reshape(-1)[:size])
        s4b = twod.reshard_2d(s2, tz2, tz4, p)
        for (k, a), (_, b) in zip(v4, _moment_vecs(s4b, tz4)):
            size = tz4.plan.shards[k].size
            for t in range(tz4.tp):
                assert np.array_equal(
                    a[:, t].reshape(-1)[:size],
                    b[:, t].reshape(-1)[:size])

    def test_reshard_program_proves_clean(self):
        inner, tz4, p, _, _, _ = self._train(steps=1)
        tz2 = twod.TwoDZero(inner, twod.make_mesh_2d(2, 2))
        tz2.ensure_plan(p)
        meta = [(tuple(l.shape), str(l.dtype))
                for l in jax.tree.leaves(p)]
        program = resharding.plan_redistribution(
            tz4.spec_2d(p), tz2.spec_2d(p), meta)
        assert program.prove() == []
        assert program.bytes_moved() > 0


class TestTrainToServe2D:
    def test_replicated_and_rows_bit_exact(self):
        inner = optax.adam(1e-2)
        tz = twod.TwoDZero(inner, twod.make_mesh_2d(2, 2))
        params, batch = _params(), _batch()
        state = tz.init_state(params)
        p, _, _ = tz.make_step(_loss_fn)(params, state, batch)
        full = tz.to_serving(p, serving_world=1, serving_rank=0)
        for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(p)):
            assert np.array_equal(np.asarray(a),
                                  np.asarray(jax.device_get(b)))
        rows = [tz.to_serving(p, serving_world=2, serving_rank=r,
                              layout="rows") for r in (0, 1)]
        for r0, r1, leaf in zip(jax.tree.leaves(rows[0]),
                                jax.tree.leaves(rows[1]),
                                jax.tree.leaves(p)):
            cat = np.concatenate(
                [np.asarray(r0), np.asarray(r1)], axis=0)
            assert np.array_equal(cat,
                                  np.asarray(jax.device_get(leaf)))

    def test_serve_program_proves_clean(self):
        inner = optax.adam(1e-2)
        tz = twod.TwoDZero(inner, twod.make_mesh_2d(2, 2))
        p = _params()
        tz.ensure_plan(p)
        meta = [(tuple(l.shape), str(l.dtype))
                for l in jax.tree.leaves(p)]
        src = resharding.Spec({"dp": 2, "tp": 2}, tz.tensor_layouts())
        dst = resharding.replicated_spec(len(meta), {"s": 1})
        program = resharding.plan_redistribution(src, dst, meta)
        assert program.prove() == []
