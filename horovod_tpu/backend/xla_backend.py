"""Single-controller XLA data plane.

This is the TPU-native replacement for the reference's NCCL op layer
(reference: horovod/common/ops/nccl_operations.cc). Instead of an async
host-side collective library bridged to the framework stream, every eager
collective here is a **jitted XLA program over the replica mesh**: each mesh
device is a virtual rank, inputs are stacked along a leading virtual-rank
axis and sharded P('hvd'), and the collective lowers to the matching XLA/ICI
primitive (psum / all_gather / psum_scatter / all_to_all).

Fusion (reference: fusion_buffer_manager.cc + batched D2D kernels,
horovod/common/ops/cuda/cuda_kernels.cu:45-139) is achieved at a different
level: the coordinator concatenates flattened tensors into one buffer per
dtype and this backend runs ONE compiled collective per buffer — XLA then
handles all layout/fusion on-device, so no hand-written memcpy kernels are
needed.

Compiled programs are cached per (op-kind, process-set, reduce-op); together
with jit's shape-keyed cache this plays the role of the reference's response
cache (reference: horovod/common/response_cache.cc) — a steady-state training
step re-dispatches a cached executable with zero negotiation.
"""

import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from . import Backend
from ..ops import reduce_ops
from ..telemetry import core as telemetry
from ..telemetry import span as tele_span
from ..utils import envparse

AXIS = "hvd"
# Bound on cached compiled programs, the analog of the reference's
# response-cache capacity (reference: horovod/common/global_state.h:89,
# HOROVOD_CACHE_CAPACITY read at operations.cc:516).
DEFAULT_CACHE_CAPACITY = 1024


def _timed(kind):
    """Per-collective telemetry around a backend method: wall time (jax
    dispatch is async, so this is submit-to-future time — first calls
    include compilation) and payload bytes by op type. Zero work when
    metrics are off."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, payload, *args, **kwargs):
            if not self._metrics_on:
                return fn(self, payload, *args, **kwargs)
            with tele_span((), kind.upper(),
                           histogram=self._m_time.labels(
                               backend=self.name, kind=kind)):
                out = fn(self, payload, *args, **kwargs)
            nbytes = telemetry.payload_nbytes(payload)
            if nbytes:
                self._m_bytes.labels(backend=self.name,
                                     kind=kind).inc(nbytes)
            return out
        return wrapper
    return deco


def _scale(x, factor):
    if factor is None:
        return x
    return x * jnp.asarray(factor).astype(x.dtype)


def _pprod(x, n):
    """Cross-replica product via ppermute: O(block) device memory (the
    gather-then-prod alternative holds n blocks).

    Binomial-tree reduce to rank 0 (ceil(log2 n) rounds for ANY n — the
    idx+shift<n mask handles partial partners; one fixed association)
    then broadcast rank 0's result — every rank returns BITWISE-identical
    values, preserving the allreduce contract that all stacked slices are
    equal. A rotation-order ring would multiply in a different
    association per rank and drift at the ulp level.
    """
    idx = lax.axis_index(AXIS)
    acc = x
    shift = 1
    while shift < n:
        recv = lax.ppermute(acc, AXIS,
                            [(i, (i - shift) % n) for i in range(n)])
        take = (idx % (2 * shift) == 0) & (idx + shift < n)
        acc = jnp.where(take, acc * recv, acc)
        shift *= 2
    return _psum_broadcast(acc, 0)


def _psum_broadcast(x, root_rank):
    """One-to-all broadcast as a masked psum: every non-root contributes
    zeros, so per-device memory stays O(block) — no all_gather
    materializing n blocks. Bool rides as int32."""
    is_bool = x.dtype == jnp.bool_
    v = x.astype(jnp.int32) if is_bool else x
    idx = lax.axis_index(AXIS)
    picked = jnp.where(idx == root_rank, v, jnp.zeros_like(v))
    out = lax.psum(picked, AXIS)
    return out.astype(jnp.bool_) if is_bool else out


class XlaSingleBackend(Backend):
    name = "xla"

    def __init__(self, mesh):
        self.global_mesh = mesh
        self._meshes = {0: mesh}
        self._fns = OrderedDict()
        self._cache_capacity = envparse.get_int(
            envparse.CACHE_CAPACITY, DEFAULT_CACHE_CAPACITY)
        # NULL no-ops when HOROVOD_TPU_METRICS is off (docs/metrics.md).
        self._metrics_on = telemetry.enabled()
        self._m_time = telemetry.histogram(
            "hvd_backend_collective_seconds",
            "Per-collective backend wall time",
            labelnames=("backend", "kind"))
        self._m_bytes = telemetry.counter(
            "hvd_backend_collective_bytes_total",
            "Payload bytes through backend collectives",
            labelnames=("backend", "kind"))

    # -- process sets ------------------------------------------------------
    def register_process_set(self, ps):
        self._meshes[ps.process_set_id] = ps.mesh

    def remove_process_set(self, ps):
        self._meshes.pop(ps.process_set_id, None)
        self._fns = OrderedDict(
            (k, v) for k, v in self._fns.items()
            if k[1] != ps.process_set_id)

    def _mesh(self, ps):
        return self._meshes[ps.process_set_id]

    def shard(self, ps, x):
        """Place a stacked array so slice i lives on virtual rank i's device."""
        mesh = self._mesh(ps)
        return jax.device_put(x, NamedSharding(mesh, P(AXIS)))

    # -- compiled-program cache -------------------------------------------
    def _cached(self, key, builder):
        """LRU-bounded program cache. Dynamic keys (e.g. ragged alltoall
        splits) would otherwise grow without bound."""
        fn = self._fns.get(key)
        if fn is None:
            fn = builder()
            self._fns[key] = fn
            while len(self._fns) > self._cache_capacity:
                self._fns.popitem(last=False)
        else:
            self._fns.move_to_end(key)
        return fn

    # -- allreduce ---------------------------------------------------------
    @_timed("allreduce")
    def allreduce(self, arrays, op, process_set, prescale=None,
                  postscale=None):
        """Stacked allreduce: each array has leading axis == set size; output
        is stacked with every slice equal to the reduction.

        One jitted shard_map carries the whole list (a fusion bucket) in a
        single XLA program → one fused ICI collective sequence.
        """
        if op == reduce_ops.Adasum:
            return self._adasum_allreduce(arrays, process_set, prescale,
                                          postscale)
        mesh = self._mesh(process_set)
        n = mesh.devices.size
        key = ("ar", process_set.process_set_id, op)

        def build():
            def body(scales, xs):
                pre, post = scales
                outs = []
                for x in xs:
                    x = _scale(x, pre)
                    if op in (reduce_ops.Sum, reduce_ops.Average):
                        y = lax.psum(x, AXIS)
                        if op == reduce_ops.Average:
                            y = (y / n).astype(x.dtype)
                    elif op == reduce_ops.Min:
                        y = lax.pmin(x, AXIS)
                    elif op == reduce_ops.Max:
                        y = lax.pmax(x, AXIS)
                    elif op == reduce_ops.Product:
                        # ppermute-based product: O(block) memory per
                        # device vs the O(n*block) of gather-then-prod;
                        # binomial tree + broadcast, ~2*ceil(log2 n)
                        # rounds for any n.
                        y = _pprod(x, n)
                    else:
                        raise ValueError(
                            f"Unsupported op {reduce_ops.op_name(op)}")
                    y = _scale(y, post)
                    outs.append(y)
                return tuple(outs)

            sm = _shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(AXIS)), out_specs=P(AXIS))
            return jax.jit(sm)

        fn = self._cached(key, build)
        pre = jnp.asarray(1.0 if prescale is None else prescale,
                          dtype=jnp.float32)
        post = jnp.asarray(1.0 if postscale is None else postscale,
                           dtype=jnp.float32)
        ins = tuple(self.shard(process_set, jnp.asarray(a)) for a in arrays)
        return list(fn((pre, post), ins))

    def _adasum_allreduce(self, arrays, process_set, prescale, postscale):
        from ..ops import adasum
        return adasum.adasum_allreduce_stacked(
            self, arrays, process_set, prescale, postscale)

    # -- quantized allreduce (EQuARX pipeline) -----------------------------
    @_timed("allreduce_quantized")
    def allreduce_quantized(self, arrays, op, process_set, codec, block,
                            prescale=None, postscale=None,
                            residuals=None):
        """Block-quantized fused allreduce: quantize → all_to_all (the
        reduce-scatter leg, wire dtype) → dequantized f32 accumulation →
        requantize → all_gather (wire dtype again) → dequantize. Both
        collective legs carry ~1 byte/value + one f32 scale per
        ``block`` instead of the input dtype's width (PAPERS.md: EQuARX,
        arXiv:2506.17615).

        ``residuals`` (error feedback, optional): f32 arrays aligned
        with ``arrays``; each is added to the (prescaled) input before
        quantization, and the call returns ``(outs, new_residuals)``
        where ``new_residuals[i] = input_i - dequant(quant(input_i))``
        — the quantization debt to carry into the next step. With
        ``residuals=None`` the second element is None.

        Only Sum/Average are supported: dequantize-then-accumulate is a
        linear-reduction identity; Min/Max/Product have no wide-dtype
        reduce stage (the policy never routes them here)."""
        if op not in (reduce_ops.Sum, reduce_ops.Average):
            raise ValueError(
                "quantized allreduce supports Sum/Average, got "
                f"{reduce_ops.op_name(op)}")
        mesh = self._mesh(process_set)
        n = mesh.devices.size
        ef = residuals is not None
        key = ("arq", process_set.process_set_id, op, codec.name,
               int(block), ef)

        def build():
            from ..compression.codecs import padded_len

            def pipeline(flats, post):
                """flats: list of f32 per-rank flat vectors (residual
                already folded in). Returns (reduced flats, local
                quantization errors)."""
                sizes = [f.shape[0] for f in flats]
                flat = (jnp.concatenate(flats) if len(flats) > 1
                        else flats[0])
                total = flat.shape[0]
                padded = padded_len(total, n, block)
                if padded != total:
                    flat = jnp.pad(flat, (0, padded - total))
                rows = flat.reshape(n, padded // n)
                q, s = codec.encode(rows, block)
                # Local reconstruction error BEFORE the exchange — the
                # residual each virtual rank carries forward.
                err = (rows - codec.decode(q, s, block)).reshape(padded)
                q = lax.all_to_all(q, AXIS, split_axis=0, concat_axis=0,
                                   tiled=True)
                s = lax.all_to_all(s, AXIS, split_axis=0, concat_axis=0,
                                   tiled=True)
                red = jnp.sum(codec.decode(q, s, block), axis=0)
                if op == reduce_ops.Average:
                    red = red / n
                red = _scale(red, post)
                q2, s2 = codec.encode(red, block)
                qg = lax.all_gather(q2, AXIS, tiled=True)
                sg = lax.all_gather(s2, AXIS, tiled=True)
                out = codec.decode(qg, sg, block)
                outs, errs, off = [], [], 0
                for size in sizes:
                    outs.append(out[off:off + size])
                    errs.append(err[off:off + size])
                    off += size
                return outs, errs

            def body(scales, xs, es):
                pre, post = scales
                flats = []
                for i, x in enumerate(xs):
                    f = _scale(x.reshape(-1).astype(jnp.float32), pre)
                    if es is not None:
                        f = f + es[i].reshape(-1)
                    flats.append(f)
                outs, errs = pipeline(flats, post)
                res, out_errs = [], []
                for x, o, err in zip(xs, outs, errs):
                    res.append(o.reshape(x.shape).astype(x.dtype))
                    out_errs.append(err.reshape(x.shape))
                if es is None:
                    return tuple(res)
                return tuple(res), tuple(out_errs)

            in_specs = ((P(), P(AXIS), P(AXIS)) if ef
                        else (P(), P(AXIS), None))
            sm = _shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=P(AXIS))
            return jax.jit(sm)

        fn = self._cached(key, build)
        pre = jnp.asarray(1.0 if prescale is None else prescale,
                          dtype=jnp.float32)
        post = jnp.asarray(1.0 if postscale is None else postscale,
                           dtype=jnp.float32)
        ins = tuple(self.shard(process_set, jnp.asarray(a))
                    for a in arrays)
        if ef:
            res_in = tuple(self.shard(process_set, jnp.asarray(r))
                           for r in residuals)
            outs, errs = fn((pre, post), ins, res_in)
            return list(outs), list(errs)
        return list(fn((pre, post), ins, None)), None

    # -- allgather ---------------------------------------------------------
    @_timed("allgather")
    def allgather(self, arrays, process_set):
        """Stacked allgather: (n, s0, ...) → (n, n*s0, ...), every slice the
        concatenation of all ranks' tensors (reference displacement logic:
        horovod/common/ops/collective_operations.h:129-179 — on TPU,
        lax.all_gather replaces the explicit displacement math)."""
        mesh = self._mesh(process_set)
        key = ("ag", process_set.process_set_id)

        def build():
            def body(*xs):
                outs = []
                for x in xs:
                    # Local block is (1, s0, ...); the gather stacks every
                    # rank's tensor then flattens to the concatenation.
                    g = lax.all_gather(x, AXIS, axis=0, tiled=True)
                    outs.append(g.reshape((-1,) + g.shape[2:])[None])
                return tuple(outs)
            sm = _shard_map(body, mesh=mesh, in_specs=P(AXIS),
                               out_specs=P(AXIS))
            return jax.jit(sm)

        fn = self._cached(key, build)
        ins = tuple(self.shard(process_set, jnp.asarray(a)) for a in arrays)
        return list(fn(*ins))

    @_timed("allgather")
    def allgather_uneven(self, per_rank_lists, process_set):
        """Allgather of per-rank tensors with differing dim-0 sizes.

        Data is already resident in this process, so "gathering" is a
        concatenation that XLA materializes replicated across the mesh.
        Returns stacked (n, total, ...) arrays for consistency with the
        equal-shape path.
        """
        mesh = self._mesh(process_set)
        n = mesh.devices.size
        sharding = NamedSharding(mesh, P(AXIS))
        outs = []
        for parts in per_rank_lists:
            full = np.concatenate([np.asarray(p) for p in parts], axis=0)
            block = full[None]
            # Build the stacked (n, total, ...) result shard-by-shard:
            # each device receives its (1, total, ...) block directly —
            # never materializing the n-fold (n, total, ...) copy that
            # broadcast_to would allocate before sharding.
            # Every stacked slice is identical, so each device's
            # (1, total, ...) shard IS the block, whatever its index.
            outs.append(jax.make_array_from_callback(
                (n,) + full.shape, sharding, lambda idx, b=block: b))
        return outs

    def replicate_stacked(self, array, process_set):
        """Stacked (n, ...) result with every slice == ``array``, built
        shard-by-shard like :meth:`allgather_uneven`: each mesh device
        receives one (1, ...) block directly — never materializing the
        n-fold copy ``broadcast_to`` would allocate before sharding
        (at bench geometry, GBs of identical replicas on one device)."""
        mesh = self._mesh(process_set)
        n = mesh.devices.size
        sharding = NamedSharding(mesh, P(AXIS))
        block = np.asarray(array)[None]
        return jax.make_array_from_callback(
            (n,) + block.shape[1:], sharding, lambda idx: block)

    # -- broadcast ---------------------------------------------------------
    @_timed("broadcast")
    def broadcast(self, arrays, root_rank, process_set):
        """Stacked broadcast: every virtual rank receives slice ``root_rank``
        (reference: BroadcastOp, horovod/common/ops/collective_operations.h:181)."""
        mesh = self._mesh(process_set)
        key = ("bc", process_set.process_set_id, root_rank)

        def build():
            def body(*xs):
                # Masked psum instead of gather-then-index: O(block)
                # device memory at any mesh size (the gather holds n
                # blocks per device before indexing one).
                return tuple(_psum_broadcast(x, root_rank) for x in xs)
            sm = _shard_map(body, mesh=mesh, in_specs=P(AXIS),
                               out_specs=P(AXIS))
            return jax.jit(sm)

        fn = self._cached(key, build)
        ins = tuple(self.shard(process_set, jnp.asarray(a)) for a in arrays)
        return list(fn(*ins))

    # -- alltoall ----------------------------------------------------------
    @_timed("alltoall")
    def alltoall(self, array, splits, process_set):
        """Stacked alltoall (reference: AlltoallOp::PrepareOutputAndParams,
        horovod/common/ops/collective_operations.h:195-273).

        ``array``: stacked (n, s0, ...); ``splits``: (n, n) host matrix where
        splits[r] partitions rank r's dim-0. Returns (list of per-rank
        outputs, recv_splits matrix). Uniform splits take the fast
        lax.all_to_all path; ragged splits compile a slicing program.
        """
        mesh = self._mesh(process_set)
        n = mesh.devices.size
        x = jnp.asarray(array)
        if splits is None:
            if x.shape[1] % n != 0:
                raise ValueError(
                    f"alltoall tensor dim0 {x.shape[1]} not divisible by "
                    f"process set size {n} and no splits given")
            splits = np.full((n, n), x.shape[1] // n, dtype=np.int64)
        else:
            splits = np.asarray(splits, dtype=np.int64)
            if splits.ndim == 1:
                splits = np.tile(splits, (n, 1))
        if splits.shape != (n, n):
            raise ValueError(f"splits must be ({n},{n}), got {splits.shape}")
        if np.any(splits.sum(axis=1) != x.shape[1]):
            raise ValueError("splits must sum to tensor dim0 per rank")
        recv_splits = splits.T.copy()

        uniform = np.all(splits == splits[0, 0])
        if uniform:
            key = ("a2a", process_set.process_set_id)

            def build():
                def body(x):
                    # Local block (1, s0, ...): split dim 1 into n pieces,
                    # exchange, stack received pieces source-major, flatten
                    # back to (1, s0, ...) — the concatenation of everyone's
                    # piece for this rank.
                    y = lax.all_to_all(x, AXIS, split_axis=1, concat_axis=0,
                                       tiled=True)
                    return y.reshape((1, -1) + y.shape[2:])
                sm = _shard_map(body, mesh=mesh, in_specs=P(AXIS),
                                   out_specs=P(AXIS))
                return jax.jit(sm)

            fn = self._cached(key, build)
            out = fn(self.shard(process_set, x))
            return [out[r] for r in range(n)], recv_splits

        # Ragged path: static-shape slicing program, cached by jit on shapes
        # and by tuple(splits) via static closure.
        key = ("a2a_ragged", process_set.process_set_id,
               tuple(splits.flatten().tolist()))

        def build():
            offs = np.zeros((n, n), dtype=np.int64)
            offs[:, 1:] = np.cumsum(splits, axis=1)[:, :-1]

            def fn(x):
                outs = []
                for r in range(n):
                    parts = [lax.slice_in_dim(x[s], int(offs[s, r]),
                                              int(offs[s, r] + splits[s, r]),
                                              axis=0)
                             for s in range(n)]
                    outs.append(jnp.concatenate(parts, axis=0))
                return tuple(outs)
            return jax.jit(fn)

        fn = self._cached(key, build)
        outs = fn(self.shard(process_set, x))
        return list(outs), recv_splits

    # -- reducescatter -----------------------------------------------------
    @_timed("reducescatter")
    def reducescatter(self, arrays, op, process_set):
        """Stacked reduce-scatter: (n, s0, ...) → list of per-rank chunks of
        the reduction, dim-0 partitioned like the reference (earlier ranks
        take the remainder, reference: horovod/common/ops/
        collective_operations.cc ReducescatterOp)."""
        if op not in (reduce_ops.Sum, reduce_ops.Average):
            raise ValueError("reducescatter supports Sum/Average")
        mesh = self._mesh(process_set)
        n = mesh.devices.size
        outs = []
        even = all(jnp.asarray(a).shape[1] % n == 0 for a in arrays)
        if even:
            key = ("rs", process_set.process_set_id, op)

            def build():
                def body(*xs):
                    res = []
                    for x in xs:
                        y = lax.psum_scatter(x, AXIS, scatter_dimension=1,
                                             tiled=True)
                        if op == reduce_ops.Average:
                            y = (y / n).astype(x.dtype)
                        res.append(y)
                    return tuple(res)
                sm = _shard_map(body, mesh=mesh, in_specs=P(AXIS),
                                   out_specs=P(AXIS))
                return jax.jit(sm)

            fn = self._cached(key, build)
            ins = tuple(self.shard(process_set, jnp.asarray(a))
                        for a in arrays)
            return list(fn(*ins))
        # Ragged: reduce fully, slice per rank on host-defined boundaries.
        reduced = self.allreduce(arrays, op, process_set)
        for full in reduced:
            s0 = full.shape[1]
            base, rem = divmod(s0, n)
            sizes = [base + (1 if r < rem else 0) for r in range(n)]
            offs = np.concatenate([[0], np.cumsum(sizes)])
            chunks = [full[r, int(offs[r]):int(offs[r + 1])]
                      for r in range(n)]
            outs.append(chunks)
        return outs

    # -- barrier / join ----------------------------------------------------
    def barrier(self, process_set):
        # Single controller: device-sync all outstanding work on the mesh.
        token = self.allreduce([jnp.zeros((self._mesh(process_set)
                                           .devices.size, 1))],
                               reduce_ops.Sum, process_set)[0]
        jax.block_until_ready(token)

    def close(self):
        self._fns.clear()
