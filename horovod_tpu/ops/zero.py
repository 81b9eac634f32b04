"""ZeRO-1 on TPU: cross-replica sharded weight update (``HVDTPU_ZERO``).

The optimizer update is the last fully-replicated stage of the data-
parallel loop: every replica holds the whole optimizer state and
redundantly computes the whole weight update. *Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training* (arXiv:2004.13336)
shows the update partitions across replicas for free — the gradient
reduction an allreduce already performs can land each replica only its
1/n slice (reduce-scatter), the optimizer steps that slice with 1/n of
the state, and the updated slice broadcasts back (allgather). Per-chip
Adam-family state drops from 2× params to 2× params / n; the two legs
move the same bytes as one allreduce (which IS reduce-scatter +
allgather in a ring/ICI formulation), so the memory win is ~free.

The plan here is the portable-collectives formulation (*Memory-
efficient array redistribution through portable collective
communication*, arXiv:2112.01075): sharding is expressed as a
deterministic pad-and-split plan over fixed fusion buckets —
:func:`plan_zero` maps (leaf shapes, world size, bucket budget,
quantization granule) to per-bucket shard geometry, so any cohort that
agrees on those inputs derives the identical plan, uneven leaf sizes
are absorbed by per-bucket padding (never by per-leaf remainders), and
a world-size change is a plan-to-plan redistribution
(:func:`reshard_state`) rather than an ad-hoc gather/scatter.

Buckets come from :func:`ops.bucketing.plan_buckets`, planned over the
reversed leaf order, so the first bucket emitted holds the last
(= earliest-available) gradients and XLA's latency-hiding scheduler can
run bucket k's reduce-scatter under the remaining backward pass and
bucket k's allgather under other buckets' updates.

Compression composes per bucket: wire codecs (int8/fp8,
``horovod_tpu/compression/codecs.py``) quantize BOTH legs — the
scatter leg rides the EQuARX all_to_all formulation (narrow payload,
f32 accumulate), the gather leg requantizes the updated shard — with
per-bucket error-feedback residuals carried in the sharded state.
Like the eager plane's ResidualStore, residuals never cross elastic
cohorts: a membership change reshards the moments and ZEROES the
residuals (the new cohort's shard geometry does not line up with the
old quantization debt).

Numerics contract (pinned by tests/test_zero.py): with no codec, the
sharded update is BIT-IDENTICAL to the replicated update for fp32
Sum/Average — psum_scatter performs the same per-element cross-replica
reduction as psum, elementwise optimizer transforms act per element,
and the allgather reassembles exactly.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map

from . import reduce_ops
from .bucketing import DEFAULT_BUCKET_BYTES, plan_buckets, _pack, _unpack
from ..utils import envparse
from ..utils.logging_util import get_logger

#: ``HVDTPU_ZERO_BUCKET_BYTES`` default mirrors the overlap plane's
#: bucket budget — one constant to retune, not two.
DEFAULT_ZERO_BUCKET_BYTES = DEFAULT_BUCKET_BYTES


def _m_state_bytes():
    from ..telemetry import core as telemetry
    return telemetry.gauge(
        "hvd_zero_state_bytes",
        "Per-replica optimizer-state bytes under ZeRO-1 sharding "
        "(moments + scalars; ~1/n of the replicated footprint)")


def _m_reshard_hist():
    from ..telemetry import core as telemetry
    return telemetry.histogram(
        "hvd_zero_reshard_seconds",
        "Deterministic optimizer-state reshard on elastic world-size "
        "change")


# ==========================================================================
# Shard plan
# ==========================================================================

class BucketShard:
    """Shard geometry of one fusion bucket: ``size`` payload elements,
    padded to ``padded`` (a multiple of the granule = n × block so every
    rank owns a whole number of quantization blocks), ``shard_len`` =
    padded / n elements per rank."""

    __slots__ = ("size", "padded", "shard_len")

    def __init__(self, size, padded, shard_len):
        self.size = size
        self.padded = padded
        self.shard_len = shard_len

    def __repr__(self):
        return (f"BucketShard(size={self.size}, padded={self.padded}, "
                f"shard_len={self.shard_len})")


class ZeroPlan:
    """Deterministic pad-and-split shard plan (portable-collectives
    formulation): identical on every rank that agrees on the leaf
    shapes, world size, bucket budget, and quantization granule."""

    __slots__ = ("n", "bucket_bytes", "block", "buckets", "shards",
                 "leaf_shapes", "leaf_dtypes")

    def __init__(self, n, bucket_bytes, block, buckets, shards,
                 leaf_shapes, leaf_dtypes):
        self.n = n
        self.bucket_bytes = bucket_bytes
        self.block = block
        self.buckets = buckets
        self.shards = shards
        self.leaf_shapes = leaf_shapes
        self.leaf_dtypes = leaf_dtypes

    def signature(self):
        """JSON-able identity of the plan — what every rank must agree
        on (guardian digests carry it per collective leg)."""
        return {
            "n": self.n,
            "bucket_bytes": int(self.bucket_bytes),
            "block": int(self.block),
            "buckets": [
                {"indices": list(b.indices), "dtype": str(b.dtype),
                 "padded": s.padded, "shard_len": s.shard_len}
                for b, s in zip(self.buckets, self.shards)],
        }


def plan_zero(leaves, n, bucket_bytes=DEFAULT_ZERO_BUCKET_BYTES, block=1):
    """Build the shard plan: fusion buckets from
    :func:`bucketing.plan_buckets` (reversed leaf order — overlap
    priority preserved), each padded to a multiple of ``n × block`` and
    split into ``n`` equal shards. Uneven leaf sizes are absorbed by the
    per-bucket pad; tensors are never split across buckets."""
    from ..compression.codecs import padded_len
    n = int(n)
    if n < 1:
        raise ValueError(f"world size must be >= 1, got {n}")
    block = max(int(block), 1)
    buckets = plan_buckets(leaves, bucket_bytes)
    shards = []
    for b in buckets:
        size = sum(int(np.prod(leaves[i].shape)) for i in b.indices)
        # padded_len is the compression plane's every-rank-owns-whole-
        # blocks rule — one granule computation across both planes.
        padded = padded_len(size, n, block)
        shards.append(BucketShard(size, padded, padded // n))
    return ZeroPlan(n, bucket_bytes, block, buckets, shards,
                    [tuple(leaf.shape) for leaf in leaves],
                    [str(jnp.asarray(leaf).dtype)
                     if not hasattr(leaf, "dtype") else str(leaf.dtype)
                     for leaf in leaves])


# ==========================================================================
# Sharded state
# ==========================================================================
#
# ZeroState is a plain 3-tuple pytree:
#   (bucket_states, scatter_res, gather_res)
# - bucket_states: tuple of per-bucket inner optax states whose vector
#   leaves are the local (shard_len,) slice — sharded P(axis) so the
#   global leaf is the (padded,) flat vector, NEVER materialized
#   replicated (state is born sharded in init_state's shard_map body).
# - scatter_res: per-bucket (1, n, shard_len) f32 error-feedback
#   residual of the quantized reduce-scatter leg (this rank's encode
#   error over its full bucket) — () when no wire codec / EF off.
# - gather_res: per-bucket (shard_len,) f32 residual of the quantized
#   allgather leg — () likewise.


def _validate_elementwise_state(inner, shard_len, dtype):
    """Every >=1-D state leaf must mirror the flat parameter shard: an
    optax transform carrying a non-per-parameter vector (a schedule
    table, a per-layer mask) would be silently sharded along the
    replica axis and corrupt its layout."""
    shape = jax.eval_shape(
        inner.init, jax.ShapeDtypeStruct((shard_len,), dtype))
    for path, leaf in jax.tree_util.tree_flatten_with_path(shape)[0]:
        if leaf.ndim >= 1 and leaf.shape != (shard_len,):
            raise ValueError(
                "ZeRO-1 requires elementwise optimizer state; leaf "
                + jax.tree_util.keystr(path)
                + f" has shape {leaf.shape} != ({shard_len},) (the "
                "per-replica parameter shard). Use make_train_step "
                "without HVDTPU_ZERO for transforms with "
                "non-per-parameter state (per-layer masks, global-norm "
                "state, schedule tables).")
    return shape


def _state_spec_for(inner, shard_len, dtype, axis_name):
    from jax.sharding import PartitionSpec as P
    shape = jax.eval_shape(
        inner.init, jax.ShapeDtypeStruct((shard_len,), dtype))
    return jax.tree.map(
        lambda s: P(axis_name) if s.ndim >= 1 else P(), shape)


def _pack_padded(leaves, bucket, padded):
    buf = _pack(leaves, bucket)
    if buf.shape[0] != padded:
        buf = jnp.pad(buf, (0, padded - buf.shape[0]))
    return buf


# ==========================================================================
# Quantized legs (EQuARX formulation, per bucket)
# ==========================================================================

def _wire_reduce_scatter(rows, axis_name, codec, block, n, residual):
    """Quantized reduce-scatter leg: encode this rank's (n, shard_len)
    rows, all_to_all so rank r holds every rank's quantized row r,
    accumulate dequantized in f32. Returns (f32 shard SUM, new
    residual rows) — residual is the local encode error (None when EF
    is off)."""
    if residual is not None:
        rows = rows + residual
    q, s = codec.encode(rows, block)
    new_res = rows - codec.decode(q, s, block) if residual is not None \
        else None
    q = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                       tiled=True)
    s = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0,
                       tiled=True)
    shard = jnp.sum(codec.decode(q, s, block), axis=0)
    return shard, new_res


def _wire_all_gather(u, axis_name, codec, block, residual):
    """Quantized allgather leg: requantize the updated shard, gather
    every rank's payload + scales, dequantize. All ranks (including the
    owner) apply the DEQUANTIZED update so params stay replica-
    identical. Returns (f32 full buffer, new residual)."""
    if residual is not None:
        u = u + residual
    q, s = codec.encode(u, block)
    new_res = u - codec.decode(q, s, block) if residual is not None \
        else None
    qg = lax.all_gather(q, axis_name, tiled=True)
    sg = lax.all_gather(s, axis_name, tiled=True)
    return codec.decode(qg, sg, block), new_res


# ==========================================================================
# Runtime: one bound instance of (inner optimizer × plan × mesh × codec)
# ==========================================================================

class ZeroRuntime:
    """Everything the sharded update path needs, bound once: the inner
    optax transformation, the mesh/axis, the shard plan (built lazily
    from the first params tree), and the codec configuration. Owned by
    ``DistributedOptimizer`` when ``zero`` is on."""

    def __init__(self, inner, mesh, axis_name, op=reduce_ops.Average,
                 bucket_bytes=DEFAULT_ZERO_BUCKET_BYTES, codec=None,
                 block=0, error_feedback=None, prescale=None,
                 postscale=None):
        from ..compression import codecs as _codecs
        if op not in (reduce_ops.Average, reduce_ops.Sum):
            raise ValueError(
                "ZeRO-1 supports Average/Sum gradient reductions only "
                f"(got {reduce_ops.op_name(op)}: Adasum's per-tensor "
                "scale-invariant combination does not reduce-scatter)")
        self.inner = inner
        self.mesh = mesh
        self.axis_name = axis_name
        self.op = op
        self.n = int(mesh.shape[axis_name])
        self.bucket_bytes = int(bucket_bytes)
        self.codec = (_codecs.get_codec(codec) if isinstance(codec, str)
                      else codec)
        self.block = (int(block) or _codecs.DEFAULT_BLOCK) \
            if self.codec is not None and self.codec.wire else 0
        if error_feedback is None:
            error_feedback = envparse.get_bool(
                envparse.COMPRESSION_ERROR_FEEDBACK, True)
        self.error_feedback = bool(error_feedback) \
            and self.codec is not None and self.codec.wire
        self.prescale = prescale
        self.postscale = postscale
        self.plan = None
        self.treedef = None
        #: elastic membership version this runtime's plan belongs to —
        #: a bump means the shard geometry is stale and the state must
        #: reshard (reshard_state) before the next step.
        self.version = envparse.get_str(envparse.ELASTIC_VERSION, "0")
        self._log = get_logger()

    def stale_version(self):
        return (envparse.get_str(envparse.ELASTIC_VERSION, "0")
                != self.version)

    # -- plan --------------------------------------------------------------
    def ensure_plan(self, params):
        leaves, treedef = jax.tree.flatten(params)
        if self.plan is None:
            self.plan = plan_zero(
                leaves, self.n, self.bucket_bytes,
                block=self.block if self.block else 1)
            self.treedef = treedef
            for b, s in zip(self.plan.buckets, self.plan.shards):
                _validate_elementwise_state(
                    self.inner, s.shard_len, b.dtype)
        elif [tuple(leaf.shape) for leaf in leaves] \
                != self.plan.leaf_shapes:
            raise ValueError(
                "ZeRO-1 shard plan was built for a different parameter "
                "tree (leaf shapes changed); build a fresh "
                "DistributedOptimizer for the new model")
        return self.plan

    # -- specs -------------------------------------------------------------
    def state_specs(self):
        """PartitionSpec pytree mirroring the ZeroState structure (for
        shard_map in/out specs)."""
        from jax.sharding import PartitionSpec as P
        plan = self.plan
        bucket_specs = tuple(
            _state_spec_for(self.inner, s.shard_len, b.dtype,
                            self.axis_name)
            for b, s in zip(plan.buckets, plan.shards))
        if self.error_feedback:
            res_scatter = tuple(P(self.axis_name) for _ in plan.buckets)
            res_gather = tuple(P(self.axis_name) for _ in plan.buckets)
        else:
            res_scatter = res_gather = ()
        return (bucket_specs, res_scatter, res_gather)

    # -- init --------------------------------------------------------------
    def init_state(self, params):
        """Materialize the optimizer state SHARDED from step 0 — the
        shard_map body inits each bucket's inner state from the local
        parameter shard, so the replicated footprint never exists."""
        from jax.sharding import PartitionSpec as P
        plan = self.ensure_plan(params)
        self.verify_plan_consistency()
        n, axis = self.n, self.axis_name

        def body(p):
            leaves = jax.tree.leaves(p)
            states, res_s, res_g = [], [], []
            for b, s in zip(plan.buckets, plan.shards):
                buf = _pack_padded(leaves, b, s.padded)
                p_shard = buf.reshape(n, s.shard_len)[
                    lax.axis_index(axis)]
                states.append(self.inner.init(p_shard))
                if self.error_feedback:
                    res_s.append(jnp.zeros((1, n, s.shard_len),
                                           jnp.float32))
                    res_g.append(jnp.zeros((s.shard_len,), jnp.float32))
            return tuple(states), tuple(res_s), tuple(res_g)

        state = jax.jit(_shard_map(
            body, mesh=self.mesh, in_specs=(P(),),
            out_specs=self.state_specs(), check_vma=False))(params)
        _m_state_bytes().set(self.state_bytes(state))
        return state

    def state_bytes(self, state):
        """Per-replica optimizer-state bytes (moments sharded 1/n +
        replicated scalars; EF residuals excluded — they are
        compression state, accounted in docs/compression.md)."""
        total = 0
        for leaf in jax.tree.leaves(state[0]):
            nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            total += nbytes // self.n if np.ndim(leaf) >= 1 else nbytes
        return total

    # -- guardian ----------------------------------------------------------
    def leg_digests(self, rank):
        """Guardian digests for the plan's two collective legs. Every
        rank must derive the identical geometry (same padded sizes,
        same shard shapes) and its own shard index; a divergent rank —
        e.g. a different HVDTPU_ZERO_BUCKET_BYTES — would reduce
        mismatched buffers and corrupt params silently."""
        plan = self.plan
        sig = plan.signature()
        codec = None
        if self.codec is not None:
            codec = (f"{self.codec.name}@b{self.block}"
                     if self.block else self.codec.name)
        common = {
            "op": reduce_ops.op_name(self.op),
            "dtype": ",".join(str(b.dtype) for b in plan.buckets),
            "shapes": [[b["padded"]] for b in sig["buckets"]],
            "process_set": 0,
            "prescale": None if self.prescale is None
            else float(self.prescale),
            "postscale": None if self.postscale is None
            else float(self.postscale),
            "root_rank": None,
            "codec": codec,
            "shard_index": rank,
            "shard_shape": [[b["shard_len"]] for b in sig["buckets"]],
        }
        return {
            "zero_reduce_scatter": dict(common, kind="zero_reduce_scatter"),
            "zero_allgather": dict(common, kind="zero_allgather"),
        }

    def verify_plan_consistency(self, board=None, rank=None, size=None,
                                timeout_s=None):
        """Cross-rank plan check through the guardian board (multi-
        process cohorts with HVDTPU_CONSISTENCY_CHECK on): publish this
        rank's leg digests, compare every peer's. Raises
        CollectiveMismatchError naming the divergent rank + field."""
        from .. import guardian
        if board is None:
            if not envparse.get_int(envparse.CONSISTENCY_CHECK, 0):
                return
            from .. import basics
            rt = basics.runtime()
            if rt.topology.size <= 1:
                return
            board = guardian.make_cross_process_board()
            if board is None:
                return
            rank, size = rt.topology.rank, rt.topology.size
        mine = self.leg_digests(rank)
        for leg, digest in mine.items():
            board.put(f"zero.plan.{leg}.{rank}",
                      guardian.render_digest(digest))
        import json
        import time
        if timeout_s is None:
            timeout_s = envparse.get_float(
                envparse.CONSISTENCY_TIMEOUT, 10.0)
        for leg, digest in mine.items():
            deadline = time.monotonic() + timeout_s
            theirs_by_rank = {}
            waiting = set(range(size)) - {rank}
            while waiting:
                for r in sorted(waiting):
                    raw = board.get(f"zero.plan.{leg}.{r}")
                    if raw is not None:
                        theirs_by_rank[r] = json.loads(raw)
                        waiting.discard(r)
                if not waiting or time.monotonic() > deadline:
                    break
                time.sleep(0.01)
            if waiting:
                self._log.warning(
                    "zero: plan consistency check for %s skipped "
                    "rank(s) %s (no digest within %.1fs)", leg,
                    sorted(waiting), timeout_s)
            divergences = guardian.compare_digests(digest, theirs_by_rank)
            if divergences:
                from ..exceptions import CollectiveMismatchError
                lines = [f"  rank {r}: {field} = {theirs!r} (rank "
                         f"{rank} derived {ours!r})"
                         for r, field, theirs, ours in divergences]
                fields = sorted({d[1] for d in divergences})
                raise CollectiveMismatchError(
                    f"ZeRO-1 {leg} shard plan diverges across ranks "
                    f"(fields: {', '.join(fields)}):\n"
                    + "\n".join(lines) +
                    "\nEvery rank must derive the identical pad-and-"
                    "split plan — check HVDTPU_ZERO_BUCKET_BYTES / "
                    "HVDTPU_COMPRESSION agree on all ranks.",
                    divergences=divergences)

    # -- the sharded update ------------------------------------------------
    def _bucket_grad_shard(self, g_leaves, k, b, s, res_s, new_res_s):
        """Reduce-scatter leg of bucket ``k``: this rank's reduced
        gradient shard (prescale/op/postscale applied), wire-quantized
        when a wire codec is configured (EF residual threaded)."""
        n, axis = self.n, self.axis_name
        average = self.op == reduce_ops.Average
        g = _pack_padded(g_leaves, b, s.padded)
        if self.prescale is not None:
            g = g * jnp.asarray(self.prescale).astype(g.dtype)
        if self.codec is not None and self.codec.wire:
            rows = g.reshape(n, s.shard_len).astype(jnp.float32)
            res = res_s[k][0] if self.error_feedback else None
            g_shard, new_res = _wire_reduce_scatter(
                rows, axis, self.codec, self.block, n, res)
            if average:
                g_shard = g_shard / n
            g_shard = g_shard.astype(b.dtype)
            if self.error_feedback:
                new_res_s.append(new_res[None])
        elif self.codec is not None:
            # Cast codec: the narrow dtype rides the collective itself
            # (reference compression semantics).
            payload, _ = self.codec.encode(g, 0)
            g_shard = self.codec.decode(
                lax.psum_scatter(payload, axis, tiled=True),
                None, 0, dtype=b.dtype)
            if average:
                g_shard = g_shard / n
        else:
            g_shard = lax.psum_scatter(g, axis, tiled=True)
            if average:
                g_shard = g_shard / n
        if self.postscale is not None:
            g_shard = g_shard * jnp.asarray(
                self.postscale).astype(g_shard.dtype)
        return g_shard

    def _run(self, grads, state, params, gather_params):
        """Shared per-bucket loop (reversed-leaf order = backprop
        availability order, so XLA can overlap bucket k's collectives
        with remaining work): reduce-scatter the gradient bucket, step
        the inner optimizer over the local 1/n shard, allgather back.

        ``gather_params=True`` (the train-step path) applies the update
        to the parameter shard BEFORE the gather and transports the NEW
        params — the optimizer multiply and the parameter add stay
        adjacent, so XLA contracts them into the same fused (FMA) form
        the replicated update compiles to and the result is
        bit-identical; gathering raw updates and adding outside would
        put a collective between mul and add and lose the contraction
        (~1-ulp noise). ``gather_params=False`` (the optax ``update``
        contract) transports the updates instead.

        With a wire codec the gather leg always carries the quantized
        UPDATES (small, lr-scaled — far friendlier to block quantization
        than raw parameter values), and every rank — owner included —
        applies the dequantized payload, so params stay replica-
        identical."""
        # The step's tracing contract (horovod_tpu/jax: both legs and
        # their packing are the exchange, the sharded step between them
        # the optimizer).
        from ..jax import SCOPE_EXCHANGE, SCOPE_OPTIMIZER
        plan = self.ensure_plan(params)
        n, axis = self.n, self.axis_name
        bucket_states, res_s, res_g = state
        g_leaves = jax.tree.leaves(grads)
        p_leaves = jax.tree.leaves(params)
        out = [None] * len(g_leaves)
        new_states, new_res_s, new_res_g = [], [], []
        for k, (b, s) in enumerate(zip(plan.buckets, plan.shards)):
            with jax.named_scope(SCOPE_EXCHANGE):
                g_shard = self._bucket_grad_shard(
                    g_leaves, k, b, s, res_s, new_res_s)
            # -- sharded optimizer step (1/n of the state) -----------------
            with jax.named_scope(SCOPE_OPTIMIZER):
                p = _pack_padded(p_leaves, b, s.padded)
                p_shard = p.reshape(n, s.shard_len)[lax.axis_index(axis)]
                u_shard, new_state_k = self.inner.update(
                    g_shard, bucket_states[k], p_shard)
                # What the gather carries: the new parameter shard where
                # the update can be applied first (see the docstring).
                send = u_shard
                if gather_params and self.codec is None:
                    send = p_shard + u_shard.astype(p_shard.dtype)
            new_states.append(new_state_k)
            # -- allgather leg ---------------------------------------------
            with jax.named_scope(SCOPE_EXCHANGE):
                if self.codec is not None and self.codec.wire:
                    res = res_g[k] if self.error_feedback else None
                    full, new_res = _wire_all_gather(
                        send.astype(jnp.float32), axis, self.codec,
                        self.block, res)
                    full = full.astype(b.dtype)
                    if self.error_feedback:
                        new_res_g.append(new_res)
                elif self.codec is not None:
                    payload, _ = self.codec.encode(send, 0)
                    full = self.codec.decode(
                        lax.all_gather(payload, axis, tiled=True),
                        None, 0, dtype=b.dtype)
                else:
                    full = lax.all_gather(send, axis, tiled=True)
            if gather_params and self.codec is not None:
                with jax.named_scope(SCOPE_OPTIMIZER):
                    full = p + full
            with jax.named_scope(SCOPE_EXCHANGE):
                if s.padded != s.size:
                    full = lax.slice(full, (0,), (s.size,))
                _unpack(full, g_leaves, b, out)
        tree = jax.tree.unflatten(jax.tree.structure(grads), out)
        new_state = (tuple(new_states),
                     tuple(new_res_s) if self.error_feedback else (),
                     tuple(new_res_g) if self.error_feedback else ())
        return tree, new_state

    def apply_in_axis(self, grads, state, params):
        """Train-step path: returns ``(new_params, new_state)`` with
        the update applied inside the shard (bit-identical to the
        replicated update for plain fp32 Sum/Average — see _run)."""
        return self._run(grads, state, params, gather_params=True)

    def update_in_axis(self, grads, state, params):
        """optax ``update`` contract: returns ``(updates, new_state)``
        with the gathered update deltas. Prefer make_train_step (which
        uses apply_in_axis); applying these updates externally rounds
        once more than the replicated fused multiply-add (~1 ulp)."""
        return self._run(grads, state, params, gather_params=False)


# ==========================================================================
# Elastic reshard
# ==========================================================================

def unshard_moments(state, runtime):
    """Host-side view of the sharded moments: for every vector position
    of the inner state tree, the per-parameter-leaf moment arrays
    (padding stripped), plus the replicated scalar leaves. The building
    block of :func:`reshard_state` and of tests that compare sharded
    moments against a replicated oracle."""
    plan = runtime.plan
    bucket_states = state[0]
    treedefs = [jax.tree.structure(bs) for bs in bucket_states]
    if any(td != treedefs[0] for td in treedefs[1:]):
        raise ValueError("per-bucket inner states diverge in structure")
    nleaves = len(plan.leaf_shapes)
    nslots = len(jax.tree.leaves(bucket_states[0]))
    per_leaf = [[None] * nleaves for _ in range(nslots)]
    scalars = [None] * nslots
    for b, s, bs in zip(plan.buckets, plan.shards, bucket_states):
        flat = jax.tree.leaves(bs)
        for j, leaf in enumerate(flat):
            if np.ndim(leaf) == 0:
                scalars[j] = np.asarray(leaf)
                continue
            if not getattr(leaf, "is_fully_addressable", True):
                # Multi-process global mesh: this process cannot read
                # the peers' shards, so an in-place reshard is
                # impossible — the exit-restart elastic path (restore
                # from checkpoint at the new world size) is the
                # supported route there.
                raise RuntimeError(
                    "zero: cannot reshard optimizer state in place — a "
                    "state shard lives on non-addressable devices "
                    "(multi-process global mesh). Restore from a "
                    "checkpoint after the elastic restart instead "
                    "(docs/performance.md \"ZeRO-1\").")
            vec = np.asarray(jax.device_get(leaf))[:s.size]
            offset = 0
            for i in b.indices:
                size = int(np.prod(plan.leaf_shapes[i]))
                per_leaf[j][i] = vec[offset:offset + size]
                offset += size
    return per_leaf, scalars, treedefs[0]


def _shard_reader(bucket_states, old_runtime, slot):
    """Windowed ``read_window`` over the old cohort's sharded moment
    vectors for one inner-state slot: resolves (rank, bucket) to the
    rank's addressable device shard and slices the requested window —
    at most one shard is ever resident host-side (cached between
    consecutive windows), so the fully-replicated flat vector the old
    gather-everything path materialized never exists."""
    devices = list(old_runtime.mesh.devices.flat)
    dev_rank = {id(d): r for r, d in enumerate(devices)}
    shard_by = {}  # (bucket k) -> {rank: jax shard}
    for k, bs in enumerate(bucket_states):
        leaf = jax.tree.leaves(bs)[slot]
        shard_by[k] = {dev_rank[id(sh.device)]: sh
                       for sh in leaf.addressable_shards
                       if id(sh.device) in dev_rank}
    cache = {}

    def read_window(rank, buf, start, length):
        _, k = buf
        key = (k, rank)
        if key not in cache:
            cache.clear()
            cache[key] = np.asarray(
                shard_by[k][rank].data).reshape(-1)
        return cache[key][start:start + length]

    return read_window


def reshard_state(state, old_runtime, new_runtime, params):
    """Deterministic optimizer-state redistribution for an elastic
    world-size change, emitted by the redistribution planner
    (``horovod_tpu/resharding/``): the old and new ``ZeroPlan``\\ s
    become flat-shard :class:`~horovod_tpu.resharding.Spec`\\ s, the
    planner derives the bounded-window program (cheapest legal
    candidate under the α–β cost model, guardian-verified and proven
    HVD501/HVD502-clean), and the host executor assembles each NEW
    rank's shard from windowed reads of the OLD ranks' addressable
    shards — peak host memory stays within one shard + 2×
    ``HVDTPU_RESHARD_BUCKET_BYTES`` instead of the full flat vector.
    Error-feedback residuals are ZEROED — the old cohort's
    quantization debt does not line up with the new shard geometry
    (same contract as the eager ResidualStore's version-keyed reset).
    Observed into ``hvd_zero_reshard_seconds``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from .. import resharding
    from ..telemetry import span as tele_span
    with tele_span(["zero"], "ZERO_RESHARD",
                   histogram=_m_reshard_hist()):
        new_plan = new_runtime.ensure_plan(params)
        old_plan = old_runtime.plan
        bucket_states = state[0]
        treedefs = [jax.tree.structure(bs) for bs in bucket_states]
        if any(td != treedefs[0] for td in treedefs[1:]):
            raise ValueError(
                "per-bucket inner states diverge in structure")
        treedef = treedefs[0]
        for leaf in jax.tree.leaves(bucket_states):
            if np.ndim(leaf) >= 1 \
                    and not getattr(leaf, "is_fully_addressable", True):
                # Multi-process global mesh: this process cannot read
                # the peers' shards, so an in-place reshard is
                # impossible — the exit-restart elastic path (restore
                # from checkpoint at the new world size) is the
                # supported route there.
                raise RuntimeError(
                    "zero: cannot reshard optimizer state in place — "
                    "a state shard lives on non-addressable devices "
                    "(multi-process global mesh). Restore from a "
                    "checkpoint after the elastic restart instead "
                    "(docs/performance.md \"ZeRO-1\").")
        meta = list(zip(old_plan.leaf_shapes, old_plan.leaf_dtypes))
        src_spec = resharding.zero_flat_spec(
            old_plan, axis=old_runtime.axis_name)
        dst_spec = resharding.zero_flat_spec(
            new_plan, axis=new_runtime.axis_name)
        program = resharding.plan_redistribution(src_spec, dst_spec,
                                                 meta)
        program.verify_consistency()
        axis = new_runtime.axis_name
        mesh = new_runtime.mesh
        new_devices = list(mesh.devices.flat)
        rep_sharding = NamedSharding(mesh, P())
        slot0 = jax.tree.leaves(bucket_states[0])
        nslots = len(slot0)
        # per bucket: the flat list of new inner-state leaves
        new_flat = [[None] * nslots
                    for _ in range(len(new_plan.buckets))]
        for j in range(nslots):
            if np.ndim(slot0[j]) == 0:
                scalar = np.asarray(slot0[j])
                for k in range(len(new_plan.buckets)):
                    new_flat[k][j] = jax.device_put(scalar,
                                                    rep_sharding)
                continue
            dtypes = {str(jax.tree.leaves(bs)[j].dtype)
                      for bs in bucket_states}
            override = dtypes.pop() if len(dtypes) == 1 else None
            results, _ = resharding.execute_host(
                program, _shard_reader(bucket_states, old_runtime, j),
                dtype_override=override)
            for k, s in enumerate(new_plan.shards):
                vec_sharding = NamedSharding(mesh, P(axis))
                new_flat[k][j] = \
                    jax.make_array_from_single_device_arrays(
                        (s.padded,), vec_sharding,
                        [jax.device_put(results[r][("bucket", k)], d)
                         for r, d in enumerate(new_devices)])
        new_bucket_states = [jax.tree.unflatten(treedef, flat)
                             for flat in new_flat]
        if new_runtime.error_feedback:
            n = new_runtime.n
            res_s = tuple(
                jax.device_put(
                    np.zeros((n, n, s.shard_len), np.float32),
                    vec_sharding)
                for s in new_plan.shards)
            res_g = tuple(
                jax.device_put(np.zeros((s.padded,), np.float32),
                               vec_sharding)
                for s in new_plan.shards)
        else:
            res_s = res_g = ()
        new_state = (tuple(new_bucket_states), res_s, res_g)
        _m_state_bytes().set(new_runtime.state_bytes(new_state))
        get_logger().warning(
            "zero: optimizer state resharded %d-way -> %d-way "
            "(%d bucket(s); error-feedback residuals reset — "
            "quantization debt never crosses cohorts)",
            old_runtime.n, new_runtime.n, len(new_plan.buckets))
        return new_state
