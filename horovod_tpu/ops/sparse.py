"""Sparse/embedding gradient plane (ISSUE 11; docs/sparse.md).

Embedding-heavy models (DLRM-style recommenders, NMT) produce gradients
that touch a small fraction of table rows per step. Reducing them as
dense tensors pays full-table allreduce wire; gathering (indices,
values) slices pays per-row wire that *grows* with cohort size. The
governing trade-off (PAPERS.md 1905.04035): per rank,

    dense  (ring allreduce)   ~ 2 * R * W * b_v           bytes
    gather (allgather-v)      ~ (n-1) * nnz * (W*b_v + b_i) bytes

with R table rows, W row width, b_v value bytes, b_i index bytes and
``nnz`` locally-touched (deduplicated) rows. Gather wins iff the row
density d = nnz/R stays under the crossover

    d* = theta * 2*W*b_v / ((n-1) * (W*b_v + b_i))

which shrinks ~1/n — the right answer is a per-tensor, **measured**
density policy, not a global switch. ``HVDTPU_SPARSE`` selects it:

    HVDTPU_SPARSE=auto                       # measured density vs d*
    HVDTPU_SPARSE=gather                     # force allgather-of-slices
    HVDTPU_SPARSE='embed*=gather;dense'      # glob rules, first wins

``auto`` smooths the observed density with a per-name EMA
(``HVDTPU_SPARSE_EMA``) so the path choice is stable across steps;
``HVDTPU_SPARSE_THRESHOLD`` scales the crossover (theta above).

Disabled contract (the telemetry/chaos/compression standard): with
``HVDTPU_SPARSE`` unset :func:`make_plane` returns ``None`` — every
sparse gradient densifies into TODAY's dense allreduce path
(bit-identical, guard-tested in tests/test_sparse.py) and the dense
hot path carries zero sparse state.

Wire compression composes: when the ``HVDTPU_COMPRESSION`` policy
selects a wire codec (int8) for a gather-path tensor, the gathered
VALUES ride the wire as row-quantized int8 (one f32 scale per slice
row) — indices are exact always (hvd-lint HVD209 flags scripts that
try). ZeRO composes through :func:`plan_row_shards` /
:func:`rowsharded_update`: embedding optimizer state shards by row
range so the sparse update stays local to the owning shard.
"""

import fnmatch
import re

import numpy as np

from ..analysis import sanitizer
from ..telemetry import core as telemetry
from ..utils import envparse
from ..utils.logging_util import get_logger
from . import reduce_ops

DEFAULT_THRESHOLD = 1.0   # theta: scales the crossover density
DEFAULT_EMA = 0.8         # history weight of the per-name density EMA
_MODES = ("auto", "gather", "dense")
# The one wire codec the gather path carries on values (row-quantized;
# docs/sparse.md). fp8 is deliberately out: row scales make int8's
# symmetric range the right fit and fp8 support is build-dependent.
_WIRE_CODECS = ("int8",)


# ==========================================================================
# SparseGradient: IndexedSlices-style (indices, values, dense_shape)
# ==========================================================================

class SparseGradient:
    """Row-sparse gradient: ``values[k]`` is the gradient of row
    ``indices[k]`` of a ``dense_shape`` parameter (TF's IndexedSlices,
    torch's COO with sparse_dim=1, reference:
    horovod/tensorflow/__init__.py:55 sparse handling).

    Registered as a jax pytree (indices/values are children,
    dense_shape is static aux data) so it is jit-traceable and can ride
    gradient trees through ``DistributedOptimizer``."""

    __slots__ = ("indices", "values", "dense_shape")

    def __init__(self, indices, values, dense_shape):
        self.indices = indices
        self.values = values
        self.dense_shape = tuple(int(s) for s in dense_shape)

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.indices, self.values), self.dense_shape

    @classmethod
    def tree_unflatten(cls, dense_shape, children):
        indices, values = children
        return cls(indices, values, dense_shape)

    # -- conversions -------------------------------------------------------
    @property
    def nnz(self):
        return int(self.indices.shape[0])

    def densify(self):
        """Segment-sum scatter-add into the dense parameter shape
        (duplicate indices accumulate — IndexedSlices semantics)."""
        import jax.numpy as jnp
        vals = jnp.asarray(self.values)
        out = jnp.zeros(self.dense_shape, vals.dtype)
        return out.at[jnp.asarray(self.indices)].add(vals)

    def deduplicate(self):
        """Host-side row dedup: unique sorted indices, duplicate rows
        segment-summed. Eager plane only (output nnz is data-dependent,
        so this cannot trace)."""
        idx = np.asarray(self.indices)
        vals = np.asarray(self.values)
        uniq, inv = np.unique(idx, return_inverse=True)
        if uniq.shape[0] == idx.shape[0]:
            order = np.argsort(idx, kind="stable")
            return SparseGradient(idx[order], vals[order],
                                  self.dense_shape)
        summed = np.zeros((uniq.shape[0],) + vals.shape[1:], vals.dtype)
        np.add.at(summed, inv, vals)
        return SparseGradient(uniq, summed, self.dense_shape)

    @classmethod
    def from_dense(cls, dense, index_dtype=np.int32):
        """Rows with any nonzero become slices (test/bench helper)."""
        dense = np.asarray(dense)
        rows = np.flatnonzero(
            np.abs(dense).reshape(dense.shape[0], -1).sum(axis=1))
        return cls(rows.astype(index_dtype), dense[rows], dense.shape)

    def __repr__(self):
        return (f"SparseGradient(nnz={self.indices.shape[0]}, "
                f"dense_shape={self.dense_shape})")


def _register_pytree():
    import jax
    jax.tree_util.register_pytree_node(
        SparseGradient,
        lambda sg: sg.tree_flatten(),
        SparseGradient.tree_unflatten)


_register_pytree()


def is_sparse(x):
    return isinstance(x, SparseGradient)


# ==========================================================================
# Row-wise int8 wire codec (values only — indices are exact always)
# ==========================================================================

def encode_rows(values):
    """Symmetric per-row int8 quantization: one f32 scale per slice
    row (scale = maxabs/127, round-trip error <= maxabs/254 — the
    compression plane's bound at block = row). Row-wise (not the fused
    plane's fixed 256-block) because gathered slices are ragged across
    ranks: per-row scales need no block-boundary metadata on the wire."""
    import jax.numpy as jnp
    v = jnp.asarray(values, jnp.float32).reshape(values.shape[0], -1)
    maxabs = jnp.max(jnp.abs(v), axis=1)
    scales = jnp.where(maxabs > 0, maxabs / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(v / scales[:, None]), -127, 127).astype(jnp.int8)
    return q.reshape(values.shape), scales


def decode_rows(q, scales, dtype):
    import jax.numpy as jnp
    qf = jnp.asarray(q, jnp.float32).reshape(q.shape[0], -1)
    out = qf * jnp.asarray(scales, jnp.float32)[:, None]
    return out.reshape(q.shape).astype(dtype)


# ==========================================================================
# Policy: HVDTPU_SPARSE grammar + crossover math + per-name EMA
# ==========================================================================

def crossover_density(world, row_bytes, index_bytes, threshold):
    """Density below which allgather-of-slices beats densify-then-
    allreduce (module docstring math). ``world <= 1`` returns inf:
    there is no wire either way, and the gather path skips the dense
    materialization."""
    if world <= 1:
        return float("inf")
    return (threshold * 2.0 * row_bytes
            / ((world - 1) * (row_bytes + index_bytes)))


def parse_rules(spec):
    """``spec`` -> [(glob, mode)] — the compression-policy grammar with
    gather/dense/auto as the codec vocabulary. Malformed specs raise at
    plane construction (a typo'd knob must never silently disable the
    feature it configures)."""
    rules = []
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            glob, _, mode = part.partition("=")
            glob, mode = glob.strip(), mode.strip()
            if not glob or not mode:
                raise ValueError(
                    f"malformed HVDTPU_SPARSE rule {part!r}: expected "
                    "'<name-glob>=<gather|dense|auto>'")
        else:
            glob, mode = "*", part
        if mode not in _MODES:
            raise ValueError(
                f"unknown HVDTPU_SPARSE mode {mode!r} in rule {part!r} "
                f"(expected one of {', '.join(_MODES)})")
        rules.append((glob, mode))
    return rules


class SparsePolicy:
    """Per-tensor path selection: explicit glob rules override; ``auto``
    compares the EMA-smoothed measured density against the world-scaled
    crossover."""

    def __init__(self, rules, threshold=DEFAULT_THRESHOLD,
                 ema=DEFAULT_EMA):
        self.rules = list(rules)
        self.threshold = float(threshold)
        # A typo'd knob must never silently disable the feature it
        # configures (the parse_rules contract): a non-positive / NaN /
        # inf theta would make auto resolve one path forever, loudly
        # looking like a policy decision.
        if not (self.threshold > 0.0 and np.isfinite(self.threshold)):
            raise ValueError(
                "HVDTPU_SPARSE_THRESHOLD must be a positive finite "
                f"number, got {threshold}")
        if not 0.0 <= float(ema) < 1.0:
            raise ValueError(
                f"HVDTPU_SPARSE_EMA must be in [0, 1), got {ema}")
        self.ema = float(ema)

    @classmethod
    def from_env(cls):
        spec = envparse.get_str(envparse.SPARSE, "")
        return cls(parse_rules(spec),
                   threshold=envparse.get_float(
                       envparse.SPARSE_THRESHOLD, DEFAULT_THRESHOLD),
                   ema=envparse.get_float(envparse.SPARSE_EMA,
                                          DEFAULT_EMA))

    def mode_for_name(self, name):
        for glob, mode in self.rules:
            if fnmatch.fnmatchcase(name or "", glob):
                return mode
        return "dense"


_AUTO_OCCURRENCE = re.compile(r"#\d+$")


def _ema_key(name):
    """Density-state key for one tensor name. Per-call auto names carry
    a '#count' occurrence suffix (one WIRE name per call — HVD203), but
    density is a property of the call site: keying the EMA and the
    `hvd_sparse_density` gauge on the raw name would grow both by one
    entry per training step, unbounded, and `prev` would always be None
    so the EMA never smooths. User-chosen names pass through."""
    if name and ".auto." in name:
        return _AUTO_OCCURRENCE.sub("", name)
    return name


class SparsePlane:
    """Policy + per-name density EMA + telemetry, attached to one
    coordinator (rebuilt on every ``init()``, so EMA state never
    crosses elastic cohorts — the residual-store precedent)."""

    def __init__(self, pol):
        self.policy = pol
        # Submitter threads race on the EMA dict; guarded like every
        # shared map (hvd-lint HVD301), instrumented under sanitize.
        self._lock = sanitizer.make_lock("sparse.plane")
        self._ema = {}
        # Engagement evidence (chaos matrix row): per-path decision
        # counts, readable without the metrics plane.
        self.path_counts = {"gather": 0, "dense": 0}
        self._log = get_logger()
        self._metrics_on = telemetry.enabled()
        self._m_density = telemetry.gauge(
            "hvd_sparse_density",
            "EMA-smoothed nnz-rows/total-rows of a sparse gradient",
            labelnames=("name",))
        self._m_path = telemetry.counter(
            "hvd_sparse_path_total",
            "Sparse-allreduce path decisions", labelnames=("path",))
        self._m_saved = telemetry.counter(
            "hvd_sparse_bytes_saved_total",
            "Wire bytes kept off the fabric by gather-path sparse "
            "collectives vs the densified allreduce")
        # Wire compression on gathered values (docs/sparse.md): the
        # HVDTPU_COMPRESSION name policy decides, the sparse plane only
        # honors wire codecs this plane implements (int8, row-wise).
        self._wire_policy = None
        if envparse.get_str(envparse.COMPRESSION, ""):
            from ..compression.policy import CompressionPolicy
            self._wire_policy = CompressionPolicy.from_env()

    # -- path selection (framework threads) --------------------------------
    def select(self, name, nnz_rows, total_rows, row_bytes, index_bytes,
               world, smooth=True):
        """Resolve gather|dense for one submission and record the
        decision. ``nnz_rows`` is post-dedup; explicit rules skip the
        EMA entirely (their choice is not density-driven).
        ``smooth=False`` decides from the raw observed density with NO
        EMA state read or written — the in-jit axis path, whose
        trace-time decision must not blend unrelated tensors through a
        shared state key or go stale inside a cached trace."""
        mode = self.policy.mode_for_name(name)
        if mode == "auto":
            observed = nnz_rows / max(1, total_rows)
            if smooth:
                key = _ema_key(name)
                with self._lock:
                    prev = self._ema.get(key)
                    smoothed = (observed if prev is None else
                                self.policy.ema * prev
                                + (1.0 - self.policy.ema) * observed)
                    self._ema[key] = smoothed
                if self._metrics_on and key:
                    self._m_density.labels(name=key).set(smoothed)
            else:
                smoothed = observed
            path = ("gather" if smoothed < crossover_density(
                world, row_bytes, index_bytes, self.policy.threshold)
                else "dense")
        else:
            path = mode
        with self._lock:
            self.path_counts[path] += 1
        self._m_path.labels(path=path).inc()
        return path

    def density(self, name):
        """Current EMA for a tensor name (None before first auto
        observation) — test/diagnostic surface. Auto-name occurrence
        suffixes resolve to their call-site key."""
        with self._lock:
            return self._ema.get(_ema_key(name))

    def wire_codec_for(self, name, values_dtype):
        """int8 when the HVDTPU_COMPRESSION policy selects a wire codec
        for this name's VALUES; indices never compress (HVD209)."""
        if self._wire_policy is None:
            return None
        import jax.numpy as jnp
        if not jnp.issubdtype(np.dtype(values_dtype), jnp.floating):
            return None
        codec_name = self._wire_policy.codec_for_name(name)
        if codec_name in _WIRE_CODECS:
            return codec_name
        return None

    # -- accounting (cycle thread / backend sweep) -------------------------
    def record_gather(self, dense_wire_bytes, gather_wire_bytes):
        """Bytes-saved accounting for one executed gather-path
        collective (model bytes — docs/sparse.md methodology)."""
        if self._metrics_on:
            self._m_saved.inc(max(0, int(dense_wire_bytes)
                                  - int(gather_wire_bytes)))


def make_plane():
    """SparsePlane when ``HVDTPU_SPARSE`` is set; None otherwise — the
    disabled-mode contract (zero sparse state on the dense hot path)."""
    spec = envparse.get_str(envparse.SPARSE, "")
    if not spec:
        return None
    return SparsePlane(SparsePolicy.from_env())


def _plane():
    """The live coordinator's sparse plane (None when disabled or
    pre-init)."""
    from .. import basics
    if not basics.is_initialized():
        return None
    return basics.runtime().coordinator._sparse


def enabled():
    return _plane() is not None


# ==========================================================================
# sparse_allreduce: the user-facing collective
# ==========================================================================

class SparseMeta:
    """Per-entry sparse metadata carried on the TensorEntry: what the
    dispatch plane and the guardian digest need beyond the raw arrays.
    ``nranks`` is the per-rank list length in single-controller mode
    (arrays = idx_0..idx_{n-1}, val_0..val_{n-1}); None on the SPMD
    plane (arrays = [idx, val], one rank's slices)."""

    __slots__ = ("dense_shape", "index_dtype", "values_dtype", "nranks",
                 "codec")

    def __init__(self, dense_shape, index_dtype, values_dtype,
                 nranks=None, codec=None):
        self.dense_shape = tuple(int(s) for s in dense_shape)
        self.index_dtype = str(index_dtype)
        self.values_dtype = str(values_dtype)
        self.nranks = nranks
        self.codec = codec


def _validate_op(op, name):
    if op not in (reduce_ops.Sum, reduce_ops.Average):
        raise ValueError(
            f"sparse_allreduce {name!r} supports Sum/Average only, got "
            f"{reduce_ops.op_name(op)}: Adasum needs exact per-tensor "
            "dot products of dense gradients, and Min/Max/Product have "
            "no scatter-add formulation (docs/sparse.md)")


def _check_shapes(slices, name):
    shape = slices[0].dense_shape
    for sg in slices[1:]:
        if sg.dense_shape != shape:
            raise ValueError(
                f"sparse_allreduce {name!r}: per-rank dense_shapes "
                f"disagree ({sg.dense_shape} vs {shape})")
    return shape


def _cohort_nnz(name, nnz, process_set):
    """Cross-rank nnz agreement for the SPMD ``auto`` decision.

    The density feeding the policy must be identical on every rank:
    per-rank nnz legally differs, and a tensor straddling the crossover
    would otherwise split the cohort — some ranks submitting the gather
    path's ``name.idx``/``name.val`` allgathers while others submit a
    plain dense allreduce under ``name``. The native negotiation never
    pairs those, so the job hangs until the stall watchdog aborts, and
    the rank-local EMA makes the disagreement persistent, not
    transient. A scalar Max-allreduce of the local post-dedup nnz
    (same name/shape/dtype on every rank — guardian-silent) gives every
    rank the cohort max, which is also what single-controller mode
    already feeds the policy (max over the virtual ranks' slices)."""
    from . import collectives as _c
    out = _c.allreduce(np.array([nnz], np.int64), name=f"{name}.nnz",
                       op=reduce_ops.Max, process_set=process_set)
    return int(np.asarray(out).reshape(-1)[0])


def sparse_allreduce_async(sparse, average=None, name=None, op=None,
                           process_set=None):
    """Async sparse allreduce of an IndexedSlices-style gradient;
    resolves to the DENSE reduced array (every rank's scatter-add of
    every rank's slices, averaged for ``op=Average``).

    Input convention follows the collectives module: on the SPMD plane
    pass one :class:`SparseGradient` (this rank's slices); in
    single-controller mode pass a LIST of per-rank SparseGradients
    (per-rank nnz legally differs, so slices cannot stack).

    The path — allgather-of-slices vs densify-then-allreduce — comes
    from the ``HVDTPU_SPARSE`` policy (module docstring). With the knob
    unset, or when the policy resolves ``dense``, the call densifies
    and rides TODAY's allreduce path bit-identically (pinned in
    tests/test_sparse.py)."""
    from .. import basics
    from ..coordinator import TensorEntry
    from ..process_sets import global_process_set
    from . import collectives as _c

    if process_set is None:
        process_set = global_process_set
    op = reduce_ops.handle_average_backwards_compatibility(op, average)
    name = name or _c._auto_name("sparse_allreduce")
    _validate_op(op, name)
    rt = basics.runtime()
    single = rt.mode == basics.MODE_SINGLE
    nset = len(process_set.ranks)
    if single:
        if is_sparse(sparse):
            if nset != 1:
                raise ValueError(
                    f"sparse_allreduce {name!r}: single-controller mode "
                    f"needs one SparseGradient per virtual rank (a list "
                    f"of {nset}); per-rank nnz differs so slices cannot "
                    "stack like dense tensors")
            slices = [sparse]
        else:
            slices = list(sparse)
            if len(slices) != nset:
                raise ValueError(
                    f"sparse_allreduce {name!r}: expected one "
                    f"SparseGradient per rank ({nset}), got "
                    f"{len(slices)}")
    else:
        if not is_sparse(sparse):
            raise ValueError(
                f"sparse_allreduce {name!r}: SPMD mode takes this "
                "rank's SparseGradient (lists are single-controller "
                "only)")
        slices = [sparse]
    dense_shape = _check_shapes(slices, name)

    plane = rt.coordinator._sparse
    if plane is None:
        path = "dense"
    else:
        # Local row-deduplication BEFORE the density measurement: the
        # measured density (and the gather wire) is unique-rows, and
        # duplicate indices must accumulate exactly once per
        # contributing row. Only when the resolved mode can gather —
        # an explicit dense rule (and the disabled path above) must
        # stay the pre-plane path, host-side dedup cost included:
        # densify's scatter-add accumulates duplicates anyway.
        if plane.policy.mode_for_name(name) != "dense":
            slices = [sg.deduplicate() for sg in slices]
        vals0 = np.asarray(slices[0].values)
        row_bytes = row_elems(dense_shape) * vals0.dtype.itemsize
        index_bytes = np.asarray(slices[0].indices).dtype.itemsize
        nnz = max(sg.nnz for sg in slices)
        if (not single and nset > 1
                and plane.policy.mode_for_name(name) == "auto"):
            nnz = _cohort_nnz(name, nnz, process_set)
        # world = the cohort the wire spans: virtual ranks in
        # single-controller mode, processes on the SPMD plane.
        path = plane.select(name, nnz, dense_shape[0], row_bytes,
                            index_bytes, nset)

    if path == "dense":
        # Densify-then-allreduce: EXACTLY the pre-sparse-plane path —
        # the entry is a plain dense allreduce, so fusion, overlap,
        # compression and the guardian all see what they saw before
        # this plane existed (bit-identity pinned by test).
        import jax.numpy as jnp
        if single:
            dense = jnp.stack([sg.densify() for sg in slices])
        else:
            dense = slices[0].densify()
        return _c.allreduce_async(dense, name=name, op=op,
                                  process_set=process_set)

    codec = plane.wire_codec_for(name, slices[0].values.dtype)
    meta = SparseMeta(dense_shape,
                      np.asarray(slices[0].indices).dtype,
                      np.asarray(slices[0].values).dtype,
                      nranks=(len(slices) if single else None),
                      codec=codec)
    arrays = ([np.asarray(sg.indices) for sg in slices]
              + [np.asarray(sg.values) for sg in slices])
    entry = TensorEntry(name, "sparse_allreduce", arrays, process_set,
                        op=op)
    entry.sparse = meta
    return _c._submit(entry)


def sparse_allreduce(sparse, average=None, name=None, op=None,
                     process_set=None):
    """Blocking :func:`sparse_allreduce_async`."""
    from . import collectives as _c
    return _c.synchronize(sparse_allreduce_async(
        sparse, average=average, name=name, op=op,
        process_set=process_set))


# ==========================================================================
# Execution helpers shared by the coordinator and the TCP backend
# ==========================================================================

def scatter_add_dense(indices, values, dense_shape, world, op,
                      dtype=None):
    """Gathered (indices, values) -> the dense reduction: scatter-add
    (order-invariant, duplicates across ranks accumulate) then /world
    for Average. The one reduction both transports share."""
    import jax.numpy as jnp
    vals = jnp.asarray(values)
    if dtype is not None:
        vals = vals.astype(dtype)
    out = jnp.zeros(dense_shape, vals.dtype)
    out = out.at[jnp.asarray(indices)].add(vals)
    if op == reduce_ops.Average:
        out = (out / world).astype(vals.dtype)
    return out


def row_elems(dense_shape):
    """Elements per row (product of the trailing dims) — the one unit
    wire accounting, segment offsets, and the crossover math all agree
    on; every caller must stay on this helper or the planes diverge."""
    return int(np.prod(dense_shape[1:])) if len(dense_shape) > 1 else 1


def gather_wire_bytes(nnz_total, row_elems, values_itemsize,
                      index_itemsize, world, codec=None):
    """Model wire bytes PER RANK of the gather transport: every rank
    receives the other ranks' slices ((n-1)/n of the gathered total).
    With the int8 row codec values carry 1 byte/elem + one f32 scale
    per row."""
    if codec == "int8":
        per_row = row_elems + 4 + index_itemsize
    else:
        per_row = row_elems * values_itemsize + index_itemsize
    frac = (world - 1) / world if world > 1 else 0.0
    return int(nnz_total * per_row * frac)


def dense_wire_bytes(dense_shape, values_itemsize):
    """Model wire bytes PER RANK of the densified ring allreduce
    (~2x the payload: reduce-scatter + allgather legs)."""
    return int(2 * int(np.prod(dense_shape)) * values_itemsize)


# ==========================================================================
# In-jit axis path (shard_map train steps)
# ==========================================================================

def sparse_allreduce_axis(sg, axis_name, op=reduce_ops.Average,
                          name=None):
    """In-jit sparse allreduce over a mesh axis: all_gather the
    (indices, values) slices (per-replica nnz is equal by construction
    under shard_map — shapes are static), scatter-add into the dense
    shape. The path decision is static too (trace-time density vs the
    crossover — no EMA in-jit; the host plane owns the smoothed
    policy): with no plane, or above the crossover, this densifies and
    psums exactly like a dense gradient."""
    import jax.numpy as jnp
    from jax import lax

    _validate_op(op, name or "<axis>")
    n = lax.axis_size(axis_name)
    plane = _plane()
    path = "dense"
    if plane is not None:
        vals = sg.values
        path = plane.select(name or "<axis>", int(sg.indices.shape[0]),
                            sg.dense_shape[0],
                            row_elems(sg.dense_shape) * vals.dtype.itemsize,
                            np.dtype(sg.indices.dtype).itemsize, int(n),
                            smooth=False)
    if path == "dense":
        dense = sg.densify()
        red = lax.pmean(dense, axis_name) if op == reduce_ops.Average \
            else lax.psum(dense, axis_name)
        return red
    idx_g = lax.all_gather(sg.indices, axis_name, tiled=True)
    val_g = lax.all_gather(sg.values, axis_name, tiled=True)
    dense = jnp.zeros(sg.dense_shape, val_g.dtype)
    dense = dense.at[idx_g].add(val_g)
    if op == reduce_ops.Average:
        dense = (dense / n).astype(val_g.dtype)
    return dense


# ==========================================================================
# ZeRO composition: embedding optimizer state sharded by row range
# ==========================================================================

def plan_row_shards(nrows, world):
    """Contiguous near-even row ranges, one per rank: [(lo, hi), ...]
    (earlier ranks take the remainder, the reducescatter convention).
    Deterministic in (nrows, world) — the cross-rank identity the ZeRO
    plane's plan signature pins."""
    base, rem = divmod(int(nrows), int(world))
    bounds, start = [], 0
    for r in range(world):
        end = start + base + (1 if r < rem else 0)
        bounds.append((start, end))
        start = end
    return bounds


def rowsharded_update(opt, gathered, param_shard, state_shard, lo, hi):
    """Apply the gathered sparse gradient to THIS rank's row range.

    ``gathered`` is the post-allgather deduplicated global slice set
    (what the gather path produces before scatter-add); rows outside
    [lo, hi) belong to other shards and are dropped here — the sparse
    update stays local to the owning shard, and the optimizer state for
    the embedding table lives row-sharded (1/n per rank) instead of
    replicated. Only the TOUCHED local rows step (sparse-apply
    semantics: untouched rows keep their moments, like torch's
    SparseAdam); ``opt`` must be an elementwise optax transform whose
    state leaves mirror the parameter rows (the ops/zero.py
    elementwise-state contract).

    Returns (new_param_shard, new_state_shard)."""
    import jax
    import jax.numpy as jnp

    # Cross-rank dedup: per-rank slices are deduplicated locally, but a
    # hot row touched by several RANKS appears once per toucher in the
    # gathered set — without segment-summing here, the .at[].set()
    # write-back below would keep only the LAST duplicate's update
    # (silently dropping the other ranks' gradient for exactly the rows
    # embeddings share most).
    gathered = gathered.deduplicate()
    idx = np.asarray(gathered.indices)
    mask = (idx >= lo) & (idx < hi)
    local_idx = jnp.asarray(idx[mask] - lo)
    local_vals = jnp.asarray(np.asarray(gathered.values)[mask])
    if int(local_idx.shape[0]) == 0:
        return param_shard, state_shard

    def take_rows(leaf):
        leaf = jnp.asarray(leaf)
        if leaf.ndim and leaf.shape[0] == param_shard.shape[0]:
            return leaf[local_idx]
        return leaf  # scalar state (count) applies as-is

    def put_rows(shard_leaf, row_leaf):
        shard_leaf = jnp.asarray(shard_leaf)
        if shard_leaf.ndim and shard_leaf.shape[0] == \
                param_shard.shape[0]:
            return shard_leaf.at[local_idx].set(row_leaf)
        return row_leaf

    rows = jnp.asarray(param_shard)[local_idx]
    row_state = jax.tree.map(take_rows, state_shard)
    updates, new_row_state = opt.update(local_vals, row_state, rows)
    new_rows = rows + updates
    new_param = jnp.asarray(param_shard).at[local_idx].set(new_rows)
    new_state = jax.tree.map(put_rows, state_shard, new_row_state)
    return new_param, new_state
