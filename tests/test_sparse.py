"""Sparse/embedding gradient plane tests (fast lane, tier-1; ISSUE 11).

Covers the SparseGradient type (pytree protocol, densify/dedup
semantics with duplicate indices), the row-wise int8 value codec, the
HVDTPU_SPARSE policy grammar + crossover math + per-name density EMA
(flip at the threshold, stability under a one-step density spike), the
gather path against a densified oracle at n=1/2/4 (duplicate indices
included), the pinned dense-path bit-identity to the pre-plane
allreduce, the guardian digest contract (index_dtype/dense_shape
stamped, per-rank nnz excluded), fusion grouping, the in-jit axis
path, framework routing (TF sparse_as_dense=False, torch COO, jax
sparse leaves), ZeRO row-range sharding, and the disabled-mode guard
(HVDTPU_SPARSE unset: zero engagement on the dense hot path — the
telemetry/chaos/compression acceptance contract).

NOTE: the disabled-guard test is first in the file on purpose — it
asserts the session coordinator has built NO plane, which must be
checked before this module's own tests install one.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map

import horovod_tpu as hvd_mod
from horovod_tpu import basics, guardian
from horovod_tpu.coordinator import Coordinator, TensorEntry
from horovod_tpu.ops import reduce_ops, sparse
from horovod_tpu.process_sets import global_process_set
from horovod_tpu.utils import envparse

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mk_slices(n, rows=32, width=4, nnz=6, seed=0, index_dtype=np.int32,
              dups=True):
    """Per-rank SparseGradients with duplicate indices by default (the
    oracle must see duplicates accumulate, IndexedSlices semantics)."""
    out = []
    for r in range(n):
        rng = np.random.RandomState(seed * 100 + r)
        idx = rng.choice(rows, size=nnz, replace=dups)
        vals = rng.randn(nnz, width).astype(np.float32)
        out.append(sparse.SparseGradient(idx.astype(index_dtype), vals,
                                         (rows, width)))
    return out


def oracle_sum(slices):
    return np.stack([np.asarray(sg.densify()) for sg in slices]).sum(0)


def install_plane(rules="gather", **kwargs):
    """Swap a policy-driven plane onto the live coordinator; returns
    (plane, restore_fn) — the compression-test idiom."""
    coord = basics.runtime().coordinator
    saved = coord._sparse
    plane = sparse.SparsePlane(
        sparse.SparsePolicy(sparse.parse_rules(rules), **kwargs))
    coord._sparse = plane

    def restore():
        coord._sparse = saved
    return plane, restore


# ==========================================================================
# Disabled-mode guard (FIRST: see module docstring)
# ==========================================================================

def test_disabled_mode_zero_engagement_on_dense_hot_path(hvd, n_devices,
                                                         monkeypatch):
    """HVDTPU_SPARSE unset: no plane object exists, dense entries carry
    sparse=None, a plain allreduce never reaches the sparse dispatch,
    and sparse_allreduce densifies into TODAY's dense path."""
    assert envparse.get_str(envparse.SPARSE, "") == ""
    assert sparse.make_plane() is None
    assert not sparse.enabled()
    coord = basics.runtime().coordinator
    assert coord._sparse is None

    def _boom(*a, **k):  # pragma: no cover — the assertion IS no call
        raise AssertionError("sparse dispatch engaged in disabled mode")
    monkeypatch.setattr(Coordinator, "_run_sparse_groups", _boom)
    x = np.random.RandomState(0).randn(n_devices, 256).astype(np.float32)
    out = np.asarray(hvd.allreduce(jnp.asarray(x), op=hvd.Sum,
                                   name="sp.disabled"))
    np.testing.assert_allclose(out, np.broadcast_to(x.sum(0), x.shape),
                               rtol=1e-5)
    e = TensorEntry("t", "allreduce", [x], global_process_set,
                    op=reduce_ops.Sum)
    assert e.sparse is None

    # sparse_allreduce still WORKS with the plane off — it densifies
    # into the dense plane (the _boom patch proves no sparse dispatch).
    slices = mk_slices(n_devices, seed=1)
    got = np.asarray(hvd.sparse_allreduce(slices, op=hvd.Sum,
                                          name="sp.disabled2"))
    np.testing.assert_array_equal(
        got, np.broadcast_to(oracle_sum(slices),
                             (n_devices, 32, 4)))
    assert coord._sparse is None  # still no state


# ==========================================================================
# SparseGradient type
# ==========================================================================

def test_densify_accumulates_duplicate_indices():
    sg = sparse.SparseGradient(np.array([1, 3, 1], np.int32),
                               np.ones((3, 4), np.float32), (8, 4))
    d = np.asarray(sg.densify())
    assert d.shape == (8, 4)
    np.testing.assert_array_equal(d[1], 2.0 * np.ones(4))
    np.testing.assert_array_equal(d[3], np.ones(4))
    assert d.sum() == 12.0


def test_deduplicate_segment_sums_and_sorts():
    sg = sparse.SparseGradient(
        np.array([5, 1, 5, 0], np.int64),
        np.arange(16, dtype=np.float32).reshape(4, 4), (8, 4))
    d = sg.deduplicate()
    np.testing.assert_array_equal(np.asarray(d.indices), [0, 1, 5])
    assert d.nnz == 3
    # Duplicate rows summed; dense meaning preserved exactly.
    np.testing.assert_array_equal(np.asarray(d.densify()),
                                  np.asarray(sg.densify()))


def test_pytree_roundtrip_is_jit_traceable():
    sg = sparse.SparseGradient(jnp.array([0, 2]), jnp.ones((2, 3)),
                               (4, 3))
    leaves, treedef = jax.tree.flatten(sg)
    assert len(leaves) == 2
    back = jax.tree.unflatten(treedef, leaves)
    assert isinstance(back, sparse.SparseGradient)
    assert back.dense_shape == (4, 3)

    @jax.jit
    def f(s):
        return s.densify()
    np.testing.assert_array_equal(np.asarray(f(sg)),
                                  np.asarray(sg.densify()))


def test_from_dense_picks_touched_rows():
    dense = np.zeros((6, 2), np.float32)
    dense[1] = 1.0
    dense[4] = -2.0
    sg = sparse.SparseGradient.from_dense(dense)
    np.testing.assert_array_equal(np.asarray(sg.indices), [1, 4])
    np.testing.assert_array_equal(np.asarray(sg.densify()), dense)


# ==========================================================================
# Row-wise int8 value codec
# ==========================================================================

def test_encode_rows_roundtrip_bound():
    """|x - dec(enc(x))| <= rowmax/254 — one f32 scale per slice row."""
    rng = np.random.RandomState(3)
    v = rng.randn(16, 8).astype(np.float32) * 3
    q, s = sparse.encode_rows(jnp.asarray(v))
    assert np.asarray(q).dtype == np.int8
    assert np.asarray(s).shape == (16,)
    dq = np.asarray(sparse.decode_rows(q, s, np.float32))
    bound = np.abs(v).max(axis=1, keepdims=True) / 254.0
    assert (np.abs(dq - v) <= bound + 1e-7).all()


def test_encode_rows_zero_row_is_exact():
    q, s = sparse.encode_rows(jnp.zeros((3, 4)))
    dq = np.asarray(sparse.decode_rows(q, s, np.float32))
    assert not np.isnan(dq).any() and (dq == 0).all()


# ==========================================================================
# Policy: grammar, crossover, EMA
# ==========================================================================

def test_parse_rules_grammar():
    assert sparse.parse_rules("auto") == [("*", "auto")]
    assert sparse.parse_rules("embed*=gather;dense") == \
        [("embed*", "gather"), ("*", "dense")]
    with pytest.raises(ValueError, match="unknown HVDTPU_SPARSE mode"):
        sparse.parse_rules("sparse")
    with pytest.raises(ValueError, match="malformed"):
        sparse.parse_rules("=gather")


def test_policy_first_match_wins_default_dense():
    pol = sparse.SparsePolicy(sparse.parse_rules(
        "embed*=gather;embed_big=dense;auto"))
    assert pol.mode_for_name("embed_big") == "gather"  # first match
    assert pol.mode_for_name("mlp/w0") == "auto"
    pol2 = sparse.SparsePolicy([("emb*", "gather")])
    assert pol2.mode_for_name("dense_w") == "dense"   # no rule matched


def test_ema_validation_is_loud():
    with pytest.raises(ValueError, match="SPARSE_EMA"):
        sparse.SparsePolicy([], ema=1.0)


def test_threshold_validation_is_loud():
    # A typo'd theta must never silently pin auto to one path (the
    # parse_rules contract applies to every knob of the plane).
    for bad in (0.0, -1.0, float("nan"), float("inf")):
        with pytest.raises(ValueError, match="SPARSE_THRESHOLD"):
            sparse.SparsePolicy([], threshold=bad)


def test_crossover_density_math():
    # d* = theta * 2*rb / ((n-1)*(rb+ib)); shrinks ~1/n.
    assert sparse.crossover_density(1, 16, 4, 1.0) == float("inf")
    d4 = sparse.crossover_density(4, 16, 4, 1.0)
    assert abs(d4 - 2 * 16 / (3 * 20)) < 1e-12
    assert sparse.crossover_density(8, 16, 4, 1.0) < d4
    # theta scales linearly.
    assert abs(sparse.crossover_density(4, 16, 4, 0.5) - d4 / 2) < 1e-12


def test_auto_crossover_flips_at_threshold():
    plane = sparse.SparsePlane(
        sparse.SparsePolicy(sparse.parse_rules("auto")))
    d_star = sparse.crossover_density(4, 16, 4, 1.0)  # ~0.533
    # Below the crossover -> gather; above -> dense (fresh names:
    # first observation seeds the EMA with the observed density).
    assert plane.select("low", 10, 100, 16, 4, 4) == "gather"
    assert plane.select("high", 60, 100, 16, 4, 4) == "dense"
    assert plane.density("low") == pytest.approx(0.10)
    assert 0.10 < d_star < 0.60
    assert plane.path_counts == {"gather": 1, "dense": 1}


def test_auto_threshold_knob_scales_crossover():
    plane = sparse.SparsePlane(
        sparse.SparsePolicy(sparse.parse_rules("auto"), threshold=0.1))
    # Density 0.10 vs the theta-scaled crossover ~0.053 -> dense now.
    assert plane.select("t", 10, 100, 16, 4, 4) == "dense"


def test_auto_ema_stable_under_density_spike():
    """One high-density step must NOT flip a stably-sparse tensor past
    the crossover (EMA 0.8 keeps the smoothed density low); sustained
    high density eventually does flip it."""
    plane = sparse.SparsePlane(
        sparse.SparsePolicy(sparse.parse_rules("auto"), ema=0.8))
    for _ in range(5):
        assert plane.select("emb", 5, 100, 16, 4, 4) == "gather"
    # Spike: observed 0.9, smoothed = 0.8*0.05 + 0.2*0.9 = 0.22 < d*.
    assert plane.select("emb", 90, 100, 16, 4, 4) == "gather"
    assert plane.density("emb") < 0.3
    # Sustained: the EMA converges toward 0.9 and crosses d* ~ 0.533.
    for _ in range(12):
        path = plane.select("emb", 90, 100, 16, 4, 4)
    assert path == "dense"


def test_explicit_rules_skip_the_ema():
    plane = sparse.SparsePlane(
        sparse.SparsePolicy(sparse.parse_rules("emb*=gather;dense")))
    assert plane.select("emb_table", 99, 100, 16, 4, 8) == "gather"
    assert plane.select("mlp", 1, 100, 16, 4, 8) == "dense"
    # Not density-driven: no EMA state was recorded.
    assert plane.density("emb_table") is None
    assert plane.density("mlp") is None


def test_malformed_env_spec_raises_at_plane_construction(monkeypatch):
    monkeypatch.setenv("HVDTPU_SPARSE", "gahter")
    with pytest.raises(ValueError, match="unknown HVDTPU_SPARSE mode"):
        sparse.make_plane()


# ==========================================================================
# Gather path == densified oracle at n=1/2/4 (duplicates included)
# ==========================================================================

@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("op_name", ["Sum", "Average"])
def test_gather_path_matches_densified_oracle(hvd, n, op_name):
    op = getattr(reduce_ops, op_name)
    plane, restore = install_plane("gather")
    ps = hvd_mod.add_process_set(list(range(n))) if n > 1 else \
        hvd_mod.add_process_set([0])
    try:
        slices = mk_slices(n, rows=32, width=4, nnz=6, seed=n)
        out = np.asarray(hvd.sparse_allreduce(
            slices, op=op, name=f"sp.gather.{op_name}.{n}",
            process_set=ps))
        expect = oracle_sum(slices)
        if op == reduce_ops.Average:
            expect = expect / n
        assert out.shape == (n, 32, 4)
        np.testing.assert_allclose(out, np.broadcast_to(expect,
                                                        out.shape),
                                   rtol=1e-6, atol=1e-6)
        assert plane.path_counts["gather"] == 1
    finally:
        restore()
        hvd_mod.remove_process_set(ps)


def test_gather_path_int64_indices_and_wide_rows(hvd, n_devices):
    plane, restore = install_plane("gather")
    try:
        slices = mk_slices(n_devices, rows=64, width=16, nnz=9, seed=7,
                           index_dtype=np.int64)
        out = np.asarray(hvd.sparse_allreduce(slices, op=hvd.Sum,
                                              name="sp.i64"))
        np.testing.assert_allclose(
            out, np.broadcast_to(oracle_sum(slices), out.shape),
            rtol=1e-6, atol=1e-6)
    finally:
        restore()


def test_gather_entries_fuse_and_complete_independently(hvd, n_devices):
    """Two same-dtype sparse entries land in one fusion group (one
    uneven-allgather transport), a different index dtype forms its own
    group — all three results exact."""
    plane, restore = install_plane("gather")
    try:
        a = mk_slices(n_devices, rows=16, width=4, nnz=3, seed=21)
        b = mk_slices(n_devices, rows=24, width=4, nnz=5, seed=22)
        c = mk_slices(n_devices, rows=16, width=4, nnz=3, seed=23,
                      index_dtype=np.int64)
        ha = hvd_mod.sparse_allreduce_async(a, op=hvd.Sum, name="sp.fa")
        hb = hvd_mod.sparse_allreduce_async(b, op=hvd.Sum, name="sp.fb")
        hc = hvd_mod.sparse_allreduce_async(c, op=hvd.Sum, name="sp.fc")
        for h, slices in ((ha, a), (hb, b), (hc, c)):
            out = np.asarray(hvd_mod.synchronize(h))
            np.testing.assert_allclose(
                out, np.broadcast_to(oracle_sum(slices), out.shape),
                rtol=1e-6, atol=1e-6)
        assert plane.path_counts["gather"] == 3
    finally:
        restore()


# ==========================================================================
# Dense path: bit-identical to the pre-plane allreduce
# ==========================================================================

@pytest.mark.parametrize("via", ["no_plane", "dense_rule"])
def test_dense_path_bit_identical_to_plain_allreduce(hvd, n_devices,
                                                     via):
    """The headline contract: when the policy resolves `dense` (or the
    plane is off), sparse_allreduce is EXACTLY the densify + allreduce
    a user would have written pre-plane — same entries, same fusion,
    bitwise-equal results."""
    if via == "dense_rule":
        plane, restore = install_plane("dense")
    else:
        restore = None
    try:
        slices = mk_slices(n_devices, rows=48, width=8, nnz=7, seed=13)
        got = np.asarray(hvd.sparse_allreduce(
            slices, op=hvd.Sum, name=f"sp.dense.{via}"))
        dense = jnp.stack([sg.densify() for sg in slices])
        ref = np.asarray(hvd.allreduce(dense, op=hvd.Sum,
                                       name=f"sp.dense.ref.{via}"))
        assert (got == ref).all()
        assert got.dtype == ref.dtype
    finally:
        if restore is not None:
            restore()


def test_dense_path_skips_host_dedup(hvd, n_devices, monkeypatch):
    """The resolved-dense path is the PRE-PLANE path, host work
    included: deduplicate() (an O(nnz log nnz) sort + scatter-sum per
    slice) is only paid when the resolved mode can gather — densify's
    scatter-add accumulates duplicates anyway."""
    calls = []
    orig = sparse.SparseGradient.deduplicate

    def counting(self):
        calls.append(1)
        return orig(self)
    monkeypatch.setattr(sparse.SparseGradient, "deduplicate", counting)
    plane, restore = install_plane("dense")
    try:
        np.asarray(hvd.sparse_allreduce(
            mk_slices(n_devices, seed=31), op=hvd.Sum,
            name="sp.nodedup"))
        assert calls == []
    finally:
        restore()
    plane, restore = install_plane("gather")
    try:
        np.asarray(hvd.sparse_allreduce(
            mk_slices(n_devices, seed=32), op=hvd.Sum, name="sp.dedup"))
        assert len(calls) == n_devices  # one per rank slice
    finally:
        restore()


def test_wire_accounting_skips_world_one(hvd, monkeypatch):
    """No fabric, nothing saved: a world-1 gather entry must not count
    the whole densified table as hvd_sparse_bytes_saved_total."""
    import types
    coord = basics.runtime().coordinator
    plane, restore = install_plane("gather")
    try:
        recorded = []
        monkeypatch.setattr(plane, "record_gather",
                            lambda d, g: recorded.append((d, g)))
        e = TensorEntry("sp.w1", "sparse_allreduce",
                        [np.zeros(3, np.int32),
                         np.zeros((3, 4), np.float32)],
                        types.SimpleNamespace(ranks=[0],
                                              process_set_id=0),
                        op=reduce_ops.Sum)
        e.sparse = sparse.SparseMeta((8, 4), "int32", "float32",
                                     nranks=None)
        coord._record_sparse_wire(e)
        assert recorded == []
        # A real cohort records.
        e2 = TensorEntry("sp.w2", "sparse_allreduce",
                         [np.zeros(3, np.int32),
                          np.zeros((3, 4), np.float32)],
                         types.SimpleNamespace(ranks=[0, 1],
                                               process_set_id=0),
                         op=reduce_ops.Sum)
        e2.sparse = sparse.SparseMeta((8, 4), "int32", "float32",
                                      nranks=None)
        coord._record_sparse_wire(e2)
        assert len(recorded) == 1
    finally:
        restore()


# ==========================================================================
# Wire codec on gathered values (int8 rows; indices exact always)
# ==========================================================================

def test_wire_codec_selection_follows_compression_policy(monkeypatch):
    # No HVDTPU_COMPRESSION -> no codec ever.
    plane = sparse.SparsePlane(
        sparse.SparsePolicy(sparse.parse_rules("gather")))
    assert plane.wire_codec_for("emb", np.float32) is None
    # With the compression name policy on: values get int8, integer
    # dtypes (index tensors) never do.
    monkeypatch.setenv("HVDTPU_COMPRESSION", "int8")
    plane2 = sparse.SparsePlane(
        sparse.SparsePolicy(sparse.parse_rules("gather")))
    assert plane2.wire_codec_for("emb", np.float32) == "int8"
    assert plane2.wire_codec_for("emb", np.int32) is None
    # Cast codecs are not wire codecs on this plane.
    monkeypatch.setenv("HVDTPU_COMPRESSION", "fp16")
    plane3 = sparse.SparsePlane(
        sparse.SparsePolicy(sparse.parse_rules("gather")))
    assert plane3.wire_codec_for("emb", np.float32) is None


def test_gather_with_int8_values_within_row_bound(hvd, n_devices,
                                                  monkeypatch):
    monkeypatch.setenv("HVDTPU_COMPRESSION", "int8")
    plane, restore = install_plane("gather")
    try:
        slices = mk_slices(n_devices, rows=32, width=8, nnz=6, seed=31)
        out = np.asarray(hvd.sparse_allreduce(slices, op=hvd.Sum,
                                              name="sp.q"))
        expect = oracle_sum(slices)
        # n per-row quantization errors accumulate through the sum.
        bound = sum(np.abs(np.asarray(sg.values)).max()
                    for sg in slices) / 254.0
        err = np.abs(out - np.broadcast_to(expect, out.shape)).max()
        assert 0 < err <= bound + 1e-7
    finally:
        restore()


# ==========================================================================
# Wire-byte accounting
# ==========================================================================

def test_wire_bytes_model():
    # dense ring ~ 2 * payload.
    assert sparse.dense_wire_bytes((16, 4), 4) == 2 * 16 * 4 * 4
    # gather: (n-1)/n of (rows * (row_bytes + index_bytes)).
    assert sparse.gather_wire_bytes(10, 4, 4, 4, 4) == \
        int(10 * (4 * 4 + 4) * 3 / 4)
    # int8 rows: 1 byte/elem + one f32 scale per row + exact indices.
    assert sparse.gather_wire_bytes(10, 4, 4, 4, 4, codec="int8") == \
        int(10 * (4 + 4 + 4) * 3 / 4)
    # world=1: no wire either way.
    assert sparse.gather_wire_bytes(10, 4, 4, 4, 1) == 0


def test_gather_beats_dense_wire_at_low_density():
    """The sparse plane's wire contract: at <=5% density the gather
    transport models >=4x fewer wire bytes than the densified ring."""
    rows, width, n = 100_000, 64, 8
    nnz_per_rank = rows // 20  # 5% density
    dense = sparse.dense_wire_bytes((rows, width), 4)
    gather = sparse.gather_wire_bytes(nnz_per_rank * n, width, 4, 4, n)
    assert dense / gather >= 4.0


# ==========================================================================
# Guardian digests
# ==========================================================================

def _sparse_entry(name, slices, codec=None):
    e = TensorEntry(name, "sparse_allreduce",
                    [np.asarray(sg.indices) for sg in slices]
                    + [np.asarray(sg.values) for sg in slices],
                    global_process_set, op=reduce_ops.Sum)
    e.sparse = sparse.SparseMeta(
        slices[0].dense_shape, np.asarray(slices[0].indices).dtype,
        np.asarray(slices[0].values).dtype, nranks=len(slices),
        codec=codec)
    return e


def test_digest_stamps_index_dtype_and_dense_shape_excludes_nnz(hvd):
    """Cross-rank-invariant fields ride the digest; nnz (per-rank-
    varying BY CONSTRUCTION) must not — a naive shape digest would
    false-abort every healthy sparse step."""
    a = _sparse_entry("sp.dig", mk_slices(1, nnz=3, seed=41))
    b = _sparse_entry("sp.dig", mk_slices(1, nnz=29, seed=42))
    da, db = guardian.entry_digest(a), guardian.entry_digest(b)
    assert da["index_dtype"] == "int32"
    assert da["dense_shape"] == [32, 4]
    assert da["shapes"] is None  # nnz excluded wholesale
    assert da == db  # different nnz, SAME digest
    assert guardian.compare_digests(da, {1: db}) == []


def test_digest_mismatch_names_the_divergent_field(hvd):
    mine = guardian.entry_digest(
        _sparse_entry("sp.mm", mk_slices(1, seed=43)))
    theirs = guardian.entry_digest(
        _sparse_entry("sp.mm", mk_slices(1, seed=43,
                                         index_dtype=np.int64)))
    divs = guardian.compare_digests(mine, {1: theirs})
    assert ("index_dtype" in [f for _, f, _, _ in divs])
    wrong_shape = dict(mine, dense_shape=[64, 4])
    divs2 = guardian.compare_digests(mine, {2: wrong_shape})
    assert [f for _, f, _, _ in divs2] == ["dense_shape"]


def test_digest_codec_field_covers_row_quantization(hvd):
    d = guardian.entry_digest(
        _sparse_entry("sp.codec", mk_slices(1, seed=44), codec="int8"))
    assert d["codec"] == "int8@rows"
    d2 = guardian.entry_digest(
        _sparse_entry("sp.codec", mk_slices(1, seed=44)))
    assert d2["codec"] is None
    divs = guardian.compare_digests(d, {1: d2})
    assert [f for _, f, _, _ in divs] == ["codec"]


def test_dense_entry_digest_unchanged_by_sparse_fields(hvd):
    """Dense digests gain two always-None fields — peers on the same
    version agree; the FIELD LIST is part of the digest schema."""
    x = np.ones((2, 8), np.float32)
    e = TensorEntry("t", "allreduce", [x], global_process_set,
                    op=reduce_ops.Sum)
    d = guardian.entry_digest(e)
    assert d["index_dtype"] is None and d["dense_shape"] is None
    assert d["shapes"] == [[2, 8]]


# ==========================================================================
# Validation / rejections
# ==========================================================================

def test_sparse_allreduce_rejects_non_linear_ops(hvd):
    slices = mk_slices(8, seed=51)
    for op in (reduce_ops.Adasum, reduce_ops.Max):
        with pytest.raises(ValueError, match="Sum/Average"):
            hvd.sparse_allreduce(slices, op=op, name="sp.reject")


def test_sparse_allreduce_rejects_wrong_list_length(hvd):
    with pytest.raises(ValueError, match="per rank"):
        hvd.sparse_allreduce(mk_slices(3, seed=52), op=hvd.Sum,
                             name="sp.len")


def test_sparse_allreduce_rejects_disagreeing_dense_shapes(hvd):
    slices = mk_slices(8, seed=53)
    bad = sparse.SparseGradient(np.array([0], np.int32),
                                np.ones((1, 4), np.float32), (64, 4))
    with pytest.raises(ValueError, match="dense_shapes"):
        hvd.sparse_allreduce(slices[:-1] + [bad], op=hvd.Sum,
                             name="sp.shape")


# ==========================================================================
# In-jit axis path (shard_map)
# ==========================================================================

def _mesh(n):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


@pytest.mark.parametrize("mode,op_name", [("gather", "Sum"),
                                          ("gather", "Average"),
                                          ("dense", "Average")])
def test_axis_path_matches_densified_psum(hvd, mode, op_name):
    """sparse_allreduce_axis inside shard_map == pmean/psum of the
    densified gradient, on both static path decisions."""
    from jax.sharding import PartitionSpec as P
    op = getattr(reduce_ops, op_name)
    n = 4
    plane, restore = install_plane(mode)
    try:
        slices = mk_slices(n, rows=16, width=4, nnz=5, seed=61)
        idx = jnp.stack([jnp.asarray(sg.indices) for sg in slices])
        vals = jnp.stack([jnp.asarray(sg.values) for sg in slices])

        def body(i, v):
            sg = sparse.SparseGradient(i[0], v[0], (16, 4))
            out = sparse.sparse_allreduce_axis(sg, "dp", op=op,
                                               name="sp.axis")
            return out[None]

        out = jax.jit(shard_map(body, mesh=_mesh(n),
                                in_specs=(P("dp"), P("dp")),
                                out_specs=P("dp")))(idx, vals)
        expect = oracle_sum(slices)
        if op == reduce_ops.Average:
            expect = expect / n
        np.testing.assert_allclose(np.asarray(out),
                                   np.broadcast_to(expect, (n, 16, 4)),
                                   rtol=1e-5, atol=1e-6)
    finally:
        restore()


# ==========================================================================
# Framework routing
# ==========================================================================

def test_jax_optimizer_accepts_sparse_leaves(hvd):
    """A gradient tree mixing SparseGradient and dense leaves reduces:
    sparse leaves come back DENSE, dense leaves ride the normal path
    unchanged."""
    import optax
    import horovod_tpu.jax as hvd_jax
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.1), op=reduce_ops.Sum)
    sg = mk_slices(1, rows=8, width=2, nnz=3, seed=71)[0]
    dense = jnp.ones((5,), jnp.float32)
    reduced = opt._reduce({"emb": sg, "w": dense})
    # Single-controller partitioner path: the sparse leaf densifies,
    # the dense leaf is identity (XLA's partitioner already reduced
    # replicated-param gradients — the pre-plane behavior, unchanged).
    np.testing.assert_array_equal(np.asarray(reduced["emb"]),
                                  np.asarray(sg.densify()))
    np.testing.assert_array_equal(np.asarray(reduced["w"]),
                                  np.asarray(dense))


def test_jax_spmd_sparse_leaves_submit_async_before_sync(
        hvd, monkeypatch):
    """Eager SPMD path: every sparse leaf is SUBMITTED before any
    handle is synchronized. A blocking call per leaf serializes one
    full coordinator cycle per embedding table, and the sparse fusion
    groups can only fuse entries landing in the same cycle batch —
    async-then-synchronize turns k tables into one fused gather."""
    import optax
    import horovod_tpu.jax as hvd_jax
    from horovod_tpu.ops import collectives as _c
    from horovod_tpu.ops import sparse as sparse_ops
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.1), op=reduce_ops.Sum)
    events = []

    def fake_async(sg, op=None, name=None, process_set=None):
        events.append(("sub", name))
        return ("handle", name, sg)

    def fake_sync(h):
        events.append(("syn", h[1]))
        return h[2].densify()

    monkeypatch.setattr(sparse_ops, "sparse_allreduce_async", fake_async)
    monkeypatch.setattr(_c, "synchronize", fake_sync)
    monkeypatch.setattr(basics.runtime(), "mode", basics.MODE_SPMD,
                        raising=False)
    orig_reduce = opt._reduce

    def spy_reduce(grads, pack=False):
        # The inner dense-leaf reduction arrives as a LIST; the test's
        # own entry call is a dict tree. The dense reduction
        # synchronizes internally, so it must come AFTER every sparse
        # submission for the gathers to ride under it.
        if isinstance(grads, list):
            events.append(("dense", len(grads)))
            return list(grads)
        return orig_reduce(grads)

    monkeypatch.setattr(opt, "_reduce", spy_reduce)
    sg0, sg1 = mk_slices(2, rows=8, width=2, nnz=3, seed=73)
    w = jnp.ones((5,), jnp.float32)
    reduced = opt._reduce({"e1": sg0, "e2": sg1, "w": w})
    assert [e[0] for e in events] == \
        ["sub", "sub", "dense", "syn", "syn"], events
    assert sorted(e[1] for e in events[:2]) == ["grad.sp0", "grad.sp1"]
    np.testing.assert_array_equal(np.asarray(reduced["e1"]),
                                  np.asarray(sg0.densify()))
    np.testing.assert_array_equal(np.asarray(reduced["e2"]),
                                  np.asarray(sg1.densify()))
    np.testing.assert_array_equal(np.asarray(reduced["w"]), np.asarray(w))


def test_jax_zero_mode_rejects_sparse_leaves(hvd):
    import optax
    import horovod_tpu.jax as hvd_jax
    opt = hvd_jax.DistributedOptimizer(optax.sgd(0.1), zero=True)
    sg = mk_slices(1, seed=72)[0]
    with pytest.raises(ValueError, match="SparseGradient"):
        opt.update({"emb": sg}, None)


def test_tf_reduce_grads_routes_indexed_slices(hvd, monkeypatch):
    """sparse_as_dense=False: IndexedSlices reach _sparse_allreduce_tf
    (the honored contract) instead of silent densification; =True
    densifies visibly before the dense sync."""
    tf = pytest.importorskip("tensorflow")
    import horovod_tpu.tensorflow as hvd_tf
    calls = []

    def fake_sparse_allreduce(g, op, name, ps):
        calls.append(name)
        return tf.convert_to_tensor(g) * 0 + 7.0
    monkeypatch.setattr(hvd_tf, "_sparse_allreduce_tf",
                        fake_sparse_allreduce)
    slices = tf.IndexedSlices(
        values=tf.ones((2, 4)), indices=tf.constant([1, 3]),
        dense_shape=tf.constant([8, 4], tf.int64))
    out = hvd_tf._reduce_grads([slices], reduce_ops.Sum,
                               global_process_set,
                               sparse_as_dense=False)
    assert calls == ["grad_reduce.sp0"]
    assert float(tf.reduce_max(out[0])) == 7.0


def test_tf_gradient_tape_carries_sparse_as_dense(hvd):
    tf = pytest.importorskip("tensorflow")
    import horovod_tpu.tensorflow as hvd_tf
    tape = hvd_tf.DistributedGradientTape(tf.GradientTape(),
                                          sparse_as_dense=False)
    assert tape._sparse_as_dense is False


def test_torch_sparse_allreduce_consults_the_plane(hvd, monkeypatch):
    """Row-sparse torch COO grads route by the density policy: past the
    crossover the handle resolves to a DENSE allreduce."""
    torch = pytest.importorskip("torch")
    import horovod_tpu.torch as hvd_torch
    plane, restore = install_plane("dense")
    captured = {}

    def fake_allreduce_async(t, name=None, op=None, process_set=None):
        captured["dense"] = t
        return hvd_torch._local_handle(t)
    monkeypatch.setattr(hvd_torch, "allreduce_async",
                        fake_allreduce_async)
    # Single-process harness: lift the not-_spmd short-circuit so the
    # plane consult (an SPMD-plane concern) is reachable in-process.
    monkeypatch.setattr(hvd_torch, "_spmd", lambda: True)
    monkeypatch.setattr(hvd_torch, "size", lambda: 4)
    try:
        sp = torch.sparse_coo_tensor(
            torch.tensor([[1, 3]]), torch.ones(2, 4), (8, 4))
        h = hvd_torch.sparse_allreduce_async(sp, name="sp.torch")
        out = hvd_torch.synchronize(h)
        assert not out.is_sparse  # densified past the crossover
        assert "dense" in captured
        assert plane.path_counts["dense"] == 1
    finally:
        restore()


def test_torch_hook_resparsifies_dense_fallback(hvd, monkeypatch):
    """The optimizer hook never flips param.grad's layout: when the
    density policy resolves dense, the reduced gradient is converted
    back to COO before the write-back — a sparse-only inner optimizer
    (SparseAdam) must survive the step the EMA crosses d*."""
    torch = pytest.importorskip("torch")
    import horovod_tpu.torch as hvd_torch
    plane, restore = install_plane("dense")
    monkeypatch.setattr(
        hvd_torch, "allreduce_async",
        lambda t, name=None, op=None, process_set=None:
        hvd_torch._local_handle(t))
    monkeypatch.setattr(hvd_torch, "_spmd", lambda: True)
    try:
        p = torch.nn.Parameter(torch.zeros(8, 4))
        p.grad = torch.sparse_coo_tensor(
            torch.tensor([[1, 3]]), torch.ones(2, 4), (8, 4))
        h = hvd_torch._sparse_grad_handle(
            p, hvd_torch.Sum, "sp.hook", hvd_torch.global_process_set,
            1.0)
        out = hvd_torch.synchronize(h)
        assert out.is_sparse and p.grad.is_sparse
        assert p.grad.sparse_dim() == 1  # the embedding-grad layout
        np.testing.assert_allclose(
            p.grad.to_dense().numpy(),
            torch.sparse_coo_tensor(
                torch.tensor([[1, 3]]), torch.ones(2, 4),
                (8, 4)).to_dense().numpy())
        assert plane.path_counts["dense"] == 1
    finally:
        restore()


# ==========================================================================
# SPMD auto-decision cohort agreement (rank-invariant path choice)
# ==========================================================================


def test_cohort_nnz_is_a_named_max_allreduce(monkeypatch):
    """The SPMD nnz sync rides a scalar Max-allreduce under a derived
    name (same shape/dtype on every rank — guardian-silent), so every
    rank feeds the policy the cohort max — mirroring single-controller
    mode's max over the virtual ranks' slices. Without it, a tensor
    straddling d* splits the cohort onto mismatched collectives."""
    from horovod_tpu.ops import collectives as _c
    captured = {}

    def fake_allreduce(arr, name=None, op=None, process_set=None):
        captured.update(arr=np.asarray(arr), name=name, op=op)
        return np.array([9], np.int64)

    monkeypatch.setattr(_c, "allreduce", fake_allreduce)
    assert sparse._cohort_nnz("emb_t", 5, global_process_set) == 9
    assert captured["name"] == "emb_t.nnz"
    assert captured["op"] == reduce_ops.Max
    assert captured["arr"].dtype == np.int64
    assert captured["arr"].shape == (1,) and captured["arr"][0] == 5


def test_single_controller_auto_never_syncs(hvd, monkeypatch):
    """Single-controller mode already sees every virtual rank's slices
    locally; a sync collective there would be pure overhead. Bombed."""
    def bomb(*a, **k):
        raise AssertionError("nnz sync on the single-controller plane")
    monkeypatch.setattr(sparse, "_cohort_nnz", bomb)
    plane, restore = install_plane("auto")
    try:
        slices = mk_slices(hvd_mod.size(), rows=4096, width=4, nnz=4)
        out = np.asarray(hvd.sparse_allreduce(slices, op=hvd.Sum,
                                              name="sp.nosync"))
        np.testing.assert_allclose(
            out, np.broadcast_to(oracle_sum(slices), out.shape),
            rtol=1e-6, atol=1e-6)
        assert plane.path_counts["gather"] == 1
    finally:
        restore()


def test_torch_auto_decision_uses_cohort_nnz(hvd, monkeypatch):
    """The torch binding's path decision feeds the policy the SYNCED
    cohort nnz, not this rank's: a locally-sparse tensor whose cohort
    max sits past the crossover must resolve dense on EVERY rank."""
    torch = pytest.importorskip("torch")
    import horovod_tpu.torch as hvd_torch
    from horovod_tpu.ops import sparse as sparse_ops
    plane, restore = install_plane("auto")
    captured = {}

    def fake_sync(nm, nnz, ps):
        captured["local"] = nnz
        return 8  # cohort max: every row touched somewhere

    monkeypatch.setattr(sparse_ops, "_cohort_nnz", fake_sync)
    monkeypatch.setattr(
        hvd_torch, "allreduce_async",
        lambda t, name=None, op=None, process_set=None:
        hvd_torch._local_handle(t))
    monkeypatch.setattr(hvd_torch, "_spmd", lambda: True)
    try:
        sp = torch.sparse_coo_tensor(
            torch.tensor([[1, 3]]), torch.ones(2, 4), (8, 4))
        out = hvd_torch.synchronize(
            hvd_torch.sparse_allreduce_async(sp, name="sp.sync"))
        assert captured["local"] == 2  # post-coalesce local nnz
        assert not out.is_sparse  # density 8/8 -> dense on every rank
        assert plane.path_counts["dense"] == 1
    finally:
        restore()


def test_torch_unnamed_sparse_tensors_key_ema_by_call_site(
        hvd, monkeypatch):
    """Unnamed torch sparse tensors take per-call-site auto names, not
    one shared key: a shared key would pool every unnamed tensor into
    one density EMA (blending a sparse table with a dense one) and
    collide the .idx/.val allgather names of two in-flight tensors.
    The EMA strips the per-call '#count' occurrence suffix, so a
    per-step unnamed tensor keeps ONE smoothed entry (bounded state,
    the smoothing actually engages) while every call still gets a
    distinct wire name."""
    torch = pytest.importorskip("torch")
    import horovod_tpu.torch as hvd_torch
    from horovod_tpu.ops import sparse as sparse_ops
    plane, restore = install_plane("auto")
    wire_names = []
    monkeypatch.setattr(sparse_ops, "_cohort_nnz",
                        lambda nm, nnz, ps: nnz)
    monkeypatch.setattr(
        hvd_torch, "allreduce_async",
        lambda t, name=None, op=None, process_set=None:
        (wire_names.append(name), hvd_torch._local_handle(t))[1])
    monkeypatch.setattr(hvd_torch, "_spmd", lambda: True)
    try:
        dense_sp = torch.sparse_coo_tensor(
            torch.arange(8).reshape(1, 8), torch.ones(8, 4), (8, 4))
        for _ in range(3):
            hvd_torch.synchronize(
                hvd_torch.sparse_allreduce_async(dense_sp))
        keys = sorted(plane._ema)
        assert len(keys) == 1, keys  # bounded: one entry per call site
        assert keys[0].startswith("sparse_allreduce.auto.")
        assert "#" not in keys[0]
        assert "sparse_allreduce" not in keys
        # Every call still carries its own wire name (occurrences).
        assert len(set(wire_names)) == 3, wire_names
        # Smoothing engaged: same density each step -> EMA == observed.
        assert plane.density(keys[0]) == pytest.approx(1.0)
        assert plane.density(wire_names[0]) == pytest.approx(1.0)
    finally:
        restore()


def test_torch_sparse_hook_submits_at_construction(hvd, monkeypatch):
    """_sparse_grad_handle submits at hook time like the dense path —
    deferring to synchronize() would serialize k embedding tables into
    k coordinator round-trips that never fuse."""
    torch = pytest.importorskip("torch")
    import horovod_tpu.torch as hvd_torch
    events = []

    def fake_sparse_async(t, name=None, op=None, process_set=None):
        events.append("sub")
        return hvd_torch._local_handle(t)

    monkeypatch.setattr(hvd_torch, "sparse_allreduce_async",
                        fake_sparse_async)
    p = torch.nn.Parameter(torch.zeros(8, 4))
    p.grad = torch.sparse_coo_tensor(
        torch.tensor([[1, 3]]), torch.ones(2, 4), (8, 4))
    h = hvd_torch._sparse_grad_handle(
        p, hvd_torch.Sum, "sp.eager", hvd_torch.global_process_set, 1.0)
    assert events == ["sub"]  # on the wire before synchronize
    out = hvd_torch.synchronize(h)
    assert out.is_sparse and p.grad.is_sparse


def test_axis_path_decides_from_raw_density_no_ema_state(hvd):
    """The in-jit axis decision is static at trace time and reads RAW
    density (select smooth=False): no EMA state is written — a shared
    '<axis>' key would blend unrelated tensors' densities, and a
    smoothed value would go stale inside a cached trace."""
    plane, restore = install_plane("auto")
    try:
        # Sparse tensor: raw density under d* -> gather.
        assert plane.select("<axis>", 2, 100, 16, 4, 8,
                            smooth=False) == "gather"
        assert plane._ema == {}  # no state written
        # Dense tensor through the SAME key: raw density past d* ->
        # dense. A shared EMA would have blended toward gather.
        assert plane.select("<axis>", 90, 100, 16, 4, 8,
                            smooth=False) == "dense"
        assert plane._ema == {}
        assert plane.density("<axis>") is None
    finally:
        restore()


def test_ema_key_strips_only_auto_occurrence_suffixes():
    assert sparse._ema_key("sparse_allreduce.auto.t:fn:12#7") == \
        "sparse_allreduce.auto.t:fn:12"
    assert sparse._ema_key("emb_table") == "emb_table"
    assert sparse._ema_key("user#3") == "user#3"  # not an auto name
    assert sparse._ema_key(None) is None


# ==========================================================================
# ZeRO composition: row-range sharded embedding state
# ==========================================================================

def test_plan_row_shards_even_and_remainder():
    assert sparse.plan_row_shards(8, 2) == [(0, 4), (4, 8)]
    assert sparse.plan_row_shards(10, 4) == \
        [(0, 3), (3, 6), (6, 8), (8, 10)]
    bounds = sparse.plan_row_shards(7, 3)
    assert bounds[0][0] == 0 and bounds[-1][1] == 7
    assert all(b[1] >= b[0] for b in bounds)


def test_rowsharded_update_matches_dense_on_touched_rows():
    """The sparse update stays local to the owning shard: touched rows
    step exactly as the full dense optax update would, untouched rows
    keep params AND moments (SparseAdam semantics)."""
    import optax
    rng = np.random.RandomState(81)
    rows, width, world = 8, 4, 2
    params = jnp.asarray(rng.randn(rows, width).astype(np.float32))
    opt = optax.sgd(0.1, momentum=0.9)
    state = opt.init(params)
    # Warm the momentum so untouched-row preservation is observable.
    g0 = jnp.asarray(rng.randn(rows, width).astype(np.float32))
    upd0, state = opt.update(g0, state, params)
    params = params + upd0

    gathered = sparse.SparseGradient(
        np.array([1, 5, 6], np.int32),
        rng.randn(3, width).astype(np.float32), (rows, width))
    # Reference: full dense update (elementwise transform -> touched
    # rows evolve identically whether stepped rowwise or tablewise).
    upd_ref, state_ref = opt.update(gathered.densify(), state, params)
    ref_params = params + upd_ref

    def shard(tree, lo, hi):
        return jax.tree.map(
            lambda l: l[lo:hi] if getattr(l, "ndim", 0)
            and l.shape[0] == rows else l, tree)

    new_rows_p, new_rows_s = [], []
    for lo, hi in sparse.plan_row_shards(rows, world):
        p_sh, s_sh = sparse.rowsharded_update(
            opt, gathered, jnp.asarray(params)[lo:hi],
            shard(state, lo, hi), lo, hi)
        new_rows_p.append(p_sh)
        new_rows_s.append(s_sh)
    full = np.concatenate([np.asarray(p) for p in new_rows_p])
    for r in (1, 5, 6):     # touched: match the dense update exactly
        np.testing.assert_allclose(full[r], np.asarray(ref_params)[r],
                                   rtol=1e-6)
    for r in (0, 2, 3, 4, 7):  # untouched: params AND moments kept
        np.testing.assert_array_equal(full[r], np.asarray(params)[r])
    trace_full = np.concatenate(
        [np.asarray(jax.tree.leaves(s)[0]) for s in new_rows_s])
    old_trace = np.asarray(jax.tree.leaves(state)[0])
    for r in (0, 2, 3, 4, 7):
        np.testing.assert_array_equal(trace_full[r], old_trace[r])


def test_rowsharded_update_no_local_rows_is_identity():
    import optax
    opt = optax.sgd(0.1)
    gathered = sparse.SparseGradient(np.array([0, 1], np.int32),
                                     np.ones((2, 4), np.float32),
                                     (8, 4))
    p = jnp.ones((4, 4))
    s = opt.init(p)
    p2, s2 = sparse.rowsharded_update(opt, gathered, p, s, 4, 8)
    assert p2 is p and s2 is s


# ==========================================================================
# Knobs
# ==========================================================================

def test_sparse_knobs_registered():
    assert "SPARSE" in envparse.KNOBS
    assert "SPARSE_THRESHOLD" in envparse.KNOBS
    assert "SPARSE_EMA" in envparse.KNOBS
    assert envparse.KNOBS["SPARSE_THRESHOLD"]["default"] == "1.0"
    assert envparse.KNOBS["SPARSE_EMA"]["default"] == "0.8"
