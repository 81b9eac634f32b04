"""Program executors: host-side windowed copies and in-jit shard_map.

The host executor is the elastic / train→serve path: it assembles each
destination rank's local buffers by reading bounded windows of the
source shards — ``read_window(src_rank, buf_key, start, length)`` is
the only way source data enters, so a fully-replicated leaf is never
materialized. Peak live bytes per destination rank stay within its
local buffers (≤ one shard) plus the in-flight window (≤ the bucket
budget) plus one source-side staging window — the shard + 2×bucket
bound the property tests pin through :class:`MemoryLedger`.

The in-jit executor lowers a same-mesh single-axis program into a
shard_map body: per step, each rank gathers its send window through
``lax.all_gather`` / exchanges per-destination rows through
``lax.all_to_all``, then scatters the received elements into its
destination buffers via precomputed index maps. Scratch per step is
world × window.

Telemetry: ``hvd_reshard_bytes_total{leg}``, ``hvd_reshard_seconds``,
``hvd_reshard_peak_bytes`` (docs/metrics.md).
"""

import numpy as np


def _m_bytes():
    from ..telemetry import core as telemetry
    return telemetry.counter(
        "hvd_reshard_bytes_total",
        "Bytes moved by redistribution programs, per leg kind",
        ("leg",))


def _m_seconds():
    from ..telemetry import core as telemetry
    return telemetry.histogram(
        "hvd_reshard_seconds",
        "Wall time of one redistribution program execution")


def _m_peak():
    from ..telemetry import core as telemetry
    return telemetry.gauge(
        "hvd_reshard_peak_bytes",
        "Peak live scratch+destination bytes of the last program "
        "execution (bounded by shard + 2x HVDTPU_RESHARD_BUCKET_BYTES)")


class MemoryLedger:
    """Counting allocator shim: every buffer the host executor holds
    is accounted here, so tests assert the memory bound instead of
    trusting it."""

    __slots__ = ("live", "peak")

    def __init__(self):
        self.live = 0
        self.peak = 0

    def alloc(self, nbytes):
        self.live += int(nbytes)
        if self.live > self.peak:
            self.peak = self.live

    def free(self, nbytes):
        self.live -= int(nbytes)


def execute_host(program, read_window, ranks=None, dtype_override=None,
                 ledger=None):
    """Run ``program`` host-side for the given destination ranks
    (default: all). Returns ``(results, report)`` where ``results``
    maps ``dst_rank -> {buf_key: 1-D np.ndarray}`` and ``report``
    carries ``peak_bytes`` (max over ranks of buffers + in-flight
    windows), per-leg byte counts, and the program's predicted cost.

    ``read_window(src_rank, buf_key, start, length)`` must return the
    1-D window of that source buffer — and must itself stay windowed
    (read a shard, slice a bucket) for the memory bound to hold
    end-to-end. ``dtype_override`` reinterprets every destination
    buffer's dtype (the optimizer-moment path reuses one geometry for
    f32 moment slots over non-f32 params)."""
    from ..telemetry import span as tele_span
    ledger = ledger if ledger is not None else MemoryLedger()
    dst, meta = program.dst, program.tree_meta
    if ranks is None:
        ranks = range(dst.world)
    results, peak_overall = {}, 0
    bytes_by_leg = {}
    with tele_span(["resharding"], "RESHARD_EXECUTE",
                   histogram=_m_seconds()):
        for rank in ranks:
            base = ledger.live
            rank_peak = 0
            bufs = {}
            for key, (n, dt) in dst.local_buffers(meta, rank).items():
                dt = np.dtype(dtype_override or dt)
                bufs[key] = np.zeros(n, dt)
                ledger.alloc(bufs[key].nbytes)
            rank_peak = max(rank_peak, ledger.live - base)
            for step in program.steps:
                moved = 0
                for c in step.copies:
                    if c.dst_rank != rank:
                        continue
                    win = np.asarray(read_window(
                        c.src_rank, c.src_buf, c.src_off, c.length))
                    win = win.reshape(-1)
                    ledger.alloc(win.nbytes)
                    rank_peak = max(rank_peak, ledger.live - base)
                    out = bufs[c.dst_buf]
                    sl = slice(c.dst_off, c.dst_off + c.length)
                    if step.op == "sum":
                        out[sl] += win.astype(out.dtype)
                    else:
                        out[sl] = win.astype(out.dtype)
                    ledger.free(win.nbytes)
                    moved += win.nbytes
                if moved:
                    bytes_by_leg[step.kind] = \
                        bytes_by_leg.get(step.kind, 0) + moved
                    _m_bytes().labels(leg=step.kind).inc(moved)
            results[rank] = bufs
            peak_overall = max(peak_overall, rank_peak)
            # Hand the rank's buffers to the caller: they leave the
            # executor's accounting (the bound is per-rank scratch,
            # not the caller's aggregate).
            for arr in bufs.values():
                ledger.free(arr.nbytes)
    _m_peak().set(peak_overall)
    report = {
        "strategy": program.strategy,
        "predicted_s": program.predicted_s,
        "peak_bytes": peak_overall,
        "bytes_by_leg": bytes_by_leg,
        "wire_bytes": program.bytes_moved(),
    }
    return results, report


def buffers_of_tree(spec, tree_meta, leaves, rank):
    """Materialize ``rank``'s local buffers under ``spec`` from full
    (host) leaf arrays — the test/bench helper for seeding a source
    side. Uses ownership intervals, so it works for any layout."""
    own = spec.ownership(tree_meta, rank)
    bufs = {key: np.zeros(n, np.dtype(dt))
            for key, (n, dt) in
            spec.local_buffers(tree_meta, rank).items()}
    for i, ivs in enumerate(own):
        flat = np.asarray(leaves[i]).reshape(-1)
        for iv in ivs:
            bufs[iv.buf][iv.b0:iv.b0 + iv.length] = \
                flat[iv.g0:iv.g0 + iv.length]
    return bufs


def reader_for_buffers(buffers):
    """``read_window`` over ``{rank: {buf_key: array}}`` that slices —
    never copies whole buffers beyond the requested window."""
    def read_window(rank, buf, start, length):
        return buffers[rank][buf][start:start + length]
    return read_window


# ==========================================================================
# In-jit execution (same mesh, single axis)
# ==========================================================================

def _index_maps(program, axis_size):
    """Per step: host-precomputed gather/scatter index maps over each
    rank's CONCATENATED local in/out buffers (-1 = padding)."""
    src_layout = _flat_layout(program.src, program.tree_meta)
    dst_layout = _flat_layout(program.dst, program.tree_meta)
    maps = []
    n = axis_size
    for step in program.steps:
        if step.kind == "slice":
            nloc = max((sum(c.length for c in step.copies
                            if c.dst_rank == r) for r in range(n)),
                       default=0)
            gidx = np.full((n, nloc), -1, np.int32)
            sidx = np.full((n, nloc), -1, np.int32)
            fill = np.zeros(n, np.int64)
            for c in step.copies:
                r = c.dst_rank
                a = int(fill[r])
                gidx[r, a:a + c.length] = np.arange(
                    src_layout[c.src_buf] + c.src_off,
                    src_layout[c.src_buf] + c.src_off + c.length)
                sidx[r, a:a + c.length] = np.arange(
                    dst_layout[c.dst_buf] + c.dst_off,
                    dst_layout[c.dst_buf] + c.dst_off + c.length)
                fill[r] += c.length
            maps.append(("slice", gidx, sidx))
            continue
        # comm step: rows keyed (src, dst); window = max pair payload
        win = 0
        for s in range(n):
            for d in range(n):
                b = sum(c.length for c in step.copies
                        if c.src_rank == s and c.dst_rank == d)
                win = max(win, b)
        send = np.full((n, n, win), -1, np.int32)   # [src, dst, :]
        recv = np.full((n, n, win), -1, np.int32)   # [dst, src, :]
        fill = np.zeros((n, n), np.int64)
        for c in sorted(step.copies,
                        key=lambda c: (c.src_rank, c.dst_rank,
                                       c.dst_buf, c.dst_off)):
            s, d = c.src_rank, c.dst_rank
            a = int(fill[s, d])
            send[s, d, a:a + c.length] = np.arange(
                src_layout[c.src_buf] + c.src_off,
                src_layout[c.src_buf] + c.src_off + c.length)
            recv[d, s, a:a + c.length] = np.arange(
                dst_layout[c.dst_buf] + c.dst_off,
                dst_layout[c.dst_buf] + c.dst_off + c.length)
            fill[s, d] += c.length
        maps.append((step.kind, send, recv))
    return maps


def _flat_layout(spec, tree_meta):
    """buf_key -> offset in the rank's concatenated local flat buffer
    (uniform across ranks — required for the SPMD body)."""
    sizes = {}
    for r in range(spec.world):
        bufs = spec.local_buffers(tree_meta, r)
        for key, (nelem, _) in bufs.items():
            if key in sizes and sizes[key] != nelem:
                raise NotImplementedError(
                    "in-jit execution requires uniform per-rank "
                    f"buffer sizes; {key} varies across ranks "
                    "(near-even sharding) — use execute_host")
            sizes[key] = nelem
    layout, off = {}, 0
    for key in sorted(sizes):
        layout[key] = off
        off += sizes[key]
    return layout


def make_jit_executor(program, mesh, axis_name):
    """Compile ``program`` (same single-axis mesh on both sides, no
    pending-sum legs) into a jitted ``fn(in_bufs) -> out_bufs`` over
    GLOBAL flat buffers sharded ``P(axis_name)``: ``in_bufs`` /
    ``out_bufs`` are dicts keyed like the spec's local buffers, each a
    ``(world * len,)`` array whose rank-r block is that rank's local
    buffer."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n = int(mesh.shape[axis_name])
    for side, name in ((program.src, "src"), (program.dst, "dst")):
        if side.mesh_signature() != [[axis_name, n]]:
            raise NotImplementedError(
                f"in-jit execution supports single-axis same-mesh "
                f"programs; {name} mesh is {side.mesh_signature()}, "
                f"executor axis is [[{axis_name!r}, {n}]]")
    if any(s.op == "sum" for s in program.steps):
        raise NotImplementedError(
            "pending-sum (reduce-scatter) programs are host-path "
            "only for now")
    meta = program.tree_meta
    if len({dt for _, dt in meta}) > 1:
        raise NotImplementedError(
            "in-jit execution requires a uniform leaf dtype (the "
            "buffers ride one concatenated flat vector); mixed-dtype "
            "trees take execute_host")
    src_layout = _flat_layout(program.src, meta)
    dst_layout = _flat_layout(program.dst, meta)
    src_keys = sorted(src_layout)
    dst_keys = sorted(dst_layout)
    src_sizes = {k: program.src.local_buffers(meta, 0)[k][0]
                 for k in src_keys}
    dst_bufs0 = program.dst.local_buffers(meta, 0)
    total_out = sum(dst_bufs0[k][0] for k in dst_keys)
    maps = _index_maps(program, n)
    out_dtype = np.result_type(*[np.dtype(dt)
                                 for _, dt in meta]) if meta else \
        np.float32

    def body(*in_flat):
        r = lax.axis_index(axis_name)
        flat_in = jnp.concatenate(
            [b.reshape(-1) for b in in_flat]) if in_flat else \
            jnp.zeros((0,), out_dtype)
        # one dump slot at the end absorbs -1 padding scatters
        flat_out = jnp.zeros((total_out + 1,), flat_in.dtype)

        def scatter(flat_out, idx_rows, values):
            idx = jnp.where(idx_rows >= 0, idx_rows, total_out)
            return flat_out.at[idx.reshape(-1)].set(
                values.reshape(-1), mode="drop")

        for kind, a, b in maps:
            if kind == "slice":
                rows = jnp.take(jnp.asarray(a), r, axis=0)
                vals = jnp.take(flat_in, jnp.clip(rows, 0),
                                mode="clip")
                flat_out = scatter(
                    flat_out, jnp.take(jnp.asarray(b), r, axis=0),
                    vals)
            elif kind == "allgather":
                send = jnp.take(jnp.asarray(a), r, axis=0)  # (n, win)
                payload = jnp.where(
                    send >= 0,
                    jnp.take(flat_in, jnp.clip(send, 0), mode="clip"),
                    0).astype(flat_in.dtype)
                # every rank contributes its full per-destination rows;
                # gather then pick the rows addressed to me.
                gathered = lax.all_gather(payload, axis_name)
                # gathered[s, d, :] = payload rank s built for dst d;
                # keep the rows addressed to me.
                mine = jnp.take(gathered, r, axis=1)
                recv_rows = jnp.take(jnp.asarray(b), r, axis=0)
                flat_out = scatter(flat_out, recv_rows, mine)
            else:  # alltoall
                send = jnp.take(jnp.asarray(a), r, axis=0)  # (n, win)
                payload = jnp.where(
                    send >= 0,
                    jnp.take(flat_in, jnp.clip(send, 0), mode="clip"),
                    0).astype(flat_in.dtype)
                recv = lax.all_to_all(payload, axis_name,
                                      split_axis=0, concat_axis=0,
                                      tiled=True)
                recv_rows = jnp.take(jnp.asarray(b), r, axis=0)
                flat_out = scatter(flat_out, recv_rows, recv)
        flat_out = flat_out[:total_out]
        outs, off = [], 0
        for k in dst_keys:
            nelem = dst_bufs0[k][0]
            outs.append(flat_out[off:off + nelem])
            off += nelem
        return tuple(outs)

    in_specs = tuple(P(axis_name) for _ in src_keys)
    out_specs = tuple(P(axis_name) for _ in dst_keys)
    mapped = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False))

    def run(in_bufs):
        args = [jnp.asarray(in_bufs[k]).reshape(
            n * src_sizes[k]) for k in src_keys]
        outs = mapped(*args)
        return {k: v for k, v in zip(dst_keys, outs)}

    return run
