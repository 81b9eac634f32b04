"""Multi-host XLA data plane: native control plane + compiled collectives.

The SPMD analog of the reference's NCCL-executes/MPI-negotiates split
(reference: horovod/common/ops/nccl_operations.cc:80-119 — the NCCL data
plane bootstraps its communicator through the controller and executes the
negotiated responses; the controller only orders and fuses). Here:

- The native TCP core (csrc/) keeps the CONTROL plane: named-tensor
  negotiation, fusion ordering, response cache, stall detection —
  byte-identical semantics to the pure-TCP backend.
- Agreed data responses are *delegated* (CoreOptions.delegate_data_ops)
  and executed as jitted XLA collectives over a global device mesh built
  with ``jax.distributed`` — psum/all_gather over ICI/DCN instead of
  host-socket rings. On a TPU pod this is where tensor bytes belong; the
  TCP plane remains the CPU fallback (gloo analog) and still carries
  alltoall (uneven splits), barrier, and join.

The data-plane mesh uses ONE device per process (Horovod semantics: one
rank contributes one tensor); the user's compiled training step sharding
owns the remaining chips. Select with ``HVDTPU_CPU_OPERATIONS=xla``.
"""

import numpy as np
from jax import shard_map as _shard_map

from .tcp_backend import TcpBackend
from .. import native
from ..exceptions import HorovodInternalError
from ..utils import envparse
from ..utils.logging_util import get_logger

# Native wire enums (csrc/common.h).
_T_ALLREDUCE, _T_ALLGATHER, _T_BROADCAST = 0, 1, 2
_T_ALLTOALL, _T_REDUCESCATTER = 3, 4
_RED_SUM, _RED_MIN, _RED_MAX, _RED_PROD = 0, 1, 2, 3

JAXDIST_SCOPE = "jaxdist"


def _enum_to_np():
    return {v: k for k, v in native._dtype_table().items()}


def _bucket(n, min_b=256):
    """Round element counts up to the next power of two (min ``min_b``) so
    the jitted-collective cache sees a bounded set of shapes instead of
    one compilation per fusion-bucket size. The minimum is the autotuned
    delegated-plane knob (autotune.py): raising it turns a flood of small
    collectives into fewer, fuller launches."""
    b = min_b
    while b < n:
        b <<= 1
    return b


def _pad(flat, to_n, op=_RED_SUM):
    if flat.shape[0] == to_n:
        return flat
    out = np.full(to_n, XlaGlobalBackend._identity(op, flat.dtype),
                  dtype=flat.dtype)
    out[:flat.shape[0]] = flat
    return out


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def init_jax_distributed(topology):
    """Initialize the JAX distributed runtime so every process sees the
    global device set. The coordinator address comes from
    ``HVDTPU_XLA_COORD`` or is brokered through the launcher's KV store
    (rank 0 publishes; the analog of the NCCL unique-id broadcast through
    the controller, nccl_operations.cc:102-119)."""
    import jax
    if jax.distributed.is_initialized():
        # Fresh world pre-initialized by user code: reuse it. (A stale
        # post-reset world cannot reach here: elastic resets on this
        # plane happen across a process boundary — elastic.py
        # exit-restart — so a live process never holds a previous
        # cohort's jax.distributed world.)
        return
    log = get_logger()
    coord = envparse.get_str(envparse.XLA_COORD, "")
    if coord:
        log.info("xla-global: jax.distributed coordinator=%s process "
                 "%d/%d", coord, topology.rank, topology.size)
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=topology.size,
                                   process_id=topology.rank)
        return

    from ..runner import http_client
    from ..runner import rendezvous as rdv
    cfg = rdv.rendezvous_config()
    if cfg is None:
        raise HorovodInternalError(
            "the xla-global backend needs HVDTPU_XLA_COORD=ip:port or "
            "the hvdrun launcher's rendezvous to broker the JAX "
            "coordinator address")
    addr, port, token = cfg
    # Elastic exit-restart: every membership version forms a fresh
    # jax.distributed world, so the coordinator key must be scoped to
    # the version this cohort joined — a respawned worker reading the
    # previous cohort's coordinator would dial a dead listener.
    ver = envparse.get_env(envparse.ELASTIC_VERSION)
    coord_key = f"coord.{ver}" if ver is not None else "coord"
    if topology.rank == 0:
        # initialize() blocks until every process connects, so the address
        # must be published while it runs. Bind happens immediately inside
        # initialize, the barrier after — so: start it in a thread, give a
        # bind failure a moment to surface (retrying a fresh port), then
        # publish the now-bound address. This closes the practical
        # publish-then-bind steal window.
        import threading
        ip = rdv._local_ip_towards(addr, port)
        errs = []
        thread = None
        last_err = None
        for _ in range(3):
            coord = f"{ip}:{_free_port()}"

            def _serve(c=coord):
                try:
                    jax.distributed.initialize(coordinator_address=c,
                                               num_processes=topology.size,
                                               process_id=0)
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

            thread = threading.Thread(target=_serve, daemon=True)
            thread.start()
            thread.join(timeout=2.0)
            if not errs:
                break  # bound (blocked in the connect barrier) or done
            last_err = errs[0]
            errs.clear()
        else:
            raise HorovodInternalError(
                f"could not start the JAX coordinator: {last_err}")
        log.info("xla-global: serving jax.distributed coordinator at %s",
                 coord)
        http_client.put_kv(addr, port, JAXDIST_SCOPE, coord_key, coord,
                           token=token)
        thread.join()  # all ranks connected (or init failed)
        if errs:
            raise HorovodInternalError(
                f"could not start the JAX coordinator: {errs[0]}")
    else:
        coord = http_client.wait_for_kv(
            addr, port, JAXDIST_SCOPE, coord_key, token=token,
            deadline_s=envparse.get_float(
                envparse.START_TIMEOUT, 120.0)).decode()
        log.info("xla-global: jax.distributed coordinator=%s process "
                 "%d/%d", coord, topology.rank, topology.size)
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=topology.size,
                                   process_id=topology.rank)


class XlaGlobalBackend(TcpBackend):
    """Delegated-execution backend: native negotiation, XLA data plane."""

    name = "xla-global"
    delegate_data_ops = True
    # Processes share one jax.distributed global mesh: jitted programs are
    # global-SPMD, so in-jit sharding-propagated reductions span every
    # rank (keras binding keys its trace-time identity-sync off this).
    global_mesh_spmd = True

    def __init__(self, topology):
        # Must run before the first jax backend touch in this process.
        init_jax_distributed(topology)
        import jax
        super().__init__(topology)
        self._jax = jax
        self._np_of = _enum_to_np()
        self._local_device = jax.local_devices()[0]
        # One data-plane device per process, ordered by process index ==
        # hvd rank (we pass process_id=rank to jax.distributed).
        by_proc = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, d)
        if len(by_proc) != topology.size:
            raise HorovodInternalError(
                f"jax.distributed sees {len(by_proc)} processes, launcher "
                f"says {topology.size}")
        self._proc_devices = [by_proc[i] for i in range(topology.size)]
        self._ps_ranks = {0: list(range(topology.size))}
        self._mesh_cache = {}
        # Delegated-plane bucket floor (autotunable; see autotune.py).
        self.min_bucket = envparse.get_int(envparse.MIN_BUCKET, 256)
        self._fn_cache = {}
        # Gradient compression on the delegated plane: only the env
        # policy's catch-all wire rule applies (fused native responses
        # carry handles, not tensor names — no globs, no error
        # feedback; docs/compression.md). Every rank parses the same
        # env, so the selection is identical cluster-wide.
        from ..compression.policy import simple_wire_policy
        (self._q_codec, self._q_block,
         self._q_threshold) = simple_wire_policy()
        if self._q_codec is not None:
            get_logger().info(
                "xla-global: quantized allreduce enabled (codec=%s "
                "block=%d threshold=%d; no error feedback on the "
                "delegated plane)", self._q_codec, self._q_block,
                self._q_threshold)

    def set_min_bucket(self, n):
        """Autotune hook: floor for collective bucket sizes (elements).
        Applied at a cycle boundary on every rank with the same value
        (candidate changes are cycle-count driven, autotune.py), so the
        jitted-collective cache stays consistent across ranks."""
        self.min_bucket = max(1, int(n))

    # -- process sets -----------------------------------------------------
    def register_process_set(self, ps):
        super().register_process_set(ps)
        if ps.process_set_id != 0:
            self._ps_ranks[self._ps_map[ps.process_set_id]] = list(ps.ranks)

    def remove_process_set(self, ps):
        native_id = self._ps_map.get(ps.process_set_id)
        super().remove_process_set(ps)
        ranks = self._ps_ranks.pop(native_id, None)
        if ranks is not None:
            # Evict the set's mesh AND its jitted collectives (keyed by
            # id(mesh)) so removed sets don't accumulate executables.
            mesh = self._mesh_cache.pop(tuple(ranks), None)
            if mesh is not None:
                dead = id(mesh)
                self._fn_cache = {k: v for k, v in self._fn_cache.items()
                                  if k[0] != dead}

    def _mesh_for(self, ranks):
        key = tuple(ranks)
        mesh = self._mesh_cache.get(key)
        if mesh is None:
            devices = np.array([self._proc_devices[r] for r in ranks])
            mesh = self._jax.sharding.Mesh(devices, ("hvd",))
            self._mesh_cache[key] = mesh
        return mesh

    # -- the cycle --------------------------------------------------------
    def _drain_delegated(self):
        while True:
            token = self.core.next_delegated()
            if token == 0:
                break
            # The whole per-token block is isolated: an exception from
            # unmarshalling (`delegated`) or completion would otherwise
            # propagate through run_cycle and kill the coordinator's
            # cycle thread — wedging every future submission — instead
            # of poisoning only this response's handles.
            d = None
            try:
                d = self.core.delegated(token)
                self._execute_delegated(d)
            except Exception as exc:  # noqa: BLE001 — fail the handles
                msg = f"XLA data-plane execution failed: {exc}"
                get_logger().error("%s", msg)
                for h in (d["handles"] if d else ()):
                    if h >= 0:
                        try:
                            self.core.delegated_complete(h, error=msg)
                        except Exception:  # noqa: BLE001
                            pass
            finally:
                try:
                    self.core.delegated_finish(token)
                except Exception:  # noqa: BLE001 — keep draining
                    pass

    # -- delegated execution ----------------------------------------------
    def _execute_delegated(self, d):
        ranks = self._ps_ranks.get(d["ps_id"])
        if ranks is None:
            raise HorovodInternalError(
                f"native process set {d['ps_id']} unknown to the XLA "
                "data plane")
        mesh = self._mesh_for(ranks)
        me = ranks.index(self.topology.rank)
        dtype = self._np_of[d["dtype"]]
        t = d["type"]
        if t == _T_ALLREDUCE:
            self._delegated_allreduce(d, mesh, dtype)
        elif t == _T_BROADCAST:
            self._delegated_broadcast(d, mesh, dtype)
        elif t == _T_ALLGATHER:
            self._delegated_allgather(d, mesh, dtype, me)
        elif t == _T_REDUCESCATTER:
            self._delegated_reducescatter(d, mesh, dtype, me, len(ranks))
        else:
            raise HorovodInternalError(f"unexpected delegated type {t}")

    def _collective(self, mesh, kind, n, dtype, extra=()):
        """Cached jitted shard_map collective over the 1-D 'hvd' mesh.
        Input: global (P, n) stacked array; output replicated."""
        key = (id(mesh), kind, int(n), np.dtype(dtype).str, extra)
        fn = self._fn_cache.get(key)
        if fn is not None:
            return fn
        jax = self._jax
        from jax.sharding import PartitionSpec as P
        lax = jax.lax

        if kind == "qallreduce":
            # EQuARX pipeline over the global mesh: quantize →
            # all_to_all (the reduce-scatter leg, wire dtype) → f32
            # accumulate → requantize → all_gather → dequantize. The
            # caller pads n to a multiple of nprocs * block and only
            # routes float SUM reductions here (docs/compression.md).
            op, post, codec_name, block = extra
            from ..compression.codecs import CODECS
            codec = CODECS[codec_name]
            nprocs = int(mesh.devices.size)
            import jax.numpy as jnp

            def body(x):  # x: (1, n) local block, n % (nprocs*block)==0
                rows = x[0].astype(jnp.float32).reshape(nprocs, -1)
                q, s = codec.encode(rows, block)
                q = lax.all_to_all(q, "hvd", split_axis=0,
                                   concat_axis=0, tiled=True)
                s = lax.all_to_all(s, "hvd", split_axis=0,
                                   concat_axis=0, tiled=True)
                red = jnp.sum(codec.decode(q, s, block), axis=0)
                if post != 1.0:
                    red = red * np.asarray(post, dtype=red.dtype)
                q2, s2 = codec.encode(red, block)
                qg = lax.all_gather(q2, "hvd", tiled=True)
                sg = lax.all_gather(s2, "hvd", tiled=True)
                return codec.decode(qg, sg, block,
                                    dtype=x.dtype)[None]
            out_specs = P()
        elif kind.startswith("allreduce"):
            op, post = extra
            def body(x):  # x: (1, n) local block; prescale applied by caller
                if op == _RED_SUM:
                    r = lax.psum(x, "hvd")
                elif op == _RED_MIN:
                    r = lax.pmin(x, "hvd")
                elif op == _RED_MAX:
                    r = lax.pmax(x, "hvd")
                else:  # product: gather + local reduce (no pprod in XLA)
                    r = lax.all_gather(x, "hvd")
                    import jax.numpy as jnp
                    r = jnp.prod(r, axis=0)
                if post != 1.0:
                    r = r * np.asarray(post, dtype=r.dtype)
                return r
            out_specs = P()
        elif kind == "broadcast":
            (root,) = extra
            def body(x):
                g = lax.all_gather(x, "hvd")  # (P, 1, n)
                return g[root]
            out_specs = P()
        else:  # allgather (pad-to-max done by caller)
            def body(x):
                return lax.all_gather(x, "hvd")  # (P, 1, n)
            out_specs = P()

        # Replication-check off: all_gather-then-index outputs ARE
        # replicated over 'hvd' but the inference can't prove it.
        fn = jax.jit(_shard_map(body, mesh=mesh, in_specs=P("hvd"),
                                out_specs=out_specs, check_vma=False))
        self._fn_cache[key] = fn
        return fn

    def _run_stacked(self, mesh, fn, flat_np):
        """Feed this process's (1, n) block of the global (P, n) array and
        return the replicated result as numpy."""
        jax = self._jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        n = int(flat_np.shape[0])
        nprocs = int(mesh.devices.size)
        local = jax.device_put(flat_np[None, :], self._local_device)
        glob = jax.make_array_from_single_device_arrays(
            (nprocs, n), NamedSharding(mesh, P("hvd")), [local])
        out = fn(glob)
        return np.asarray(out.addressable_data(0))

    @staticmethod
    def _identity(op, dtype):
        """Reduce-op identity for entry-less slots (joined ranks or
        handles released mid-negotiation) — zeros would corrupt
        min/max/prod, same guard as the native FillReduceIdentity
        (csrc/collectives.cc; integer dtypes use type extrema there too:
        np.inf would OverflowError on int min/max)."""
        dt = np.dtype(dtype)
        if op == _RED_MIN:
            return dt.type(np.inf) if dt.kind == "f" else np.iinfo(dt).max
        if op == _RED_MAX:
            return dt.type(-np.inf) if dt.kind == "f" else np.iinfo(dt).min
        if op == _RED_PROD:
            return dt.type(1)
        return dt.type(0)

    def _delegated_allreduce(self, d, mesh, dtype):
        sizes = d["sizes"]  # flat element count per fused tensor
        pre = float(d["prescale"])
        op = d["red_op"]
        parts = []
        for h, nelem in zip(d["handles"], sizes):
            if h >= 0:
                arr = np.ascontiguousarray(self._handle_arrays[h],
                                           dtype=dtype).reshape(-1)
                # Prescale contributed data HOST-SIDE so identity slots
                # below stay exact (the native path does the same,
                # csrc/core.cc per-entry ScaleBuffer).
                if pre != 1.0:
                    arr = arr * np.asarray(pre, dtype=dtype)
                parts.append(arr)
            else:
                parts.append(np.full(int(nelem), self._identity(op, dtype),
                                     dtype=dtype))
        flat = parts[0] if len(parts) == 1 else np.concatenate(parts)
        n = int(flat.shape[0])
        if (self._q_codec is not None and op == _RED_SUM
                and np.dtype(dtype).kind == "f"
                and n >= self._q_threshold):
            # Quantized pipeline (env policy catch-all; __init__ note).
            # Pad the power-of-two bucket up to a whole number of
            # blocks per rank; zero padding is SUM-neutral.
            from ..compression.codecs import padded_len
            pn = padded_len(_bucket(n, self.min_bucket),
                            int(mesh.devices.size), self._q_block)
            fn = self._collective(
                mesh, "qallreduce", pn, dtype,
                (op, float(d["postscale"]), self._q_codec,
                 self._q_block))
            out = self._run_stacked(mesh, fn, _pad(flat, pn, op))[0]
        else:
            fn = self._collective(
                mesh, "allreduce", _bucket(n, self.min_bucket), dtype,
                (op, float(d["postscale"])))
            out = self._run_stacked(
                mesh, fn, _pad(flat, _bucket(n, self.min_bucket), op))[0]
        off = 0
        for h, nelem in zip(d["handles"], sizes):
            nelem = int(nelem)
            if h >= 0:
                shape = self._handle_arrays[h].shape
                self.core.delegated_complete(
                    h, out[off:off + nelem].reshape(shape))
            off += nelem

    def _delegated_broadcast(self, d, mesh, dtype):
        # sizes = [count, root] (csrc/core.cc broadcast response layout).
        count, root = int(d["sizes"][0]), int(d["sizes"][1])
        h = d["handles"][0]
        if h >= 0:
            arr = np.ascontiguousarray(self._handle_arrays[h], dtype=dtype)
            shape = arr.shape
        else:
            arr = np.zeros(count, dtype=dtype)
            shape = None
        flat = arr.reshape(-1)
        fn = self._collective(mesh, "broadcast",
                              _bucket(count, self.min_bucket), dtype,
                              (root,))
        out = self._run_stacked(
            mesh, fn, _pad(flat, _bucket(count, self.min_bucket)))[0]
        if h >= 0:
            self.core.delegated_complete(h, out[:count].reshape(shape))

    def _delegated_allgather(self, d, mesh, dtype, me):
        # sizes = [rows per rank..., row_elems].
        nranks = int(mesh.devices.size)
        rows = [int(r) for r in d["sizes"][:nranks]]
        row_elems = int(d["sizes"][nranks])
        max_n = max(rows) * row_elems if rows else 0
        h = d["handles"][0]
        if h >= 0:
            arr = np.ascontiguousarray(self._handle_arrays[h], dtype=dtype)
            tail = arr.shape[1:] if arr.ndim > 0 else ()
            flat = arr.reshape(-1)
        else:
            tail = None
            flat = np.zeros(rows[me] * row_elems, dtype=dtype)
        bn = _bucket(max_n, self.min_bucket) if max_n else self.min_bucket
        padded = np.zeros(bn, dtype=dtype)
        padded[:flat.shape[0]] = flat
        fn = self._collective(mesh, "allgather", bn, dtype)
        out = self._run_stacked(mesh, fn, padded)  # (P, 1, bn)
        if h < 0:
            return
        pieces = [out[r, 0, :rows[r] * row_elems] for r in range(nranks)]
        total_rows = sum(rows)
        result = np.concatenate(pieces).reshape((total_rows,) + tail)
        self.core.delegated_complete(h, result)

    def _delegated_reducescatter(self, d, mesh, dtype, me, nranks):
        # Uneven dim-0 split (remainder to low ranks) prevents a direct
        # psum_scatter; reduce fully, then slice this rank's rows.
        h = d["handles"][0]
        if h < 0:
            # Unreachable via Join (the controller rejects join +
            # reducescatter at ConstructResponse, like the reference); only
            # a handle released mid-negotiation lands here, and the native
            # path errors identically (csrc/core.cc kReducescatter !e).
            raise HorovodInternalError("reducescatter with no local entry")
        arr = np.ascontiguousarray(self._handle_arrays[h], dtype=dtype)
        rows = arr.shape[0] if arr.ndim else 1
        row_elems = int(np.prod(arr.shape[1:])) if arr.ndim > 1 else 1
        op = d["red_op"]
        pre = float(d["prescale"])
        flat = arr.reshape(-1)
        if pre != 1.0:
            flat = flat * np.asarray(pre, dtype=dtype)
        fn = self._collective(
            mesh, "allreduce", _bucket(flat.shape[0], self.min_bucket), dtype,
            (op, float(d["postscale"])))
        out = self._run_stacked(mesh, fn,
                                _pad(flat, _bucket(flat.shape[0],
                                                   self.min_bucket), op))[0]
        base, rem = divmod(rows, nranks)
        my_rows = base + (1 if me < rem else 0)
        offset_rows = me * base + min(me, rem)
        seg = out[offset_rows * row_elems:(offset_rows + my_rows)
                  * row_elems]
        shape = (my_rows,) + arr.shape[1:]
        self.core.delegated_complete(h, seg.reshape(shape))
