"""ctypes binding to the native C++ core runtime (csrc/ -> libhvdcore.so).

The reference loads its C++ runtime the same way — a shared library exposing
a flat C API consumed via ctypes (reference: horovod/common/basics.py:48
loads the per-framework mpi_lib and calls horovod_init/...). Our native core
owns the host-side machinery for multi-process SPMD jobs: coordinator/worker
negotiation with a bitvector-coordinated response cache, allreduce fusion,
the CPU ring-collective data plane over TCP, the chrome-trace timeline, and
the stall inspector (see csrc/*.cc for the component map).

The library is built lazily with ``make`` on first import if missing or
stale — the build environment always carries g++ (no wheels to ship).
"""

import ctypes
import os
import subprocess
import threading

import numpy as np

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_PKG_DIR, "libhvdcore.so")
_CSRC_DIR = os.path.join(os.path.dirname(os.path.dirname(_PKG_DIR)), "csrc")

_lib = None
_lib_lock = threading.RLock()

# Enum values must match csrc/common.h.
REQ_ALLREDUCE, REQ_ALLGATHER, REQ_BROADCAST, REQ_ALLTOALL = 0, 1, 2, 3
REQ_REDUCESCATTER, REQ_BARRIER, REQ_JOIN = 4, 5, 6
RED_SUM, RED_MIN, RED_MAX, RED_PROD, RED_ADASUM = 0, 1, 2, 3, 4

_DTYPE_TO_ENUM = {}


def _dtype_table():
    global _DTYPE_TO_ENUM
    if _DTYPE_TO_ENUM:
        return _DTYPE_TO_ENUM
    table = {
        np.dtype(np.uint8): 0,
        np.dtype(np.int8): 1,
        np.dtype(np.int32): 2,
        np.dtype(np.int64): 3,
        np.dtype(np.float16): 4,
        np.dtype(np.float32): 5,
        np.dtype(np.float64): 6,
        np.dtype(np.bool_): 7,
    }
    try:
        import ml_dtypes
        table[np.dtype(ml_dtypes.bfloat16)] = 8
    except ImportError:
        pass
    _DTYPE_TO_ENUM = table
    return table


def _build_library():
    if not os.path.isdir(_CSRC_DIR):
        raise ImportError(
            f"libhvdcore.so missing at {_LIB_PATH} and no csrc/ tree to "
            "build it from")
    subprocess.run(["make", "-s", "all"], cwd=_CSRC_DIR, check=True)


def _stale():
    if not os.path.exists(_LIB_PATH):
        return True
    if not os.path.isdir(_CSRC_DIR):
        return False
    lib_mtime = os.path.getmtime(_LIB_PATH)
    for f in os.listdir(_CSRC_DIR):
        if f.endswith((".cc", ".h")) and not f.startswith("test_"):
            if os.path.getmtime(os.path.join(_CSRC_DIR, f)) > lib_mtime:
                return True
    return False


def ensure_built():
    """Build the library if it is missing or older than csrc/. The
    launcher calls this once before it spawns workers: the lock below
    is per process, so N workers starting from a clean tree would
    otherwise each run ``make`` in csrc/ at once."""
    with _lib_lock:
        if _stale():
            _build_library()


def load_library():
    """Load (building if needed) the native core library."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        ensure_built()
        lib = ctypes.CDLL(_LIB_PATH)

        lib.hvd_core_create.restype = ctypes.c_void_p
        lib.hvd_core_create.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_double, ctypes.c_char_p,
            ctypes.c_int, ctypes.c_double]
        lib.hvd_core_destroy.argtypes = [ctypes.c_void_p]
        lib.hvd_reserve_listen_port.restype = ctypes.c_int
        lib.hvd_reserve_listen_port.argtypes = []
        lib.hvd_core_rank.argtypes = [ctypes.c_void_p]
        lib.hvd_core_size.argtypes = [ctypes.c_void_p]
        lib.hvd_core_add_process_set.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int), ctypes.c_int]
        lib.hvd_core_remove_process_set.argtypes = [
            ctypes.c_void_p, ctypes.c_int]
        lib.hvd_core_enqueue.restype = ctypes.c_int64
        lib.hvd_core_enqueue.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.c_double,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int]
        lib.hvd_core_run_cycle.argtypes = [ctypes.c_void_p]
        lib.hvd_core_request_shutdown.argtypes = [ctypes.c_void_p]
        lib.hvd_core_shutdown_complete.argtypes = [ctypes.c_void_p]
        lib.hvd_core_poll.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.hvd_core_wait.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_double]
        lib.hvd_core_handle_error.restype = ctypes.c_char_p
        lib.hvd_core_handle_error.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.hvd_core_output_ndim.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.hvd_core_output_shape.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int64)]
        lib.hvd_core_output_nbytes.restype = ctypes.c_int64
        lib.hvd_core_output_nbytes.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.hvd_core_output_copy.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64]
        lib.hvd_core_recv_splits.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int]
        lib.hvd_core_release.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.hvd_core_cycles.restype = ctypes.c_uint64
        lib.hvd_core_cycles.argtypes = [ctypes.c_void_p]
        lib.hvd_core_bytes_processed.restype = ctypes.c_uint64
        lib.hvd_core_bytes_processed.argtypes = [ctypes.c_void_p]
        lib.hvd_core_set_fusion_threshold.argtypes = [
            ctypes.c_void_p, ctypes.c_int64]
        lib.hvd_core_set_topology.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
            ctypes.c_int64]
        lib.hvd_core_next_delegated.restype = ctypes.c_int64
        lib.hvd_core_next_delegated.argtypes = [ctypes.c_void_p]
        lib.hvd_core_delegated_info.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]
        lib.hvd_core_delegated_meta.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
        lib.hvd_core_delegated_complete.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.c_char_p]
        lib.hvd_core_delegated_finish.argtypes = [
            ctypes.c_void_p, ctypes.c_int64]
        _lib = lib
        return lib


def reserve_listen_port():
    """Bind + listen an ephemeral TCP port inside the native core and keep
    it open; the next NativeCore whose peers entry names this port adopts
    the socket. Closes the publish-then-rebind rendezvous race."""
    port = load_library().hvd_reserve_listen_port()
    if port <= 0:
        raise OSError("could not reserve a listen port")
    return port


class NativeError(RuntimeError):
    pass


_ENQUEUE_ERRORS = {
    -1: "duplicate tensor name in flight",
    -2: "invalid arguments (shape/dtype/byte count mismatch)",
    -3: "runtime shut down",
    -4: "this rank is not a member of the process set",
}


class NativeCore:
    """One native runtime context (= one rank of an SPMD job).

    transport 'tcp' with peers "host:port,..." for real multi-process jobs;
    'local' with a job-name string for in-process multi-rank tests.
    """

    def __init__(self, rank, size, transport="tcp", peers="",
                 fusion_threshold=0, cache_capacity=0, stall_warning_s=0.0,
                 timeline_path="", delegate_data_ops=False,
                 stall_shutdown_s=0.0):
        self._lib = load_library()
        self._ctx = self._lib.hvd_core_create(
            rank, size, transport.encode(), peers.encode(),
            int(fusion_threshold), int(cache_capacity),
            float(stall_warning_s), timeline_path.encode(),
            1 if delegate_data_ops else 0, float(stall_shutdown_s))
        if not self._ctx:
            raise NativeError(
                f"native core init failed (rank {rank}/{size}, transport "
                f"{transport}) — see stderr for details")
        self.rank = rank
        self.size = size

    # -- lifecycle --------------------------------------------------------
    def close(self):
        if self._ctx:
            self._lib.hvd_core_destroy(self._ctx)
            self._ctx = None

    def request_shutdown(self):
        self._lib.hvd_core_request_shutdown(self._ctx)

    def shutdown_complete(self):
        return bool(self._lib.hvd_core_shutdown_complete(self._ctx))

    # -- process sets -----------------------------------------------------
    def add_process_set(self, ranks):
        arr = (ctypes.c_int * len(ranks))(*ranks)
        ps = self._lib.hvd_core_add_process_set(self._ctx, arr, len(ranks))
        if ps < 0:
            raise NativeError("add_process_set failed")
        return ps

    def remove_process_set(self, ps_id):
        return self._lib.hvd_core_remove_process_set(self._ctx, ps_id) == 0

    # -- submission -------------------------------------------------------
    def enqueue(self, ps_id, name, req_type, array=None, red_op=RED_SUM,
                root_rank=-1, prescale=1.0, postscale=1.0, splits=None):
        data_ptr, shape_arr, ndim = None, None, 0
        if array is not None:
            array = np.ascontiguousarray(array)
            dt = _dtype_table().get(array.dtype)
            if dt is None:
                raise NativeError(
                    f"dtype {array.dtype} unsupported by the native core")
            shape = array.shape
            shape_arr = (ctypes.c_int64 * len(shape))(*shape)
            ndim = len(shape)
            data_ptr = array.ctypes.data_as(ctypes.c_void_p)
        else:
            dt = 0
        splits_arr, nsplits = None, 0
        if splits is not None:
            splits = np.ascontiguousarray(splits, dtype=np.int32)
            splits_arr = splits.ctypes.data_as(
                ctypes.POINTER(ctypes.c_int32))
            nsplits = len(splits)
        h = self._lib.hvd_core_enqueue(
            self._ctx, ps_id, name.encode(), req_type, red_op, dt, data_ptr,
            shape_arr, ndim, root_rank, prescale, postscale, splits_arr,
            nsplits)
        if h < 0:
            raise NativeError(
                f"enqueue {name!r}: "
                f"{_ENQUEUE_ERRORS.get(h, f'error {h}')}")
        # Keep the input alive until the cycle copies it (the C side copies
        # at enqueue, synchronously — nothing to hold after return).
        return h

    # -- cycle / completion ----------------------------------------------
    def run_cycle(self):
        """One negotiation+execution cycle (blocking, releases the GIL)."""
        return self._lib.hvd_core_run_cycle(self._ctx)

    def poll(self, handle):
        return self._lib.hvd_core_poll(self._ctx, handle)

    def wait(self, handle, timeout_s=300.0):
        if self._lib.hvd_core_wait(self._ctx, handle, timeout_s) != 0:
            raise NativeError(f"wait on handle {handle} timed out")

    def error(self, handle):
        e = self._lib.hvd_core_handle_error(self._ctx, handle)
        return e.decode() if e else ""

    def output(self, handle, dtype):
        """Copy out the completed handle's output as a numpy array."""
        ndim = self._lib.hvd_core_output_ndim(self._ctx, handle)
        if ndim < 0:
            raise NativeError(f"unknown handle {handle}")
        shape_arr = (ctypes.c_int64 * max(ndim, 1))()
        self._lib.hvd_core_output_shape(self._ctx, handle, shape_arr)
        shape = tuple(shape_arr[i] for i in range(ndim))
        nbytes = self._lib.hvd_core_output_nbytes(self._ctx, handle)
        out = np.empty(shape, dtype=dtype)
        if out.nbytes != nbytes:
            # Shapeless payloads (e.g. join's int32) come back flat.
            out = np.empty(nbytes // np.dtype(dtype).itemsize, dtype=dtype)
        if nbytes > 0:
            rc = self._lib.hvd_core_output_copy(
                self._ctx, handle, out.ctypes.data_as(ctypes.c_void_p),
                out.nbytes)
            if rc != 0:
                raise NativeError("output copy failed")
        return out

    def recv_splits(self, handle):
        arr = (ctypes.c_int32 * self.size)()
        n = self._lib.hvd_core_recv_splits(self._ctx, handle, arr, self.size)
        if n < 0:
            raise NativeError("recv_splits failed")
        return np.array([arr[i] for i in range(n)], dtype=np.int32)

    def release(self, handle):
        self._lib.hvd_core_release(self._ctx, handle)

    # -- stats ------------------------------------------------------------
    def set_fusion_threshold(self, nbytes):
        """Apply an autotuned fusion threshold (all ranks must call with
        the same value at the same cycle boundary)."""
        self._lib.hvd_core_set_fusion_threshold(self._ctx, int(nbytes))

    def set_topology(self, host_of, threshold):
        """Host map for hierarchical collectives: host_of[r] = host index
        of global rank r; buffers >= threshold bytes take the two-level
        (intra-host reduce-scatter / cross-host ring / intra-host
        allgather) allreduce. threshold 0 disables."""
        arr = (ctypes.c_int32 * len(host_of))(*host_of)
        self._lib.hvd_core_set_topology(self._ctx, arr, len(host_of),
                                        int(threshold))

    # -- delegated execution (external XLA data plane) --------------------
    def next_delegated(self):
        """Token of the next negotiated-but-externally-executed response,
        or 0 when none is pending."""
        return int(self._lib.hvd_core_next_delegated(self._ctx))

    def delegated(self, token):
        """Fetch a delegated response descriptor as a dict."""
        ps_id = ctypes.c_int32()
        rtype = ctypes.c_int32()
        dtype = ctypes.c_int32()
        red_op = ctypes.c_int32()
        pre = ctypes.c_double()
        post = ctypes.c_double()
        nt = ctypes.c_int32()
        ns = ctypes.c_int32()
        rc = self._lib.hvd_core_delegated_info(
            self._ctx, token, ctypes.byref(ps_id), ctypes.byref(rtype),
            ctypes.byref(dtype), ctypes.byref(red_op), ctypes.byref(pre),
            ctypes.byref(post), ctypes.byref(nt), ctypes.byref(ns))
        if rc != 0:
            raise NativeError(f"bad delegated token {token}")
        handles = (ctypes.c_int64 * max(1, nt.value))()
        sizes = (ctypes.c_int64 * max(1, ns.value))()
        self._lib.hvd_core_delegated_meta(self._ctx, token, handles, sizes)
        return {
            "token": token,
            "ps_id": ps_id.value,
            "type": rtype.value,
            "dtype": dtype.value,
            "red_op": red_op.value,
            "prescale": pre.value,
            "postscale": post.value,
            "handles": list(handles[:nt.value]),
            "sizes": list(sizes[:ns.value]),
        }

    def delegated_complete(self, handle, array=None, error=""):
        """Write the externally computed result (C-contiguous numpy array)
        into the native entry, or fail it with ``error``."""
        if error:
            self._lib.hvd_core_delegated_complete(
                self._ctx, handle, None, 0, None, 0, error.encode())
            return
        arr = np.ascontiguousarray(array)
        shape = (ctypes.c_int64 * max(1, arr.ndim))(*arr.shape)
        self._lib.hvd_core_delegated_complete(
            self._ctx, handle, arr.ctypes.data_as(ctypes.c_void_p),
            arr.nbytes, shape, arr.ndim, b"")

    def delegated_finish(self, token):
        self._lib.hvd_core_delegated_finish(self._ctx, token)

    def cycles(self):
        return self._lib.hvd_core_cycles(self._ctx)

    def bytes_processed(self):
        return self._lib.hvd_core_bytes_processed(self._ctx)
