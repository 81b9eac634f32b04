"""Runtime singleton: topology discovery, mesh construction, init/shutdown.

Design (TPU-first rethink of the reference's HorovodGlobalState +
InitializeHorovodOnce, reference: horovod/common/operations.cc:811,
horovod/common/global_state.h):

The reference runs one process per accelerator and negotiates collectives
between processes over MPI/Gloo. On TPU the natural unit is a **device mesh**
driven by one controller process per host (or one for the whole slice), with
collectives compiled by XLA onto ICI. This runtime therefore supports two
execution modes:

- ``single`` (single-controller): one Python process owns all visible TPU
  chips. Every chip is a *virtual rank*: ``size()`` is the chip count, eager
  collectives operate on arrays stacked along a leading virtual-rank axis and
  lower to one jitted XLA collective over the 1-D replica mesh. This is the
  primary TPU path — the data plane is entirely compiled, the coordination
  machinery only batches and orders work.

- ``spmd`` (launcher-spawned): N processes, Horovod-identical semantics.
  ``rank()``/``size()`` come from launcher env vars (analog of
  HOROVOD_RANK/SIZE, reference: horovod/runner/gloo_run.py:65-77), and the
  eager data plane runs over the TCP backend (CPU fallback, gloo-analog) or
  the global XLA backend (multi-host TPU via jax.distributed).
"""

import atexit
import threading
import time

import jax
import numpy as np

from .exceptions import NotInitializedError, TpuHostSharedError
from .utils import envparse
from .utils.logging_util import get_logger

MODE_SINGLE = "single"
MODE_SPMD = "spmd"


class Topology:
    """Process-level topology (reference: rank/size/local/cross getters,
    horovod/common/basics.py:183-264)."""

    def __init__(self, rank, size, local_rank, local_size, cross_rank,
                 cross_size):
        self.rank = rank
        self.size = size
        self.local_rank = local_rank
        self.local_size = local_size
        self.cross_rank = cross_rank
        self.cross_size = cross_size

    @classmethod
    def from_env(cls):
        rank = envparse.get_int(envparse.RANK, 0)
        size = envparse.get_int(envparse.SIZE, 1)
        local_rank = envparse.get_int(envparse.LOCAL_RANK, rank)
        local_size = envparse.get_int(envparse.LOCAL_SIZE, size)
        cross_rank = envparse.get_int(envparse.CROSS_RANK, 0)
        cross_size = envparse.get_int(envparse.CROSS_SIZE, 1)
        return cls(rank, size, local_rank, local_size, cross_rank, cross_size)


class Runtime:
    """Owns topology, mesh, backend, coordinator and process-set table."""

    def __init__(self, mode, topology, backend, mesh, devices):
        self.mode = mode
        self.topology = topology
        self.backend = backend
        self.mesh = mesh            # 1-D jax Mesh over the replica axis 'hvd'
        self.devices = devices      # list of jax devices backing the mesh
        self.process_set_table = None   # attached by process_sets._setup
        self.coordinator = None         # attached by coordinator.start
        self.timeline = None            # attached by timeline module on demand
        self.autotuner = None
        self.metrics_pusher = None      # telemetry.MetricsPusher (SPMD)
        self.tracer = None              # tracing.Tracer (set by Coordinator)
        self._shutdown = False

    @property
    def size(self):
        if self.mode == MODE_SINGLE:
            return len(self.devices)
        return self.topology.size

    @property
    def rank(self):
        return self.topology.rank

    def check_alive(self):
        if self._shutdown:
            raise NotInitializedError("Runtime was shut down; operations")


_runtime = None
_init_lock = threading.Lock()


def _select_devices():
    """All addressable devices form the replica mesh."""
    return list(jax.local_devices())


def _make_replica_mesh(devices):
    return jax.sharding.Mesh(np.array(devices), ("hvd",))


def _refuse_shared_tpu_host(local_size, platforms):
    """Raise before the backend is touched when ``local_size`` launcher
    processes on this host would all open the TPU (``platforms`` is the
    resolved ``jax_platforms`` list). Seen on a v5e host with hvdrun
    -np 2: one worker dies on libtpu's multi-process lockfile, the other
    hangs in TPU client creation until the launcher kills it."""
    if local_size > 1 and "tpu" in (platforms or "").split(","):
        raise TpuHostSharedError(
            f"{local_size} launcher processes on this host would each "
            f"open the TPU (JAX_PLATFORMS={platforms!r}), and a chip "
            f"belongs to one process. Run the script without the "
            f"launcher (single-controller mode drives every local chip "
            f"from one process), one process per TPU host, or with "
            f"JAX_PLATFORMS=cpu for a host-plane job.")


def init(comm=None, process_sets=None):
    """Initialize the runtime (idempotent; reference: horovod_init,
    horovod/common/operations.cc:889).

    Args:
      comm: ignored (MPI communicators do not exist on TPU); accepted for
        signature compatibility with the reference.
      process_sets: optional list of ProcessSet objects to materialize at
        startup (reference: horovod/common/basics.py:48 ``init`` takes
        process_sets).
    """
    global _runtime
    with _init_lock:
        if _runtime is not None and not _runtime._shutdown:
            # Re-sync process sets like the reference's re-init path.
            from . import process_sets as ps_mod
            ps_mod._setup(_runtime, process_sets or [])
            return _runtime

        started = time.perf_counter()
        from .utils import compile_cache
        compile_cache.listen()

        # Fresh runtime: auto-name counters restart with it so ranks
        # that re-init (elastic restart) agree on generated names.
        from .ops.collectives import reset_auto_name_counters
        reset_auto_name_counters()

        log = get_logger()
        if envparse.get_bool(envparse.ELASTIC):
            # Elastic workers are spawned WITHOUT rank env: ranks come from
            # the driver's latest membership version via the rendezvous
            # store, so a re-init after a reset lands in the new cohort
            # (reference: horovod/runner/elastic/rendezvous.py:28-60).
            from .runner import rendezvous as rdv
            if rdv.rendezvous_config() is not None:
                rdv.elastic_bootstrap()
                # Liveness lease: one background beat thread for the
                # whole process lifetime (re-inits must not stop it — a
                # worker mid-reset is alive; docs/fault_tolerance.md).
                from .runner import heartbeat
                heartbeat.start_worker_heartbeat()
        topology = Topology.from_env()
        spmd = (envparse.get_env(envparse.SIZE) is not None
                and envparse.get_env(envparse.RANK) is not None)
        if spmd and (topology.size < 1
                     or not 0 <= topology.rank < topology.size):
            raise ValueError(
                f"Invalid launcher topology: rank={topology.rank} "
                f"size={topology.size}")

        if spmd:
            _refuse_shared_tpu_host(topology.local_size,
                                    jax.config.jax_platforms)
            from .backend import make_spmd_backend
            backend = make_spmd_backend(topology)
            devices = _select_devices()
            mesh = _make_replica_mesh(devices[:1])
            runtime = Runtime(MODE_SPMD, topology, backend, mesh, devices)
            log.info("init: spmd mode rank=%d size=%d backend=%s",
                     topology.rank, topology.size, backend.name)
        else:
            from .backend.xla_backend import XlaSingleBackend
            devices = _select_devices()
            mesh = _make_replica_mesh(devices)
            backend = XlaSingleBackend(mesh)
            runtime = Runtime(MODE_SINGLE, topology, backend, mesh, devices)
            log.info("init: single-controller mode, %d device(s) on mesh",
                     len(devices))

        from . import process_sets as ps_mod
        ps_mod._setup(runtime, process_sets or [])

        from .coordinator import Coordinator
        runtime.coordinator = Coordinator(runtime)
        runtime.coordinator.start()

        if envparse.get_bool(envparse.AUTOTUNE):
            from .autotune import ParameterManager
            runtime.autotuner = ParameterManager(runtime)
        else:
            # Tuned overlay values deliberately survive elastic
            # re-inits (the new cohort's tuner re-validates them), but
            # an init WITHOUT a tuner has nothing to re-validate: drop
            # them so a stale tuned value from an earlier job in this
            # process can't shadow the explicit env knobs. sys.modules
            # guard keeps the disabled path import-free.
            import sys as _sys
            overlay_mod = _sys.modules.get(
                "horovod_tpu.autotune.overlay")
            if overlay_mod is not None and overlay_mod.snapshot():
                overlay_mod.clear()

        timeline_path = envparse.get_str(envparse.TIMELINE, "")
        if timeline_path:
            from .timeline import Timeline
            runtime.timeline = Timeline(
                timeline_path,
                mark_cycles=envparse.get_bool(
                    envparse.TIMELINE_MARK_CYCLES))
            runtime.timeline.start()

        # The host while the step runs (docs/tracing.md): the pulse and
        # the collector's hook, after the timeline that follows their
        # spans. An elastic reset's init finds them running.
        from .utils import pulse
        pulse.start()

        # Metrics plane (docs/metrics.md): when the job has a launcher
        # rendezvous, push this rank's snapshot to the driver KV store
        # on a timer so its /metrics route can serve the cluster roll-up.
        if envparse.get_bool(envparse.METRICS):
            from .runner import rendezvous as rdv
            from .telemetry import MetricsPusher
            cfg = rdv.rendezvous_config()
            if cfg is not None:
                addr, port, token = cfg
                runtime.metrics_pusher = MetricsPusher(
                    addr, port, token, topology.rank,
                    interval_s=envparse.get_float(
                        envparse.METRICS_PUSH_INTERVAL, 5.0)).start()

        # The start-up log (docs/tracing.md): this init is one span, and
        # the first of a process says where the time before it went.
        first = not compile_cache.startup_seconds()["init"]
        compile_cache.record("init", compile_cache.PACKAGE, started,
                             time.perf_counter())
        if first:
            log.info("init: start-up so far: %s", ", ".join(
                f"{stage} {seconds:.2f} s" for stage, seconds
                in compile_cache.startup_seconds().items() if seconds))

        _runtime = runtime
        return _runtime


def shutdown():
    """Tear down the runtime (reference: horovod_shutdown,
    horovod/common/operations.cc)."""
    global _runtime
    with _init_lock:
        if _runtime is None:
            return
        if _runtime.coordinator is not None:
            _runtime.coordinator.stop()
        # Before the timeline, which writes the spans it hands over last.
        from .utils import pulse
        pulse.stop()
        if _runtime.timeline is not None:
            _runtime.timeline.stop()
        if _runtime.tracer is not None:
            # Flush + close this cohort's trace shard and push it to the
            # driver KV store (docs/tracing.md); an elastic re-init then
            # opens a fresh shard under the new membership version.
            _runtime.tracer.close()
            from . import tracing
            if tracing.active() is _runtime.tracer:
                tracing._set_active(None)
            _runtime.tracer = None
        if _runtime.metrics_pusher is not None:
            # Final push so shutdown-time counters (elastic restarts)
            # reach the driver before the store loses this rank.
            _runtime.metrics_pusher.stop()
            _runtime.metrics_pusher = None
        _maybe_dump_metrics()
        if _runtime.backend is not None:
            _runtime.backend.close()
        from . import process_sets as ps_mod
        ps_mod._teardown(_runtime)
        _runtime._shutdown = True
        _runtime = None
        # hvd-sanitize thread-leak audit (no-op when HVDTPU_SANITIZE is
        # off): name every non-daemon thread that survived teardown —
        # each one keeps the interpreter from exiting.
        from .analysis import sanitizer
        sanitizer.audit_shutdown()


def _maybe_dump_metrics():
    """Write a final JSON snapshot to HVDTPU_METRICS_DUMP (if set) —
    the file `hvd-metrics diff` consumes."""
    path = envparse.get_str(envparse.METRICS_DUMP, "")
    if not path or not envparse.get_bool(envparse.METRICS):
        return
    from . import telemetry
    try:
        with open(path, "w") as f:
            f.write(telemetry.render_json(metrics_snapshot(), indent=1))
    except OSError as exc:
        get_logger().warning("could not write metrics dump %s: %s",
                             path, exc)


def metrics_snapshot():
    """JSON-able snapshot of the metrics registry (docs/metrics.md),
    with rank/size/mode context when the runtime is up. Families are
    empty unless HOROVOD_TPU_METRICS is on."""
    from . import telemetry
    snap = telemetry.snapshot()
    if _runtime is not None and not _runtime._shutdown:
        snap["rank"] = _runtime.topology.rank
        snap["size"] = _runtime.size
        snap["mode"] = _runtime.mode
    return snap


atexit.register(shutdown)


def is_initialized():
    return _runtime is not None and not _runtime._shutdown


def runtime():
    if _runtime is None or _runtime._shutdown:
        raise NotInitializedError()
    return _runtime


def rank():
    return runtime().topology.rank


def size():
    return runtime().size


def local_rank():
    return runtime().topology.local_rank


def local_size():
    rt = runtime()
    if rt.mode == MODE_SINGLE:
        return len(rt.devices)
    return rt.topology.local_size


def cross_rank():
    return runtime().topology.cross_rank


def cross_size():
    return runtime().topology.cross_size


def mesh():
    """The 1-D replica mesh (axis name 'hvd') for in-jit collectives."""
    return runtime().mesh


def is_homogeneous():
    """True when every host has the same number of slots (reference:
    horovod_is_homogeneous, horovod/common/operations.cc)."""
    rt = runtime()
    if rt.mode == MODE_SINGLE:
        return True
    return rt.topology.size == rt.topology.local_size * rt.topology.cross_size


# Build-feature queries: kept for API parity with the reference
# (horovod/torch/mpi_ops.py:55-63). On TPU the data plane is XLA.
def mpi_enabled():
    return False


def mpi_built():
    return False


def gloo_enabled():
    return gloo_built()


def gloo_built():
    # Our TCP backend is the gloo-analog CPU data plane; report it built
    # only if the module actually imports.
    try:
        from .backend import tcp_backend  # noqa: F401
        return True
    except ImportError:
        return False


def nccl_built():
    return False


def ddl_built():
    return False


def ccl_built():
    return False


def cuda_built():
    return False


def rocm_built():
    return False


def xla_built():
    return True


def mpi_threads_supported():
    return False
