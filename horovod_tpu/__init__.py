"""horovod_tpu: a TPU-native distributed training framework.

Provides the capabilities of the reference data-parallel framework
(Horovod; see SURVEY.md) re-designed for TPU: the eager data plane is
jitted XLA collectives over the ICI mesh, the compiled path is pjit/
shard_map sharding (see horovod_tpu.parallel), and the job machinery
(launcher, elastic, autotune, timeline) is re-built around TPU-VM slices.

Public API shape follows the reference's per-framework modules
(reference: horovod/torch/mpi_ops.py, horovod/common/basics.py).
"""

import time as _time
_T0 = _time.perf_counter()      # first line: what ran before is not ours

# The start-up log (docs/tracing.md "From the process's start to the
# first step"): this package's own import is its first span.
from .utils import compile_cache as _startup
_startup.before_program(_T0)

from .version import __version__  # noqa: F401

from .basics import (  # noqa: F401
    init, shutdown, is_initialized, rank, size, local_rank, local_size,
    cross_rank, cross_size, mesh, is_homogeneous, metrics_snapshot,
    mpi_enabled, mpi_built, gloo_enabled, gloo_built, nccl_built,
    ddl_built, ccl_built, cuda_built, rocm_built, xla_built,
    mpi_threads_supported,
)
from .exceptions import (  # noqa: F401
    HorovodInternalError, HostsUpdatedInterrupt, NotInitializedError,
    DuplicateNameError, StalledTensorError, SubmissionOrderError,
    CollectiveLintError, TpuHostSharedError,
)
from .ops.reduce_ops import (  # noqa: F401
    Average, Sum, Adasum, Min, Max, Product,
)
from .ops.compression import Compression  # noqa: F401
from .ops.collectives import (  # noqa: F401
    allreduce, allreduce_, allreduce_async, allreduce_async_,
    grouped_allreduce, grouped_allreduce_, grouped_allreduce_async,
    grouped_allreduce_async_,
    allgather, allgather_async, grouped_allgather, grouped_allgather_async,
    broadcast, broadcast_, broadcast_async, broadcast_async_,
    alltoall, alltoall_async,
    reducescatter, reducescatter_async, grouped_reducescatter,
    grouped_reducescatter_async,
    barrier, join, poll, synchronize,
)
from .ops.sparse import (  # noqa: F401
    SparseGradient, sparse_allreduce, sparse_allreduce_async,
)
from .process_sets import (  # noqa: F401
    ProcessSet, global_process_set, add_process_set, remove_process_set,
)
from .functions import (  # noqa: F401
    broadcast_object, broadcast_parameters, broadcast_optimizer_state,
    broadcast_variables, allgather_object,
)
from . import elastic  # noqa: F401  (hvd.elastic.run / State / ObjectState)


def __getattr__(name):
    # horovod_tpu.run(func, num_proc=N) — the reference's programmatic
    # launcher (horovod/runner/__init__.py:92 ``horovod.run``). Lazy so
    # importing the package never pulls the runner machinery.
    if name == "run":
        from .runner import run
        return run
    if name in ("analysis", "telemetry"):
        # hvd.analysis.check_fn / hvd.telemetry.counter etc. — lazy so
        # importing the package never loads the subsystem.
        # (importlib, not `from . import`: the latter resolves through
        # this very __getattr__ and recurses.)
        import importlib
        return importlib.import_module("." + name, __name__)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


def start_timeline(file_path, mark_cycles=None, jax_profiler_dir=None):
    """Start recording a Chrome-trace timeline at runtime (reference:
    horovod/common/basics.py:156 start_timeline). ``jax_profiler_dir``
    additionally captures a jax.profiler device trace alongside the host
    timeline (the TPU analog of the reference's NVTX ranges).
    ``mark_cycles`` defaults to the HVDTPU_TIMELINE_MARK_CYCLES env knob
    (hvdrun --timeline-mark-cycles) so the launcher flag applies to
    runtime-started timelines too."""
    from . import basics
    from .timeline import Timeline
    from .utils import envparse
    rt = basics.runtime()
    if rt.timeline is not None:
        rt.timeline.stop()
    if mark_cycles is None:
        mark_cycles = envparse.get_bool(envparse.TIMELINE_MARK_CYCLES)
    rt.timeline = Timeline(file_path, jax_profiler_dir=jax_profiler_dir,
                           mark_cycles=mark_cycles)
    rt.timeline.start()


def stop_timeline():
    """Stop the runtime timeline (reference: horovod/common/basics.py
    stop_timeline)."""
    from . import basics
    rt = basics.runtime()
    if rt.timeline is not None:
        rt.timeline.stop()
        rt.timeline = None


_startup.imported(__name__, _T0)
