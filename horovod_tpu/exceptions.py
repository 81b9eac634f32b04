"""Framework exceptions.

Mirrors the exception hierarchy of the reference framework
(reference: horovod/common/exceptions.py) so elastic training loops can be
written the same way: a recoverable collective failure raises
``HorovodInternalError`` and a membership change raises
``HostsUpdatedInterrupt``; both are caught by ``elastic.run``.
"""


# Process exit code a worker uses to request a fresh respawn of its slot
# (elastic exit-restart on the compiled data plane — see elastic.py).
# Defined here so the launcher/driver can import it without dragging the
# jax-importing elastic module into the supervisor process.
RESTART_EXIT_CODE = 79

# Exit code for a graceful preemption hand-off: the worker caught
# SIGTERM, persisted its last commit at a commit boundary, and left.
# The elastic driver treats this as a membership change, NOT a failure
# (no blacklist count) — see docs/fault_tolerance.md.
PREEMPT_EXIT_CODE = 83


class HorovodInternalError(RuntimeError):
    """Internal error raised when a collective routine fails.

    Recoverable via elastic mode: the training loop restores the last
    committed state and re-initializes (reference: horovod/common/elastic.py:151).
    """


class HostsUpdatedInterrupt(Exception):
    """Raised when the set of participating hosts/devices changed.

    In elastic mode the driver notifies workers of host-set changes; the
    worker raises this at the next commit/state-check boundary
    (reference: horovod/common/exceptions.py, horovod/common/elastic.py:57).
    """

    def __init__(self, skip_sync=False):
        super().__init__()
        self.skip_sync = skip_sync


class HorovodVersionMismatchError(ImportError):
    """Library/extension version mismatch (reference: horovod/common/exceptions.py)."""


class TpuHostSharedError(RuntimeError):
    """Several launcher-spawned processes on one host were about to open
    the TPU. A chip belongs to one process and the launcher pins no chip
    to a worker, so all but one of them would die on libtpu's lockfile
    or hang in client creation."""


class NotInitializedError(RuntimeError):
    """An API that requires ``init()`` was called before initialization."""

    def __init__(self, what="Collective operations"):
        super().__init__(
            f"{what} called before init(); call horovod_tpu.init() first.")


class DuplicateNameError(ValueError):
    """Two in-flight tensors share a name within one process set.

    Matches the reference's DUPLICATE_NAME_ERROR surfaced by the tensor queue
    (reference: horovod/common/common.h:229, tensor_queue.cc).
    """


class StalledTensorError(RuntimeError):
    """A named tensor was submitted by some ranks but not all within the stall
    timeout (reference: horovod/common/stall_inspector.cc:26)."""


class CollectiveAbortError(HorovodInternalError):
    """The stuck-collective watchdog aborted every in-flight operation
    after ``HVDTPU_COLLECTIVE_TIMEOUT`` (guardian.py; the enforcement
    analog of the reference's stall inspector + STALL_SHUTDOWN_TIME,
    horovod/common/stall_inspector.cc). The message carries the
    watchdog's diagnostic — which ops stalled and which ranks never
    submitted them. A ``HorovodInternalError`` on purpose: under
    elastic the abort converts into a restore-and-reset instead of an
    eternal hang or a job death."""


class CollectiveMismatchError(RuntimeError):
    """Ranks submitted the same named collective with divergent metadata
    (kind, op, dtype, shapes, process set, or scale factors), detected
    by the pre-dispatch consistency check (``HVDTPU_CONSISTENCY_CHECK``;
    guardian.py — the analog of the reference controller's message-table
    mismatch errors, horovod/common/controller.cc).

    Deliberately NOT a ``HorovodInternalError``: like
    ``SubmissionOrderError``, the divergence is a deterministic program
    bug — the elastic restore/retry loop must surface it instead of
    retrying into the same mismatch forever. ``self.divergences`` holds
    ``(rank, field, theirs, ours)`` tuples."""

    def __init__(self, message, divergences=()):
        super().__init__(message)
        self.divergences = list(divergences)


class CheckpointCorruptError(RuntimeError):
    """A checkpoint file failed its integrity check (truncated payload,
    checksum mismatch, or foreign format) and no intact fallback was
    available (checkpoint.py; docs/fault_tolerance.md)."""


class SubmissionOrderError(RuntimeError):
    """Ranks submitted collectives in divergent orders (or with divergent
    auto-generated names), detected by the opt-in runtime order guard
    (``HOROVOD_TPU_ORDER_CHECK=1``; analysis/order_guard.py). The static
    analog is hvd-lint rule HVD203.

    Deliberately NOT a ``HorovodInternalError``: the divergence is a
    deterministic program bug, so the elastic restore/retry loop (which
    catches internal errors as recoverable) must surface it instead of
    retrying into the same divergence forever."""


class LockOrderError(RuntimeError):
    """hvd-sanitize detected a lock-acquisition-order cycle: acquiring
    this lock while holding another reverses an order recorded earlier
    in the process, so two threads interleaving the two paths can
    deadlock (ABBA). The message carries BOTH acquisition stacks — the
    current one and the first recorded reverse-order one
    (``HVDTPU_SANITIZE``; analysis/sanitizer.py, docs/lint.md).

    Deliberately NOT a ``HorovodInternalError``: like
    ``SubmissionOrderError``, a lock-order inversion is a deterministic
    program bug — elastic retry would deadlock (or trip) again."""


class ChaosInjectedError(RuntimeError):
    """A chaos ``fail`` injection fired at a point with no more specific
    error type (``HVDTPU_CHAOS``; docs/fault_tolerance.md). KV points
    raise transport errors and collective points raise
    ``HorovodInternalError`` instead, so recovery paths see exactly the
    exceptions real faults produce."""


class CollectiveLintError(ValueError):
    """Static analysis (hvd-lint) found error-severity collective hazards
    and ``verify=`` asked for enforcement. ``self.diagnostics`` carries
    the structured findings (analysis/diagnostics.py)."""

    def __init__(self, diagnostics):
        self.diagnostics = list(diagnostics)
        lines = "\n".join(d.format() for d in self.diagnostics)
        super().__init__(
            f"hvd-lint found {len(self.diagnostics)} collective-"
            f"correctness finding(s):\n{lines}")
