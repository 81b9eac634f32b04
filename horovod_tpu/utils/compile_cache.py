"""JAX's persistent compilation cache at a path that does not move,
and the program's log: what ran from the process's start to the first
step, each span under the program or package it belonged to, and after
it the pauses of the host.

The cache directory is part of the cache key, so it must be the same in
every process of a checkout: where ``JAX_COMPILATION_CACHE_DIR`` is set
JAX reads it and this module sets nothing; otherwise the cache lives in
``<checkout>/.jax_cache`` (gitignored), derived from this file's path.

The log (docs/tracing.md "From the process's start to the first step"
and "The host while the step runs") is one list of spans ``(name,
owner, start, end)`` on ``time.perf_counter()``, the timeline's clock:

- ``before_program``: from the process's start (the kernel's record) to
  the first line of ``horovod_tpu/__init__.py``; owner ``horovod_tpu``.
- ``import``: first to last line of a package's ``__init__.py``; the
  owner is the package.
- ``init``: a ``hvd.init()`` that made a runtime; owner ``horovod_tpu``.
- ``trace``, ``lower``, ``backend_compile``: JAX's compile pipeline,
  from ``jax.monitoring`` listeners that ``enable()`` or ``hvd.init()``
  registers, once a process. The owner is the program: the function's
  name as JAX sends it, ``jit(...)`` taken off, so the compiled step's
  three spans are owned by ``STEP_NAME``. A span arrives when it ends
  and ends at its arrival; it starts its seconds before.
- ``cache_load`` (the seconds of a retrieval), ``cache_hit``,
  ``cache_miss`` (instants): JAX sends them without a name, inside a
  backend compilation; they take that span's owner when it arrives and
  have owner None until then.
- ``host_pause`` (owner ``pulse``) and ``gc`` (owner ``gen0`` /
  ``gen1`` / ``gen2``): ``utils/pulse.py``'s, a wake-up of the pulse
  that came late and a collection of a millisecond or more. They are the
  entries that arrive in the steady state, so the log keeps the newest
  ``STEADY_KEPT`` of them; what it keeps of the rest has no bound
  (ROADMAP D11).

``events()`` is the older view of the same list: ``(phase, value,
perf_counter)`` of the compile pipeline's entries. An entry is one list
append and arrives only when something is imported, initialised or
compiled, or when the host stood still: a steady step that nothing
interrupts leaves none (what the pulse itself costs is in
``utils/pulse.py``). With ``HOROVOD_TPU_METRICS`` on, the same
arrivals feed ``hvd_startup_seconds{stage}``,
``hvd_compile_seconds{phase}``, ``hvd_compile_cache_hits_total`` and
``hvd_compile_cache_misses_total`` (docs/metrics.md): a recompile after
warm-up is a counter that moves, and the log says which program it was.
"""

import collections
import os
import threading
import time

import jax

from .. import telemetry

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PACKAGE = __name__.split(".")[0]

# The name ``make_train_step`` gives the jitted step
# (``horovod_tpu.jax.STEP_NAME``), and so the owner of its spans.
STEP_NAME = "hvd_train_step"

# JAX's event -> the phase it is logged under.
_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
# JAX's event -> the instant it is logged as, and the counter it moves.
_INSTANTS = {
    _HIT: ("cache_hit", "hvd_compile_cache_hits_total",
           "Executables loaded from the persistent cache"),
    _MISS: ("cache_miss", "hvd_compile_cache_misses_total",
            "Executables compiled and written to the persistent cache"),
}

# The stages of ``hvd_startup_seconds{stage}``: the spans that are not
# the compile pipeline's under their own name, the step's pipeline by
# phase, every other program's together.
_STEP_STAGES = {"trace": "step_trace", "lower": "step_lower",
                "backend_compile": "step_backend"}
STAGES = ("before_program", "import", "init", *_STEP_STAGES.values(),
          "other_programs")

# The spans that arrive in the steady state, and how many are kept.
STEADY = ("host_pause", "gc")
STEADY_KEPT = 1024


class _Union:
    """Seconds under spans that arrive in the order they end, a span
    inside another counted once: disjoint ``(start, end)`` parts,
    ascending."""

    def __init__(self):
        self.parts = []
        self.seconds = 0.0

    def cut(self, at):
        """Forget what lies after ``at``."""
        parts = self.parts
        while parts and parts[-1][1] > at:
            start, end = parts.pop()
            self.seconds -= end - start
            if start < at:
                parts.append((start, at))
                self.seconds += at - start
                break

    def add(self, start, end):
        if self.parts:      # two threads: a span may end before the last
            end = max(end, self.parts[-1][1])
        self.cut(start)
        self.parts.append((start, end))
        self.seconds += end - start


# jax.monitoring is process-wide and its listeners cannot be taken off,
# so the log they feed is process-wide too: [name, owner, start, end,
# value], where value is what ``events()`` gives for the entry and None
# for a span that is not the compile pipeline's.
_lock = threading.Lock()
_log = []
# The steady state's entries, each with the length the log had when it
# arrived: its place among entries that are never taken out.
_steady = collections.deque(maxlen=STEADY_KEPT)
_stages = {stage: _Union() for stage in STAGES}
_followers = []
_listening = False


def _publish(entry):
    """An entry that has its owner: to the stages, the gauge and the
    followers. Called under the lock."""
    name, owner, start, end, _ = entry
    for follower in _followers:
        follower(name, owner, start, end)
    if name in _STEP_STAGES:
        stage = (_STEP_STAGES[name] if owner == STEP_NAME
                 else "other_programs")
    elif name in _stages:
        stage = name
    else:
        return      # the cache's entries lie inside a backend_compile
    _stages[stage].add(start, end)
    changed = [stage]
    if owner == STEP_NAME:
        # What was traced inside the step's span arrived before it.
        _stages["other_programs"].cut(start)
        changed.append("other_programs")
    gauge = telemetry.gauge(
        "hvd_startup_seconds",
        "Seconds from the process's start to the first step, by stage",
        ("stage",))
    for stage in changed:
        gauge.labels(stage=stage).set(_stages[stage].seconds)


def record(name, owner, start, end, value=None):
    """Append one span, times on ``time.perf_counter()``."""
    with _lock:
        entry = [name, owner, start, end, value]
        if name == "backend_compile":
            # The cache's entries that arrived inside it are its own.
            for waiting in reversed(_log):
                if waiting[3] < start:
                    break
                if waiting[1] is None:
                    waiting[1] = owner
                    _publish(waiting)
        if name in STEADY:
            _steady.append((len(_log), entry))
        else:
            _log.append(entry)
        if owner is not None:
            _publish(entry)


def imported(package, start):
    """Last line of a package's ``__init__.py``; ``start`` is the clock
    at its first."""
    record("import", package, start, time.perf_counter())


def before_program(end):
    """The span from the process's start to ``end``, the clock at the
    first line of the package: the interpreter, the caller's own imports
    and whatever it ran first. The start is the kernel's
    (``/proc/self/stat`` field 22, in ticks after boot, against
    ``CLOCK_BOOTTIME``); where the platform has neither, no span."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rpartition(")")[2].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, AttributeError, ValueError, IndexError):
        return
    record("before_program", PACKAGE,
           min(time.perf_counter() - age, end), end)


def _owner(fun_name):
    """``jit(hvd_train_step)``, the module's name, -> the function's."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name.partition("(")[2][:-1]
    return fun_name


def _on_seconds(name, seconds, fun_name="", **_):
    phase = _SECONDS.get(name)
    if phase is None:
        return
    at = time.perf_counter()
    owner = None if phase == "cache_load" else _owner(fun_name)
    record(phase, owner, at - seconds, at, seconds)
    telemetry.histogram(
        "hvd_compile_seconds", "Seconds in JAX's compile pipeline",
        ("phase",)).labels(phase=phase).observe(seconds)


def _on_event(name, **_):
    instant = _INSTANTS.get(name)
    if instant is None:
        return
    phase, counter, text = instant
    at = time.perf_counter()
    record(phase, None, at, at, 1)
    telemetry.counter(counter, text).inc()


def listen():
    """Register the listeners; a second call registers nothing."""
    global _listening
    if _listening:
        return
    _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_seconds)
    jax.monitoring.register_event_listener(_on_event)


def _entries():
    """The log with the steady state's entries in their places, in
    arrival order. Called under the lock."""
    entries, at = [], 0
    for place, entry in _steady:
        entries += _log[at:place]
        entries.append(entry)
        at = place
    return entries + _log[at:]


def spans():
    """A copy of the log: ``(name, owner, start, end)`` tuples in
    arrival order, which is the order they ended in (a ``gc`` span
    arrives with the pulse's next wake-up)."""
    with _lock:
        return [tuple(entry[:4]) for entry in _entries()]


def events():
    """The compile pipeline's entries of the log: ``(phase, value,
    perf_counter)`` tuples in arrival order; ``phase`` is ``trace``,
    ``lower``, ``backend_compile`` or ``cache_load`` (value in seconds),
    or ``cache_hit`` / ``cache_miss`` (value 1)."""
    with _lock:
        return [(name, value, end) for name, _, _, end, value in _log
                if value is not None]


def startup_seconds():
    """Seconds so far by stage of ``STAGES``, overlapping spans of a
    stage counted once, and ``other_programs`` outside the step's."""
    with _lock:
        return {stage: union.seconds for stage, union in _stages.items()}


def follow(follower):
    """Call ``follower(name, owner, start, end)`` for every span in the
    log that has its owner, and for later ones as they get it."""
    with _lock:
        for name, owner, start, end, _ in _entries():
            if owner is not None:
                follower(name, owner, start, end)
        _followers.append(follower)


def unfollow(follower):
    with _lock:
        _followers.remove(follower)


def enable():
    """Point JAX at the persistent cache; returns the directory in use.
    Call before the first compilation."""
    listen()
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
