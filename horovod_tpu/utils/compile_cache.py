"""JAX's persistent compilation cache at a path that does not move,
and a log of what JAX's compile pipeline did in this process.

The cache directory is part of the cache key, so it must be the same in
every process of a checkout: where ``JAX_COMPILATION_CACHE_DIR`` is set
JAX reads it and this module sets nothing; otherwise the cache lives in
``<checkout>/.jax_cache`` (gitignored), derived from this file's path.

The log (``events()``) is fed by ``jax.monitoring`` listeners that
``enable()`` or ``hvd.init()`` registers, once a process: seconds of
jaxpr tracing, MLIR lowering, backend compilation (which includes a
cache load) and cache retrieval, and one entry a persistent-cache hit
or miss. An entry is one list append and arrives only when something
compiles, so the steady state pays nothing. With ``HOROVOD_TPU_METRICS``
on, the same listeners feed ``hvd_compile_seconds{phase}``,
``hvd_compile_cache_hits_total`` and ``hvd_compile_cache_misses_total``
(docs/metrics.md): a recompile after warm-up is a counter that moves.
"""

import os
import time

import jax

from .. import telemetry

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# JAX's event -> the phase it is logged under.
_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"

# jax.monitoring is process-wide and its listeners cannot be taken off,
# so the log they feed is process-wide too: (phase, value, perf_counter).
_events = []
_listening = False


def _on_seconds(name, seconds, **_):
    phase = _SECONDS.get(name)
    if phase is None:
        return
    _events.append((phase, seconds, time.perf_counter()))
    telemetry.histogram(
        "hvd_compile_seconds", "Seconds in JAX's compile pipeline",
        ("phase",)).labels(phase=phase).observe(seconds)


def _on_event(name, **_):
    if name == _HIT:
        _events.append(("cache_hit", 1, time.perf_counter()))
        telemetry.counter(
            "hvd_compile_cache_hits_total",
            "Executables loaded from the persistent cache").inc()
    elif name == _MISS:
        _events.append(("cache_miss", 1, time.perf_counter()))
        telemetry.counter(
            "hvd_compile_cache_misses_total",
            "Executables compiled and written to the persistent "
            "cache").inc()


def listen():
    """Register the listeners; a second call registers nothing."""
    global _listening
    if _listening:
        return
    _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_seconds)
    jax.monitoring.register_event_listener(_on_event)


def events():
    """A copy of the log: ``(phase, value, perf_counter)`` tuples in
    arrival order; ``phase`` is ``trace``, ``lower``,
    ``backend_compile`` or ``cache_load`` (value in seconds), or
    ``cache_hit`` / ``cache_miss`` (value 1)."""
    return list(_events)


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
