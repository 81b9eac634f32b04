"""Autotune knob overlay: tuned values for construction-time knobs.

Most tuned knobs apply instantly (the coordinator's fusion threshold,
cycle time and ``HVDTPU_BUCKET_BYTES`` are plain attributes the cycle
thread re-reads). One does not: ``HVDTPU_ZERO_BUCKET_BYTES`` is read
once when a ``DistributedOptimizer(zero=True)`` is constructed and
baked into the traced train step. The overlay is the indirection that
closes that gap: the tuner (a warm-started cache hit at init, or a
zero-arm candidate mid-sweep) writes here, and the constructor reads
through :func:`resolve_int` so a tuned value wins over the raw
environment. The ZeRO step wrapper additionally polls :func:`generation` (one int
compare per step) so a mid-run change triggers a deterministic
re-plan + reshard at the next step boundary.

Values persist across elastic re-inits on purpose: the new cohort's
fresh ParameterManager re-validates them against the warm-start store
(docs/autotune.md) instead of silently dropping the tuned config.
"""

import threading

_lock = threading.Lock()
_values = {}
_generation = 0


def set_int(name, value):
    """Overlay knob ``name`` (an envparse registry name, no prefix)
    with a tuned integer value; bumps the generation counter consumers
    poll for cheap change detection."""
    global _generation
    with _lock:
        _values[name] = int(value)
        _generation += 1


def get_int(name, default=None):
    """Tuned value for ``name``, or ``default`` when the tuner never
    touched it."""
    with _lock:
        return _values.get(name, default)


def resolve_int(name, default=None):
    """The one overlay-then-env-then-default resolution every
    construction-time reader uses: a tuned value wins over the raw
    environment knob, which wins over ``default``."""
    value = get_int(name)
    if value is not None:
        return value
    from ..utils import envparse
    return envparse.get_int(name, default)


def generation():
    """Monotonic change counter (0 = nothing overlaid yet)."""
    return _generation


def snapshot():
    """Copy of the overlay dict (CLI / test surface)."""
    with _lock:
        return dict(_values)


def clear():
    """Drop every overlaid value (test hook; bumps the generation so
    pollers notice)."""
    global _generation
    with _lock:
        _values.clear()
        _generation += 1
