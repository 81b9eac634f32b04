"""From a device trace and the compiled step's text to device time by
what the program was doing: forward, backward, exchange, optimizer; the
three flash kernels and the XLA work around them; scope paths.

The trace names a device operation by its HLO instruction and carries
no metadata, so the scope is joined from the compiled step's text
(``ctx["hlo"]``), in which every instruction has an ``op_name`` such as
``jit(hvd_train_step)/hvd_grad/transpose(jvp(TransformerLM))/backbone/
block_3/mlp_in/dot_general``. The join key is the instruction's name.

The scope and kernel names are the program's tracing contract
(``horovod_tpu/jax/__init__.py``, ``ops/flash_attention.py``), written
here as literals: a rename in the program must show as ``unscoped``
time, not follow silently.
"""

import functools
import json
import os
import re

from benchmark import flops, peaks, trace_reduce

GRAD, EXCHANGE, OPTIMIZER = "hvd_grad", "hvd_exchange", "hvd_optimizer"
FLASH = "hvd_flash"
KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dkdv", "hvd_flash_bwd_dq")
PHASES = ("fwd", "bwd", "exchange", "optimizer", "unscoped")
# Root module, backbone, block, attn, qkv: the depth at which a
# transformer's projections, rope and kernel come apart.
PATH_DEPTH = 5

_COMPUTATION = re.compile(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*")
_INSTRUCTION = re.compile(r"\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_TRANSFORMED = re.compile(r"(?:jvp|transpose|vmap)\((.*)\)")
# The first operand: what follows the opcode's parenthesis (types hold
# no lower-case word before a parenthesis).
_OPERAND = re.compile(r" [a-z][a-z0-9_\-]*\(%?([\w.\-]+)[,)]")
_HOPS = 8   # copy-done <- copy-start <- get-tuple-element <- fusion, ...


@functools.lru_cache(maxsize=None)
def _parts(op_name):
    """The scopes of an ``op_name``, outermost first, each without the
    ``jvp(...)`` / ``transpose(...)`` a transformation wrapped it in."""
    out = []
    for part in op_name.split("/"):
        while (wrapped := _TRANSFORMED.fullmatch(part)):
            part = wrapped.group(1)
        if part:
            out.append(part)
    return tuple(out)


@functools.lru_cache(maxsize=None)
def classify(op_name):
    """(phase, kernel, path) of an ``op_name``. ``phase`` is one of
    ``PHASES``, by the outermost of the step's scopes (so a collective
    of the model's own, under ``hvd_grad``, is the model's); backward
    is ``hvd_grad`` with a ``transpose(`` anywhere. ``kernel`` is the
    flash kernel's name or None. ``path`` is the scopes below the
    step's own, without the primitive, cut to ``PATH_DEPTH``."""
    parts = _parts(op_name)
    phase = "unscoped"
    for part in parts:
        if part == GRAD:
            phase = "bwd" if "transpose(" in op_name else "fwd"
        elif part in (EXCHANGE, OPTIMIZER):
            phase = "exchange" if part == EXCHANGE else "optimizer"
        else:
            continue
        break
    kernel = next((p for p in parts if p in KERNELS), None)
    below = [p for i, p in enumerate(parts[:-1])
             if p not in (GRAD, EXCHANGE, OPTIMIZER, "shard_map")
             and not (i == 0 and p.startswith("jit("))]
    return phase, kernel, "/".join(below[:PATH_DEPTH])


def op_names(hlo_text):
    """``{instruction: [op_name, phases]}`` for every instruction of the
    compiled step. A fusion is named by its root (a tuple root has no
    name, so the last named instruction before it stands for it), and
    by its own ``op_name`` only where the fused computation has none.
    An instruction that is neither named nor a fusion (a layout copy,
    an asynchronous copy or slice, a ``get-tuple-element``) belongs to
    what made its first operand. ``phases`` lists the scoped phases
    (sorted) of the instructions inside a fusion, nested fusions
    included, and is empty for anything else."""
    computations, own, calls, operand = {}, {}, {}, {}
    body = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.fullmatch(line)
        if head:
            body = computations.setdefault(head.group(1), [])
            continue
        found = _INSTRUCTION.match(line)
        if not found or body is None:
            continue
        name = found.group(1)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        body.append(name)
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        first = _OPERAND.search(line, found.end())
        if first:
            operand[name] = first.group(1)

    def inside(computation, seen):
        for name in computations.get(computation, ()):
            seen.add(classify(own[name])[0])
            if name in calls:
                inside(calls[name], seen)
        return seen

    named = dict(own)
    for name, computation in calls.items():
        inner = [own[n] for n in computations.get(computation, ())
                 if own[n]]
        if inner:
            named[name] = inner[-1]

    def resolve(name):
        for _ in range(_HOPS):
            if named.get(name) or name not in operand:
                break
            name = operand[name]
        return named.get(name, "")

    return {name: [resolve(name),
                   sorted(inside(calls[name], set()) - {"unscoped"})
                   if name in calls else []]
            for name in own}


def reduce(trace, hlo):
    """Self-time nanoseconds of the first chip inside the window, by
    phase, by flash kernel, in the XLA operations under ``hvd_flash``
    that are not kernels (``flash_glue_ns``), by scope path and by
    family of operation. ``hlo`` is the compiled step's text, or what
    ``op_names`` made of it. The time of fusions that hold instructions
    of two phases is counted under its root's phase and summed apart
    as ``mixed_ns`` (``mixed`` says which phases)."""
    names = op_names(hlo) if isinstance(hlo, str) else hlo
    first = min(trace["devices"], key=int)
    events = trace_reduce.clip(trace["devices"][first],
                               trace_reduce.window_of(trace))
    out = {"device": first,
           "busy_ns": trace_reduce.total(trace_reduce.union(
               [e[1], e[1] + e[2]] for e in events)),
           "by_phase": dict.fromkeys(PHASES, 0),
           "by_kernel": {}, "flash_glue_ns": 0, "flash_seen": False,
           "mixed_ns": 0, "mixed": {},
           "by_path": {phase: {} for phase in PHASES}, "by_op": {},
           "op_names": {}}

    def add(table, key, ns):
        table[key] = table.get(key, 0) + ns

    for name, ns in trace_reduce.self_times(events):
        instruction = name.partition(" ")[0]
        op_name, inside = names.get(instruction, ("", []))
        out["op_names"][instruction] = [op_name, inside]
        phase, kernel, path = classify(op_name)
        out["by_phase"][phase] += ns
        add(out["by_path"][phase], path, ns)
        add(out["by_op"].setdefault(trace_reduce.family(name), {}),
            f"{phase} {path}", ns)
        if len(inside) > 1:
            out["mixed_ns"] += ns
            add(out["mixed"], "+".join(inside), ns)
        if FLASH in _parts(op_name):
            out["flash_seen"] = True
            if kernel and trace_reduce.classify(name) == "kernel":
                add(out["by_kernel"], kernel, ns)
            else:
                out["flash_glue_ns"] += ns
    return out


def of(ctx):
    """The reduction of this run's trace, made once for all readers and
    kept in ``ctx``; the first use writes ``scopes.json`` beside the
    trace. None where the run took no trace."""
    if "scopes" not in ctx:
        ctx["scopes"] = None
        if ctx.get("trace_dir") and ctx.get("hlo"):
            ctx["scopes"] = reduce(
                trace_reduce.load_xplane(ctx["trace_dir"]), ctx["hlo"])
            ctx["scopes"]["steps"] = ctx.steps
            with open(os.path.join(ctx["trace_dir"], "scopes.json"),
                      "w") as f:
                json.dump(ctx["scopes"], f)
    return ctx["scopes"]


def ms_per_step(ctx, ns):
    return ns / 1e6 / ctx.steps if ns else None


def phase_ms(ctx, phase):
    """Milliseconds a step of ``phase``; None where the program has no
    such scope (every operation then reads ``unscoped``)."""
    scopes = of(ctx)
    return ms_per_step(ctx, scopes["by_phase"][phase]) if scopes else None


def kernel_ms(ctx, *kernels):
    """Milliseconds a step in the named flash kernels together; None
    where the trace has none of them."""
    scopes = of(ctx)
    if not scopes:
        return None
    return ms_per_step(ctx, sum(scopes["by_kernel"].get(k, 0)
                                for k in kernels))


def flash_roofline(ctx, part, *kernels):
    """The least time the chip could take for the forward (``part`` 0)
    or backward (1) half of the attention a step requires, as a share
    of the time the named kernels took: ``flops.attention_flops`` and
    ``attention_bytes`` over the peaks, the larger of the two."""
    ms = kernel_ms(ctx, *kernels)
    attention_shape = getattr(ctx["reference"], "attention_shape", None)
    if not ms or attention_shape is None:
        return None
    cfg = ctx["cell"]["cfg"]
    shape = attention_shape(cfg, ctx["cell"]["traffic_params"])
    kind = ctx["device_kind"]
    need = flops.attention_flops(*shape, causal=True)[part] / peaks.peak(
        kind, "bf16_flops_per_s")
    move = flops.attention_bytes(*shape)[part] / peaks.peak(
        kind, "hbm_bytes_per_s")
    return 100.0 * cfg["num_hidden_layers"] * max(need, move) / (ms / 1e3)


def compile_events(ctx, *phases):
    """Entries ``(phase, value, at)`` of the program's compile log
    (``compile_cache.events()``) in ``phases`` that arrived before the
    window; None where the program keeps no such log or it is empty."""
    from horovod_tpu.utils import compile_cache
    log = getattr(compile_cache, "events", lambda: [])()
    if not log:
        return None
    return [e for e in log
            if e[0] in phases and e[2] < ctx["seen"]["start"]]


def compile_seconds(ctx, *phases):
    """Seconds the process spent in ``phases`` before the window. An
    entry arrives when its phase ends, and the trace of a function
    holds the traces of the functions it calls, so the seconds are
    those of the union of the entries' intervals, not their sum."""
    entries = compile_events(ctx, *phases)
    if entries is None:
        return None
    return trace_reduce.total(trace_reduce.union(
        [at - seconds, at] for _, seconds, at in entries))
