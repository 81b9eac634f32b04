"""Device time under a named scope wherever it sits in an ``op_name``.

``scope_reduce`` files time by phase, by flash kernel and by scope path
cut to five levels; the scopes of the expert layer (``hvd_moe`` /
``route`` | ``experts``), of latent attention (``hvd_mla``) and of the
multi-token-prediction module (``hvd_mtp``) lie deeper than that, and
inside one another. This walks the first chip's events once more and
keeps, for each, the scopes of its instruction (joined as
``scope_reduce`` joins them) and whether it is a Mosaic kernel.

The scope names are the program's tracing contract
(``horovod_tpu/parallel/moe.py``, ``models/transformer.py``), written in
the readers as literals.

One kind of operation has no ``op_name`` to join: the TPU compiler
expands ``jax.lax.ragged_dot`` into Mosaic calls of its own
(``ragged-dot-none``, ``ragged-dot-metadata``) and names them for
themselves, with none of the program's scopes, so ``scope_reduce`` files
them as ``unscoped``. The program makes grouped
products in one place only, under ``hvd_moe/experts``, so they are filed
there, by the instruction's name; which layer's they are (the MTP
module's or a main block's) cannot be told, so ``hvd_mtp`` reads
without them. That filing rests on a name that is XLA's to change: a
trace with work under ``hvd_moe/experts`` and no Mosaic kernel filed
there has lost them, and ``scope_ms`` then reads nothing, for any
scope, rather than too little.
"""

import re
import sys

from benchmark import scope_reduce, trace_reduce

GROUPED = "ragged-dot"              # XLA's grouped-product kernels
GROUPED_SCOPES = ("hvd_moe", "experts")
_TRANSFORMED = re.compile(r"(?:jvp|transpose|vmap)\((.*)\)")


def events(ctx):
    """``[(scopes, is_kernel, self_ns)]`` of the first chip inside the
    window, made once and kept in ``ctx``; None where the run took no
    trace."""
    if "scope_events" not in ctx:
        ctx["scope_events"] = None
        scopes = scope_reduce.of(ctx)
        if scopes:
            trace = trace_reduce.load_xplane(ctx["trace_dir"])
            ops = trace_reduce.clip(trace["devices"][scopes["device"]],
                                    trace_reduce.window_of(trace))
            found = [
                (_scopes(name, scopes["op_names"]),
                 trace_reduce.classify(name) == "kernel", ns)
                for name, ns in trace_reduce.self_times(ops)]
            if grouped_lost(found):
                print(f"scope_sum: work under {'/'.join(GROUPED_SCOPES)} "
                      f"and no Mosaic kernel filed there: has XLA renamed "
                      f"{GROUPED}*? Reading nothing.", file=sys.stderr)
            else:
                ctx["scope_events"] = found
    return ctx["scope_events"]


def scopes_of(op_name):
    """The scopes of an ``op_name``, outermost first, each without the
    ``jvp(...)`` / ``transpose(...)`` a transformation wrapped it in,
    at any depth (``scope_reduce.classify`` cuts its path at five)."""
    out = []
    for part in op_name.split("/"):
        while (wrapped := _TRANSFORMED.fullmatch(part)):
            part = wrapped.group(1)
        if part:
            out.append(part)
    return tuple(out)


def _scopes(name, op_names):
    instruction = name.partition(" ")[0]
    parts = scopes_of(op_names[instruction][0])
    if instruction.startswith(GROUPED) and GROUPED_SCOPES[0] not in parts:
        return GROUPED_SCOPES
    return parts


def grouped_lost(events):
    """True where something ran under ``hvd_moe/experts`` and none of
    it was a Mosaic kernel: the grouped products are in the trace under
    a name this file does not know."""
    experts = [kernel for parts, kernel, _ in events
               if _within(GROUPED_SCOPES, parts)]
    return bool(experts) and not any(experts)


def _within(scopes, parts):
    """True where ``scopes`` occur in ``parts`` in that order."""
    rest = iter(parts)
    return all(scope in rest for scope in scopes)


def scope_ms(ctx, *scopes, kernels=True):
    """Milliseconds a step under ``scopes`` (outermost first, anything
    between them), forward and backward; without the Mosaic kernels
    where ``kernels`` is False. None where the program has no such
    scope."""
    found = [(kernel, ns) for parts, kernel, ns in events(ctx) or ()
             if _within(scopes, parts)]
    if not found:
        return None
    return sum(ns for kernel, ns in found
               if kernels or not kernel) / 1e6 / ctx.steps


def least_seconds(ctx, operations, moved):
    """The least time the chip could take for ``operations`` FLOPs and
    ``moved`` bytes: the larger of the two over their peaks."""
    from benchmark import peaks
    kind = ctx["device_kind"]
    return max(operations / peaks.peak(kind, "bf16_flops_per_s"),
               moved / peaks.peak(kind, "hbm_bytes_per_s"))
