"""Where the compiler put a step's all-reduces: read from the scheduled
HLO of a compiled step (``compiled.as_text()``; the entry computation
lists its instructions in the order the chip runs them).

An all-reduce is either one synchronous instruction, during which the
chip does nothing else, or a start / done pair with other instructions
between them: XLA's generic ``all-reduce-start`` / ``all-reduce-done``,
or the TPU's async collective fusions, named ``async-collective-start``
/ ``async-collective-done`` (fusions whose called computation holds the
all-reduce). ``make_train_step`` asks for the pairs on a TPU mesh of
more than one chip (``_OVERLAP_OPTIONS``).
"""

import math
import re

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
             "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_SHAPE = re.compile(r"\b([a-z]+[0-9]+[a-z0-9]*)\[([0-9,]*)\]")
_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_PAIR = re.compile(r"(async-collective|all-reduce)-(start|done)((?:\.\d+)?)$")
# What counts as the step's own compute between a start and its done:
# the backward Mosaic kernel, or a product of the backward pass (the
# weight-gradient products are the ones nothing else waits for).
_KERNEL = "hvd_flash_bwd_dkdv"
_BACKWARD_PRODUCT = re.compile(r'op_name="[^"]*transpose\(jvp[^"]*dot_general')


def _shapes_bytes(text):
    """(number of arrays, their bytes) of an HLO shape or tuple of
    shapes, as written before the opcode."""
    found = _SHAPE.findall(text)
    return len(found), sum(
        _ITEMSIZE.get(dtype, 4) * math.prod(int(d) for d in dims.split(",")
                                            if d)
        for dtype, dims in found)


def _computations(text):
    """name -> list of instruction lines; and the entry's name."""
    out, entry, current = {}, None, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = out.setdefault(head.group(2), [])
            if head.group(1):
                entry = head.group(2)
        elif line.startswith("}"):
            current = None
        elif current is not None:
            current.append(line)
    return out, entry


def _inner_all_reduce(lines):
    """The all-reduce a fused computation holds: (operands, bytes) or
    None."""
    for line in lines:
        found = _INSTRUCTION.match(line)
        if found and " all-reduce(" in found.group(2):
            return _shapes_bytes(found.group(2).split(" all-reduce(")[0])
    return None


def exchange_schedule(compiled):
    """The all-reduces of a compiled step, in schedule order.

    ``compiled`` is a ``jax.stages.Compiled`` or its ``as_text()``.
    Returns a dict: ``collectives``, a list of ``{"kind": "sync" |
    "async", "operands", "bytes", "at": (first, last) instruction
    index, "over_backward"}``, where ``over_backward`` says that a
    backward kernel or a product of the backward pass runs between the
    start and the done; and the counts ``sync``, ``async``,
    ``sync_bytes``, ``async_bytes``, ``async_bytes_share`` (of the
    bytes all-reduced; 0.0 where there are none),
    ``async_over_backward`` and ``max_operands`` (the most arrays one
    all-reduce combines)."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    computations, entry = _computations(text)
    lines = computations.get(entry, [])
    collectives, open_pairs = [], {}
    for at, line in enumerate(lines):
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        name, rest = found.groups()
        pair = _PAIR.search(name)
        if " all-reduce(" in rest and not pair:
            operands, nbytes = _shapes_bytes(rest.split(" all-reduce(")[0])
            collectives.append({"kind": "sync", "operands": operands,
                                "bytes": nbytes, "at": (at, at),
                                "over_backward": False})
        elif pair and pair.group(2) == "start":
            if pair.group(1) == "all-reduce":
                inner = _shapes_bytes(
                    rest.split(" all-reduce-start(")[0])
            else:
                called = re.search(r"calls=%?([\w.\-]+)", rest)
                inner = _inner_all_reduce(
                    computations.get(called.group(1), [])) if called \
                    else None
            if inner:
                open_pairs[(pair.group(1), pair.group(3))] = (at, inner)
        elif pair and (pair.group(1), pair.group(3)) in open_pairs:
            start, (operands, nbytes) = open_pairs.pop(
                (pair.group(1), pair.group(3)))
            between = lines[start + 1:at]
            collectives.append({
                "kind": "async", "operands": operands, "bytes": nbytes,
                "at": (start, at),
                "over_backward": any(
                    (_KERNEL in b and " custom-call(" in b)
                    or _BACKWARD_PRODUCT.search(b) for b in between)})
    collectives.sort(key=lambda c: c["at"])
    by_kind = {kind: [c for c in collectives if c["kind"] == kind]
               for kind in ("sync", "async")}
    nbytes = {kind: sum(c["bytes"] for c in found)
              for kind, found in by_kind.items()}
    total = nbytes["sync"] + nbytes["async"]
    return {
        "collectives": collectives,
        "sync": len(by_kind["sync"]), "async": len(by_kind["async"]),
        "sync_bytes": nbytes["sync"], "async_bytes": nbytes["async"],
        "async_bytes_share": nbytes["async"] / total if total else 0.0,
        "async_over_backward": sum(c["over_backward"]
                                   for c in by_kind["async"]),
        "max_operands": max((c["operands"] for c in collectives),
                            default=0),
    }


def publish_exchange_schedule(compiled):
    """Set ``hvd_exchange_collectives{kind}`` and
    ``hvd_exchange_async_bytes_share`` (docs/metrics.md) from
    ``exchange_schedule(compiled)``, and return that. Sets nothing when
    ``HOROVOD_TPU_METRICS`` is off."""
    from ..telemetry import core as telemetry
    schedule = exchange_schedule(compiled)
    if telemetry.enabled():
        count = telemetry.gauge(
            "hvd_exchange_collectives",
            "All-reduces in the compiled step last read, by kind: async "
            "(a start / done pair with compute between) or sync",
            ("kind",))
        for kind in ("async", "sync"):
            count.labels(kind=kind).set(float(schedule[kind]))
        telemetry.gauge(
            "hvd_exchange_async_bytes_share",
            "Share of the bytes the compiled step last read all-reduces "
            "that ride asynchronous pairs").set(
                schedule["async_bytes_share"])
    return schedule
