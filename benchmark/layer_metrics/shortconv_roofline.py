"""The least time the chip could take for the gated short convolutions a
step requires (``conv_work`` of the reference: the two products of every
conv mixer three times over; the mixer's input, ``[B, C, z]``, ``C * c``
and the output across HBM once with their gradients; the gates and taps
count no operation; FLOP-bound at hidden 2048) as a share of the time of
**every device operation that holds any of the mixers' work**.

Not of the time under scope ``hvd_shortconv`` as ``shortconv_ms`` reads
it: a trace files a fusion under its root's scope, and XLA fuses the
mixers' products into what uses them, whose roots lie elsewhere (the
product back to the mixer's input into the norm's backward pass, the
weights' gradients into AdamW's update, the output product into the
residual add). Over the scope alone the share read 166% (my chip run,
PR 40): the time left out most of the products. So the time here is
that of every operation whose own ``op_name`` holds the scope or whose
fused computation, at any depth, holds an instruction that does
(``holders``, from the compiled step's text). What such a fusion holds
besides (a norm's backward, an update) counts in the time and not in
the requirement, so the share is a floor of the products' own and
cannot pass 100. Products that recomputation runs a second time count
in the time too. None where the program has no such scope, where the
run took no trace, or where the reference counts no ``conv_work``."""

import functools

from benchmark import scope_reduce, scope_sum, trace_reduce

SCOPE = "hvd_shortconv"


def holders(hlo_text, scope=SCOPE):
    """Names of the compiled step's instructions that hold work under
    ``scope``: by their own ``op_name``, or, a fusion, by that of any
    instruction of the computation it calls, nested fusions included.
    The text is read as ``scope_reduce.op_names`` reads it."""
    computations, own, calls = {}, {}, {}
    body = None
    for line in hlo_text.splitlines():
        head = scope_reduce._COMPUTATION.fullmatch(line)
        if head:
            body = computations.setdefault(head.group(1), [])
            continue
        found = scope_reduce._INSTRUCTION.match(line)
        if not found or body is None:
            continue
        name = found.group(1)
        op = scope_reduce._OP_NAME.search(line)
        own[name] = bool(op) and scope in scope_sum.scopes_of(op.group(1))
        body.append(name)
        called = scope_reduce._CALLS.search(line)
        if called:
            calls[name] = called.group(1)

    def holds(name):
        return own[name] or (name in calls and inside(calls[name]))

    @functools.cache
    def inside(computation):
        return any(holds(name) for name in computations.get(computation, ()))

    return {name for name in own if holds(name)}


def instruction_ns(ctx):
    """``[(instruction, self_ns)]`` of the first chip inside the window,
    made once and kept in ``ctx``; None where the run took no trace."""
    if "instruction_ns" not in ctx:
        ctx["instruction_ns"] = None
        scopes = scope_reduce.of(ctx)
        if scopes:
            trace = trace_reduce.load_xplane(ctx["trace_dir"])
            ops = trace_reduce.clip(trace["devices"][scopes["device"]],
                                    trace_reduce.window_of(trace))
            ctx["instruction_ns"] = [
                (name.partition(" ")[0], ns)
                for name, ns in trace_reduce.self_times(ops)]
    return ctx["instruction_ns"]


def held_ms(ctx):
    """Milliseconds a step in the operations that hold work under the
    scope; None where there are none."""
    events = instruction_ns(ctx)
    if not events or not ctx.get("hlo"):
        return None
    mine = holders(ctx["hlo"])
    found = [ns for instruction, ns in events if instruction in mine]
    return sum(found) / 1e6 / ctx.steps if found else None


def read(ctx):
    conv_work = getattr(ctx["reference"], "conv_work", None)
    ms = held_ms(ctx) if conv_work else None
    if not ms:
        return None
    cell = ctx["cell"]
    operations, moved = conv_work(cell["cfg"], cell["traffic_params"])
    rows = cell["traffic_params"]["rows_per_chip"]
    return 100.0 * rows * scope_sum.least_seconds(
        ctx, operations, moved) / (ms / 1e3)
