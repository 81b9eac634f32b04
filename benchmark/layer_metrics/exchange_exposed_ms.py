"""Milliseconds per step the first chip spends on the gradient exchange
with nothing else running (trace): the synchronous collectives' exposed
part (``collective_exposed_ms``) and the own time of the
``async-collective-start`` / ``-done`` fusions, the asynchronous pairs
of a step compiled to overlap its exchange. The ``-done`` is where the
chip waits for what the compute between the two did not cover (and
copies the result out, so this reads a little over the stall)."""

PAIR = ("async-collective-start", "async-collective-done")


def read(ctx):
    if ctx["cell"]["chips"] < 2:
        return None
    device = next(iter(ctx["trace"]["devices"].values()))
    pairs = sum(ns for name, ns in device["by_name"].items()
                if name.startswith(PAIR))
    return (device["collective_exposed_ns"] + pairs) / 1e6 / ctx.steps
