"""The per-layer metrics of a traced run. Each metric is a file of its
own, ``layer_metrics/<name>.py``, with one function ``read(ctx)`` that
returns the number, or None where it finds nothing to read."""

import os

from benchmark import harness, trace_reduce


class Context(dict):
    """What a reader may read: ``cell``, ``spans`` (host seconds by
    name), ``seen`` (the window), ``hlo`` (the compiled step's text),
    ``memory_bytes``, ``end_to_end`` values, ``device_kind``,
    ``reference`` (the configuration's reference module), and
    ``trace``: the reduction of the profiler's trace, made on first
    use."""

    def __missing__(self, key):
        if key == "trace":
            self["trace"] = trace_reduce.reduce(
                trace_reduce.load_xplane(self["trace_dir"]))
            return self["trace"]
        raise KeyError(key)

    @property
    def steps(self):
        return len(self["seen"]["done"])


def read_all(context):
    ctx = Context(context)
    root = ctx["root"]
    metrics = {}
    for metric in ctx["cell"]["per_layer"]:
        reader = harness.load_module(root, os.path.join(
            harness.DATA, "layer_metrics", metric["name"] + ".py"))
        value = reader.read(ctx)
        if value is not None:
            metrics[metric["name"]] = value
    reduced = ctx["trace"]
    times = {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
    breakdown = {"device_ops": reduced["device_ops"],
                 "idle_gaps": reduced["idle_gaps"]}
    return metrics, times, breakdown
