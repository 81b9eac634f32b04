"""All-reduces the compiled step runs: the synchronous ones and the
start / done pairs of the scheduled HLO's entry computation, each once
(the program's own counter, ``horovod_tpu.jax.exchange_schedule``;
``allreduce_ops`` counts every ``all-reduce(`` of the text, a pair's
three times). None where the program has no such counter."""


def read(ctx):
    if ctx["cell"]["chips"] < 2:
        return None
    try:
        from horovod_tpu.jax import exchange_schedule
    except ImportError:
        return None
    schedule = exchange_schedule(ctx["hlo"])
    return schedule["sync"] + schedule["async"]
