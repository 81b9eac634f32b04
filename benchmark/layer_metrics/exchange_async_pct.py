"""Share of the bytes the compiled step all-reduces that ride
asynchronous start / done pairs (the program's own counter,
``horovod_tpu.jax.exchange_schedule``, on the scheduled HLO's entry
computation). None where the program has no such counter."""


def read(ctx):
    if ctx["cell"]["chips"] < 2:
        return None
    try:
        from horovod_tpu.jax import exchange_schedule
    except ImportError:
        return None
    return 100.0 * exchange_schedule(ctx["hlo"])["async_bytes_share"]
