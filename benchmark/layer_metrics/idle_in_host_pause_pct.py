"""The share of the first chip's idle time inside the window that lies
under a pause of the host, on the profiler's clock: under a gap between
two ``hvd:pulse`` events wider than the pulse's period and threshold, or
under an ``hvd:gc`` event (``benchmark/host_reduce.py: host_pauses``).
0 where nothing overlaps; None where the trace holds no pulse."""

from benchmark import host_reduce


def read(ctx):
    return host_reduce.of(ctx)["idle_in_host_pause_pct"]
