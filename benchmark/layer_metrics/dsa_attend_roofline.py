"""The least time the chip could take for the attention over the
selected keys that a step requires (``attention_work`` of the
reference: the selected pairs alone, two products forward and four
backward at every query head's width; FLOP-bound at seq 16384) as a
share of the time of the kernels under ``hvd_dsa/attend``
(``dsa_attend_ms``). It counts the same work whatever computes it: the
flash kernels under a mask run every causal tile, 4.27 times the
selected pairs at 16,384 positions and ``topk`` 2048, so they can read
23% at the most; a path that gathers the selected keys is judged by the
same yardstick. None where the program has no such scope."""

from benchmark import harness, scope_sum

ATTEND = "benchmark/layer_metrics/dsa_attend_ms.py"


def read(ctx):
    ms = harness.load_module(ctx["root"], ATTEND).read(ctx)
    attention_work = getattr(ctx["reference"], "attention_work", None)
    if attention_work is None or not ms:
        return None
    cell = ctx["cell"]
    operations, moved = attention_work(cell["cfg"], cell["traffic_params"])
    rows = cell["traffic_params"]["rows_per_chip"]
    return 100.0 * rows * scope_sum.least_seconds(
        ctx, operations, moved) / (ms / 1e3)
