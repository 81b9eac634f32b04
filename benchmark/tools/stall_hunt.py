"""Untraced runs of one cell, each with a timeline of its own, and for
every run what the program's log holds inside the window: the hunt for
the stall that takes a third of a second to three out of one window in
ten (ROADMAP D8; docs/tracing.md "The host while the step runs").

    python3 benchmark/tools/stall_hunt.py --workload <cell> \\
        --seeds 1,2,3 --seconds 30 --out chiprun_out/hunt_<cell>.jsonl

A run is ``run_sets.one`` with ``HOROVOD_TIMELINE`` set: the program
writes its ``host_pause`` and ``gc`` spans there (category
``hvd_host``) on ``perf_counter``. An untraced run's result line holds
end-to-end metrics only, so the window is placed on that clock from
outside: it starts ``setup_s`` after the harness's first clock read,
which lies a few tens of ms after the process's start (the
``before_program`` span's), and lasts what the harness's note says.
Kept of each timeline: the ``hvd_host`` events, each with its offset
from the window's start, and the seconds of them before the window that
lie under no other span of the log (what ``setup_unnamed_s`` no longer
holds). At the end: each run whose rate lies over 1%
under the set's median, with what its window holds.
"""

import argparse
import json
import os
import re
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402
from benchmark.tools import run_sets  # noqa: E402

SLOW = 0.01         # a run this far under the median rate is a stalled one
EDGE_S = 0.1        # how well the window's start is known from outside


def host_events(path, setup_s):
    """``[name, owner, offset_s, seconds]`` of the timeline's
    ``hvd_host`` events, the offset from the window's start, and the
    seconds of them before the window under no other span."""
    with open(path) as f:
        events = json.load(f)
    start = min(e["ts"] for e in events
                if e.get("name") == "before_program") / 1e6 + setup_s
    host = [e for e in events if e.get("cat") == "hvd_host"]
    before = trace_reduce.union(
        [e["ts"], min(e["ts"] + e["dur"], start * 1e6)] for e in host
        if e["ts"] < start * 1e6)
    named = trace_reduce.union([e["ts"], e["ts"] + e["dur"]] for e in events
                               if e.get("cat") == "hvd_startup")
    alone_s = trace_reduce.total(trace_reduce.subtract(before, named)) / 1e6
    return [[e["name"], e["args"]["owner"], e["ts"] / 1e6 - start,
             e["dur"] / 1e6] for e in host], alone_s


def hunt(workload, seed, seconds, timeline):
    os.environ["HOROVOD_TIMELINE"] = timeline
    record = {"workload": workload,
              **run_sets.one(workload, seed, seconds, 0)}
    if "result" in record and os.path.exists(timeline):
        window = re.search(r"window ([0-9.]+) s", " ".join(record["notes"]))
        record["window_s"] = float(window.group(1))
        record["host_events"], record["host_alone_before_s"] = host_events(
            timeline, record["result"]["metrics"]["setup_s"]["value"])
        os.remove(timeline)     # a megabyte or two a run
    return record


def inside(record):
    return [e for e in record["host_events"]
            if e[2] + e[3] > -EDGE_S and e[2] < record["window_s"] + EDGE_S]


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    out_path = os.path.join(ROOT, args.out)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    records = []
    with open(out_path, "a") as out:
        for seed in args.seeds.split(","):
            record = hunt(args.workload, int(seed), args.seconds,
                          f"{out_path}.{seed}.timeline.json")
            out.write(json.dumps(record) + "\n")
            out.flush()
            if "result" not in record:
                print(seed, record["rc"], record["stderr"][-1500:],
                      flush=True)
                continue
            records.append(record)
            brief = {k: v["value"]
                     for k, v in record["result"]["metrics"].items()}
            print(seed, record["result"]["correct"], json.dumps(brief),
                  "inside the window:", json.dumps(inside(record)),
                  "before it, alone:", record["host_alone_before_s"],
                  flush=True)
    rate = next(name for name in records[0]["result"]["metrics"]
                if name.endswith("_per_s_per_chip"))
    rates = [r["result"]["metrics"][rate]["value"] for r in records]
    median = statistics.median(rates)
    print(f"{len(records)} runs, median {rate} {median}")
    for record, value in zip(records, rates):
        if value < (1 - SLOW) * median:
            metrics, found = record["result"]["metrics"], inside(record)
            print(f"seed {record['seed']}: {rate} {value} "
                  f"({100 * (value / median - 1):.2f}%), step_ms_p90 "
                  f"{metrics['step_ms_p90']['value']}; inside the window: "
                  f"{json.dumps(found) if found else 'neither'}")


if __name__ == "__main__":
    main()
