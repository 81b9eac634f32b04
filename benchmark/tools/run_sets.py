"""Run sets of runs of one cell, one process a run, and keep every
result line: what a bound is set from.

    python3 benchmark/tools/run_sets.py --workload <cell> --seeds 1,2,3 \\
        --sets 2 --seconds 40 --out chiprun_out/<cell>.jsonl [--trace-seed n]

This parent never touches JAX: a chip belongs to one process at a time.
"""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def one(workload, seed, seconds, trace):
    t = time.time()
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=ROOT, capture_output=True, text=True)
    lines = [x for x in done.stdout.splitlines() if x.strip()]
    record = {"seed": seed, "trace": trace, "rc": done.returncode,
              "wall_s": time.time() - t,
              "notes": [x for x in lines[:-1] if not x.startswith("{")]}
    if done.returncode == 0 and lines and lines[-1].startswith("{"):
        record["result"] = json.loads(lines[-1])
    else:
        record["stderr"] = done.stderr[-3000:]
    return record


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace-seed", type=int, default=None)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
    runs = [(s, int(seed), 0) for s in range(args.sets)
            for seed in args.seeds.split(",")]
    if args.trace_seed is not None:
        runs.append((args.sets, args.trace_seed, 1))
    failures = 0
    with open(os.path.join(ROOT, args.out), "a") as out:
        for set_, seed, trace in runs:
            if failures == 2:
                sys.exit("two runs in a row failed: stopping")
            record = {"workload": args.workload, "set": set_,
                      **one(args.workload, seed, args.seconds, trace)}
            out.write(json.dumps(record) + "\n")
            out.flush()
            brief = {k: v["value"] for k, v in record.get(
                "result", {}).get("metrics", {}).items()}
            print(set_, seed, trace, record["rc"],
                  record.get("result", {}).get("correct"),
                  json.dumps(brief), *record["notes"][:4], sep=" | ",
                  flush=True)
            failures = failures + 1 if "stderr" in record else 0
            if "stderr" in record:
                print(record["stderr"][-1500:], flush=True)


if __name__ == "__main__":
    main()
