"""Spreads of the end-to-end metrics over the sets ``run_sets.py`` made:
per set the distance between the first and the third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, the
wider of the sets, and five times the widest: the rule a bound is set by.

    python3 benchmark/tools/spread.py chiprun_out/<cell>.jsonl [...]
"""

import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main():
    for path in sys.argv[1:]:
        with open(path) as f:
            records = [json.loads(line) for line in f if line.strip()]
        runs = [r for r in records if "result" in r and not r["trace"]]
        bad = [(r["seed"], r["rc"]) for r in records
               if "result" not in r or not r["result"]["correct"]]
        print(f"{path}: {len(runs)} runs, not correct or failed: {bad}")
        sets = sorted({r["set"] for r in runs})
        names = sorted(runs[0]["result"]["metrics"])
        for name in names:
            per_set, medians = [], []
            for s in sets:
                values = [r["result"]["metrics"][name]["value"]
                          for r in runs if r["set"] == s]
                # The first run of a cell compiles: its set-up is apart.
                if name == "setup_s" and s == sets[0]:
                    values = values[1:]
                medians.append(statistics.median(values))
                per_set.append(spread(values) if len(values) > 1
                               else float("nan"))
            print(f"  {name:24s} medians "
                  + " ".join(f"{m:.6g}" for m in medians)
                  + "  spreads " + " ".join(f"{100 * s:.3f}%"
                                            for s in per_set)
                  + f"  -> 5x widest {500 * max(per_set):.2f}%")


if __name__ == "__main__":
    main()
