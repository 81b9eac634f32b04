"""Read the numbers a limit of ``correct`` is set from, on the chip, in
one process: for each seed the program's trail, the reference's, and
the control's, which is the reference put in the program's place in the
precision below the one the configuration states.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 \\
        [--precisions int8] [--program 0|1] [--out chiprun_out/x.json]

Each line it prints is one comparison against the float32 reference;
the benchmark's own runs never run this.
"""

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(session, seed, precisions, with_program=True, keep=None):
    """{who: rows} for one seed, ``who`` being "program" or a control's
    precision; rows as ``check.compare`` gives them. ``keep``, a dict,
    is given every trail leaf by leaf under the seed."""
    from benchmark import check
    feed = session.feed(seed)
    trails = {}
    if with_program:
        state, trails["program"] = session.first_steps(seed, feed)
        del state
    reference = session.follow(seed, feed)
    for precision in precisions:
        trails[precision] = session.follow(seed, feed, precision)
    limits = session.cfg["limits"]
    out = {who: check.compare(trail, reference, limits)
           for who, trail in trails.items()}
    if keep is not None:
        trails["reference"] = reference
        keep[seed] = {who: dataclasses.asdict(t)
                      for who, t in trails.items()}
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--precisions", default=None)
    parser.add_argument("--program", type=int, default=1)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    sys.path.insert(0, ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark import harness
    session = harness.Session(ROOT, args.workload)
    precisions = ([p for p in args.precisions.split(",") if p != "none"]
                  if args.precisions
                  else [session.cfg["control_precision"]])
    table, trails = [], {}
    for seed in (int(s) for s in args.seeds.split(",")):
        for who, (correct, rows) in readings(
                session, seed, precisions, bool(args.program),
                keep=trails).items():
            line = {"seed": seed, "who": who, "correct": correct,
                    **{r["name"]: r["value"] for r in rows},
                    "where": {r["name"]: r["where"] for r in rows}}
            table.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"table": table, "trails": trails}, f)
    session.hvd.shutdown()


if __name__ == "__main__":
    main()
