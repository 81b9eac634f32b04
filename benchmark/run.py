"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>: one run of one cell; the last line of stdout is the
result as one JSON object. See BENCHMARK.json and PERF.md."""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    sys.path.insert(0, ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark import harness
    result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), T0)
    import horovod_tpu as hvd
    hvd.shutdown()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
