"""Print the ``scopes.json`` a traced run leaves beside its trace
(``benchmark/scope_reduce.py``) as the tables PERF.md section 5 holds:
time by phase and by kernel, the share of mixed fusions, the longest
scope paths, and the longest families of operations with the scopes
each belongs to. Milliseconds are per step on the first chip.

    python3 benchmark/tools/scope_table.py <scopes.json> [top]
"""

import json
import re
import sys


def fold(path):
    """``backbone/block_17/attn`` -> ``backbone/block_N/attn``: what a
    model repeats under numbered names, as one."""
    return re.sub(r"\d+", "N", path)


def folded(table):
    out = {}
    for key, ns in table.items():
        out[fold(key)] = out.get(fold(key), 0) + ns
    return sorted(out.items(), key=lambda kv: -kv[1])


def main():
    with open(sys.argv[1]) as f:
        scopes = json.load(f)
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 10
    steps, busy = scopes["steps"], scopes["busy_ns"]

    def row(label, ns):
        print(f"  {ns / 1e6 / steps:10.3f} ms {100 * ns / busy:6.2f}%  "
              f"{label}")

    print(f"chip {scopes['device']}, {steps} steps, busy")
    row("busy (union of the operations)", busy)
    print("by phase")
    for phase, ns in scopes["by_phase"].items():
        row(phase, ns)
    row("sum of the phases", sum(scopes["by_phase"].values()))
    print("fusions that hold instructions of two phases (counted above "
          "under their root's)")
    row("mixed", scopes["mixed_ns"])
    for phases, ns in sorted(scopes["mixed"].items(), key=lambda kv: -kv[1]):
        row("  " + phases, ns)
    if scopes["flash_seen"]:
        print("flash attention")
        for kernel, ns in sorted(scopes["by_kernel"].items()):
            row(kernel, ns)
        row("sum of the kernels", sum(scopes["by_kernel"].values()))
        row("hvd_flash, not a kernel (glue)", scopes["flash_glue_ns"])
    print(f"the {top} longest scope paths")
    paths = {f"{phase} {path}": ns
             for phase, table in scopes["by_path"].items()
             for path, ns in table.items()}
    for path, ns in folded(paths)[:top]:
        row(path, ns)
    print(f"the {top} longest families of operations, and whose they are")
    families = sorted(scopes["by_op"].items(),
                      key=lambda kv: -sum(kv[1].values()))
    for family, table in families[:top]:
        row(family, sum(table.values()))
        for path, ns in folded(table)[:3]:
            row("    " + path, ns)


if __name__ == "__main__":
    main()
