"""Look at a profiler trace by hand: its planes, their lines, and the
events that took most time, with the statistics each carries.

    python3 benchmark/tools/trace_summary.py <trace_dir> [top]
"""

import glob
import os
import sys


def main():
    from jax.profiler import ProfileData
    trace_dir = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 25
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            span = (max(e.start_ns + e.duration_ns for e in events)
                    - min(e.start_ns for e in events))
            print(f"  LINE {line.name!r}: {len(events)} events over "
                  f"{span / 1e6:.3f} ms")
            sums, sample = {}, {}
            for e in events:
                sums[e.name] = sums.get(e.name, 0) + e.duration_ns
                sample.setdefault(e.name, e)
            for name, ns in sorted(sums.items(), key=lambda kv: -kv[1])[:top]:
                stats = {k: str(v)[:60] for k, v in sample[name].stats}
                print(f"    {ns / 1e6:10.3f} ms  {name[:70]!r}  {stats}")


if __name__ == "__main__":
    main()
