"""Print what an .xplane.pb holds, for reading one trace by hand: every
plane, its lines, how many events each has, their extent, and the names
that took most time on each line.

    python benchmark/trace_dump.py <trace dir or .xplane.pb> [top_n]
"""
from __future__ import annotations

import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv) -> int:
    from jax.profiler import ProfileData

    from benchmark.trace_reduce import find_xplane

    path = argv[0] if argv[0].endswith(".pb") else find_xplane(argv[0])
    top_n = int(argv[1]) if len(argv) > 1 else 12
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            total, count, lo, hi = defaultdict(float), 0, None, None
            for ev in line.events:
                count += 1
                total[ev.name] += ev.duration_ns
                lo = ev.start_ns if lo is None else min(lo, ev.start_ns)
                end = ev.start_ns + ev.duration_ns
                hi = end if hi is None else max(hi, end)
            if not count:
                continue
            print(f"  LINE {line.name!r}: {count} events, "
                  f"{lo / 1e9:.6f}..{hi / 1e9:.6f} s")
            for name, ns in sorted(total.items(),
                                   key=lambda kv: -kv[1])[:top_n]:
                print(f"      {ns / 1e6:12.3f} ms  {name[:110]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
