"""Print what a profiler trace holds, to write or check kernels.json.

    python3 perfbench/inspect_trace.py <trace dir or .xplane.pb> [top]

For every plane and line: the number of events; for device lines, the
``top`` event names by summed duration, each with one event's stats
(long strings cut short). Needs nothing but JAX; runs on any host.
"""
from __future__ import annotations

import os
import sys
from collections import defaultdict


def main(argv) -> int:
    from jax.profiler import ProfileData
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from perfbench import devtrace
    path = argv[0]
    if os.path.isdir(path):
        path = devtrace.newest_xplane(path)
    top = int(argv[1]) if len(argv) > 1 else 25
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            if not plane.name.startswith("/device:") or not evs:
                continue
            tot, one = defaultdict(float), {}
            for e in evs:
                tot[e.name] += e.duration_ns
                one.setdefault(e.name, e)
            print(f"    span {evs[0].start_ns:.0f} .. "
                  f"{max(e.start_ns + e.duration_ns for e in evs):.0f} ns")
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                n = sum(1 for e in evs if e.name == name)
                stats = {k: (v[:300] if isinstance(v, str) else v)
                         for k, v in one[name].stats}
                print(f"    {ns * 1e-9:10.6f} s {n:7d}x {name}\n"
                      f"        {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
