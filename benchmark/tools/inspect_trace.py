"""Look at a trace by hand before trusting the reducer: which planes and
lines it has, how the device's ops are named, and what stats they carry.

    python3 benchmark/tools/inspect_trace.py <trace dir or .xplane.pb> [top]
"""

from __future__ import annotations

import glob
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> None:
    from benchmark import trace_reduce

    path = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 40
    if os.path.isdir(path):
        (path,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True)
    profile = trace_reduce.load_profile(path)
    for plane in profile:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            if len(events) == 0:
                continue
            total, sample = {}, {}
            for e in events:
                total[e.name] = total.get(e.name, 0.0) + (e.end - e.start)
                sample.setdefault(e.name, e)
            for name in sorted(total, key=lambda n: -total[n])[:top]:
                stats = {k: str(v)[:160] for k, v in sample[name].stats.items()}
                print(f"    {total[name] / 1e6:10.3f} ms  {name[:100]!r}  "
                      f"{stats}")


if __name__ == "__main__":
    main()
