#!/usr/bin/env python3
"""Print what a profiler trace holds: planes, lines, event counts, and the
longest events of each device line with their stats.

    python3 bench/inspect_trace.py <trace dir or .xplane.pb> [--top 15]
"""

from __future__ import annotations

import argparse
import os
from collections import defaultdict


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench.trace_reduce import find_xplane

    path = args.path if args.path.endswith(".pb") else find_xplane(args.path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            total = defaultdict(float)
            count = defaultdict(int)
            for e in evs:
                total[e.name] += e.duration_ns
                count[e.name] += 1
            print(f"  LINE {line.name!r}: {len(evs)} events")
            if not plane.name.startswith("/device") and len(evs) > 5000:
                continue
            for name, ns in sorted(total.items(), key=lambda kv: -kv[1])[
                    :args.top]:
                ex = next(e for e in evs if e.name == name)
                stats = {k: v for k, v in dict(ex.stats).items()
                         if isinstance(v, (str, int, float))}
                print(f"    {ns / 1e6:10.3f} ms x{count[name]:6d} {name[:80]}"
                      f"  {str(stats)[:300]}")


if __name__ == "__main__":
    main()
