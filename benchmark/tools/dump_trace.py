#!/usr/bin/env python3
"""Print what is in a profiler trace, to look at one by hand.

    python3 benchmark/tools/dump_trace.py <file.xplane.pb | directory>

Planes and their lines with event counts; for the first device plane,
the first events of every line with their statistics, and the names that
took most time on each line.
"""

from __future__ import annotations

import collections
import glob
import os
import sys


def main(path):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    print("trace:", path, os.path.getsize(path), "bytes")
    profile = ProfileData.from_file(path)
    shown = False
    for plane in profile.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            if not plane.name.startswith("/device:") or shown:
                continue
            for e in events[:12]:
                print(f"      {e.name!r} start {e.start_ns} dur "
                      f"{e.duration_ns} stats {dict(e.stats)}")
            total = collections.Counter()
            count = collections.Counter()
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
            for name, ns in total.most_common(40):
                print(f"      TOP {ns * 1e-6:10.3f} ms x{count[name]:5d} "
                      f"{name!r}")
        if plane.name.startswith("/device:"):
            shown = True


if __name__ == "__main__":
    main(sys.argv[1])
