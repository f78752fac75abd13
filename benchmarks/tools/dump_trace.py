#!/usr/bin/env python3
"""Look at one profiler trace by hand: planes, lines, and the event names
that take most time on each line.

    python3 benchmarks/tools/dump_trace.py <file.xplane.pb>
"""

import sys
from collections import Counter


def main(path: str) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            total = Counter()
            for e in events:
                total[e.name] += e.duration_ns
            print(f"  line {line.name!r}: {len(events)} events")
            for name, ns in total.most_common(8):
                print(f"    {ns / 1e6:10.3f} ms  {name[:100]}")


if __name__ == "__main__":
    main(sys.argv[1])
