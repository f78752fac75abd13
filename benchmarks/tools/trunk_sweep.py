#!/usr/bin/env python3
"""``sweep.py`` for a cell whose bank is a shared trunk: the same sweep (one
server, the mix's generator at each of a few fixed rates in turn), with the
server started by the cell's own driver, which stages the trunk artifact
first (``harness/trunk_serve.py``; ``sweep.py`` starts every server through
``serve.py``).

    python3 benchmarks/tools/trunk_sweep.py --workload keye_trunk300.week --rates 1,2,3 --seconds 10
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if path not in sys.path:
        sys.path.insert(0, path)

if __name__ == "__main__":
    import sweep
    from harness import serve, trunk_serve

    # the one seam: the sweep asks ``serve`` for its server
    serve.start_server = trunk_serve.start_server
    sys.exit(sweep.main())
