#!/usr/bin/env python3
"""The readings ``glm52_trunk300.week``'s limits are set from (PERF.md
section 2), on the chip at the cell's own size, with no server:
``trunk_control.py`` with the cell's own driver's reference
(``harness/selected_latent_trunk_serve.py``; ``trunk_control.py`` asks
``trunk_serve`` for the readings). For each seed: the reference with
bfloat16 operands (what the configuration states), the control (float8 e4m3
operands, one precision below) and each planted fault, each against the
reference; 2 minutes a seed at ``--requests 1``.

    python3 benchmarks/tools/selected_latent_trunk_control.py --seeds 2 --requests 1
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if path not in sys.path:
        sys.path.insert(0, path)

if __name__ == "__main__":
    import trunk_control
    from harness import selected_latent_trunk_serve, trunk_serve

    # the one seam: the tool asks ``trunk_serve`` for the readings
    trunk_serve.control_readings = selected_latent_trunk_serve.control_readings
    sys.exit(trunk_control.main(
        ["--workload", "glm52_trunk300.week", "--first-seed", "3500000033", *sys.argv[1:]]))
