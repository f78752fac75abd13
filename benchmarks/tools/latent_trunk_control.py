#!/usr/bin/env python3
"""The readings ``axk1_trunk300.week``'s limits are set from (PERF.md
section 2), on the chip at the cell's own size, with no server:

    python3 benchmarks/tools/latent_trunk_control.py --seeds 3 [--requests 1] \
        [--only fault_no_q_norm,fault_softmax_router]

``trunk_control.py`` for the cell's own driver
(``harness/latent_trunk_serve.py``). For each seed: the reference with
bfloat16 operands (what the configuration states), the control (float8 e4m3
operands, one precision below) and each planted fault, each against the
reference. The program's own readings are the ``checks`` every run prints."""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if path not in sys.path:
        sys.path.insert(0, path)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="axk1_trunk300.week")
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--requests", type=int, default=None)
    parser.add_argument("--first-seed", type=int, default=2_300_000_033)
    parser.add_argument("--only", default=None, help="comma-separated labels; default: every variant")
    args = parser.parse_args(argv)

    from harness import adapter, common, latent_trunk_serve, spec

    cell = spec.Cell(args.workload)
    adapter.compile_cache_dir()
    print(f"device {common.device_block()}", flush=True)
    t0 = time.time()
    rows = latent_trunk_serve.control_readings(
        cell, [args.first_seed + 7 * i for i in range(args.seeds)], args.requests,
        args.only.split(",") if args.only else None,
    )
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"control_{args.workload}.jsonl"), "a") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")
    print(f"control readings: {time.time() - t0:.1f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
