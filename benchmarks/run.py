#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. It makes its inputs and weights from ``--seed``,
warms the shapes this cell uses (set-up), measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints one
JSON object as the last line of standard output. It exits non-zero, with no
result line, when JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.time()

import argparse
import importlib
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for path in (BENCH_DIR, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from harness import adapter, common, spec

    cell = spec.Cell(args.workload)
    seconds = args.seconds
    if seconds is None:
        seconds = float(spec.load_benchmark()["run_seconds"])
    cache_dir = adapter.compile_cache_dir()
    device = common.device_block()
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(
            f"{args.workload} needs {cell.chips} TPU chip(s); JAX reports {device}",
            file=sys.stderr,
        )
        return 2
    print(f"device {device}, compile cache {cache_dir}", flush=True)
    driver = importlib.import_module("harness." + cell.traffic["driver"])
    driver.run(cell, args.seed, seconds, bool(args.trace), T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
