#!/usr/bin/env python3
"""Find the knee of an open-loop serve cell once, on the chip: one server,
the mix's generator at each of a few fixed rates in turn.

    python3 benchmarks/sweep.py --workload dense300.live --rates 50,100,200 --seconds 10

Prints per rate: completed/s, p50/p99 from due time, the generator's own
lateness, how long the backlog took to drain after the window closed, and
the engine's queue depth then. The highest rate "sustained" is the highest
whose backlog does not grow (drain under a second, completed/s = offered).
"""

import argparse
import asyncio
import io
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
for path in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if path not in sys.path:
        sys.path.insert(0, path)


async def sweep(cell, seed, rates, seconds):
    import numpy as np

    from harness import common, serve

    work = common.work_dir()
    rows = []
    try:
        served = await serve.start_server(cell, seed, False, True, work)
        try:
            for i, rate in enumerate(rates):
                traffic = dict(cell.traffic, rate_rps=rate)
                got = await served.window(traffic, seconds, seed=seed + 1000 * i)
                arrays = np.load(io.BytesIO(got["blob"]))
                s, lat, late = got["summary"], arrays["latency_ms"], arrays["late_ms"]
                row = {
                    "rate_rps": rate, "attempted": s["attempted"], "failed": s["failed"],
                    "completed_per_s": s["completed_in_window"] / s["window_s"],
                    "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
                    "p95_ms": float(np.percentile(lat, 95)) if len(lat) else None,
                    "p99_ms": float(np.percentile(lat, 99)) if len(lat) else None,
                    "gen_late_p99_ms": float(np.percentile(late, 99)) if len(late) else None,
                    "drain_s": s["drain_s"], "queue_depth_after": got["queue_depth"],
                    "requests_per_batch": got["engine"]["requests"] / max(1, got["engine"]["batches"]),
                }
                print(json.dumps(row), flush=True)
                rows.append(row)
        finally:
            await served.runner.cleanup()
    finally:
        common.remove(work)
    return rows


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True, help="comma-separated requests/s")
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--seed", type=int, default=2_300_000_003)
    args = parser.parse_args(argv)

    from harness import adapter, common, spec

    cell = spec.Cell(args.workload)
    adapter.compile_cache_dir()
    print(f"device {common.device_block()}", flush=True)
    rates = [float(r) for r in args.rates.split(",")]
    rows = asyncio.run(sweep(cell, args.seed, rates, args.seconds))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"sweep_{args.workload}.jsonl"), "a") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
