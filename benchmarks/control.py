#!/usr/bin/env python3
"""The readings ``correct``'s limits are set from (PERF.md section 2), on
the chip at the cell's own size:

    python3 benchmarks/control.py --workload <name> --seeds 3 [--program 12]

For each seed it reads every number of the cell for: the control (the
reference computed in bfloat16, the nearest precision below the one the
configuration states; a refit cell keeps the parameters and Adam's moments
in bfloat16 too, and is also read with only the matmuls in bfloat16, which
reads like the program), the reference at ``highest`` matmul precision (how
far the platform default is from exact float32), and each planted fault.
``--program N`` also reads the program itself on N seeds in this one
process (a refit cell: one fit per seed; a serve cell: a short window at the
cell's own load). The benchmark's own runs never run this.
"""

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
for path in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if path not in sys.path:
        sys.path.insert(0, path)


def refit_readings(cell, seeds, program_seeds):
    from harness import refit

    out = []
    for seed in sorted(set(seeds) | set(program_seeds)):
        gang = refit.Gang(cell, seed)
        row = {"seed": seed}
        if seed in program_seeds:
            models, stats, wall = gang.fit()
            gang.check_padding(stats["buckets"][0])
            got = gang.sampled(models)
            del models
            gc.collect()
            row["fit_s"] = wall
        want = gang.reference()
        if seed in program_seeds:
            row["program"] = refit._compare(cell.config, got, want, gang.sample_rows)
            row["program_loss_gap_by_epoch"] = np.max([
                np.abs(m["losses"] - want["losses"][s]) / np.abs(want["losses"][s])
                for s, m in enumerate(got.values())
            ], axis=0).tolist()
        if seed in seeds:
            for label, how in (
                ("control_bf16", dict(dtype="bfloat16", state_dtype="bfloat16")),
                ("bf16_compute_only", dict(dtype="bfloat16")),
                ("highest", dict(precision="highest")),
                ("fault_half_batch", dict(fault="half_batch")),
                ("fault_no_update", dict(fault="no_update")),
            ):
                other = refit.reference_as_program(gang.reference(**how))
                row[label] = refit._compare(cell.config, other, want, gang.sample_rows)
        print(json.dumps(row), flush=True)
        out.append(row)
        del gang
        gc.collect()
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--program", type=int, default=0)
    parser.add_argument("--first-seed", type=int, default=2_200_000_011)
    args = parser.parse_args(argv)

    from harness import adapter, common, spec

    cell = spec.Cell(args.workload)
    adapter.compile_cache_dir()
    print(f"device {common.device_block()}", flush=True)
    seeds = [args.first_seed + 7 * i for i in range(args.seeds)]
    program_seeds = [args.first_seed + 7 * i for i in range(args.program)]
    t0 = time.time()
    if cell.traffic["driver"] == "refit":
        rows = refit_readings(cell, seeds, program_seeds)
    else:
        from harness import serve

        rows = serve.control_readings(cell, seeds)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"control_{args.workload}.jsonl"), "a") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")
    print(f"control readings: {time.time() - t0:.1f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
