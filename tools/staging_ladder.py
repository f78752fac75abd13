"""How long a gang's block takes to be whole on the device, from its
members' host arrays: the whole-block way (``fleet_stack_pad``, one
``device_put``: what a one-piece gang does) against ``stage_gang`` over a
ladder of piece sizes, for gang shapes ``members x rows x padded_rows x
tags`` (``r`` after a shape draws ragged row counts). On the chip:
``python3 tools/staging_ladder.py`` (PERF.md section 6, PR 32). ``host`` is
when the call returned, ``ready`` when the block was whole on the device."""

import argparse
import json
import os
import statistics
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gordo_components_tpu.native import fleet_stack_pad  # noqa: E402
from gordo_components_tpu.parallel import fleet  # noqa: E402
from gordo_components_tpu.parallel.mesh import fleet_mesh, shard_model_axis  # noqa: E402


def timed(fn, reps):
    fn().block_until_ready()  # compiles, first touches
    out = []
    for _ in range(reps):
        t0 = time.monotonic()
        X = fn()
        t1 = time.monotonic()
        X.block_until_ready()
        out.append((t1 - t0, time.monotonic() - t0))
        del X
    return {
        "host_ms": round(1e3 * statistics.median(o[0] for o in out), 1),
        "ready_ms": round(1e3 * statistics.median(o[1] for o in out), 1),
        "ready_all_ms": [round(1e3 * o[1], 1) for o in out],
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=["640x1440x1600x300"])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--pieces-mb", type=int, nargs="*", default=[64, 128, 256, 512])
    args = ap.parse_args(argv)
    sharding = shard_model_axis(fleet_mesh(1))
    rng = np.random.default_rng(0)
    out = {"device": jax.devices()[0].device_kind}
    for shape in args.shapes:
        M, rows, R, F = (int(v) for v in shape.rstrip("r").split("x"))
        lengths = rng.integers(rows - 100, rows + 1, M) if shape.endswith("r") else [rows] * M
        members = [rng.random((int(n), F), dtype=np.float32) for n in lengths]
        out[shape] = {"block_bytes": 4 * M * R * F}

        def show(name, fn):
            out[shape][name] = timed(fn, args.reps)
            print(shape, name, json.dumps(out[shape][name]), flush=True)

        show("whole block", lambda: jax.device_put(fleet_stack_pad(members, M, R, F)[0], sharding))
        Xs = fleet_stack_pad(members, M, R, F)[0]
        show("the link alone (block already stacked)", lambda: jax.device_put(Xs, sharding))
        del Xs
        fleet.STAGING_MEMBER_BYTES = 0  # every shape by both ways: the rule is what is measured
        for mb in args.pieces_mb:
            fleet.STAGING_PIECE_BYTES = mb << 20
            pieces = fleet.stage_gang(members, M, R, F, sharding)[2]["pieces"]
            show(f"stage_gang, {mb} MB a piece ({pieces} pieces)",
                 lambda: fleet.stage_gang(members, M, R, F, sharding)[0])
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/staging_ladder.json", "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
