#!/usr/bin/env python3
"""Device time of one bank bucket's scoring program at each batch size B
(run on the chip; no test and no benchmark runs this):

    python3 tools/bucket_ladder.py --members 4096 --tags 300 --rows 256 \
        --batches 1,2,4,8,16,32,64 --out chiprun_out/ladder.json

Builds ONE ``server/bank.py::_Bucket`` of ``--members`` randomly
initialised members as ``ModelBank`` would (same ``finalize()``, the
backend's own kernel decisions), prints where one stacked kernel lives
(``leaf.format``), then for every B runs the bucket program ``--repeats``
times on members drawn from the whole bank under ONE profiler session and
reads each run's duration from the device trace (the ``XLA Modules`` line,
module ``jit_score``): median, min and max milliseconds per B, beside the
host's wall time around ``block_until_ready``. It fails where JAX finds no
TPU: a CPU timing of this program says nothing.

``PYTHONPATH`` decides which checkout's program is measured, so the same
file measures a parent commit unpacked elsewhere.
"""

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time

import numpy as np

MODULE = "jit_score"
KINDS = {
    "dense": dict(registry_type="AutoEncoder", kind="feedforward_hourglass", lookback=1),
    "lstm": dict(registry_type="LSTMAutoEncoder", kind="lstm_hourglass", lookback=12),
}


def build_bucket(kind: str, members: int, tags: int, bank_dtype: str):
    import jax
    import jax.numpy as jnp

    from gordo_components_tpu.models.register import lookup_factory
    from gordo_components_tpu.ops.pallas_score import resolve_bank_kernel_mode
    from gordo_components_tpu.server import bank as bank_mod

    spec = KINDS[kind]
    module = lookup_factory(spec["registry_type"], spec["kind"])(tags)
    sample = jnp.zeros((1, tags) if spec["lookback"] == 1 else (1, spec["lookback"], tags))
    rng = np.random.default_rng(0)
    variants = []  # a few distinct members, repeated: answers differ by member
    for k in range(4):
        params = module.init(jax.random.PRNGKey(k), sample)
        variants.append(jax.tree.map(np.asarray, params))
    bucket = bank_mod._Bucket(
        spec["kind"], tags, {}, registry_type=spec["registry_type"],
        lookback=spec["lookback"], bank_dtype=bank_dtype,
        kernel_mode=resolve_bank_kernel_mode(),
    )
    for i in range(members):
        bucket.add(
            bank_mod._BankEntry(
                name=f"m{i}", registry_type=spec["registry_type"], kind=spec["kind"],
                factory_kwargs={}, compute_dtype="float32", n_features=tags,
                lookback=spec["lookback"], target_offset=0, params=variants[i % 4],
                in_shift=rng.normal(size=tags).astype(np.float32),
                in_scale=(1 + rng.random(tags)).astype(np.float32),
                err_shift=rng.normal(size=tags).astype(np.float32),
                err_scale=(1 + rng.random(tags)).astype(np.float32),
            )
        )
    bucket.finalize()
    return bucket


def module_runs(log_dir: str):
    """(start_ns, duration_ns) of every ``jit_score`` run in the trace."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    runs = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                runs += [
                    (e.start_ns, e.duration_ns) for e in line.events
                    if e.name.split("(")[0] == MODULE
                ]
    return sorted(runs)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--kind", choices=sorted(KINDS), default="dense")
    parser.add_argument("--members", type=int, default=4096)
    parser.add_argument("--tags", type=int, default=300)
    parser.add_argument("--rows", type=int, default=256)
    parser.add_argument("--bank-dtype", default="float32")
    parser.add_argument("--batches", default="1,2,4,8,16,32,64")
    parser.add_argument("--repeats", type=int, default=20)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU here ({device.platform}): nothing to measure", file=sys.stderr)
        return 1
    batches = [int(b) for b in args.batches.split(",")]
    t0 = time.monotonic()
    bucket = build_bucket(args.kind, args.members, args.tags, args.bank_dtype)
    jax.block_until_ready(bucket.params)
    built_s = time.monotonic() - t0
    kernel = max(jax.tree.leaves(bucket.params), key=lambda a: a.size)
    print(f"bucket of {args.members} built and placed in {built_s:.1f}s; largest leaf "
          f"{kernel.dtype}{list(kernel.shape)} lives as {kernel.format.layout}")
    stats = device.memory_stats() or {}
    rng = np.random.default_rng(1)
    X = {B: rng.normal(size=(B, args.rows, args.tags)).astype(np.float32) for B in batches}
    draw = lambda B: rng.integers(0, args.members, size=B).astype(np.int32)
    compile_s = {}
    for B in batches:  # every shape compiled (or loaded) before the session
        t0 = time.monotonic()
        jax.block_until_ready(bucket.score_batch(draw(B), X[B], X[B]))
        compile_s[B] = time.monotonic() - t0
    host_ms = {B: [] for B in batches}
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir)
        for B in batches:
            for _ in range(args.repeats):
                idx = draw(B)
                t0 = time.monotonic()
                jax.block_until_ready(bucket.score_batch(idx, X[B], X[B]))
                host_ms[B].append((time.monotonic() - t0) * 1e3)
        jax.profiler.stop_trace()
        runs = module_runs(log_dir)
    if len(runs) != len(batches) * args.repeats:
        print(f"the trace holds {len(runs)} runs of {MODULE}, expected "
              f"{len(batches) * args.repeats}", file=sys.stderr)
        return 1
    rows = []
    for k, B in enumerate(batches):
        ms = [d / 1e6 for _, d in runs[k * args.repeats:(k + 1) * args.repeats]]
        rows.append({
            "B": B, "device_ms_median": statistics.median(ms), "device_ms_min": min(ms),
            "device_ms_max": max(ms), "host_ms_median": statistics.median(host_ms[B]),
            "first_call_s": compile_s[B],
        })
        print(json.dumps(rows[-1]))
    result = {
        "device": {"platform": device.platform, "kind": device.device_kind},
        "kind": args.kind, "members": args.members, "tags": args.tags, "rows": args.rows,
        "bank_dtype": args.bank_dtype, "layout": str(kernel.format.layout),
        "built_s": built_s, "bytes_in_use": stats.get("bytes_in_use"), "ladder": rows,
    }
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
