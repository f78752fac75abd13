#!/usr/bin/env python3
"""Compile ``keye_trunk300.week``'s bucket programs for a described
``v5e:2x2`` chip at the real sizes, with no chip, and print
``memory_analysis()`` (``rehearse_compile.py`` for a bucket with shared
leaves: its members hold the projections only, and the trunk's layer
program is compiled once whatever the depth).

    JAX_PLATFORMS=cpu python3 benchmarks/tools/rehearse_trunk.py [--batches 1,2]

Nothing runs: no time or rate comes out of this, and a compile that passes
is not a chip run."""

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if path not in sys.path:
        sys.path.insert(0, path)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", default="keye_trunk300.week")
    parser.add_argument("--batches", default=None, help="comma-separated; default: the mix's warm_batches")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import rehearse_compile
    from gordo_components_tpu.server import bank as bank_mod
    from harness import adapter, spec, weights

    cell = spec.Cell(args.workload)
    config, traffic = cell.config, cell.traffic
    chip = rehearse_compile.describe_chip()
    members = int(config["bank_members"])
    det = adapter.make_member(config, 0, 0)
    est = det.base_estimator.steps[-1][1]
    module = est.module
    # the entry as the bank extracts it, but for the trunk, which is only shapes here
    type(est).trunk_params = property(lambda self: {"layers": [], "final_norm": np.ones((module.hidden_size,), np.float32)})
    entry, why = bank_mod._extract_entry("m", det)
    if entry is None:
        raise SystemExit(f"not bankable: {why}")
    bucket = bank_mod._Bucket(
        entry.kind, entry.n_features, entry.factory_kwargs, registry_type=entry.registry_type,
        lookback=entry.lookback, target_offset=entry.target_offset, kernel_mode="pallas",
        shared=entry.shared,
    )
    bucket.add(entry)
    bucket.finalize()
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    grow = lambda a: on((members,) + a.shape[1:], a.dtype)
    params = jax.tree.map(grow, bucket.params)
    scalers = tuple(grow(s) for s in bucket.scalers)
    layer = {
        name: on(shape, jnp.float32 if len(shape) == 1 else jnp.bfloat16)
        for name, shape in module.layer_shapes().items()
    }
    T = bucket.rows_per_call(int(traffic["request_rows"]), 8192)
    batches = [int(b) for b in args.batches.split(",")] if args.batches else traffic["warm_batches"]
    bank_bytes = sum(np.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves((params, scalers)))
    trunk_bytes = int(config["num_hidden_layers"]) * sum(
        np.prod(a.shape) * a.dtype.itemsize for a in layer.values())
    print(f"{cell.name}: bank of {members} stored {bank_bytes / 1e9:.2f} GB, trunk "
          f"{trunk_bytes / 1e9:.2f} GB, {weights.n_params(config)} parameters a machine", flush=True)
    for B in batches:
        X = on((B, T, entry.n_features), jnp.float32)
        idx, n_valid = on((B,), jnp.int32), on((B,), jnp.int32)
        state = on((B, T, module.hidden_size), jnp.float32)
        for label, lowered in (
            ("score_enter", lambda: bucket._enter.lower(params, *scalers[:2], idx, X)),
            ("score_layer", lambda: bucket._layer.lower(layer, state, n_valid)),
            ("score", lambda: bucket._score.lower(
                params, *scalers, idx, X, X, state, on((module.hidden_size,), jnp.float32))),
        ):
            t0 = time.time()
            compiled = lowered().compile()
            rehearse_compile._report(
                f"{cell.name} batch {B} x {T} rows: {label} (compiled in {time.time() - t0:.0f}s here)",
                compiled,
            )
        print(f"  the bucket's own count for this batch: {module.program_bytes(B, T) / 1e9:.2f} GB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
