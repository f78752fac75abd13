#!/usr/bin/env python3
"""Compile the cells' device programs for a described ``v5e:2x2`` chip at
the real sizes, with no chip, and print ``memory_analysis()`` (on-chip-
measurement guide, section 2): what the chip's compiler refuses, and how
many bytes each program needs, before any chip time is spent.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile.py [workload ...] [--members N]

Nothing runs: no time or rate comes out of this, and a compile that passes
is not a chip run. It counts one program at a time, not what else the
process keeps on the device.
"""

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
for path in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if path not in sys.path:
        sys.path.insert(0, path)


def describe_chip():
    """One described v5e device (inside a function: describing a topology
    loads libtpu, which one process at a time may do)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def _report(label: str, compiled) -> float:
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    print(
        f"{label}: arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, outputs "
        f"{mem.output_size_in_bytes / 1e9:.2f} GB (aliased {mem.alias_size_in_bytes / 1e9:.2f}), "
        f"temp {mem.temp_size_in_bytes / 1e9:.2f} GB -> {total / 1e9:.2f} GB of 16 GB; "
        f"tpu_custom_call x{compiled.as_text().count('tpu_custom_call')}",
        flush=True,
    )
    return total


def rehearse_refit(cell, chip, members: int) -> None:
    import jax
    import jax.numpy as jnp

    from gordo_components_tpu.models.register import lookup_factory
    from gordo_components_tpu.parallel import fleet
    from harness import adapter, refit

    config = cell.config
    F, bs = int(config["tags_per_machine"]), int(config["batch_size"])
    pad = cell.traffic["padding"]
    M = refit.ladder_up(members, **pad["members"])
    items = refit.ladder_up(-(-int(cell.traffic["rows"]) // bs), **pad["batches"]) * bs
    # the estimator as the configuration's pipeline definition names it
    path, est = adapter.estimator_entry(config["model"])
    kwargs = {k: est[k] for k in ("encoding_layers", "compression_factor", "func")}
    module = lookup_factory(path.rsplit(".", 1)[1], est["kind"])(F, compute_dtype="float32", **kwargs)
    seq = (int(est["lookback_window"]), 0) if "lookback_window" in est else None
    rows = items + (0 if seq is None else seq[0] - 1)
    os.environ["GORDO_SEQ_LAYOUT"] = "time_major"
    progs = fleet._bucket_programs(
        module, "adam", float(config["learning_rate"]), bs, seq, "mse", 1.0, 1.0
    )
    on = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree
    )
    f32 = jnp.float32
    X = jax.ShapeDtypeStruct((M, rows, F), f32, sharding=chip)
    mask = jax.ShapeDtypeStruct((M, items), f32, sharding=chip)
    active = jax.ShapeDtypeStruct((M,), f32, sharding=chip)
    rngs = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0), M))
    sample = jax.ShapeDtypeStruct((M, F) if seq is None else (M, seq[0], F), f32)
    states = on(jax.eval_shape(progs.init_stacked, rngs, sample))
    label = f"{cell.name} gang of {M} x {rows} rows"
    _report(f"{label}: epoch program", progs.run_epoch.lower(states, X, mask, active).compile())
    _report(
        f"{label}: error scalers",
        progs.fit_error_scalers.lower(states.params, X, mask).compile(),
    )


def rehearse_serve(cell, chip, members: int) -> None:
    import jax
    import jax.numpy as jnp

    from gordo_components_tpu.server import bank as bank_mod
    from harness import adapter

    config, traffic = cell.config, cell.traffic
    os.environ["GORDO_SEQ_LAYOUT"] = "time_major"
    os.environ["GORDO_SEQ_KERNEL"] = "pallas"
    det = adapter.make_member(config, 0, 0)
    entry, why = bank_mod._extract_entry("m", det)
    if entry is None:
        raise SystemExit(f"not bankable: {why}")
    bucket = bank_mod._Bucket(
        entry.kind, entry.n_features, entry.factory_kwargs,
        compute_dtype=entry.compute_dtype, registry_type=entry.registry_type,
        lookback=entry.lookback, target_offset=entry.target_offset, kernel_mode="pallas",
    )
    bucket.add(entry)
    bucket.finalize()
    grow = lambda a: jax.ShapeDtypeStruct((members,) + a.shape[1:], a.dtype, sharding=chip)
    params = jax.tree.map(grow, bucket.params)
    scalers = tuple(grow(s) for s in bucket.scalers)
    T = bank_mod._next_pow2(int(traffic["request_rows"]))
    for B in (min(traffic["warm_batches"]), max(traffic["warm_batches"])):
        X = jax.ShapeDtypeStruct((B, T, entry.n_features), jnp.float32, sharding=chip)
        idx = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=chip)
        _report(
            f"{cell.name} bank of {members}, batch {B} x {T} rows "
            f"({bucket.seq_layout}/{bucket.seq_kernel})",
            bucket._score.lower(params, *scalers, idx, X, X).compile(),
        )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("workloads", nargs="*")
    parser.add_argument("--members", type=int, default=None,
                        help="try another gang or bank size than the configuration's")
    args = parser.parse_args(argv)

    from harness import spec

    names = args.workloads or [w["name"] for w in spec.load_benchmark()["workloads"]]
    chip = describe_chip()
    for name in names:
        cell = spec.Cell(name)
        if cell.traffic["driver"] == "refit":
            rehearse_refit(cell, chip, args.members or cell.config["gang_members"])
        else:
            rehearse_serve(cell, chip, args.members or cell.config["bank_members"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
