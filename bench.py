#!/usr/bin/env python
"""Benchmark: fleet training throughput + server scoring throughput on the
available accelerator, covering every BASELINE.md config:

  1. single feedforward autoencoder build      -> sequential_models_per_hour
  2. LSTM autoencoder (windowed sequences)     -> lstm_models_per_hour_per_chip
  3. 1k-scale fleet vmap engine                -> fleet_models_per_hour_per_chip
  4. conv1d / variational autoencoder variants -> conv_/vae_models_per_hour
  5. streaming HBM bank serving                -> bank_serving_samples_per_sec

Output contract: the LAST stdout line is a compact (<=1 KB) headline JSON
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
     "platform": ..., "device_kind": ..., "mfu": ..., "errors": {...}}
that survives tail-only capture. Full per-metric detail is written to
``BENCH_DETAIL.json`` next to this file; each metric also prints one
``METRIC <name> <json>`` line, with the platform it ran on, as it completes.

Device contract: ONE process runs the metric list in order and holds the
chip for the whole run. It reads ``jax.devices()[0]`` once and exits
non-zero when the platform is not ``tpu`` — there is no CPU re-run under
the same metric names. ``--platform cpu`` (explicit) is the functional
rehearsal: every record then says ``cpu`` and carries the names of what it
would report, never the values. A metric that raises is recorded in
``errors`` and makes the run exit non-zero. The ten control-plane legs
(``CPU_TOOL_METRICS``) drive ``tools/*_demo.py`` children pinned to the CPU
— a chip belongs to one process — and their records say ``cpu`` on any run.

FLOPs accounting: dense train step ~= 6 * params FLOPs/sample (2 forward +
4 backward, the standard dense-layer convention), so the fleet metric also
reports achieved FLOP/s and — when the chip's peak is known — MFU. The
models are deliberately tiny (the reference's per-machine autoencoders,
SURVEY.md §0); per-model matmuls cannot feed the MXU, so the whole perf
story is vmap width x bf16, and these numbers make that judgeable.

``vs_baseline`` compares the fleet engine against a measured single-model
sequential rate on the same hardware (the reference's one-pod-per-model
architecture transplanted here): it captures the speedup of many-model
vmap/shard_map training over pod-style sequential builds.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

# Dense bf16 peak FLOP/s per chip (public spec sheets).
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e / Trillium
    "TPU v6e": 918e12,
}

# HBM bandwidth peak per chip, bytes/s (public spec sheets). For the
# 417-param reference-scale models the chip is bandwidth-bound by design,
# so achieved-bytes/s vs THIS peak — not MFU — is the honest efficiency
# number (VERDICT r2 weak #6).
PEAK_HBM_BYTES = {
    "TPU v4": 1.2288e12,
    "TPU v5 lite": 8.19e11,  # v5e
    "TPU v5e": 8.19e11,
    "TPU v5p": 2.765e12,
    "TPU v6 lite": 1.64e12,  # v6e / Trillium
    "TPU v6e": 1.64e12,
}


# tools/*_demo.py children: hard timeout, and pinned to the CPU — the bench
# process holds the chip, and a child that reached for it would fail or hang
TOOL_TIMEOUT_S = 600.0


def _cpu_tool_env():
    return dict(os.environ, JAX_PLATFORMS="cpu")


def _synth_fleet(n_models: int, rows: int, n_features: int):
    rng = np.random.RandomState(0)
    t = np.arange(rows)
    out = {}
    for i in range(n_models):
        freqs = 0.01 + 0.002 * rng.rand(n_features)
        phases = 2 * np.pi * rng.rand(n_features)
        X = np.sin(np.outer(t, freqs) + phases) + rng.normal(
            scale=0.05, size=(rows, n_features)
        )
        out[f"machine-{i}"] = X.astype("float32")
    return out


def _count_params(model_type: str, kind: str, n_features: int, sample_shape, **kw):
    """Parameter count of one model (for FLOPs accounting)."""
    import jax
    import jax.numpy as jnp

    from gordo_components_tpu.models.register import lookup_factory

    module = lookup_factory(model_type, kind)(n_features, **kw)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros(sample_shape, jnp.float32))
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(params)))


def _hbm_traffic_model(params, padded_rows, n_features, epochs, n_models,
                       batch_size, dtype_bytes=2):
    """Estimated LOWER-BOUND HBM bytes moved by one fleet fit.

    Per member-epoch: the data block read once (padded_rows x f), and per
    batch step the param/optimizer working set — read params + grads
    written/read + adam m/v read+written + params written ≈ 7 accesses of
    the param block (f32 opt state: 4 bytes). Activations are assumed
    fused/register-resident (XLA fuses the tiny dense stacks), so real
    traffic is strictly higher; the estimate still bounds how far from
    the bandwidth roof the engine runs.
    """
    n_batches = -(-padded_rows // batch_size)
    data = padded_rows * n_features * dtype_bytes
    state = 7 * params * 4 * n_batches
    return float((data + state) * epochs * n_models)


def _timed_fleet_fit(config, members, n_chips):
    """Warm + timed FleetTrainer fit -> (models/hour/chip, seconds, trainer).

    The warmup fit uses the SAME config and member shapes (XLA specializes
    per shape); the process-wide program cache then makes the timed fit
    measure steady-state training, not tracing/XLA compilation. Shared by
    the fleet headline, the wide-width leg, and the width sweep so the
    warmup convention and the per-chip divisor can't silently diverge.
    """
    from gordo_components_tpu.parallel import FleetTrainer

    FleetTrainer(**config).fit(members)
    trainer = FleetTrainer(**config)
    t0 = time.time()
    trainer.fit(members)
    elapsed = time.time() - t0
    rate = len(members) / elapsed * 3600 / n_chips
    return rate, elapsed, trainer


def bench_fleet(
    n_models=1024, rows=1440, n_features=10, epochs=5, batch_size=128,
    host_sync_every=5,
):
    """Config 3 — many-model fleet training: models/hour/chip + FLOP/s +
    estimated HBM bytes/s (the honest roof for tiny models).
    ``host_sync_every`` is the on-device chunk size; with the defaults
    (epochs=5, chunk=5) the whole epoch budget is one dispatch.

    The headline stays at width 1024: BASELINE.json config 3 is a
    1k-machine fleet, and every prior round's number is comparable at that
    width. The knee-width rate lives in its own ``fleet_wide`` metric."""
    import jax

    members = _synth_fleet(n_models, rows, n_features)
    config = dict(
        kind="feedforward_hourglass",
        epochs=epochs,
        batch_size=batch_size,
        compute_dtype="bfloat16",
        host_sync_every=host_sync_every,
    )
    n_chips = len(jax.devices())
    models_per_hour_per_chip, elapsed, trainer = _timed_fleet_fit(
        config, members, n_chips
    )

    # FLOPs: ES is off, so every model runs every epoch over its padded
    # rows. 6 * params per sample-step (fwd 2x + bwd 4x, dense convention).
    # The EXECUTED row count comes from the trainer's own bucket stats:
    # row quantization pads batch counts up a ladder, and the padded
    # batches still execute value_and_grad (their updates are masked out).
    params = _count_params(
        "AutoEncoder", config["kind"], n_features, (1, n_features)
    )
    buckets = trainer.last_stats.get("buckets") or []
    padded_rows = buckets[0]["padded_rows"] if buckets else -(-rows // batch_size) * batch_size
    train_flops = 6.0 * params * padded_rows * epochs * n_models
    vmap_width = buckets[0]["n_members"] if buckets else n_models
    hbm_bytes = _hbm_traffic_model(
        params, padded_rows, n_features, epochs, n_models, batch_size
    )
    out = {
        "fleet_models_per_hour_per_chip": round(models_per_hour_per_chip, 1),
        "fleet_wall_seconds": round(elapsed, 2),
        "model_params": params,
        "train_flops_total": train_flops,
        "achieved_flops_per_sec": round(train_flops / elapsed / n_chips, 1),
        "hbm_bytes_model_total": hbm_bytes,
        "achieved_hbm_bytes_per_sec": round(hbm_bytes / elapsed / n_chips, 1),
        "vmap_width": int(vmap_width),
        "fleet_config": (
            f"{n_models} models x {rows} rows x {n_features} tags, "
            f"hourglass AE, {epochs} epochs, bf16, chunk={host_sync_every}"
        ),
    }
    return out


def bench_fleet_wide(
    width="auto", rows=1440, n_features=10, epochs=5, batch_size=128,
):
    """Fleet training at the knee of the measured width->rate curve.

    Times the FULL headline config (1440 rows, 5 epochs) at the widest
    width the curve still rewards — the single-chip rate an operator
    actually gets by raising the gang width. ``width="auto"`` uses the
    knee ``bench_width_sweep`` measured earlier in this same process
    (METRICS order puts the sweep first), so the knee tracks the hardware.
    When the sweep did not run (``--skip``/``--order``) it falls back to
    4096, the widest width that has run on a chip, and the provenance is
    recorded either way. ``width=None`` skips."""
    import jax

    if not width:
        return {"fleet_wide_skipped": "width=None (CPU: vmap width gains nothing)"}
    if width == "auto":
        if _SWEEP_KNEE["width"]:
            width, source = _SWEEP_KNEE["width"], "width_sweep knee (this run)"
        else:
            width, source = 4096, "default 4096 (sweep absent in this process)"
    else:
        source = "explicit"
    if width == 1024:
        # the headline fleet metric already times this exact config
        return {"fleet_wide_skipped": "knee equals the 1024 headline width"}
    config = dict(
        kind="feedforward_hourglass", epochs=epochs, batch_size=batch_size,
        compute_dtype="bfloat16", host_sync_every=epochs,
    )
    rate, elapsed, _ = _timed_fleet_fit(
        config, _synth_fleet(width, rows, n_features), len(jax.devices())
    )
    return {
        "fleet_wide_models_per_hour_per_chip": round(rate, 1),
        "fleet_wide_width": int(width),
        "fleet_wide_width_source": source,
        "fleet_wide_wall_seconds": round(elapsed, 2),
        "fleet_wide_config": (
            f"{width} models x {rows} rows x {n_features} tags, hourglass "
            f"AE, {epochs} epochs, bf16"
        ),
    }


# knee measured by bench_width_sweep in THIS process, consumed by
# bench_fleet_wide (they run sequentially in the one bench process)
_SWEEP_KNEE = {"width": None}


def bench_width_sweep(widths=(256, 1024, 2048, 4096, 8192, 16384), rows=720,
                      n_features=10, epochs=3, batch_size=128):
    """vmap-width -> throughput curve (VERDICT r2 weak #6): "width is the
    lever" as a measurement, not an assertion. Reports models/hour/chip at
    each width plus where the curve knees (last width whose per-model rate
    still improved >10% — the grid keeps uniform 2x steps so that
    threshold stays calibrated). The 2026-07-31 TPU run still gained >10%
    at its top width (4096 -> 3.48M models/hour), so the sweep now
    extends to 16384 — ~0.47 GB of member data at 720x10 f32,
    comfortably inside v5e HBM — to find where the curve flattens. Each
    width prints a progress line so the supervisor's stall watchdog
    bounds one width's compile+fit, not the whole sweep."""
    import jax

    n_chips = len(jax.devices())
    config = dict(
        kind="feedforward_hourglass", epochs=epochs, batch_size=batch_size,
        compute_dtype="bfloat16", host_sync_every=epochs,
    )
    curve = {}
    prev_rate = None
    knee = widths[0]
    for width in widths:
        members = _synth_fleet(width, rows, n_features)
        rate, _, _ = _timed_fleet_fit(config, members, n_chips)
        curve[str(width)] = round(rate, 1)
        # any line counts as progress to the supervising parent
        print(f"# width_sweep {width}: {rate:.0f} models/h", flush=True)
        if prev_rate is not None and rate > prev_rate * 1.1:
            knee = width
        prev_rate = rate
    _SWEEP_KNEE["width"] = int(knee)
    return {
        "width_sweep_models_per_hour": curve,
        "width_sweep_knee": int(knee),
        "width_sweep_config": (
            f"{rows} rows x {n_features} tags, hourglass AE, {epochs} "
            f"epochs, bf16"
        ),
    }


def bench_single_sequential(rows=1440, n_features=10, epochs=5, batch_size=128, n_probe=3):
    """Config 1 — reference-architecture stand-in: one feedforward model
    at a time (pod-style)."""
    from gordo_components_tpu.models import AutoEncoder

    members = _synth_fleet(n_probe, rows, n_features)
    # compile warmup
    AutoEncoder(kind="feedforward_hourglass", epochs=1, batch_size=batch_size).fit(
        next(iter(members.values()))
    )
    t0 = time.time()
    for X in members.values():
        AutoEncoder(
            kind="feedforward_hourglass", epochs=epochs, batch_size=batch_size
        ).fit(X)
    elapsed = time.time() - t0
    return {"sequential_models_per_hour_per_chip": round(n_probe / elapsed * 3600, 1)}


def bench_sequence_models(rows=1440, n_features=10, epochs=5, batch_size=128):
    """Configs 2 and 4 — the rest of the model zoo, one timed fit each
    (these are single-machine configs in BASELINE.md; the fleet metric
    covers many-model scale). Warmup fit first so XLA compile is excluded."""
    from gordo_components_tpu.models import (
        AutoEncoder,
        ConvAutoEncoder,
        LSTMAutoEncoder,
    )

    X = _synth_fleet(1, rows, n_features)["machine-0"]
    out = {}
    zoo = {
        # config 2: windowed LSTM reconstruction
        "lstm": lambda e: LSTMAutoEncoder(
            kind="lstm_hourglass", lookback_window=32, epochs=e,
            batch_size=batch_size, compute_dtype="bfloat16",
        ),
        # config 4: conv1d + variational variants
        "conv": lambda e: ConvAutoEncoder(
            lookback_window=32, epochs=e, batch_size=batch_size,
            compute_dtype="bfloat16",
        ),
        "vae": lambda e: AutoEncoder(
            kind="feedforward_variational", epochs=e, batch_size=batch_size,
            compute_dtype="bfloat16",
        ),
    }
    for name, make in zoo.items():
        make(1).fit(X)  # warmup/compile
        t0 = time.time()
        make(epochs).fit(X)
        elapsed = time.time() - t0
        out[f"{name}_models_per_hour_per_chip"] = round(3600.0 / elapsed, 1)
    return out


def bench_checkpoint_overhead(n_models=256, rows=1440, n_features=10, epochs=5):
    """Preemption-checkpoint cost at fleet scale: wall-time ratio of a
    checkpointed fit (key content-hash of every member + one orbax save
    per epoch) vs the plain fit. Quantifies SURVEY §5 checkpoint/resume
    overhead so operators can pick checkpoint_every."""
    import shutil
    import tempfile

    from gordo_components_tpu.parallel import FleetTrainer

    members = _synth_fleet(n_models, rows, n_features)
    config = dict(
        kind="feedforward_hourglass", epochs=epochs, batch_size=128,
        compute_dtype="bfloat16",
    )
    FleetTrainer(**config).fit(members)  # warm the programs
    # TWO timed plain fits: their spread is the run-to-run noise floor,
    # so a drifting overhead ratio can be told apart from host noise
    # (VERDICT r3 weak #6 — r2->r3 drifted 1.17->1.29 with no way to know)
    plains = []
    for _ in range(2):
        t0 = time.time()
        FleetTrainer(**config).fit(members)
        plains.append(time.time() - t0)
    # mean, not min: a min-of-2 denominator against single-sample
    # checkpointed numerators would bias the ratio up vs earlier rounds'
    # single-sample definition — a phantom drift
    plain = sum(plains) / len(plains)
    noise = (max(plains) - min(plains)) / max(plains)

    # warm orbax imports/registry once, with a tiny fit — checkpointing
    # adds no XLA program, so the plain warm fit above already compiled
    # everything the timed runs execute
    warm_dir = tempfile.mkdtemp(prefix="bench-ckpt-warm-")
    try:
        FleetTrainer(
            checkpoint_dir=warm_dir, checkpoint_every=1,
            kind=config["kind"], epochs=2, batch_size=128,
            compute_dtype=config["compute_dtype"],
        ).fit({"warm": next(iter(members.values()))})
    finally:
        shutil.rmtree(warm_dir, ignore_errors=True)

    def timed_ckpt(every: int) -> float:
        ckpt_dir = tempfile.mkdtemp(prefix="bench-ckpt-")
        try:
            t0 = time.time()
            FleetTrainer(
                checkpoint_dir=ckpt_dir, checkpoint_every=every, **config
            ).fit(members)
            return time.time() - t0
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)

    every_epoch = timed_ckpt(1)  # worst case: gather+save every epoch
    # the operator lever (checkpoint_every): one mid-run save
    amortized = timed_ckpt(max(2, epochs // 2 + 1))
    return {
        "checkpoint_overhead_ratio": round(every_epoch / plain, 3),
        "checkpoint_overhead_ratio_amortized": round(amortized / plain, 3),
        "checkpoint_fit_seconds": round(every_epoch, 2),
        "plain_fit_seconds": round(plain, 2),
        # relative spread of the two plain fits: an overhead-ratio drift
        # smaller than ~2x this is host noise, not a regression
        "plain_fit_noise_rel": round(noise, 3),
    }


def bench_bank_serving(n_models=64, n_features=10, rows=256, iters=10):
    """Config 5 — many-model serving through the HBM-resident bank:
    coalesced batched scoring vs one-model-at-a-time (the reference's one
    process per model, transplanted)."""
    from gordo_components_tpu.models import AutoEncoder, DiffBasedAnomalyDetector
    from gordo_components_tpu.server.bank import ModelBank

    rng = np.random.RandomState(0)
    X = rng.rand(512, n_features).astype("float32")
    models = {}
    for i in range(n_models):
        det = DiffBasedAnomalyDetector(
            base_estimator=AutoEncoder(epochs=1, batch_size=256)
        )
        det.fit(X + 0.01 * i)
        models[f"m-{i}"] = det

    bank = ModelBank.from_models(models)
    requests = [
        (f"m-{i}", rng.rand(rows, n_features).astype("float32"), None)
        for i in range(n_models)
    ]
    # both paths measured end-to-end as the server runs them, INCLUDING
    # response-frame assembly, so the speedup is dispatch coalescing —
    # not pandas bookkeeping skipped on one side
    [r.to_frame() for r in bank.score_many(requests)]  # warm/compile
    t0 = time.time()
    for _ in range(iters):
        [r.to_frame() for r in bank.score_many(requests)]
    bank_elapsed = time.time() - t0
    bank_rate = n_models * rows * iters / bank_elapsed

    # sequential per-model path (same math, no coalescing); warm EVERY
    # model — each has its own jit program, and a one-model warm would
    # leave 63 compiles inside the timed loop
    for name, Xr, _ in requests:
        models[name].anomaly(Xr)
    t0 = time.time()
    for _ in range(iters):
        for name, Xr, _ in requests:
            models[name].anomaly(Xr)
    seq_elapsed = time.time() - t0
    seq_rate = n_models * rows * iters / seq_elapsed

    # request latency under the REAL continuous-batching path (VERDICT r3
    # next #4): concurrent clients submit through BatchingEngine.score on
    # one event loop, so the percentiles include the flush_ms coalescing
    # wait — the trade the throughput numbers alone hide. Client-side
    # submit->result stamps; the engine's own queue-wait histogram rides
    # along for the dispatch-wait split.
    import asyncio

    from gordo_components_tpu.observability.goodput import GoodputLedger
    from gordo_components_tpu.observability.slo import SLOTracker
    from gordo_components_tpu.server.bank import BatchingEngine

    concurrency = min(n_models, 32)

    # goodput accounting over the measured round (ISSUE 7): the perf
    # trajectory should carry efficiency (goodput ratio, device busy
    # share, burn rate) next to throughput, not just samples/sec
    ledger = GoodputLedger()
    tracker = SLOTracker(ledger, sample_interval_s=0.005, registry=None)

    async def _drive(n_iters, record=False):
        # registry=False: the warm and measured rounds each build a fresh
        # engine, and shared registry histograms would blend them — the
        # reported queue-wait snapshot must cover the measured round only
        engine = BatchingEngine(
            bank, max_batch=concurrency, flush_ms=2.0, registry=False
        )
        engine.start()
        lat: list = []

        async def client(i):
            name, Xr, _ = requests[i % n_models]
            for _ in range(n_iters):
                t0 = time.monotonic()
                r = await engine.score(name, Xr)
                dt = time.monotonic() - t0
                lat.append(dt)
                if record:
                    ledger.finish_request(200, dt, r.device_s)

        await asyncio.gather(*(client(i) for i in range(concurrency)))
        await engine.stop()
        return lat, engine

    async def _measure():
        # warm round first: coalescing produces batch sizes (1,2,4,...)
        # the block warm-up above never compiled, and those one-time XLA
        # compiles must not masquerade as tail latency (the bank's jit
        # cache persists across engines, so one throwaway round suffices)
        await _drive(1)
        # attach the ledger AFTER the warm round: its compile-heavy
        # device windows must not inflate the steady-state busy ratio
        bank.ledger = ledger
        ledger.started = time.monotonic()
        tracker.sample(force=True)
        return await _drive(iters, record=True)

    lat, engine = asyncio.run(_measure())
    bank.ledger = None
    tracker.sample(force=True)
    slo_snap = tracker.snapshot()
    goodput = ledger.snapshot()
    lat.sort()
    pct = lambda q: lat[min(len(lat) - 1, int(q * len(lat)))] * 1e3

    # pipelined hot-path evidence (ISSUE 5): a second bucket (different
    # feature width) makes each score_many span multiple bucket-group
    # dispatches, so the measured host/device overlap_ratio and the
    # padded-buffer arena hit rate land in BENCH_DETAIL.json where the
    # next re-anchor can see the perf trajectory
    n_wide = max(4, n_models // 8)
    Xw = rng.rand(512, n_features + 2).astype("float32")
    wide = {}
    for i in range(n_wide):
        det = DiffBasedAnomalyDetector(
            base_estimator=AutoEncoder(epochs=1, batch_size=256)
        )
        det.fit(Xw + 0.01 * i)
        wide[f"w-{i}"] = det
    mixed_bank = ModelBank.from_models({**models, **wide})
    mixed_requests = requests[: max(4, n_models // 2)] + [
        (f"w-{i}", rng.rand(rows, n_features + 2).astype("float32"), None)
        for i in range(n_wide)
    ]
    mixed_bank.score_many(mixed_requests)  # warm/compile both buckets
    # steady-state ratios from DELTAS over the timed loop only: the warm
    # call's seconds of XLA compile would otherwise dominate the
    # cumulative counters and mask whatever the pipeline actually did
    pipe0 = mixed_bank.pipeline_stats()
    t0 = time.time()
    for _ in range(iters):
        mixed_bank.score_many(mixed_requests)
    mixed_elapsed = time.time() - t0
    pipeline = mixed_bank.pipeline_stats()
    d_wall = pipeline["overlap"]["wall_s"] - pipe0["overlap"]["wall_s"]
    d_busy = (
        pipeline["overlap"]["device_busy_s"] - pipe0["overlap"]["device_busy_s"]
    )
    d_hits = pipeline["arena"]["hits"] - pipe0["arena"]["hits"]
    d_total = d_hits + pipeline["arena"]["misses"] - pipe0["arena"]["misses"]
    overlap_ratio = round(d_busy / d_wall, 4) if d_wall > 0 else None
    arena_hit_rate = round(d_hits / d_total, 4) if d_total > 0 else None

    return {
        "bank_serving_samples_per_sec": round(bank_rate, 1),
        "bank_vs_sequential_serving": round(bank_rate / seq_rate, 2),
        "bank_serving_p50_ms": round(pct(0.50), 2),
        "bank_serving_p99_ms": round(pct(0.99), 2),
        "bank_serving_concurrency": concurrency,
        "bank_queue_wait": engine.queue_wait.snapshot(),
        "bank_avg_batch": round(
            engine.stats["requests"] / max(1, engine.stats["batches"]), 2
        ),
        "bank_multi_bucket_samples_per_sec": round(
            len(mixed_requests) * rows * iters / mixed_elapsed, 1
        ),
        "bank_overlap_ratio": overlap_ratio,
        "bank_arena_hit_rate": arena_hit_rate,
        "bank_inflight_window": pipeline["inflight_window"],
        "bank_pipeline": pipeline,
        # efficiency next to throughput (ISSUE 7): goodput over the
        # measured engine round, device-busy share of its wall, and the
        # worst SLO burn rate (0.0 on a clean run — nonzero means the
        # bench itself missed objectives, which IS perf signal)
        "goodput_ratio": goodput["goodput_ratio"],
        "device_busy_ratio": goodput["device"]["busy_ratio"],
        "slo_worst_burn_rate": (slo_snap["worst"] or {}).get("burn_rate"),
        "goodput": goodput,
    }


def bench_rebalance(members=256, devices=8, hot_weight=8, request_rows=64):
    """Placement control plane (ISSUE 8) — a deliberately skewed fleet
    on an 8-shard virtual mesh: the LPT planner + zero-downtime swap
    must cut the measured shard skew >=2x, with a sub-ms generation
    flip. Runs in a subprocess: the virtual device count has to land in
    XLA_FLAGS before jax initializes, and this process already
    committed its backend."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
    tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools",
        "rebalance_demo.py",
    )
    out = subprocess.run(
        [
            sys.executable, tool, "--members", str(members),
            "--devices", str(devices), "--hot-weight", str(hot_weight),
            "--request-rows", str(request_rows), "--platform", "cpu",
        ],
        capture_output=True, text=True, timeout=TOOL_TIMEOUT_S, env=env,
    )
    if out.returncode != 0:
        tail = (out.stderr or out.stdout or "").strip().splitlines()
        raise RuntimeError(f"rebalance demo failed: {' | '.join(tail[-3:])}")
    # the JSON document is the LAST block whose opening line is a bare
    # "{" (indent=1 keeps nested braces off column 0) — jax/absl banners
    # before it may themselves contain brace characters
    lines = out.stdout.splitlines()
    start = max(i for i, ln in enumerate(lines) if ln.strip() == "{")
    doc = json.loads("\n".join(lines[start:]))
    assert doc["skew_reduction"] >= 2.0, doc
    return {
        "rebalance_members": doc["members"],
        "rebalance_devices": doc["devices"],
        "rebalance_shard_skew_before": doc["shard_skew_before"],
        "rebalance_shard_skew_after": doc["shard_skew_after"],
        "rebalance_skew_reduction": doc["skew_reduction"],
        "rebalance_predicted_improvement": doc["plan"][
            "predicted_improvement"
        ],
        "rebalance_moved_members": doc["plan"]["moved"],
        "rebalance_swap_pause_ms": doc["swap_pause_ms"],
        "rebalance_bank_rebuild_s": doc["rebuild_s"],
        "rebalance": doc,
    }


def bench_streaming(members=6, rows=96, epochs=3, mean_shift=4.0):
    """Streaming & online adaptation (ISSUE 9) — the live loop over the
    real HTTP surface: inject a mean-shift drift into K members of a
    heterogeneous fleet, watch detection flag exactly those members,
    recalibrate + incrementally refit through the zero-downtime swap,
    and verify the false-positive rate on shifted-but-healthy data
    drops. Runs in a subprocess (the env knobs must land before the
    server module reads them) via tools/stream_demo.py."""
    tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "stream_demo.py"
    )
    out = subprocess.run(
        [
            sys.executable, tool, "--members", str(members),
            "--rows", str(rows), "--epochs", str(epochs),
            "--mean-shift", str(mean_shift), "--platform", "cpu",
        ],
        capture_output=True, text=True, timeout=TOOL_TIMEOUT_S,
        env=_cpu_tool_env(),
    )
    if out.returncode != 0:
        tail = (out.stderr or out.stdout or "").strip().splitlines()
        raise RuntimeError(f"stream demo failed: {' | '.join(tail[-3:])}")
    # same JSON-tail parse as the rebalance leg: the document is the last
    # block whose opening line is a bare "{"
    lines = out.stdout.splitlines()
    start = max(i for i, ln in enumerate(lines) if ln.strip() == "{")
    doc = json.loads("\n".join(lines[start:]))
    assert doc["fp_rate_drop"] > 0.25, doc
    assert max(doc["fp_rate_after"].values()) < max(
        doc["fp_rate_before"].values()
    ), doc
    return {
        "streaming_members": doc["members"],
        "streaming_detection_latency_s": doc["detection_latency_s"],
        "streaming_recalibration_s": doc["recalibration_s"],
        "streaming_refit_s": doc["refit_s"],
        "streaming_swap_pause_ms": doc["swap_pause_ms"],
        "streaming_fp_rate_before": max(doc["fp_rate_before"].values()),
        "streaming_fp_rate_after": max(doc["fp_rate_after"].values()),
        "streaming_fp_rate_drop": doc["fp_rate_drop"],
        "streaming_generations": doc["generation_after_refit"],
        "streaming": doc,
    }


def bench_replay(epochs=3, speed=500.0):
    """Time-compressed replay backtest (ISSUE 12) — the standard
    incident library (mean shift, variance inflation, dropout,
    flatline, late+duplicate delivery, seasonal cycle, correlated
    fleet failure, refit-fault co-fire) driven through the real
    ingest -> drift -> recalibrate/refit -> hot-swap path on a
    ReplayClock. Records per-incident-class detection latency, FP/FN
    before/after adaptation, adaptation cost, and the achieved
    compression. Subprocess (env knobs land before server import) via
    tools/replay_demo.py."""
    tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "replay_demo.py"
    )
    out = subprocess.run(
        [
            sys.executable, tool, "--epochs", str(epochs),
            "--speed", str(speed), "--platform", "cpu",
        ],
        capture_output=True, text=True, timeout=TOOL_TIMEOUT_S,
        env=_cpu_tool_env(),
    )
    if out.returncode != 0:
        tail = (out.stderr or out.stdout or "").strip().splitlines()
        raise RuntimeError(f"replay demo failed: {' | '.join(tail[-3:])}")
    lines = out.stdout.splitlines()
    start = max(i for i, ln in enumerate(lines) if ln.strip() == "{")
    doc = json.loads("\n".join(lines[start:]))
    assert doc["passed"], {
        k: v["failures"] for k, v in doc["scenarios"].items() if v["failures"]
    }
    assert doc["total_non_200"] == 0, doc["total_non_200"]
    assert doc["min_speedup"] >= 100.0, doc["min_speedup"]
    ms = doc["scenarios"]["mean_shift"]
    # PR 9 parity, replayed: the post-adaptation FP rate collapses
    fp_before = max(ms["fp_rate_before"].values())
    fp_after = max(ms["fp_rate_after"].values())
    assert fp_after == 0.0 or fp_before / fp_after >= 2.0, (fp_before, fp_after)
    detection = {
        name: min(
            (
                e["detection_latency_s"]
                for e in v["incidents"].values()
                if e["detected"]
            ),
            default=None,
        )
        for name, v in doc["scenarios"].items()
    }
    return {
        "replay_scenarios": len(doc["scenarios"]),
        "replay_min_speedup": doc["min_speedup"],
        "replay_non200_total": doc["total_non_200"],
        "replay_mean_shift_detection_s": detection["mean_shift"],
        "replay_mean_shift_fp_before": fp_before,
        "replay_mean_shift_fp_after": fp_after,
        "replay_adaptation_cost_s": round(
            sum(v["adaptation_cost_s"] for v in doc["scenarios"].values()), 3
        ),
        "replay_refit_s": round(
            sum(v["refit_s"] for v in doc["scenarios"].values()), 3
        ),
        "replay_swap_pause_ms_max": max(
            v["swap_pause_ms_max"] for v in doc["scenarios"].values()
        ),
        "replay_rolled_back": sum(
            v["rolled_back"] for v in doc["scenarios"].values()
        ),
        "replay_duplicates_absorbed": sum(
            v["duplicate_rows_total"] for v in doc["scenarios"].values()
        ),
        "replay_detection_latency_s": detection,
        "replay": doc,
    }


def bench_history(burn_seconds=2.0):
    """Fleet flight recorder (ISSUE 16) — the game-day drill via
    tools/incident_demo.py: scoring-error + queue-stall faults under
    live load, recovery, then a real watchman ``/incidents``
    correlation. Records the recorder's cost figures (sampler ms per
    pass, /history query ms, retained bytes per series) and the
    detection outcome (incidents found, peak burn, the correlated event
    types). Subprocess so the GORDO_HISTORY/GORDO_SLO env knobs land
    before server import."""
    tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "incident_demo.py"
    )
    out = subprocess.run(
        [
            sys.executable, tool, "--burn-seconds", str(burn_seconds),
            "--platform", "cpu",
        ],
        capture_output=True, text=True, timeout=TOOL_TIMEOUT_S,
        env=_cpu_tool_env(),
    )
    if out.returncode != 0:
        tail = (out.stderr or out.stdout or "").strip().splitlines()
        raise RuntimeError(f"incident demo failed: {' | '.join(tail[-3:])}")
    lines = out.stdout.splitlines()
    start = max(i for i, ln in enumerate(lines) if ln.strip() == "{")
    doc = json.loads("\n".join(lines[start:]))
    assert doc["passed"], doc
    assert doc["detected"] >= 1, doc["detected"]
    # the recorder must stay cheap: one full-registry sample pass in
    # single-digit ms, queries in low ms, a bounded per-series footprint
    assert doc["sample_ms_avg"] < 50.0, doc["sample_ms_avg"]
    return {
        "history_incidents_detected": doc["detected"],
        "history_burn_episodes": doc["episodes"],
        "history_peak_burn": doc["peak_burn"],
        "history_event_types_correlated": doc["incident_event_types"],
        "history_sample_ms_avg": doc["sample_ms_avg"],
        "history_query_ms": doc["query_ms"],
        "history_bytes_per_series": doc["bytes_per_series"],
        "history_series_retained": doc["history_series"],
        "history_timeline_len": len(doc["timeline"]),
        "history": doc,
    }


def bench_heat_cost():
    """Fleet heat & device-cost observatory (ISSUE 18) — the capacity
    advisor drill via tools/capacity_demo.py: skewed load over a mixed
    dense/LSTM fleet, then ``GET /heat`` (the hot quartet must rank
    hottest), ``GET /costs`` (a live MFU for every bucket), and the
    bank-capacity projection (members per HBM budget per storage
    dtype). Records the tier split, per-bucket MFU, the fix-this-first
    pad-waste ranking, and the models/GB projection. Subprocess so the
    GORDO_HEAT/GORDO_COST cadence knobs land before server import."""
    tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "capacity_demo.py"
    )
    out = subprocess.run(
        [sys.executable, tool, "--platform", "cpu"],
        capture_output=True, text=True, timeout=TOOL_TIMEOUT_S,
        env=_cpu_tool_env(),
    )
    if out.returncode != 0:
        tail = (out.stderr or out.stdout or "").strip().splitlines()
        raise RuntimeError(f"capacity demo failed: {' | '.join(tail[-3:])}")
    lines = out.stdout.splitlines()
    start = max(i for i, ln in enumerate(lines) if ln.strip() == "{")
    doc = json.loads("\n".join(lines[start:]))
    assert doc["passed"], doc
    assert doc["tiers"].get("hot", 0) >= 1, doc["tiers"]
    assert doc["mfu_by_bucket"], doc
    return {
        "heat_tiers": doc["tiers"],
        "heat_hottest": doc["hottest"],
        "heat_rate_total": doc["rate_total"],
        "cost_peak_source": doc["peak_source"],
        "cost_mfu_by_bucket": doc["mfu_by_bucket"],
        "cost_fix_first": doc["fix_first"],
        "capacity_models_per_gb": doc["models_per_gb"],
        "heat_cost": doc,
    }


def bench_fleet_compile(members_compile=2048, demo_members=8):
    """Declarative fleet compiler (ISSUE 15) — two measurements:

    (a) compile-side scale, in-process: one ``members_compile``-machine
    spec compiled to the typed build/place/canary/promote DAG (wall
    time, step counts, DAG JSON size), then ONE machine edited and the
    stale subgraph computed against the first DAG's content-digest keys
    — the incremental-recompile ratio a 100k-member fleet's edit loop
    rides on (cached fraction; higher is better, bounded by the rollout
    tail that must always re-run).

    (b) the full rollout loop end to end via tools/fleet_demo.py in a
    subprocess (env knobs land before server import): compile -> gang
    build -> live canary under traffic -> promote -> incremental re-run
    -> injected fast-burn auto-rollback, with the zero-non-200 and
    rollback verdicts asserted."""
    import time as _time

    from gordo_components_tpu.workflow import compile_fleet

    def synth_spec(n, rev=1):
        machines = []
        for i in range(n):
            tags = [f"t{i}-{j}" for j in range(3 + (i % 4))]  # 4 buckets
            machines.append(
                {
                    "name": f"fc-{i}",
                    "dataset": {
                        "type": "RandomDataset",
                        "tag_list": tags,
                        "train_start_date": "2020-01-01T00:00:00Z",
                        "train_end_date": "2020-01-08T00:00:00Z",
                    },
                    "metadata": {"rev": rev if i == 0 else 1},
                }
            )
        return {
            "machines": machines,
            "fleet": {"canary": {"window_s": 30}, "schedules": {"refit_every": "6h"}},
        }

    t0 = _time.time()
    dag = compile_fleet(synth_spec(members_compile), "bench")
    compile_s = _time.time() - t0
    doc = dag.to_json()
    t0 = _time.time()
    edited = compile_fleet(synth_spec(members_compile, rev=2), "bench")
    recompile_s = _time.time() - t0
    stale = edited.stale_steps(dag.keys())
    total = len(dag.steps)
    out = {
        "fleet_compile_members": members_compile,
        "fleet_compile_s": round(compile_s, 4),
        "fleet_recompile_s": round(recompile_s, 4),
        "fleet_compile_steps": total,
        "fleet_compile_step_counts": dag.counts(),
        "fleet_dag_json_bytes": len(doc),
        "fleet_edit_stale_steps": len(stale),
        # cached fraction on a one-machine edit: the incremental-recompile
        # ratio (rollout tail + the edited chain always re-run)
        "fleet_incremental_ratio": round((total - len(stale)) / total, 6),
    }
    assert (
        compile_fleet(synth_spec(members_compile), "bench").to_json() == doc
    ), "fleet DAG compile must be deterministic"
    assert len(stale) <= 5, stale  # build + bucket + place/canary/promote

    tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "fleet_demo.py"
    )
    res = subprocess.run(
        [sys.executable, tool, "--members", str(demo_members), "--platform", "cpu"],
        capture_output=True, text=True, timeout=TOOL_TIMEOUT_S,
        env=_cpu_tool_env(),
    )
    if res.returncode != 0:
        tail = (res.stderr or res.stdout or "").strip().splitlines()
        raise RuntimeError(f"fleet demo failed: {' | '.join(tail[-3:])}")
    lines = res.stdout.splitlines()
    start = max(i for i, ln in enumerate(lines) if ln.strip() == "{")
    demo = json.loads("\n".join(lines[start:]))
    assert demo["passed"], demo
    out.update(
        {
            "fleet_demo_members": demo["members"],
            "fleet_demo_seed_build_s": demo["seed_build_s"],
            "fleet_demo_rollout_s": demo["rollout"]["wall_s"],
            "fleet_demo_incremental_rerun_s": demo["incremental"]["wall_s"],
            "fleet_demo_incremental_ratio": demo["incremental"][
                "incremental_ratio"
            ],
            "fleet_demo_non200": (
                demo["rollout"]["non_200"] + demo["incremental"]["non_200"]
            ),
            "fleet_demo_burn_rollback": demo["burn_rollback"]["rolled_back"],
            "fleet_demo": demo,
        }
    )
    return out


def bench_serving_saturation(rows=500, posts=40, workers=2, push_batches=8):
    """Serving-plane saturation (ISSUE 13) — end-to-end rows/s per
    transport (tcp / uds / shm ring) through the real multi-worker pool
    with a bitwise parity gate, the end-to-end vs in-process gap ratio
    (acceptance: within 5x), and push-mode windows-scored/s. Subprocess
    (GORDO_STREAM/GORDO_PUSH knobs must land before server import) via
    tools/saturate_demo.py."""
    tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "saturate_demo.py"
    )
    out = subprocess.run(
        [
            sys.executable, tool, "--rows", str(rows), "--posts", str(posts),
            "--workers", str(workers), "--push-batches", str(push_batches),
        ],
        capture_output=True, text=True, timeout=TOOL_TIMEOUT_S,
        env=_cpu_tool_env(),
    )
    if out.returncode != 0:
        tail = (out.stderr or out.stdout or "").strip().splitlines()
        raise RuntimeError(f"saturate demo failed: {' | '.join(tail[-3:])}")
    lines = out.stdout.splitlines()
    start = max(i for i, ln in enumerate(lines) if ln.strip() == "{")
    doc = json.loads("\n".join(lines[start:]))
    assert doc["parity"] == "bitwise", doc
    # the ISSUE 13 acceptance bar: best end-to-end transport within 5x
    # of the in-process bank rate on this box
    assert doc["end_to_end_gap_ratio"] <= 5.0, doc["end_to_end_gap_ratio"]
    assert doc["push"]["windows_scored"] > 0, doc["push"]
    return {
        "saturation_rows_per_sec": {
            name: leg["rows_per_sec"] for name, leg in doc["legs"].items()
        },
        "saturation_in_process_rows_per_sec": doc["in_process_rows_per_sec"],
        "saturation_end_to_end_gap_ratio": doc["end_to_end_gap_ratio"],
        "saturation_uds_vs_tcp": doc["uds_vs_tcp"],
        "saturation_shm_vs_tcp": doc["shm_vs_tcp"],
        "saturation_workers": doc["workers"],
        "saturation_push_windows_per_sec": doc["push"]["windows_per_sec"],
        "saturation_push_dropped": doc["push"]["dropped"],
        "serving_saturation": doc,
    }


def bench_mesh_serving(models=8, rows=500, posts=16, replicas=2, concurrency=16):
    """Multi-host serving mesh (ISSUE 14) — a REAL multi-process mesh:
    N partitioned server processes + a live watchman routing table,
    measured as (a) aggregate partition-aware bulk rows/s vs ONE replica
    on the same member set, (b) bitwise cross-replica parity, (c) a live
    cross-replica member migration under concurrent load with zero
    non-200s. Subprocess via tools/mesh_demo.py (the children must boot
    with their own GORDO_MESH_* env before jax imports).

    The >=1.7x aggregate acceptance asserts only on multi-core hosts:
    N server PROCESSES timesharing one core cannot beat one process
    (measured ~0.6x here — the same honesty rule PR 13's multi-worker
    leg documented), so on a single-core container the leg records the
    ratio + cpu_count and asserts the structural guarantees instead."""
    tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "mesh_demo.py"
    )
    out = subprocess.run(
        [
            sys.executable, tool, "--models", str(models), "--rows", str(rows),
            "--posts", str(posts), "--replicas", str(replicas),
            "--concurrency", str(concurrency),
        ],
        capture_output=True, text=True, timeout=TOOL_TIMEOUT_S,
        env=_cpu_tool_env(),
    )
    if out.returncode != 0:
        tail = (out.stderr or out.stdout or "").strip().splitlines()
        raise RuntimeError(f"mesh demo failed: {' | '.join(tail[-3:])}")
    lines = out.stdout.splitlines()
    start = max(i for i, ln in enumerate(lines) if ln.strip() == "{")
    doc = json.loads("\n".join(lines[start:]))
    # structural acceptance: always asserted, any host
    assert doc["parity"] == "bitwise", doc
    assert all(int(v) > 0 for v in doc["requests_per_replica"].values()), doc
    assert doc["migration"]["non_200"] == 0, doc["migration"]
    assert doc["migration"]["requests_during"] > 0, doc["migration"]
    single_core = (doc.get("cpu_count") or 1) < 2
    if not single_core:
        # the ISSUE 14 acceptance bar: aggregate rows/s across the mesh
        # >= 1.7x one replica on the same member set
        assert doc["mesh_vs_single"] >= 1.7, doc["mesh_vs_single"]
    return {
        "mesh_replicas": doc["replicas"],
        "mesh_aggregate_rows_per_sec": doc["mesh"]["rows_per_sec"],
        "mesh_single_replica_rows_per_sec": (
            doc["single_replica"]["rows_per_sec"]
        ),
        "mesh_vs_single_replica": doc["mesh_vs_single"],
        "mesh_single_core_container": single_core,
        "mesh_cpu_count": doc.get("cpu_count"),
        "mesh_requests_per_replica": doc["requests_per_replica"],
        "mesh_migration_non_200": doc["migration"]["non_200"],
        "mesh_migration_requests_during": doc["migration"]["requests_during"],
        "mesh_migration_swap_pause_ms": {
            "acquire": doc["migration"]["acquire_swap_pause_ms"],
            "release": doc["migration"]["release_swap_pause_ms"],
        },
        "mesh_routing_version": doc["migration"]["routing_version"],
        "mesh_serving": doc,
    }


def bench_gameday(scenarios=None, members=4):
    """Mesh-scale game days (ISSUE 17) — break the REAL multi-process
    mesh on purpose (replica SIGKILL, watchman partition, migration
    storm, gray slow-replica, thundering herd, correlated drift) and
    judge every failure with the SLO/incident stack: detection latency,
    burn peak, causal event order, non-200 containment, observed
    recovery. Subprocess via tools/gameday_demo.py (the children must
    boot with their own GORDO_MESH_*/GORDO_FAULTS env before jax
    imports). Structural bounds assert on any host; load-level bounds
    (hedge-win counts) are judged only on multi-core hosts — the
    single-core honesty rule, recorded via cpu_count in the doc."""
    tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "gameday_demo.py"
    )
    cmd = [sys.executable, tool, "--members", str(members)]
    for name in scenarios or ():
        cmd += ["--scenario", name]
    out = subprocess.run(
        cmd, capture_output=True, text=True, timeout=TOOL_TIMEOUT_S,
        env=_cpu_tool_env(),
    )
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    try:
        # the demo prints ONE compact JSON doc on its last line
        doc = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        tail = (out.stderr or out.stdout or "").strip().splitlines()
        raise RuntimeError(f"gameday demo failed: {' | '.join(tail[-3:])}")
    # structural acceptance: every drill in the catalog ran, was judged,
    # and passed — the per-scenario verdicts land in BENCH_DETAIL
    verdicts = doc["scenarios"]
    assert verdicts, doc
    for name, v in verdicts.items():
        assert v["schema"] == "gordo.scenario-verdict/v1", v
        assert v["passed"], (name, v["failures"])
    assert doc["passed"] and out.returncode == 0, doc
    crash = verdicts.get("replica_crash_restart") or {}
    gray = verdicts.get("gray_failure_slow_replica") or {}
    return {
        "gameday_scenarios_run": len(verdicts),
        "gameday_all_passed": doc["passed"],
        "gameday_single_core": doc["single_core"],
        "gameday_cpu_count": doc.get("cpu_count"),
        "gameday_crash_detection_s": crash.get("detection_latency_s"),
        "gameday_crash_recovery_s": crash.get("recovery_s"),
        "gameday_gray_burn_peak": gray.get("burn_peak"),
        "gameday_gray_hedge_wins": gray.get("hedge_wins"),
        "gameday_non_200_total": sum(
            int(v.get("non_200") or 0) for v in verdicts.values()
        ),
        "gameday": doc,
    }


def bench_qos(flood_workers=10, flood_seconds=8.0, baseline=40):
    """Multi-tenant QoS fairness (ISSUE 19) — a best_effort flood
    (tenant ``flood``, token-bucket limited) against a steady
    interactive probe through the real admission + weighted-fair
    batching stack. Subprocess via tools/qos_demo.py (the child must
    set its QoS/SLO env before jax imports). Records the fairness
    headline numbers: interactive p99 under flood vs unloaded,
    per-class goodput ratios, and shed precision (the fraction of
    admission sheds that landed on the flooding class)."""
    tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "qos_demo.py"
    )
    cmd = [
        sys.executable, tool,
        "--flood-workers", str(flood_workers),
        "--flood-seconds", str(flood_seconds),
        "--baseline", str(baseline),
    ]
    out = subprocess.run(
        cmd, capture_output=True, text=True, timeout=TOOL_TIMEOUT_S,
        env=_cpu_tool_env(),
    )
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    try:
        doc = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        tail = (out.stderr or out.stdout or "").strip().splitlines()
        raise RuntimeError(f"qos demo failed: {' | '.join(tail[-3:])}")
    # structural acceptance: interactive stays clean while the flood is
    # shed precisely — the noisy neighbor pays, the quiet one does not
    assert out.returncode == 0, doc
    assert doc["interactive_non_200"] == 0, doc
    precision = doc["shed_precision"]
    assert precision is None or precision >= 0.9, doc
    return {
        "qos_interactive_p99_flood_ms": doc["interactive_p99_flood_ms"],
        "qos_interactive_p99_ratio": doc["interactive_p99_ratio"],
        "qos_interactive_non_200": doc["interactive_non_200"],
        "qos_shed_total": doc["shed_total"],
        "qos_shed_precision": precision,
        "qos_goodput_ratio_interactive": doc["goodput_ratio_interactive"],
        "qos_goodput_ratio_best_effort": doc["goodput_ratio_best_effort"],
        "qos": doc,
    }


def bench_bank_sequence(n_models=16, n_features=10, rows=256, iters=10):
    """Config 5 extension — sequence models served from the HBM bank
    (windowing runs in-graph with the bucket's static lookback)."""
    from gordo_components_tpu.models import DiffBasedAnomalyDetector, LSTMAutoEncoder
    from gordo_components_tpu.server.bank import ModelBank

    rng = np.random.RandomState(0)
    X = rng.rand(512, n_features).astype("float32")
    models = {}
    for i in range(n_models):
        det = DiffBasedAnomalyDetector(
            base_estimator=LSTMAutoEncoder(
                lookback_window=32, epochs=1, batch_size=256,
                compute_dtype="bfloat16",
            )
        )
        det.fit(X + 0.01 * i)
        models[f"s-{i}"] = det
    bank = ModelBank.from_models(models)
    requests = [
        (f"s-{i}", rng.rand(rows, n_features).astype("float32"), None)
        for i in range(n_models)
    ]
    [r.to_frame() for r in bank.score_many(requests)]  # warm/compile
    t0 = time.time()
    for _ in range(iters):
        [r.to_frame() for r in bank.score_many(requests)]
    elapsed = time.time() - t0
    return {
        "lstm_bank_samples_per_sec": round(n_models * rows * iters / elapsed, 1)
    }


def bench_bank_capacity(n_models=4, n_features=32, rows=256, iters=8):
    """ISSUE 6 — low-precision weight bank + fused banked kernel: the
    models-per-GB capacity win per storage dtype, the parity error each
    mode actually costs, and the fused-kernel-vs-XLA throughput ratio at
    equal dtype. Realistically sized stacks (explicit 256/128/64 dims)
    so the int8 scale overhead is measured at production-shaped leaves,
    not toy ones."""
    from gordo_components_tpu.models import AutoEncoder, DiffBasedAnomalyDetector
    from gordo_components_tpu.server.bank import ModelBank

    rng = np.random.RandomState(0)
    X = rng.rand(512, n_features).astype("float32")
    models = {}
    for i in range(n_models):
        det = DiffBasedAnomalyDetector(
            base_estimator=AutoEncoder(
                kind="feedforward_symmetric",
                dims=(256, 128, 64),
                epochs=1,
                batch_size=256,
            )
        )
        det.fit(X + 0.01 * i)
        models[f"m-{i}"] = det
    requests = [
        (f"m-{i}", rng.rand(rows, n_features).astype("float32"), None)
        for i in range(n_models)
    ]

    out: dict = {}
    legs = {}
    ref = None
    bpm = {}
    for dtype in ("float32", "bfloat16", "int8"):
        bank = ModelBank.from_models(models, registry=False, bank_dtype=dtype)
        cap = bank.capacity_stats()
        results = bank.score_many(requests)  # warm/compile
        if ref is None:
            ref = results
        t0 = time.time()
        for _ in range(iters):
            bank.score_many(requests)
        elapsed = time.time() - t0
        # parity evidence rides with the capacity claim: max relative
        # error of the scaled anomaly totals vs the fp32 bank
        err = max(
            float(
                np.max(
                    np.abs(g.total_scaled - r.total_scaled)
                    / (np.abs(r.total_scaled) + 1e-6)
                )
            )
            for g, r in zip(results, ref)
        )
        bpm[dtype] = cap["bytes_per_member"]
        legs[dtype] = {
            "weight_bytes_per_member": cap["bytes_per_member"],
            "models_per_gb": cap["models_per_gb"],
            "capacity_ratio_vs_fp32": cap["capacity_ratio"],
            "samples_per_sec": round(n_models * rows * iters / elapsed, 1),
            "max_rel_err_total_scaled": round(err, 6),
        }
    # fused-kernel-vs-XLA at equal dtype (fp32): the auto-resolved mode
    # (compiled Pallas kernel on TPU; the identical jnp program on CPU,
    # where this ratio is ~1.0 by construction — `make perf-guard`
    # asserts the no-slower contract) against a bank forced to the XLA
    # epilogue
    xla_bank = ModelBank.from_models(models, registry=False, bank_kernel="jnp")
    fused_bank = ModelBank.from_models(models, registry=False)
    xla_bank.score_many(requests)
    fused_bank.score_many(requests)
    t0 = time.time()
    for _ in range(iters):
        xla_bank.score_many(requests)
    t_xla = time.time() - t0
    t0 = time.time()
    for _ in range(iters):
        fused_bank.score_many(requests)
    t_fused = time.time() - t0

    out["bank_dtype"] = fused_bank.bank_dtype  # the deployed default
    out["bank_kernel_mode"] = fused_bank.kernel_mode
    # the deployed dtype's footprint, so the headline pair stays
    # self-consistent under GORDO_BANK_DTYPE; fp32 recorded alongside as
    # the explicit baseline (per-dtype detail in bank_dtype_legs)
    out["weight_bytes_per_member"] = bpm.get(
        fused_bank.bank_dtype, bpm["float32"]
    )
    out["fp32_bytes_per_member"] = bpm["float32"]
    out["bank_dtype_legs"] = legs
    # the headline capacity wins the acceptance criteria name
    out["bank_capacity_win_bf16"] = round(bpm["float32"] / bpm["bfloat16"], 2)
    out["bank_capacity_win_int8"] = round(bpm["float32"] / bpm["int8"], 2)
    out["bank_kernel_vs_xla_speedup"] = round(t_xla / t_fused, 3)
    return out


def bench_server_scoring(n_features=10, batch=4096, iters=20):
    """Reconstruction-error samples/sec through the jit'd scoring path."""
    import jax
    import jax.numpy as jnp

    from gordo_components_tpu.models.factories import feedforward_hourglass
    from gordo_components_tpu.ops.scaler import fit_minmax, scaler_transform

    module = feedforward_hourglass(n_features, compute_dtype="bfloat16")
    rng = jax.random.PRNGKey(0)
    X = jax.random.normal(rng, (batch, n_features), dtype=jnp.float32)
    params = module.init(rng, X[:1])
    scaler = fit_minmax(X)

    @jax.jit
    def score(params, scaler, X):
        Xs = scaler_transform(scaler, X)
        recon = module.apply(params, Xs)
        return jnp.linalg.norm(jnp.abs(Xs - recon), axis=-1)

    score(params, scaler, X).block_until_ready()  # compile
    t0 = time.time()
    for _ in range(iters):
        out = score(params, scaler, X)
    out.block_until_ready()
    elapsed = time.time() - t0
    return {"server_recon_samples_per_sec": round(batch * iters / elapsed, 1)}


def bench_host_pipeline(n_members=1000, n_tags=10, days=30):
    """Host-side staging throughput at fleet scale: members/sec through
    the full provider->resample->join->dropna path via the SAME
    stage_members engine a gang build uses (SURVEY.md §7 hard part 2 —
    one process feeds the whole gang, so staging rate bounds fleet build
    throughput together with the device step). Measures the sequential
    baseline, the thread engine, and — on multi-core hosts — the spawned
    process pool."""
    import os

    from gordo_components_tpu.utils.staging import (
        load_worker_count,
        stage_members,
    )

    def configs(n, salt):
        return [
            {
                "type": "RandomDataset",
                "train_start_date": "2020-01-01",
                "train_end_date": f"2020-01-{days + 1:02d}",
                "tag_list": [f"bench-{salt}-{i}-{j}" for j in range(n_tags)],
            }
            for i in range(n)
        ]

    stage_members(configs(1, "warm"), workers=1)  # warm imports
    workers = load_worker_count(n_members)
    out = {}

    # sequential baseline on a smaller probe (the engines below cover the
    # full member count; a second full sequential pass would double the
    # metric's wall time for no information)
    n_probe = max(8, n_members // 8)
    t0 = time.time()
    loaded = stage_members(configs(n_probe, "seq"), workers=1)
    seq_el = time.time() - t0
    rows = sum(len(X) for X, _ in loaded)
    out["host_staging_members_per_sec"] = round(n_probe / seq_el, 2)
    out["host_staging_rows_per_member"] = rows // n_probe

    t0 = time.time()
    stage_members(configs(n_members, "thr"), workers=workers, mode="thread")
    out["host_staging_members_per_sec_threaded"] = round(
        n_members / (time.time() - t0), 2
    )
    out["host_staging_workers"] = workers
    out["host_staging_members"] = n_members

    cores = os.cpu_count() or 1
    if cores > 1:
        t0 = time.time()
        stage_members(
            configs(n_members, "proc"), workers=workers, mode="process"
        )
        out["host_staging_members_per_sec_process"] = round(
            n_members / (time.time() - t0), 2
        )
        # worker-count scaling curve (VERDICT r3 weak #2: the process
        # engine's throughput claim needs a measured curve, not just
        # correctness tests): per-mode rates at 1/2/4/8/... workers up to
        # the core count, on a reduced member count so the sweep stays
        # bounded. Any multi-core run (CI, a future bench host) captures
        # it; the driver's 1-core box records the skip reason instead.
        n_sweep = max(32, n_members // 4)
        # shared 1-worker baseline: stage_members short-circuits workers<=1
        # to the sync loop REGARDLESS of mode, so a "process @ 1" label
        # would report a rate that never pays the spawn cost — the serial
        # point is published once, honestly, as sync
        t0 = time.time()
        stage_members(configs(n_sweep, "sw-sync"), workers=1)
        sweep: dict = {
            "sync": {"1": round(n_sweep / (time.time() - t0), 2)}
        }
        w, widths = 2, []
        while w <= min(cores, 16):
            widths.append(w)
            w *= 2
        if widths and widths[-1] != min(cores, 16):
            widths.append(min(cores, 16))
        for mode in ("thread", "process"):
            rates = {}
            for w in widths:
                t0 = time.time()
                stage_members(
                    configs(n_sweep, f"sw-{mode}-{w}"), workers=w, mode=mode
                )
                rates[str(w)] = round(n_sweep / (time.time() - t0), 2)
            sweep[mode] = rates
        out["host_staging_worker_sweep"] = {
            "members": n_sweep, "cores": cores, "rates": sweep,
        }
    else:
        # single-core host: spawned workers would only time-slice; record
        # why the numbers are absent rather than publishing bogus ones
        out["host_staging_process_skipped"] = "single-core host"
        out["host_staging_worker_sweep_skipped"] = "single-core host"
    return out


def bench_north_star_serving(n_members=10000, epochs=2, concurrency=64):
    """Config 5 at the north star (VERDICT r3 next #3): train 10k ragged
    members in one gang, stack them into ONE HBM ModelBank, and serve
    concurrent load through the continuous-batching engine — bank build
    time, request latency percentiles, throughput, and host RSS from one
    process (tools/north_star_check.py, whose full document BASELINE.md
    cites)."""
    import os
    import sys

    tools_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    from north_star_check import run_check

    res = run_check(members=n_members, epochs=epochs, concurrency=concurrency)
    return {
        "north_star_members": n_members,
        "north_star_train_seconds": res["phases"]["train"]["seconds"],
        "north_star_xla_programs": res["phases"]["train"]["xla_programs"],
        "north_star_bank_build_seconds": res["phases"]["bank"]["seconds"],
        "north_star_bank_buckets": res["phases"]["bank"]["n_buckets"],
        "north_star_serving_p50_ms": res["serving"]["p50_ms"],
        "north_star_serving_p99_ms": res["serving"]["p99_ms"],
        "north_star_serving_samples_per_sec": res["serving"]["samples_per_sec"],
        "north_star_serving_avg_batch": res["serving"]["avg_batch"],
        "north_star_peak_rss_mb": res["peak_rss_mb"],
        "north_star_digest_gzip_mb": res["control_plane"]["digest_gzip_mb"],
        "north_star_device_memory": res.get("device_memory") or None,
        # round-5 legs: bounded-queue overload behavior and the
        # fleet-scale bulk-client backfill through a live server
        "north_star_overload": {
            k: res["overload"][k]
            for k in ("offered_rps", "served_rps", "shed_rate",
                      "served_p50_ms", "served_p99_ms")
        },
        "north_star_overload_compliant": {
            k: res["overload_compliant"][k]
            for k in ("offered_rps", "served_rps", "shed_rate",
                      "served_p50_ms", "served_p99_ms")
        },
        "north_star_client_backfill": {
            k: res["client_backfill"][k]
            for k in ("machines", "machines_ok", "rows_per_sec", "parquet",
                      "wall_s")
        },
    }


def bench_client_bulk(n_models=16, rows=3000, batch_size=500):
    """Bulk-client throughput through the real HTTP path (VERDICT r2 weak
    #7): rows/sec scoring a collection with JSON bodies vs parquet
    bodies, same models, same server."""
    import asyncio
    import shutil
    import tempfile

    import pandas as pd

    from gordo_components_tpu import serializer
    from gordo_components_tpu.client import Client
    from gordo_components_tpu.models import AutoEncoder, DiffBasedAnomalyDetector

    rng = np.random.RandomState(0)
    root = tempfile.mkdtemp(prefix="bench-client-")
    try:
        X = rng.rand(512, 10).astype("float32")
        for i in range(n_models):
            det = DiffBasedAnomalyDetector(
                base_estimator=AutoEncoder(epochs=1, batch_size=256)
            )
            det.fit(X + 0.01 * i)
            serializer.dump(
                det,
                f"{root}/bench-m{i}",
                metadata={"name": f"bench-m{i}"},
            )

        async def run():
            from aiohttp.test_utils import TestServer

            from gordo_components_tpu.server import build_app

            server = TestServer(build_app(root))
            await server.start_server()
            try:
                base = f"http://{server.host}:{server.port}"
                # the time range sets the scored row count: RandomDataset
                # fallback at 1min resolution -> rows minutes
                start = pd.Timestamp("2020-01-01T00:00:00Z")
                end = start + pd.Timedelta(minutes=rows)
                fallback = {
                    "type": "RandomDataset",
                    "tag_list": [f"t-{j}" for j in range(10)],
                    "resolution": "1min",
                }
                from gordo_components_tpu.utils import parquet_engine_available

                encodings = [("json", dict(use_parquet=False, use_tensor=False))]
                if parquet_engine_available():
                    encodings.append(
                        ("parquet", dict(use_parquet=True, use_tensor=False))
                    )
                # the framed binary tensor path (utils/wire.py): measured
                # LAST so its rows/s never benefits from server-side
                # warmup the earlier legs paid for
                encodings.append(
                    ("tensor", dict(use_parquet=False, use_tensor=True))
                )
                rates, bytes_per_row = {}, {}
                for label, enc_kwargs in encodings:
                    client = Client(
                        "proj", base_url=base, batch_size=batch_size,
                        metadata_fallback_dataset=fallback,
                        **enc_kwargs,
                    )
                    t0 = time.time()
                    results = await client.predict_async(start, end)
                    el = time.time() - t0
                    scored = sum(
                        len(r.predictions)
                        for r in results
                        if r.predictions is not None
                    )
                    ok = sum(r.ok for r in results)
                    assert ok == n_models, (label, ok)
                    rates[label] = scored / el
                    wire = client.wire_stats.get(label)
                    if wire and wire["rows"]:
                        bytes_per_row[label] = wire["bytes_out"] / wire["rows"]
                return rates, bytes_per_row
            finally:
                await server.close()

        rates, bytes_per_row = asyncio.run(run())
        out = {
            "client_bulk_rows_per_sec_json": round(rates["json"], 1),
            "client_bulk_config": (
                f"{n_models} models x {rows} rows, batch {batch_size}"
            ),
        }
        if "parquet" in rates:
            out["client_bulk_rows_per_sec_parquet"] = round(rates["parquet"], 1)
            out["client_parquet_vs_json"] = round(
                rates["parquet"] / rates["json"], 2
            )
        else:
            # the JSON figure still reports; the absent leg is explained
            out["client_bulk_parquet_skipped"] = "no parquet engine installed"
        # the binary data plane's headline numbers (ISSUE 10 acceptance:
        # tensor >= 5x JSON rows/s on the same machine, guarded in
        # tests/test_wire.py's perf-guard leg)
        out["client_bulk_rows_per_sec_tensor"] = round(rates["tensor"], 1)
        out["client_tensor_vs_json"] = round(rates["tensor"] / rates["json"], 2)
        out["client_bulk_request_bytes_per_row"] = {
            enc: round(v, 1) for enc, v in bytes_per_row.items()
        }
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


_FLEET_FAMILIES = {
    # arch summary strings double as the recorded config
    "lstm": (
        dict(model_type="LSTMAutoEncoder", kind="lstm_symmetric", dims=(16,)),
        "lstm_symmetric(16)",
    ),
    "conv": (
        dict(model_type="ConvAutoEncoder", channels=(16, 8)),
        "conv1d_autoencoder(16,8)",
    ),
    "vae": (
        dict(kind="feedforward_variational", dims=(64,), latent_dim=8),
        "feedforward_variational(64->8)",
    ),
}


def _bench_family_fleet(
    fam, n_models, rows, n_features, lookback, epochs, batch_size,
):
    """One zoo family at fleet scale (configs 2/4): gang rate AND a
    single-build rate of the IDENTICAL architecture/rows/epochs measured
    in the same run, so the reported speedup is like-for-like."""
    import jax

    from gordo_components_tpu.parallel import FleetTrainer

    fam_kwargs, arch = _FLEET_FAMILIES[fam]
    members = _synth_fleet(n_models, rows, n_features)
    n_chips = len(jax.devices())
    config = dict(
        epochs=epochs, batch_size=batch_size, compute_dtype="bfloat16",
        host_sync_every=epochs, **fam_kwargs,
    )
    if fam != "vae":
        config["lookback_window"] = lookback
    FleetTrainer(**config).fit(members)  # warm the programs
    trainer = FleetTrainer(**config)
    t0 = time.time()
    trainer.fit(members)
    elapsed = time.time() - t0
    fleet_rate = n_models / elapsed * 3600 / n_chips

    # single-build baseline: the SAME config trained one member at a time
    # (reference-style), measured over a few members on warm programs
    one = dict(list(members.items())[:1])
    single_cfg = dict(config)
    single_cfg.pop("host_sync_every")
    FleetTrainer(host_sync_every=1, **single_cfg).fit(one)  # warm
    n_probe = min(3, n_models)
    t0 = time.time()
    for name in list(members)[:n_probe]:
        FleetTrainer(host_sync_every=1, **single_cfg).fit({name: members[name]})
    single_rate = n_probe / (time.time() - t0) * 3600 / n_chips

    buckets = trainer.last_stats.get("buckets", [])
    out = {
        f"{fam}_fleet_models_per_hour_per_chip": round(fleet_rate, 1),
        f"{fam}_fleet_wall_seconds": round(elapsed, 2),
        f"{fam}_fleet_vs_single_same_arch": round(fleet_rate / single_rate, 1),
        # sequence fast-path provenance (ops/seq_scan.py): which layout
        # the measured epoch programs compiled with, and the width cap
        # the dispatches ran under (None = uncapped; GORDO_FLEET_WIDTH
        # =auto records the autotuned knee here)
        f"{fam}_fleet_layout": (
            buckets[0]["layout"] if buckets else "legacy"
        ),
        f"{fam}_fleet_autotuned_width": trainer.last_stats.get("width_cap"),
        f"{fam}_fleet_config": (
            f"{n_models} models x {rows} rows x {n_features} tags, {arch}, "
            + (f"lookback {lookback}, " if fam != "vae" else "")
            + f"{epochs} epochs, bf16"
        ),
    }
    if fam == "lstm":
        # layout A/B on THIS backend: the same fleet trained with the
        # time-major gang scan vs the legacy vmap(member) nesting, each
        # against the identical single-build baseline — BOTH paths'
        # vs_single ratios land in BENCH_DETAIL
        from gordo_components_tpu.ops.seq_scan import (
            SEQ_LAYOUT_ENV,
            resolve_seq_kernel_mode,
        )

        out[f"{fam}_fleet_kernel"] = resolve_seq_kernel_mode()
        default_layout = out[f"{fam}_fleet_layout"]
        other = "legacy" if default_layout == "time_major" else "time_major"
        prior = os.environ.get(SEQ_LAYOUT_ENV)
        try:
            os.environ[SEQ_LAYOUT_ENV] = other
            FleetTrainer(**config).fit(members)  # warm the flipped programs
            t0 = time.time()
            FleetTrainer(**config).fit(members)
            other_elapsed = time.time() - t0
        finally:
            if prior is None:
                os.environ.pop(SEQ_LAYOUT_ENV, None)
            else:
                os.environ[SEQ_LAYOUT_ENV] = prior
        other_rate = n_models / other_elapsed * 3600 / n_chips
        by_layout = {
            default_layout: (elapsed, fleet_rate),
            other: (other_elapsed, other_rate),
        }
        for layout, (wall, rate) in by_layout.items():
            out[f"{fam}_fleet_{layout}_wall_seconds"] = round(wall, 2)
            out[f"{fam}_fleet_vs_single_same_arch_{layout}"] = round(
                rate / single_rate, 1
            )
        tm_wall, _ = by_layout["time_major"]
        leg_wall, _ = by_layout["legacy"]
        out[f"{fam}_fleet_time_major_vs_legacy"] = round(leg_wall / tm_wall, 2)
    if fam == "conv":
        # no recurrence, no recurrent-step kernel: conv's fast path is
        # the matmul formulation A/B'd below
        out[f"{fam}_fleet_kernel"] = "n/a"
        # conv-impl A/B on THIS backend: slice+matmul (the default since
        # 2026-07-31 — 3-16x faster for gangs, 5-8x for singles on CPU,
        # and the MXU-native formulation) vs the stock lax conv ops,
        # which have exact numeric parity (models/factories/conv.py).
        # >1 means the matmul default is the right call on this backend.
        lax_cfg = dict(config, conv_impl="lax")
        FleetTrainer(**lax_cfg).fit(members)  # warm
        t0 = time.time()
        FleetTrainer(**lax_cfg).fit(members)
        lax_elapsed = time.time() - t0
        out["conv_matmul_impl_vs_lax"] = round(lax_elapsed / elapsed, 2)
        out["conv_lax_impl_wall_seconds"] = round(lax_elapsed, 2)
    return out


def _family_fleet_metric(fam):
    def run(n_models=256, rows=720, n_features=10, lookback=32, epochs=3,
            batch_size=128):
        return _bench_family_fleet(
            fam, n_models, rows, n_features, lookback, epochs, batch_size
        )

    run.__name__ = f"bench_{fam}_fleet"
    return run


bench_lstm_fleet = _family_fleet_metric("lstm")
bench_conv_fleet = _family_fleet_metric("conv")
bench_vae_fleet = _family_fleet_metric("vae")


METRICS = (
    ("fleet", bench_fleet),
    ("sequential", bench_single_sequential),
    ("width_sweep", bench_width_sweep),
    ("fleet_wide", bench_fleet_wide),
    ("lstm_fleet", bench_lstm_fleet),
    ("conv_fleet", bench_conv_fleet),
    ("vae_fleet", bench_vae_fleet),
    ("server_scoring", bench_server_scoring),
    ("bank_serving", bench_bank_serving),
    ("bank_capacity", bench_bank_capacity),
    ("bank_sequence", bench_bank_sequence),
    ("rebalance", bench_rebalance),
    ("streaming", bench_streaming),
    ("replay", bench_replay),
    ("fleet_compile", bench_fleet_compile),
    ("history", bench_history),
    ("heat_cost", bench_heat_cost),
    ("serving_saturation", bench_serving_saturation),
    ("mesh_serving", bench_mesh_serving),
    ("gameday", bench_gameday),
    ("qos", bench_qos),
    ("model_zoo", bench_sequence_models),
    ("checkpoint", bench_checkpoint_overhead),
    ("host_pipeline", bench_host_pipeline),
    ("client_bulk", bench_client_bulk),
    ("north_star", bench_north_star_serving),
)

# legs that drive a tools/*_demo.py child (or several server processes):
# those children are pinned to the CPU (_cpu_tool_env), so their records
# say ``cpu`` on every run
CPU_TOOL_METRICS = frozenset(
    {
        "rebalance", "streaming", "replay", "fleet_compile", "history",
        "heat_cost", "serving_saturation", "mesh_serving", "gameday", "qos",
    }
)


def run_metrics(platform: str, order=None, skip=(), on_cpu: bool = False):
    """Run the metric list in this process, in order; returns ``(detail,
    errors)``. One ``METRIC <name> <json>`` line is printed as each metric
    completes, with the platform it ran on. ``on_cpu`` (the explicit
    ``--platform cpu`` rehearsal) withholds every value: the record
    carries the names it would report and nothing measured."""
    by_name = dict(METRICS)
    names = [n for n in (order or [n for n, _ in METRICS]) if n not in skip]
    unknown = [n for n in names if n not in by_name]
    if unknown:
        raise SystemExit(f"unknown metric(s): {', '.join(unknown)}")
    detail, errors = {"metric_platforms": {}}, {}
    for name in names:
        t0 = time.time()
        try:
            out = by_name[name]()
        except Exception as exc:
            errors[name] = f"{type(exc).__name__}: {exc}"
            print(
                "METRIC_ERROR "
                + json.dumps({"name": name, "error": errors[name]}),
                flush=True,
            )
            continue
        ran_on = "cpu" if (on_cpu or name in CPU_TOOL_METRICS) else platform
        detail["metric_platforms"][name] = ran_on
        if on_cpu:
            record = {"platform": "cpu", "reports": sorted(out)}
        else:
            out[f"{name}_bench_seconds"] = round(time.time() - t0, 1)
            detail.update(out)
            record = {"platform": ran_on, **out}
        print(f"METRIC {name} " + json.dumps(record, default=str), flush=True)
    return detail, errors


def main() -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--platform", choices=["cpu"],
        help="functional rehearsal on the CPU: records carry names, no values",
    )
    parser.add_argument("--order", help="comma-separated metrics to run, in order")
    parser.add_argument("--skip", help="comma-separated metrics to leave out")
    args = parser.parse_args()
    order, skip = args.order, args.skip

    import jax

    on_cpu = args.platform == "cpu"
    if on_cpu:
        jax.config.update("jax_platforms", "cpu")
    from gordo_components_tpu.utils import resolve_compile_cache

    cache_dir = resolve_compile_cache()
    dev = jax.devices()[0]
    platform, device_kind, n_devices = (
        dev.platform, dev.device_kind, len(jax.devices()),
    )
    if platform != "tpu" and not on_cpu:
        print(
            f"bench.py measures the TPU; this process sees {platform!r} "
            f"({device_kind}). Pass --platform cpu for the functional "
            "rehearsal (no device metric is printed there).",
            file=sys.stderr,
        )
        return 2

    detail, errors = run_metrics(
        platform,
        order=order.split(",") if order else None,
        skip=set(skip.split(",")) if skip else (),
        on_cpu=on_cpu,
    )
    detail.update(
        platform=platform, device_kind=device_kind, n_devices=n_devices,
        compile_cache_dir=cache_dir,
    )
    headline = {
        "metric": "autoencoder models trained/hour/chip (fleet vmap engine)",
        "value": None,
        "unit": "models/hour/chip",
        "vs_baseline": None,
        "platform": platform,
        "device_kind": device_kind,
        "n_devices": n_devices,
    }
    if not on_cpu:
        fleet_rate = detail.get("fleet_models_per_hour_per_chip")
        seq_rate = detail.get("sequential_models_per_hour_per_chip")
        peak = PEAK_BF16_FLOPS.get(device_kind)
        if peak and detail.get("achieved_flops_per_sec"):
            detail["mfu"] = round(detail["achieved_flops_per_sec"] / peak, 6)
            detail["peak_bf16_flops_per_sec"] = peak
        # bandwidth roofline: for 417-param models HBM bytes/s vs peak is
        # the efficiency number that matters (the traffic model is a
        # documented lower bound, so the fraction is optimistic)
        hbm_peak = PEAK_HBM_BYTES.get(device_kind)
        if hbm_peak and detail.get("achieved_hbm_bytes_per_sec"):
            detail["peak_hbm_bytes_per_sec"] = hbm_peak
            detail["hbm_fraction_of_peak"] = round(
                detail["achieved_hbm_bytes_per_sec"] / hbm_peak, 4
            )
        from gordo_components_tpu.observability import get_registry

        detail["observability_registry"] = get_registry().snapshot()
        # the LAST stdout line is a compact headline that survives a tail
        # capture; the full detail goes to BENCH_DETAIL.json
        detail_file = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAIL.json"
        )
        with open(detail_file, "w") as fh:
            json.dump(
                {"detail": detail, "errors": errors}, fh, indent=1, default=str
            )
        headline.update(
            value=fleet_rate,
            vs_baseline=(
                round(fleet_rate / seq_rate, 2)
                if fleet_rate and seq_rate
                else None
            ),
            mfu=detail.get("mfu"),
            hbm_fraction_of_peak=detail.get("hbm_fraction_of_peak"),
            detail_file="BENCH_DETAIL.json",
        )
    if errors:
        headline["errors"] = {k: v[:100] for k, v in list(errors.items())[:6]}
    print(json.dumps(headline))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
