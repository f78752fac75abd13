#!/usr/bin/env python
"""North-star serving check (VERDICT r3 next #3; BASELINE.json config 5).

The round-3 engine check proved the BUILD leg at 10k models (staging +
one FleetTrainer process); this script proves the SERVE leg: the same
scale of members stacked into one HBM ModelBank behind one serving
process, with measured construction cost and request latency under
concurrent continuously-batched load.

Phases (each timed, with host RSS after):
  1. synth    — ragged member data (600-1440 rows x tags, sine+noise)
  2. train    — one FleetTrainer gang, 2 epochs (the build leg, for scale
                context)
  3. estimators — FleetMemberModel -> DiffBasedAnomalyDetector per member
                (the artifact-object shape the server collection holds)
  4. bank     — ModelBank.from_models over all members (the per-model
                Python extraction loop this check exists to measure)
  5. warmup   — per-bucket XLA pre-compile
  6. serve    — BatchingEngine under concurrent clients: client-side
                p50/p99, throughput, coalescing stats, queue-wait split

Prints one JSON document; run with --members 10000 for the north star
(defaults are CI-sized). CPU-safe: pass --platform cpu.
"""

import argparse
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rss_mb() -> float:
    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1)


def run_check(
    members: int = 512,
    tags: int = 10,
    min_rows: int = 600,
    max_rows: int = 1440,
    epochs: int = 2,
    platform: str | None = None,
    concurrency: int = 64,
    requests_per_client: int = 4,
    request_rows: int = 64,
    devices: int = 1,
) -> dict:
    """The full check as a callable (the CLI below wraps it). Returns the
    result document.

    ``devices > 1`` shards the ModelBank over a ``models``-axis mesh
    (``parallel/mesh.fleet_mesh``) and serves through the routed
    multi-chip path — on CPU this needs
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before
    jax initializes (the CLI below does this for you)."""

    from types import SimpleNamespace

    args = SimpleNamespace(
        members=members, tags=tags, min_rows=min_rows, max_rows=max_rows,
        epochs=epochs, platform=platform, concurrency=concurrency,
        requests_per_client=requests_per_client, request_rows=request_rows,
        devices=devices,
    )

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    import numpy as np

    from gordo_components_tpu.observability import (
        CostModel,
        GoodputLedger,
        HeatAccountant,
        MetricsRegistry,
        SLOTracker,
        get_registry,
    )
    from gordo_components_tpu.parallel.fleet import FleetTrainer
    from gordo_components_tpu.server.bank import BatchingEngine, ModelBank
    from gordo_components_tpu.utils.profiling import device_memory_stats

    out = {"config": dict(vars(args)), "phases": {}}

    def phase(name, t0):
        out["phases"][name] = {
            "seconds": round(time.time() - t0, 1),
            "peak_rss_mb": rss_mb(),
        }

    # ---- 1. synth ragged members ----
    t0 = time.time()
    rng = np.random.RandomState(0)
    t = np.arange(args.max_rows)
    members = {}
    for i in range(args.members):
        rows = int(rng.randint(args.min_rows, args.max_rows + 1))
        freqs = 0.01 + 0.002 * rng.rand(args.tags)
        phases_ = 2 * np.pi * rng.rand(args.tags)
        X = np.sin(np.outer(t[:rows], freqs) + phases_) + rng.normal(
            scale=0.05, size=(rows, args.tags)
        )
        members[f"machine-{i}"] = X.astype("float32")
    phase("synth", t0)

    # ---- 2. train the gang ----
    t0 = time.time()
    trainer = FleetTrainer(
        kind="feedforward_hourglass", epochs=args.epochs, batch_size=128
    )
    fleet = trainer.fit(members)
    phase("train", t0)
    out["phases"]["train"]["n_members"] = len(fleet)
    out["phases"]["train"]["xla_programs"] = len(trainer.last_stats["buckets"])

    # ---- 3. estimator objects (what a server collection holds) ----
    t0 = time.time()
    models = {name: fm.to_estimator() for name, fm in fleet.items()}
    phase("estimators", t0)

    # ---- 4. bank construction (the startup Python loop) ----
    mesh = None
    if args.devices > 1:
        import jax

        from gordo_components_tpu.parallel.mesh import fleet_mesh

        n_avail = len(jax.devices())
        if n_avail < args.devices:
            raise SystemExit(
                f"--devices {args.devices} but only {n_avail} jax device(s); "
                "on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{args.devices} before jax initializes"
            )
        mesh = fleet_mesh(args.devices)
    t0 = time.time()
    # dedicated registry: the per-shard/per-bucket assertions below must
    # see ONLY this check's serving traffic, not whatever else the process
    # recorded into the default registry
    registry = MetricsRegistry()
    # goodput/SLO evidence at scale (ISSUE 7): the ledger accounts the
    # serve phase's device windows + request outcomes, the tracker turns
    # them into burn rates, and both land in the artifact below
    ledger = GoodputLedger(registry=registry)
    slo_tracker = SLOTracker(ledger, sample_interval_s=0.05, registry=registry)
    # baseline sample NOW: windows are deltas between ring samples, so
    # without a pre-serve baseline every window would be empty and the
    # burn assertions below would pass vacuously
    slo_tracker.sample(force=True)
    # heat/cost observatory (ISSUE 18): the access-heat accountant rides
    # the serve phase's scoring path, the cost model joins the ledger's
    # device seconds to the bank's analytic FLOPs — both asserted below
    heat = HeatAccountant(registry=registry)
    bank = ModelBank.from_models(
        models, mesh=mesh, registry=registry, ledger=ledger, heat=heat
    )
    cost = CostModel(ledger, lambda: bank, registry=registry)
    bank_elapsed = time.time() - t0  # unrounded: CI-sized builds are ~ms
    phase("bank", t0)
    cov = bank.coverage()
    out["phases"]["bank"].update(
        banked=cov["banked"], n_buckets=cov["n_buckets"],
        fallback=len(cov["fallback"]),
        models_per_sec=round(len(models) / max(1e-9, bank_elapsed), 1),
    )
    assert cov["banked"] == args.members, cov
    # HBM capacity evidence (ISSUE 6): storage dtype, bytes per member,
    # models-per-GB at the configured GORDO_BANK_DTYPE — with no bucket
    # silently degraded to fp32 (a quantize fallback here would mean the
    # capacity headline is not what the knob claims)
    out["capacity"] = bank.capacity_stats()
    assert out["capacity"]["weight_bytes"] > 0, out["capacity"]
    assert out["capacity"]["models_per_gb"] > 0, out["capacity"]
    assert not out["capacity"]["quantize_fallbacks"], out["capacity"]

    # ---- 5. warmup (per-bucket XLA compile, off the request path) ----
    t0 = time.time()
    warmed = bank.warmup(rows=args.request_rows)
    phase("warmup", t0)
    out["phases"]["warmup"]["buckets"] = warmed
    out["device_memory"] = device_memory_stats()

    # ---- 6. concurrent serving latency through the real engine ----
    import asyncio

    reqs = {
        name: rng.rand(args.request_rows, args.tags).astype("float32")
        for name in list(models)[: max(args.concurrency * 4, 256)]
    }
    req_names = list(reqs)

    async def drive():
        # registry=False: warm + measured rounds each build a fresh engine,
        # and a shared registry histogram would accumulate across them —
        # the per-engine snapshot must cover the measured round only
        engine = BatchingEngine(
            bank, max_batch=args.concurrency, flush_ms=2.0, registry=False
        )
        engine.start()
        lat: list = []

        async def client(ci):
            for k in range(args.requests_per_client):
                name = req_names[(ci * args.requests_per_client + k) % len(req_names)]
                t0 = time.monotonic()
                r = await engine.score(name, reqs[name])
                dt = time.monotonic() - t0
                lat.append(dt)
                # every served request classifies with the goodput
                # ledger, exactly as the HTTP middleware would
                ledger.finish_request(200, dt, r.device_s)
                assert np.isfinite(r.total_scaled).all()

        await asyncio.gather(*(client(i) for i in range(args.concurrency)))
        await engine.stop()
        return lat, engine

    asyncio.run(drive())  # warm round: compiles the coalesced batch shapes
    t0 = time.time()
    lat, engine = asyncio.run(drive())
    wall = time.time() - t0
    lat.sort()
    pct = lambda q: round(lat[min(len(lat) - 1, int(q * len(lat)))] * 1e3, 2)
    out["serving"] = {
        "requests": len(lat),
        "concurrency": args.concurrency,
        "rows_per_request": args.request_rows,
        "p50_ms": pct(0.50),
        "p95_ms": pct(0.95),
        "p99_ms": pct(0.99),
        "requests_per_sec": round(len(lat) / wall, 1),
        "samples_per_sec": round(len(lat) * args.request_rows / wall, 1),
        "avg_batch": round(
            engine.stats["requests"] / max(1, engine.stats["batches"]), 2
        ),
        "queue_wait": engine.queue_wait.snapshot(),
    }
    # scoring-pipeline evidence at scale (ISSUE 5): in-flight window,
    # padded-buffer arena hit rate, and the host/device overlap ratio
    # (non-null only when the request mix spans several buckets — the
    # single-architecture north-star fleet coalesces into one group).
    # The arena must never leak a buffer across the whole serve phase.
    out["pipeline"] = bank.pipeline_stats()
    assert out["pipeline"]["arena"]["outstanding"] == 0, out["pipeline"]
    # ---- goodput + SLO evidence (ISSUE 7): captured BEFORE the overload
    # legs below so the headline numbers cover the clean serve phase.
    # Everything served 200 with finite scores, so the goodput ratio is
    # 1.0 by construction and the availability budget must not burn. ----
    slo_tracker.sample(force=True)
    out["goodput"] = ledger.snapshot()
    out["slo"] = slo_tracker.snapshot()
    gr = out["goodput"]["goodput_ratio"]
    assert gr is not None and 0.0 < gr <= 1.0, out["goodput"]
    assert out["goodput"]["device"]["total_s"] > 0, out["goodput"]
    # no-drift: the registry renders the SAME ratio the snapshot reports
    reg_snap = registry.snapshot()
    g_series = reg_snap.get("gordo_goodput_ratio", {}).get("values", [])
    assert g_series and abs(g_series[0]["value"] - gr) < 1e-6, g_series
    # per-OBJECTIVE rows only: the family also carries {tenant,class}
    # rows once the ledger holds tenant cells (the QoS leg below)
    burn_series = [
        v
        for v in reg_snap.get("gordo_slo_burn_rate", {}).get("values", [])
        if "objective" in v["labels"]
    ]
    assert len(burn_series) == len(out["slo"]["objectives"]) * len(
        out["slo"]["windows"]
    ), burn_series
    avail = next(
        o for o in out["slo"]["objectives"] if o["name"] == "availability"
    )
    # non-vacuous: the windows must actually have seen the serve traffic
    # (the baseline sample predates it) before the zero-burn claim counts
    assert any(w["total"] > 0 for w in avail["windows"].values()), avail
    assert all(
        w["burn_rate"] == 0.0 for w in avail["windows"].values()
    ), avail
    # ---- 6b. overload: offered load past capacity must shed (429 path)
    # with bounded latency, not grow the queue without bound. Clients
    # hammer in closed loops at ~4x the concurrency the engine coalesces,
    # with max_queue deliberately small relative to the storm; served
    # p99 stays bounded by (max_queue/max_batch + 1) batches. ----
    async def overload(duration_s=3.0, compliant=False):
        """Past-capacity storm. ``compliant=False``: greedy clients retry
        ~immediately after a shed (the worst case — on a 1-core host the
        429 machinery itself then competes with scoring). ``True``:
        clients honor the shed's queue-drain estimate before re-offering,
        exactly as the bulk client's transport does with the HTTP
        Retry-After header (client/io.py)."""
        from gordo_components_tpu.server.bank import EngineOverloaded

        engine = BatchingEngine(
            bank, max_batch=args.concurrency, flush_ms=2.0,
            max_queue=2 * args.concurrency, registry=False,
        )
        engine.start()
        served_lat: list = []
        sheds = 0
        stop_at = time.monotonic() + duration_s

        async def client(ci):
            nonlocal sheds
            k = 0
            while time.monotonic() < stop_at:
                name = req_names[(ci + k) % len(req_names)]
                k += 1
                t0 = time.monotonic()
                try:
                    await engine.score(name, reqs[name])
                    served_lat.append(time.monotonic() - t0)
                except EngineOverloaded as exc:
                    sheds += 1
                    await asyncio.sleep(
                        exc.retry_after_s if compliant else 0.001
                    )

        n_clients = 4 * args.concurrency
        t0 = time.monotonic()
        await asyncio.gather(*(client(i) for i in range(n_clients)))
        wall = time.monotonic() - t0
        await engine.stop()
        served_lat.sort()
        pct = lambda q: round(
            served_lat[min(len(served_lat) - 1, int(q * len(served_lat)))] * 1e3, 2
        ) if served_lat else None
        offered = len(served_lat) + sheds
        return {
            "clients": n_clients,
            "compliant_backoff": compliant,
            "max_queue": engine.max_queue,
            "offered_rps": round(offered / wall, 1),
            "served_rps": round(len(served_lat) / wall, 1),
            "shed": sheds,
            "shed_rate": round(sheds / max(1, offered), 3),
            "served_p50_ms": pct(0.50),
            "served_p99_ms": pct(0.99),
            "engine_shed_counter": engine.stats["shed"],
        }

    out["overload"] = asyncio.run(overload())
    out["overload_compliant"] = asyncio.run(overload(compliant=True))

    # ---- 6b-qos. multi-tenant fairness under the same storm (ISSUE 19):
    # a best_effort flood past capacity must burn ONLY its own class
    # budget. The admission controller's per-class depth thresholds turn
    # the flood away at half the queue, the weighted-fair queue drains
    # interactive first, and the paced interactive closed loops see zero
    # sheds — so the interactive availability burn stays EXACTLY 0 while
    # best_effort eats 429s (all classified as wasted by the ledger).
    async def qos_flood(duration_s=3.0):
        from gordo_components_tpu.qos.admission import (
            AdmissionController,
            QosShed,
        )
        from gordo_components_tpu.qos.classify import RequestClass
        from gordo_components_tpu.server.bank import EngineOverloaded

        admission = AdmissionController()  # default fractions, no buckets
        admission.burn_for = slo_tracker.class_burn
        engine = BatchingEngine(
            bank, max_batch=args.concurrency, flush_ms=2.0,
            max_queue=2 * args.concurrency, registry=False,
        )
        engine.start()
        served = {"interactive": 0, "best_effort": 0}
        sheds = {"interactive": 0, "best_effort": 0}
        stop_at = time.monotonic() + duration_s

        async def client(ci, rc, pace_s):
            k = 0
            while time.monotonic() < stop_at:
                name = req_names[(ci + k) % len(req_names)]
                k += 1
                t0 = time.monotonic()
                try:
                    label = admission.admit(
                        rc, queue_depth=engine._queue.qsize(),
                        max_queue=engine.max_queue,
                        drain_s=engine.drain_estimate(),
                    )
                    r = await engine.score(
                        name, reqs[name], tenant=rc.tenant,
                        qos_class=rc.qos_class,
                    )
                    served[rc.qos_class] += 1
                    ledger.finish_request(
                        200, time.monotonic() - t0, r.device_s,
                        tenant=label, qos_class=rc.qos_class,
                    )
                except (QosShed, EngineOverloaded) as exc:
                    sheds[rc.qos_class] += 1
                    ledger.finish_request(
                        429, time.monotonic() - t0, 0.0,
                        tenant=getattr(exc, "tenant", "other"),
                        qos_class=rc.qos_class,
                    )
                    await asyncio.sleep(exc.retry_after_s)
                if pace_s:
                    await asyncio.sleep(pace_s)

        flood_rc = RequestClass(tenant="flood", qos_class="best_effort")
        inter_rc = RequestClass()
        await asyncio.gather(
            *(client(i, flood_rc, 0.0) for i in range(4 * args.concurrency)),
            *(
                client(i, inter_rc, 0.02)
                for i in range(max(4, args.concurrency // 8))
            ),
        )
        await engine.stop()
        slo_tracker.sample(force=True)
        classes = slo_tracker.snapshot().get("classes", {})
        inter_windows = [
            w
            for key, entry in classes.items()
            if key.rsplit("|", 1)[-1] == "interactive"
            for w in entry["windows"].values()
        ]
        verdict = {
            "served": dict(served),
            "shed": dict(sheds),
            "admission": admission.snapshot(),
            "interactive_burn_max": max(
                (w["burn_rate"] for w in inter_windows), default=None
            ),
            "best_effort_burn_fast": slo_tracker.class_burn("best_effort"),
            "engine_class_stats": {
                c: dict(s) for c, s in engine.class_stats.items()
            },
        }
        # the storm was real, yet interactive never shed and its per-class
        # availability budget did not burn at all
        assert served["interactive"] > 0, verdict
        assert sheds["interactive"] == 0, verdict
        assert sheds["best_effort"] > 0, verdict
        assert any(w["total"] > 0 for w in inter_windows), verdict
        assert all(w["burn_rate"] == 0.0 for w in inter_windows), verdict
        assert (verdict["best_effort_burn_fast"] or 0.0) > 0.0, verdict
        return verdict

    out["qos_fairness"] = asyncio.run(qos_flood())

    # ---- 6d. metrics registry: the per-shard skew and per-bucket program
    # visibility this scale exists to prove (VERDICT r5 weak #2 — a hot
    # shard was previously invisible). Asserted sane here so every
    # result document carries skew evidence. ----
    heat.sample(force=True)  # fold the serve phase's routed rows now
    cost.sample(force=True)  # join the ledger's device time to FLOPs
    snap = registry.snapshot()

    def series(name, label):
        return {
            v["labels"][label]: v["value"]
            for v in snap.get(name, {}).get("values", [])
        }

    shard_rows = series("gordo_bank_shard_routed_rows_total", "shard")
    shard_pad = series("gordo_bank_shard_padded_rows_total", "shard")
    assert len(shard_rows) == max(1, args.devices), (
        f"expected {max(1, args.devices)} shard series, got {shard_rows}"
    )
    vals = list(shard_rows.values())
    mean_rows = sum(vals) / len(vals)
    assert mean_rows > 0, shard_rows
    skew = max(vals) / mean_rows
    assert 1.0 <= skew < float("inf"), skew
    bucket_calls = series("gordo_bank_bucket_calls_total", "bucket")
    assert bucket_calls and all(v >= 1 for v in bucket_calls.values()), bucket_calls
    # capacity series (ISSUE 6 contract): per-dtype HBM weight bytes must
    # render and agree with the bank's own accounting
    weight_series = series("gordo_bank_weight_bytes", "dtype")
    assert weight_series, "gordo_bank_weight_bytes missing from the registry"
    assert sum(weight_series.values()) == out["capacity"]["weight_bytes"], (
        weight_series, out["capacity"]["weight_bytes"],
    )
    # heat/cost observatory (ISSUE 18 contract): a gordo_bucket_mfu
    # series for EVERY live bucket — where the device has a known peak;
    # a device in neither the spec table nor GORDO_DEVICE_PEAK_FLOPS (a CPU
    # run) has no MFU at all — heat tiers covering the whole fleet, and
    # ZERO series dropped by the cardinality guard — the exposition must
    # stay bounded at 10k members, not grow per member
    mfu_series = series("gordo_bucket_mfu", "bucket")
    if cost.peak_flops is None:
        assert not mfu_series, mfu_series
    else:
        assert set(mfu_series) >= set(bank.flops_stats()), (
            set(bank.flops_stats()) - set(mfu_series)
        )
        assert all(v >= 0 for v in mfu_series.values()), mfu_series
    heat_snap = heat.snapshot()
    tier_series = series("gordo_heat_tier_members", "tier")
    assert sum(tier_series.values()) == heat_snap["members_total"], (
        tier_series, heat_snap["members_total"],
    )
    assert heat_snap["members_total"] == args.members, heat_snap["members_total"]
    assert "gordo_metrics_dropped_series_total" not in snap, snap.get(
        "gordo_metrics_dropped_series_total"
    )
    out["heat"] = {
        "tiers": heat_snap["tiers"],
        "members_total": heat_snap["members_total"],
        "rate_total": heat_snap["rate_total"],
    }
    out["costs"] = {
        label: {
            "mfu": row["mfu"],
            "flops_per_row": row["flops_per_row"],
            "pad_waste_score": row["pad_waste_score"],
        }
        for label, row in cost.snapshot()["buckets"].items()
    }
    # fleet-train side (process default registry): program-build counts
    # recorded by FleetTrainer during phase 2 — present and bounded (a
    # recompile storm at 10k members would show up as builds >> buckets)
    fleet_snap = get_registry().snapshot()
    prog = fleet_snap.get("gordo_fleet_program_builds_total", {}).get("values", [])
    prog_builds = prog[0]["value"] if prog else 0
    bucket_builds = {
        v["labels"]["bucket"]: v["value"]
        for v in fleet_snap.get("gordo_fleet_bucket_builds_total", {}).get(
            "values", []
        )
    }
    assert prog_builds >= 1, fleet_snap.keys()
    assert bucket_builds and all(v >= 1 for v in bucket_builds.values()), (
        bucket_builds
    )
    out["metrics"] = {
        "per_shard_routed_rows": shard_rows,
        "per_shard_padded_rows": shard_pad,
        "shard_skew_ratio": round(skew, 3),
        "bank_bucket_calls": bucket_calls,
        "fleet_program_builds": prog_builds,
        "fleet_bucket_builds": bucket_builds,
    }

    # ---- 6e. placement control plane (ISSUE 8, sharded runs only): a
    # DELIBERATELY skewed window — all of shard 0's members at 8x — must
    # plan + swap to a >=2x measured skew cut, with the generation flip
    # pause recorded (the only serving pause a rebalance incurs; run
    # with --members 10000 --devices 8 for the north-star fixture). ----
    if args.devices > 1:
        from gordo_components_tpu.placement.planner import (
            plan_rebalance,
            skew_ratio,
        )
        from gordo_components_tpu.placement.swap import (
            build_bank,
            snapshot_collectors,
            swap_bank,
        )

        placement = bank.placement()
        pbucket = placement["buckets"][0]
        hot = set(pbucket["members"][: pbucket["shard_size"]])

        def skewed_traffic(b, names, weight=8):
            sreqs = []
            for name in names:
                for _ in range(weight if name in hot else 1):
                    sreqs.append(
                        (
                            name,
                            rng.rand(args.request_rows, args.tags).astype(
                                "float32"
                            ),
                            None,
                        )
                    )
            b.score_many(sreqs)

        def shard_rows_now():
            return {
                v["labels"]["shard"]: v["value"]
                for v in registry.snapshot()[
                    "gordo_bank_shard_routed_rows_total"
                ]["values"]
            }

        # bounded member sample: shard 0's block hot, a slice of each
        # other shard cold — enough signal without re-driving all 10k
        sample = sorted(hot) + [
            n for n in pbucket["members"] if n not in hot
        ][: max(64, len(hot) * 7)]
        base_loads = dict(bank.model_rows)
        skewed_traffic(bank, sample)  # warm the skewed batch shapes
        m0 = shard_rows_now()
        skewed_traffic(bank, sample)
        m1 = shard_rows_now()
        skew_before = skew_ratio(
            [m1[s] - m0.get(s, 0.0) for s in sorted(m1)]
        )
        window_loads = {
            n: v - base_loads.get(n, 0)
            for n, v in bank.model_rows.items()
            if v > base_loads.get(n, 0)
        }
        plan = plan_rebalance(
            placement["buckets"], window_loads, threshold=1.2, min_rows=1
        )
        assert plan.should_apply, plan.reason
        app_like = {
            "bank": bank, "bank_mesh": mesh, "metrics": registry,
            "bank_config": {}, "goodput": None,
        }
        prev_collectors = snapshot_collectors(registry)
        t0 = time.time()
        new_bank = build_bank(
            app_like, models, member_order=plan.member_order(), warmup=False
        )
        rebuild_s = time.time() - t0
        swap_result = swap_bank(
            app_like, new_bank, prev_collectors=prev_collectors
        )
        skewed_traffic(new_bank, sample)  # warm the new routed shapes
        m0 = shard_rows_now()
        skewed_traffic(new_bank, sample)
        m1 = shard_rows_now()
        skew_after = skew_ratio(
            [m1[s] - m0.get(s, 0.0) for s in sorted(m1)]
        )
        out["rebalance"] = {
            "sampled_members": len(sample),
            "hot_members": len(hot),
            "shard_skew_before": round(skew_before, 3),
            "shard_skew_after": round(skew_after, 3),
            "skew_reduction": round(skew_before / skew_after, 3),
            "predicted_improvement": round(plan.improvement, 3),
            "moved_members": plan.moved,
            "swap_pause_ms": round(swap_result.pause_s * 1e3, 3),
            "bank_rebuild_s": round(rebuild_s, 2),
            "generation": swap_result.generation,
        }
        # the acceptance bar: the planner must cut the measured skew 2x
        assert out["rebalance"]["skew_reduction"] >= 2.0, out["rebalance"]
        # the flip is a pointer swing — anything slower means the swap
        # started doing work inside the critical section
        assert swap_result.pause_s < 0.25, out["rebalance"]
        bank = new_bank  # later legs serve the rebalanced generation

    # ---- 6c. fleet-scale client backfill through a REAL server
    # (VERDICT r4 next #4): dump a few hundred members as artifacts,
    # serve them with build_app on a live port, and drive the bulk
    # Client (metadata prefetch -> chunk -> POST -> frame reassembly,
    # parquet when advertised) across all of them concurrently — the
    # §3.3 throughput hot loop at a width tests/test_client.py never
    # reaches. ----
    import tempfile

    import pandas as pd
    from aiohttp import web as aioweb

    from gordo_components_tpu import serializer as _ser
    from gordo_components_tpu.client.client import Client
    from gordo_components_tpu.server import build_app

    backfill_names = list(models)[: min(256, len(models))]
    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="ns-client-") as artdir:
        for n in backfill_names:
            _ser.dump(models[n], os.path.join(artdir, n), metadata={"name": n})
        dump_s = time.time() - t0
        # same sharding as the phases above measured — NOT whatever
        # GORDO_SERVER_DEVICES/jax.devices() would imply on this host
        app = build_app(artdir, devices=args.devices)

        async def drive_client():
            runner = aioweb.AppRunner(app)
            await runner.setup()
            site = aioweb.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = site._server.sockets[0].getsockname()[1]
            try:
                client = Client(
                    "northstar",
                    base_url=f"http://127.0.0.1:{port}",
                    parallelism=32,
                    batch_size=100,  # forces multi-chunk requests per machine
                    metadata_fallback_dataset={
                        "type": "RandomDataset",
                        "tag_list": [f"t-{j}" for j in range(args.tags)],
                    },
                )
                t1 = time.time()
                results = await client.predict_async(
                    pd.Timestamp("2020-01-01T00:00:00Z"),
                    pd.Timestamp("2020-01-02T10:00:00Z"),  # 204 rows @ 10min
                )
                return (
                    results,
                    time.time() - t1,
                    client._parquet_active,
                    client._tensor_active,
                )
            finally:
                await runner.cleanup()

        results, wall, parquet_active, tensor_active = asyncio.run(
            drive_client()
        )
    ok = [r for r in results if r.ok]
    rows = sum(len(r.predictions) for r in ok)
    out["client_backfill"] = {
        "machines": len(backfill_names),
        "machines_ok": len(ok),
        "errors": [r.error_messages for r in results if not r.ok][:5],
        "artifact_dump_s": round(dump_s, 1),
        "wall_s": round(wall, 1),
        "rows": rows,
        "rows_per_sec": round(rows / max(1e-9, wall), 1),
        "parquet": bool(parquet_active),
        # the negotiated data plane: True means the backfill rode the
        # framed binary tensor format (architecture.md "Wire protocol")
        "tensor": bool(tensor_active),
        "server_requests": dict(app["stats"]["requests"]),
        "peak_rss_mb": rss_mb(),  # client+server share this process: a
        # scale ceiling for the leg, not a pure client number
    }
    assert len(ok) == len(backfill_names), out["client_backfill"]["errors"]

    # ---- 7. control-plane snapshot size at this scale (VERDICT r3 #5:
    # the digest exists so watchman's periodic poll of an N-model fleet
    # is O(small) bytes; measure both bodies as metadata-all would build
    # them, with representative per-member metadata) ----
    import gzip

    from gordo_components_tpu.utils.digest import metadata_digest

    def fat_meta(name):
        return {
            "name": name,
            "checked_at": "2026-07-31T00:00:00+00:00",
            "dataset": {"tag_list": [{"name": f"t-{j}"} for j in range(args.tags)]},
            "model": {
                "model_config": {
                    "gordo_components_tpu.models.DiffBasedAnomalyDetector": {}
                },
                "model_builder_cache_key": f"{hash(name) & 0xFFFFFFFF:064x}",
                "trained": True,
                "fleet_trained": True,
                "history": {"loss": [0.1] * 50},
            },
        }

    full_body = {n: {"healthy": True, "endpoint-metadata": fat_meta(n)} for n in models}
    digest_body = {
        n: {"healthy": True, "digest": metadata_digest(fat_meta(n))} for n in models
    }
    full_json = json.dumps(full_body).encode()
    digest_json = json.dumps(digest_body).encode()
    out["control_plane"] = {
        "targets": len(models),
        "full_metadata_mb": round(len(full_json) / 1e6, 2),
        "digest_mb": round(len(digest_json) / 1e6, 2),
        "digest_gzip_mb": round(len(gzip.compress(digest_json, 6)) / 1e6, 3),
    }

    # ---- 8. sequence fast path (ISSUE 20): the time-major gang scan
    # must be ACTIVE when forced (auto keeps legacy on CPU) and
    # parity-clean against the legacy layout, end to end through both
    # training and bank scoring — tiny shapes, this is a wiring check,
    # not a benchmark ----
    t0 = time.time()
    from gordo_components_tpu.ops.seq_scan import SEQ_LAYOUT_ENV

    rng = np.random.RandomState(7)
    seq_members = {
        f"seq-{i}": rng.rand(48, args.tags).astype("float32")
        for i in range(3)
    }
    seq_cfg = dict(
        model_type="LSTMAutoEncoder", kind="lstm_symmetric", dims=(6,),
        lookback_window=8, epochs=1, batch_size=16, seed=0,
    )
    prior_layout = os.environ.get(SEQ_LAYOUT_ENV)
    try:
        os.environ[SEQ_LAYOUT_ENV] = "legacy"
        leg_trainer = FleetTrainer(**seq_cfg)
        leg_fleet = leg_trainer.fit(seq_members)
        os.environ[SEQ_LAYOUT_ENV] = "time_major"
        tm_trainer = FleetTrainer(**seq_cfg)
        tm_fleet = tm_trainer.fit(seq_members)
        tm_layouts = [
            b["layout"] for b in tm_trainer.last_stats["buckets"]
        ]
        assert tm_layouts and all(l == "time_major" for l in tm_layouts), (
            tm_layouts
        )
        import jax as _jax

        max_err = 0.0
        for n in seq_members:
            for a, b in zip(
                _jax.tree.leaves(leg_fleet[n].params),
                _jax.tree.leaves(tm_fleet[n].params),
            ):
                denom = np.maximum(np.abs(np.asarray(a)), 1e-3)
                max_err = max(
                    max_err,
                    float(np.max(np.abs(np.asarray(a) - np.asarray(b)) / denom)),
                )
        # documented fp32 band: the layouts re-associate the gate matmuls
        assert max_err < 1e-3, max_err
        # bank scoring through the time-major program (interpret-mode
        # fused step = the CI parity vehicle for the Pallas kernel)
        from gordo_components_tpu.ops.seq_scan import SEQ_KERNEL_ENV

        seq_dets = {n: m.to_estimator() for n, m in tm_fleet.items()}
        os.environ[SEQ_LAYOUT_ENV] = "legacy"
        leg_bank = ModelBank.from_models(seq_dets)
        os.environ[SEQ_LAYOUT_ENV] = "time_major"
        prior_kernel = os.environ.get(SEQ_KERNEL_ENV)
        try:
            os.environ[SEQ_KERNEL_ENV] = "interpret"
            tm_bank = ModelBank.from_models(seq_dets)
            row = next(iter(tm_bank.flops_stats().values()))
            assert row["seq_layout"] == "time_major", row
            assert row["seq_kernel"] == "interpret", row
            Xq = seq_members["seq-0"]
            score_err = 0.0
            for n in seq_members:
                a = leg_bank.score(n, Xq)
                b = tm_bank.score(n, Xq)
                score_err = max(
                    score_err,
                    float(np.max(np.abs(a.total_scaled - b.total_scaled))),
                )
            assert score_err < 1e-3, score_err
        finally:
            if prior_kernel is None:
                os.environ.pop(SEQ_KERNEL_ENV, None)
            else:
                os.environ[SEQ_KERNEL_ENV] = prior_kernel
    finally:
        if prior_layout is None:
            os.environ.pop(SEQ_LAYOUT_ENV, None)
        else:
            os.environ[SEQ_LAYOUT_ENV] = prior_layout
    out["seq_fleet"] = {
        "layout": "time_major",
        "kernel": "interpret",
        "members": len(seq_members),
        "train_param_rel_err": float(f"{max_err:.2e}"),
        "bank_score_abs_err": float(f"{score_err:.2e}"),
        "seconds": round(time.time() - t0, 1),
    }

    out["peak_rss_mb"] = rss_mb()
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--members", type=int, default=512)
    ap.add_argument("--tags", type=int, default=10)
    ap.add_argument("--min-rows", type=int, default=600)
    ap.add_argument("--max-rows", type=int, default=1440)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--platform", default=None,
                    help="in-process jax platform pin (e.g. cpu)")
    ap.add_argument("--concurrency", type=int, default=64)
    ap.add_argument("--requests-per-client", type=int, default=4)
    ap.add_argument("--request-rows", type=int, default=64)
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the bank over an N-device models mesh")
    a = ap.parse_args()
    if a.devices > 1 and (a.platform or "") == "cpu":
        # must land before jax initializes; run_check imports jax lazily
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={a.devices}"
            ).strip()
    print(json.dumps(run_check(**vars(a)), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
