#!/usr/bin/env python
"""capacity-demo: the fleet heat & device-cost observatory as a capacity
advisor, in one process (``make capacity-demo``).

Trains a small mixed-architecture fleet (dense + LSTM buckets), serves
it through the real ``build_app`` stack, drives deliberately skewed
traffic (a hot quartet at ~8x the cold members), then asks the three
observatory surfaces the operator's capacity questions:

1. ``GET /heat`` — who is actually hot? (decayed routed-row rates,
   hot/warm/cold tier split, per-bucket breakdown);
2. ``GET /costs`` — where do device seconds go? (per-bucket MFU from
   analytic FLOPs x the goodput ledger's measured device time, pad
   waste, the fix-this-first ranking);
3. ``/stats bank_capacity`` — what does the bank weigh? (stacked bytes
   by dtype, models/GB).

From those three it prints the ADVISOR tables: the tier split with the
hottest members, the per-bucket MFU league, and the projected members
per HBM budget per storage dtype (fp32 baseline vs the current mix vs a
hypothetical int8 cold tier — the tiered-bank sizing the heat ranking
exists to feed). Ends with one machine-readable JSON doc.
"""

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# fold on demand only (?refresh=1): the demo controls its own cadence
os.environ.setdefault("GORDO_HEAT_SAMPLE_S", "3600")
os.environ.setdefault("GORDO_COST_SAMPLE_S", "3600")
# demo-scale tier thresholds: the drive loop produces ~0.6 rows/s on the
# hot quartet and ~0.07 on everyone else (vs the production default of
# 10/s), so classify at that scale to show a real hot/cold split
os.environ.setdefault("GORDO_HEAT_HOT_RATE", "0.3")
os.environ.setdefault("GORDO_HEAT_WARM_RATE", "0.1")

import numpy as np  # noqa: E402

HOT = ("hot-0", "hot-1", "hot-2", "hot-3")
COLD = ("cold-0", "cold-1", "cold-2", "cold-3")
LSTM = ("lstm-0", "lstm-1")

# HBM budgets the projection table quotes (bytes)
BUDGETS_GB = (8, 16, 32)


def build_artifacts(root: str) -> None:
    from gordo_components_tpu import serializer
    from gordo_components_tpu.models import (
        AutoEncoder,
        DiffBasedAnomalyDetector,
        LSTMAutoEncoder,
    )

    rng = np.random.RandomState(0)
    X = rng.rand(200, 3).astype("float32")
    for i, name in enumerate(HOT + COLD):
        det = DiffBasedAnomalyDetector(
            base_estimator=AutoEncoder(epochs=1, batch_size=64)
        )
        det.fit(X + 0.01 * i)
        serializer.dump(det, os.path.join(root, name), metadata={"name": name})
    for i, name in enumerate(LSTM):
        det = DiffBasedAnomalyDetector(
            base_estimator=LSTMAutoEncoder(
                lookback_window=6, epochs=1, batch_size=64
            )
        )
        det.fit(X + 0.01 * i)
        serializer.dump(det, os.path.join(root, name), metadata={"name": name})


def advise_capacity(capacity: dict, heat: dict) -> dict:
    """The projection table: members that fit per HBM budget per storage
    dtype, from the bank's measured bytes/member — plus the tiered-bank
    what-if (cold members demoted to int8) the heat split prices."""
    members = capacity.get("members") or 0
    weight = capacity.get("weight_bytes") or 0
    fp32 = capacity.get("fp32_bytes") or 0
    if not members or not weight:
        return {}
    bpm_now = weight / members
    bpm_fp32 = fp32 / members
    bpm_int8 = bpm_fp32 / 4.0  # int8 storage ~ quarter of fp32
    tiers = heat.get("tiers") or {}
    cold_n = int(tiers.get("cold") or 0)
    hot_warm_n = max(0, members - cold_n)
    # tiered what-if: hot/warm stay at the current mix, cold demote to
    # int8 — the blended bytes/member a heat-driven tier policy buys
    bpm_tiered = (
        (hot_warm_n * bpm_now + cold_n * bpm_int8) / members
    )
    rows = {}
    for label, bpm in (
        ("fp32_baseline", bpm_fp32),
        ("current_mix", bpm_now),
        ("cold_tier_int8", bpm_tiered),
    ):
        rows[label] = {
            "bytes_per_member": round(bpm, 1),
            "members_per_budget": {
                f"{gb}GB": int(gb * 1024**3 // bpm) for gb in BUDGETS_GB
            },
        }
    return {
        "members": members,
        "cold_members": cold_n,
        "projection": rows,
    }


async def main() -> int:
    from aiohttp.test_utils import TestClient, TestServer

    from gordo_components_tpu.server import build_app

    root = tempfile.mkdtemp(prefix="gordo-capacity-demo-")
    print(f"training {len(HOT + COLD + LSTM)} demo models into {root} ...",
          flush=True)
    build_artifacts(root)

    app = build_app(root)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        rng = np.random.RandomState(1)

        async def score(name):
            resp = await client.post(
                f"/gordo/v0/demo/{name}/prediction",
                json={"X": rng.rand(32, 3).tolist()},
            )
            assert resp.status == 200, (name, resp.status)

        print("driving skewed load: 4 hot members at 8x, 6 at 1x ...",
              flush=True)
        t0 = time.perf_counter()
        n_requests = 0
        for name in HOT:
            for _ in range(8):
                await score(name)
                n_requests += 1
        for name in COLD + LSTM:
            await score(name)
            n_requests += 1
        drive_s = time.perf_counter() - t0

        heat = await (
            await client.get("/gordo/v0/demo/heat?refresh=1&top=4")
        ).json()
        costs = await (
            await client.get("/gordo/v0/demo/costs?refresh=1")
        ).json()
        stats = await (await client.get("/gordo/v0/demo/stats")).json()
        capacity = stats.get("bank_capacity") or {}

        # ---------------------- advisor: heat ---------------------- #
        tiers = heat.get("tiers") or {}
        print()
        print(f"ACCESS HEAT  (halflife {heat.get('halflife_s')}s, "
              f"thresholds hot>={heat.get('hot_rate')}/s "
              f"warm>={heat.get('warm_rate')}/s)")
        print(f"  tier split: hot={tiers.get('hot', 0)} "
              f"warm={tiers.get('warm', 0)} cold={tiers.get('cold', 0)} "
              f"of {heat.get('members_total')} members")
        print("  hottest:")
        for e in heat.get("hottest") or ():
            print(f"    {e['member']:<10} {e['rate']:>10.3f} rows/s "
                  f"[{e['tier']}]  bucket={e['bucket']}")

        # ---------------------- advisor: cost ----------------------- #
        print()
        peak = costs.get("peak_flops")
        print(f"DEVICE COST  (peak "
              f"{(f'{peak:.3g} FLOP/s' if peak else 'unknown')}, "
              f"source={costs.get('peak_source')})")
        print(f"  {'bucket':<34} {'mfu':>10} {'flops/row':>12} "
              f"{'dev_s/1k':>10} {'pad_waste':>10}")
        for label, row in sorted((costs.get("buckets") or {}).items()):
            mfu = row.get("mfu")
            d1k = row.get("device_s_per_1k_rows")
            print(f"  {label:<34} "
                  f"{(f'{mfu:.2e}' if mfu is not None else '-'):>10} "
                  f"{row.get('flops_per_row', 0):>12.0f} "
                  f"{(f'{d1k:.4f}' if d1k is not None else '-'):>10} "
                  f"{row.get('pad_waste_score', 0):>10.3f}")
        ranking = costs.get("ranking") or []
        if ranking:
            worst = ranking[0]
            print(f"  fix first: {worst['bucket']} "
                  f"(pad_waste={worst['pad_waste_score']}, "
                  f"device_share={worst['device_share']})")

        # -------------------- advisor: capacity --------------------- #
        advice = advise_capacity(capacity, heat)
        print()
        print(f"CAPACITY  (bank dtype={capacity.get('dtype')}, "
              f"{capacity.get('weight_bytes')} bytes for "
              f"{capacity.get('members')} members, "
              f"models/GB={capacity.get('models_per_gb')})")
        for label, row in (advice.get("projection") or {}).items():
            fits = ", ".join(
                f"{k}:{v}" for k, v in row["members_per_budget"].items()
            )
            print(f"  {label:<16} {row['bytes_per_member']:>10.0f} B/member"
                  f"  -> fits {fits}")

        # ------------------------- verdict -------------------------- #
        hottest = sorted(e["member"] for e in heat.get("hottest") or ())
        live = {
            label: row
            for label, row in (costs.get("buckets") or {}).items()
            if row.get("live")
        }
        passed = (
            heat.get("enabled") is True
            and hottest == sorted(HOT)
            and costs.get("enabled") is True
            and len(live) >= 2
            # MFU is null where the device's peak is unknown (a CPU run):
            # the attribution itself is what must be live
            and all(row.get("device_s", 0) > 0 for row in live.values())
            and bool(advice)
        )
        doc = {
            "members": len(HOT + COLD + LSTM),
            "requests": n_requests,
            "drive_s": round(drive_s, 3),
            "tiers": tiers,
            "hottest": hottest,
            "rate_total": heat.get("rate_total"),
            "peak_source": costs.get("peak_source"),
            "mfu_by_bucket": {
                label: row.get("mfu") for label, row in live.items()
            },
            "pad_waste_by_bucket": {
                label: row.get("pad_waste_score")
                for label, row in live.items()
            },
            "fix_first": ranking[0]["bucket"] if ranking else None,
            "models_per_gb": capacity.get("models_per_gb"),
            "capacity_advice": advice,
            "passed": passed,
        }
        print()
        print(json.dumps(doc, indent=2))
        return 0 if passed else 1
    finally:
        await client.close()


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--platform", default=None, help="in-process jax platform pin"
    )
    args = parser.parse_args()
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    sys.exit(asyncio.run(main()))
