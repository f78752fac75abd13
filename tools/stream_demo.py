#!/usr/bin/env python
"""Streaming ingestion & online adaptation demo.

Builds a small heterogeneous fleet trained on the simulated live
provider's healthy signal, serves it with the streaming plane enabled
(``GORDO_STREAM=1``), then walks the full online loop over the real HTTP
surface:

1. stream healthy windows for every member — nothing drifts;
2. inject a mean-shift drift into K members and stream on —
   ``GET /drift`` flags exactly those members (detection latency is
   measured from first drifted ingest to the flagging sweep);
3. ``POST /adapt`` recalibrates the drifted members' thresholds on the
   fresh windows and lands them as a new bank generation through the
   zero-downtime swap (pause measured);
4. one member is incrementally REFIT for a few epochs (FleetTrainer
   warm-started from the serving weights) — another generation;
5. the false-positive anomaly rate on shifted-but-healthy data is
   measured before and after: recalibration must make it drop.

Prints one JSON document: detection latency, recalibration/refit time,
swap pause, and the FP-rate drop. Run directly (``make stream-demo``).
"""

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_demo(
    members: int = 6,
    rows: int = 96,
    epochs: int = 3,
    mean_shift: float = 4.0,
    platform: str | None = None,
) -> dict:
    os.environ.setdefault("GORDO_STREAM", "1")
    os.environ.setdefault("GORDO_SERVER_WARMUP", "0")
    os.environ.setdefault("GORDO_STREAM_WINDOW", "128")
    os.environ.setdefault("GORDO_STREAM_MIN_ROWS", "32")
    os.environ.setdefault("GORDO_REFIT_EPOCHS", "2")
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)
    import tempfile

    import numpy as np
    import pandas as pd

    from gordo_components_tpu import serializer
    from gordo_components_tpu.dataset.data_provider.streaming import (
        SimulatedLiveProvider,
    )
    from gordo_components_tpu.models import (
        AutoEncoder,
        DiffBasedAnomalyDetector,
    )
    from gordo_components_tpu.server import build_app

    t_train = pd.Timestamp("2026-08-01T00:00:00Z")
    t_live = pd.Timestamp("2026-08-02T00:00:00Z")
    prov = SimulatedLiveProvider(freq="10s", noise=0.1, seed=5)
    # heterogeneous: two feature counts -> two bank buckets
    fleet = {
        f"machine-{i:03d}": [f"tag-{j}" for j in range(3 if i % 2 else 5)]
        for i in range(members)
    }
    shifted = sorted(fleet)[:2]  # K=2 drifted members

    root = tempfile.mkdtemp(prefix="stream-demo-")
    t0 = time.monotonic()
    for name, tags in fleet.items():
        frame = prov.frame(t_train, max(240, rows * 2), tags)
        det = DiffBasedAnomalyDetector(
            base_estimator=AutoEncoder(epochs=epochs, batch_size=64)
        )
        det.fit(frame)
        serializer.dump(det, os.path.join(root, name), metadata={"name": name})
    build_s = time.monotonic() - t0

    from aiohttp.test_utils import TestClient, TestServer

    doc: dict = {
        "members": members,
        "shifted_members": list(shifted),
        "fleet_build_s": round(build_s, 3),
    }

    async def main():
        client = TestClient(TestServer(build_app(root, devices=1)))
        await client.start_server()
        app = client.server.app
        cursor = [time.time() - 3600]

        def stamp(ts):
            out = (np.asarray(ts) - ts[0] + cursor[0]).tolist()
            cursor[0] = out[-1] + 10.0
            return out

        async def ingest(name, ts, vals):
            resp = await client.post(
                f"/gordo/v0/demo/{name}/ingest",
                json={
                    "rows": [
                        [None if v != v else float(v) for v in row]
                        for row in vals.tolist()
                    ],
                    "timestamps": stamp(ts),
                },
            )
            assert resp.status == 200, await resp.text()
            await resp.release()

        async def drift(refresh=True):
            resp = await client.get(
                "/gordo/v0/demo/drift" + ("?refresh=1" if refresh else "")
            )
            return await resp.json()

        async def fp_rate(name, X, threshold):
            resp = await client.post(
                f"/gordo/v0/demo/{name}/anomaly/prediction",
                json={"X": X.tolist()},
            )
            body = await resp.json()
            assert resp.status == 200, body
            totals = np.asarray(body["data"]["total-anomaly-scaled"])
            return float((totals > threshold).mean())

        # healthy windows, a touch of late/dropout noise for realism
        prov.inject(dropout_p=0.01, late_fraction=0.05)
        for name, tags in fleet.items():
            ts, vals = prov.batch(t_live, rows, tags)
            await ingest(name, ts, vals)
        body = await drift()
        assert body["drifted"] == [], body["drifted"]

        # drift injection -> detection
        prov.inject(mean_shift=mean_shift, dropout_p=0.01, late_fraction=0.05)
        t_inject = time.monotonic()
        shifted_data = {}
        for name in shifted:
            tags = fleet[name]
            for k in range(2):
                ts, vals = prov.batch(
                    t_live + pd.Timedelta(f"{k + 1}h"), rows, tags
                )
                await ingest(name, ts, vals)
            shifted_data[name] = vals[~np.isnan(vals).any(axis=1)]
        body = await drift()
        detection_s = time.monotonic() - t_inject
        assert body["drifted"] == shifted, body["drifted"]
        doc["detection_latency_s"] = round(detection_s, 3)
        doc["drift_scores"] = {
            n: body["members"][n]["drift_score"] for n in shifted
        }
        doc["late_rows_total"] = body["late_rows_total"]

        collection = app["collection"]
        fp_before = {}
        for name in shifted:
            fp_before[name] = await fp_rate(
                name, shifted_data[name],
                collection.models[name].total_threshold_,
            )

        # recalibrate -> generation 1
        t0 = time.monotonic()
        resp = await client.post("/gordo/v0/demo/adapt", json={})
        recal = await resp.json()
        assert resp.status == 200 and recal["applied"], recal
        doc["recalibration_s"] = round(time.monotonic() - t0, 3)
        doc["recalibrated_members"] = recal["members"]
        doc["swap_pause_ms"] = recal["swap"]["pause_ms"]
        doc["generation_after_recal"] = recal["swap"]["generation"]

        # incremental refit of one member -> generation 2
        t0 = time.monotonic()
        resp = await client.post(
            "/gordo/v0/demo/adapt",
            json={"mode": "refit", "targets": [shifted[0]]},
        )
        refit = await resp.json()
        assert resp.status == 200 and refit["applied"], refit
        doc["refit_s"] = round(time.monotonic() - t0, 3)
        doc["refit_members"] = refit["members"]
        doc["generation_after_refit"] = refit["swap"]["generation"]

        fp_after = {}
        for name in shifted:
            fp_after[name] = await fp_rate(
                name, shifted_data[name],
                collection.models[name].total_threshold_,
            )
        doc["fp_rate_before"] = {k: round(v, 4) for k, v in fp_before.items()}
        doc["fp_rate_after"] = {k: round(v, 4) for k, v in fp_after.items()}
        doc["fp_rate_drop"] = round(
            max(fp_before.values()) - max(fp_after.values()), 4
        )
        await client.close()

    asyncio.run(main())
    return doc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--members", type=int, default=6)
    ap.add_argument("--rows", type=int, default=96)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--mean-shift", type=float, default=4.0)
    ap.add_argument("--platform", default="cpu",
                    help="in-process jax platform pin")
    a = ap.parse_args()
    print(
        json.dumps(
            run_demo(
                members=a.members, rows=a.rows, epochs=a.epochs,
                mean_shift=a.mean_shift, platform=a.platform,
            ),
            indent=1,
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
