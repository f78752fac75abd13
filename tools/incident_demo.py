#!/usr/bin/env python
"""incident-demo: the fleet flight recorder end to end, in one process
(``make incident-demo``).

Game-day drill: train two tiny models, serve them through the real
``build_app`` stack with metric history + the event log enabled, then

1. drive a healthy phase (baseline goodput, burn ~0);
2. arm a ``bank.score`` error fault under scoring load — requests 5xx,
   the quarantine trips, the SLO budget burns, and the history sampler
   records the burn while the event log records the transitions
   (``fault.fired``, ``quarantine.enter``);
3. recover: clear the fault and ``POST /reload`` (a ``models.reload`` +
   ``bank.swap`` on the timeline).

Then points a real ``WatchmanState`` at the replica and asks
``fleet_incidents()`` the operator question: *what burned, when, and
what else happened around it?* Prints the detected incident's rendered
timeline — fault -> burn -> quarantine -> recovery in order — plus the
flight-recorder cost figures (sampler ms, query ms, bytes/series), and
a final machine-readable JSON doc.
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
# flight recorder on, at drill cadence: sample every 250ms into a raw
# ring so a ~2s injected burn leaves several retained points
os.environ.setdefault("GORDO_HISTORY", "1")
os.environ.setdefault("GORDO_HISTORY_INTERVAL_S", "0.25")
os.environ.setdefault("GORDO_HISTORY_TIERS", "0.25s@10m,2s@1h")
os.environ.setdefault("GORDO_SLO_SAMPLE_S", "0.25")

import numpy as np  # noqa: E402


def build_artifacts(root: str) -> None:
    from gordo_components_tpu import serializer
    from gordo_components_tpu.models import (
        AutoEncoder,
        DiffBasedAnomalyDetector,
    )

    rng = np.random.RandomState(0)
    X = rng.rand(200, 3).astype("float32")
    for i, name in enumerate(("demo-a", "demo-b")):
        det = DiffBasedAnomalyDetector(
            base_estimator=AutoEncoder(epochs=1, batch_size=64)
        )
        det.fit(X + 0.01 * i)
        serializer.dump(det, os.path.join(root, name), metadata={"name": name})


async def main(burn_seconds: float = 2.0) -> int:
    from aiohttp.test_utils import TestClient, TestServer

    from gordo_components_tpu import resilience
    from gordo_components_tpu.server import build_app
    from gordo_components_tpu.watchman.server import WatchmanState

    root = tempfile.mkdtemp(prefix="gordo-incident-demo-")
    print(f"training 2 demo models into {root} ...", flush=True)
    build_artifacts(root)

    app = build_app(root)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        rng = np.random.RandomState(1)

        async def score(name, deadline_ms=None):
            headers = (
                {"X-Gordo-Deadline-Ms": str(deadline_ms)} if deadline_ms else {}
            )
            resp = await client.post(
                f"/gordo/v0/demo/{name}/anomaly/prediction",
                json={"X": rng.rand(48, 3).tolist()},
                headers=headers,
            )
            return resp.status

        print("phase 1: healthy load ...", flush=True)
        for i in range(16):
            status = await score(("demo-a", "demo-b")[i % 2])
            assert status == 200, status
        await asyncio.sleep(0.6)  # a few healthy sampler ticks

        print(
            f"phase 2: bank.score errors (quarantine demo-a) + "
            f"engine.queue latency vs tight deadlines for ~{burn_seconds}s ...",
            flush=True,
        )
        # a bounded error fault: enough fires to trip demo-a's
        # quarantine (3 consecutive failures; engine retries consume ~2
        # fires per request) -> fault.fired + quarantine.enter on the
        # timeline, then it stops so demo-b reaches the queue fault
        resilience.arm("bank.score", times=12, exc=resilience.FaultInjected)
        # ...where tight 10ms budgets 504 against a 50ms injected stall:
        # real 5xx that the availability objective books as burn
        resilience.arm("engine.queue", delay_s=0.05, exc=None)
        statuses = {}
        t0 = time.monotonic()
        i = 0
        while time.monotonic() - t0 < burn_seconds:
            if i < 8:
                status = await score("demo-a")  # trips the quarantine
            else:
                status = await score("demo-b", deadline_ms=10)
            statuses[status] = statuses.get(status, 0) + 1
            i += 1
            await asyncio.sleep(0.05)  # let the sampler tick mid-burn
        print(f"  statuses: {statuses}")

        print("phase 3: recover (disarm fault, reload the bank) ...", flush=True)
        resilience.reset()
        reload_resp = await client.post("/gordo/v0/demo/reload")
        assert reload_resp.status == 200, reload_resp.status
        for i in range(8):
            await score(("demo-a", "demo-b")[i % 2])
        await asyncio.sleep(0.6)  # post-recovery sampler ticks

        # ---------------- flight-recorder cost figures ---------------- #
        store = app["history"]
        t0 = time.perf_counter()
        for _ in range(20):
            store.sample()
        sample_ms = (time.perf_counter() - t0) / 20 * 1e3
        snap = store.snapshot()
        bytes_per_series = (
            store.memory_bytes() / max(1, snap["n_series"])
        )
        meta = await (await client.get("/gordo/v0/demo/history")).json()
        burn_names = [
            n for n in meta["names"] if n.startswith("gordo_slo_burn_rate")
        ]
        t0 = time.perf_counter()
        hist_resp = await client.get(
            "/gordo/v0/demo/history",
            params={"series": ",".join(burn_names[:4])},
        )
        query_ms = (time.perf_counter() - t0) * 1e3
        assert hist_resp.status == 200, hist_resp.status

        # ------------- the watchman asks: what happened? -------------- #
        server = client.server
        base = f"http://{server.host}:{server.port}"
        state = WatchmanState(
            "demo",
            base,
            metrics_urls=[f"{base}/gordo/v0/demo/metrics"],
        )
        report = await state.fleet_incidents(threshold=1.0, margin_s=5.0)

        print()
        print(f"incidents detected: {report['detected']} "
              f"(burn episodes: {report['episodes']})")
        for inc in report["incidents"]:
            print("=" * 64)
            print(
                f"incident #{inc['id']}: {inc['duration_s']}s, "
                f"peak burn {inc['peak_burn']:.1f}x budget, "
                f"series={inc['series']}"
            )
            print("-" * 64)
            for line in inc["timeline"]:
                print(f"  {line}")

        events_body = await (
            await client.get("/gordo/v0/demo/events")
        ).json()
        by_type = events_body["by_type"]
        incident = report["incidents"][0] if report["incidents"] else None
        seen_types = (
            {e["type"] for e in incident["events"]} if incident else set()
        )
        passed = (
            report["detected"] >= 1
            and "fault.fired" in seen_types
            and "quarantine.enter" in seen_types
            and "models.reload" in seen_types
        )
        doc = {
            "detected": report["detected"],
            "episodes": report["episodes"],
            "peak_burn": (
                max(i["peak_burn"] for i in report["incidents"])
                if report["incidents"] else 0.0
            ),
            "incident_event_types": sorted(seen_types),
            "timeline": incident["timeline"] if incident else [],
            "events_by_type": by_type,
            "history_series": snap["n_series"],
            "history_samples": snap["samples"],
            "history_memory_bytes": store.memory_bytes(),
            "bytes_per_series": round(bytes_per_series, 1),
            "sample_ms_avg": round(sample_ms, 3),
            "query_ms": round(query_ms, 3),
            "passed": passed,
        }
        print()
        print(json.dumps(doc, indent=2))
        return 0 if passed else 1
    finally:
        resilience.reset()
        await client.close()


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--burn-seconds", type=float, default=2.0)
    parser.add_argument(
        "--platform", default=None, help="in-process jax platform pin"
    )
    args = parser.parse_args()
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    sys.exit(asyncio.run(main(burn_seconds=args.burn_seconds)))
