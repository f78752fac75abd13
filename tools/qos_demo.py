#!/usr/bin/env python
"""qos-demo: a best_effort flood vs a steady interactive probe against
the real serving stack, printing the multi-tenant fairness story
(``make qos-demo``).

Trains two tiny models into a temp dir, serves them through the real
``build_app`` stack (bank + weighted-fair batching engine + admission
controller + goodput ledger + per-class SLO tracker) with a tight
engine queue, and drives two phases:

1. an unloaded phase — the interactive probe's baseline p99;
2. a flood phase — N concurrent best_effort workers (tenant ``flood``,
   rate-limited by ``GORDO_QOS_TENANTS``) while the SAME interactive
   probe keeps scoring.

Then prints the per-class fairness table (admitted/shed per tenant and
class, per-class goodput, per-class burn, the interactive p99 delta)
and ends with ONE compact JSON doc: interactive p99 under the flood,
per-class goodput ratio, and shed precision.
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
os.environ.setdefault("GORDO_SLO_SAMPLE_S", "0.2")
os.environ.setdefault("GORDO_SLO_WINDOWS", "30s,5m")
os.environ.setdefault(
    "GORDO_SLO_OBJECTIVES",
    json.dumps([{"name": "availability", "target": 0.999}]),
)
# a queue small enough that the flood reaches the per-class shed
# thresholds in seconds, and a named flood tenant so its label survives
# the cardinality bound
os.environ.setdefault("GORDO_BANK_MAX_QUEUE", "24")
os.environ.setdefault(
    "GORDO_QOS_TENANTS", json.dumps({"flood": {"rate": 40.0, "burst": 60.0}})
)

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


def p99_ms(samples) -> float:
    if not samples:
        return 0.0
    s = sorted(samples)
    return round(s[min(len(s) - 1, int(0.99 * len(s)))] * 1000.0, 2)


def print_fairness_table(qos: dict, slo: dict, goodput: dict) -> None:
    admission = qos.get("admission") or {}
    print()
    print("admission (tenant|class)")
    print("=" * 64)
    shed = admission.get("shed") or {}
    for key, n in sorted((admission.get("admitted") or {}).items()):
        print(f"  admitted  {key:<28} {n}")
    for key, n in sorted(shed.items()):
        print(f"  shed      {key:<28} {n}")
    engine = qos.get("engine") or {}
    queue = engine.get("queue") or {}
    print()
    print("weighted-fair queue")
    print("=" * 64)
    for cls, w in sorted((queue.get("weights") or {}).items()):
        dq = (queue.get("dequeued") or {}).get(cls, 0)
        depth = (queue.get("depth") or {}).get(cls, 0)
        print(f"  {cls:<14} weight={w:<6} dequeued={dq:<8} depth={depth}")
    print()
    print("per-(tenant|class) goodput + fast-window burn")
    print("=" * 64)
    tenants = (goodput or {}).get("tenants") or {}
    classes = (slo or {}).get("classes") or {}
    for key in sorted(set(tenants) | set(classes)):
        cell = tenants.get(key, {})
        total = sum(cell.values()) or 1
        ratio = cell.get("goodput", 0) / total
        windows = (classes.get(key) or {}).get("windows") or {}
        fast = next(iter(windows.values()), {})
        print(
            f"  {key:<28} goodput_ratio={ratio:.3f} "
            f"burn={fast.get('burn_rate', 0.0)}"
        )


async def main(
    flood_workers: int = 10, flood_seconds: float = 8.0, baseline: int = 40
) -> int:
    from aiohttp.test_utils import TestClient, TestServer

    from gordo_components_tpu.server import build_app

    root = tempfile.mkdtemp(prefix="gordo-qos-demo-")
    print(f"training 2 demo models into {root} ...", flush=True)
    build_artifacts(root)

    client = TestClient(TestServer(build_app(root)))
    await client.start_server()
    try:
        rng = np.random.RandomState(1)
        X_probe = rng.rand(16, 3).tolist()
        X_flood = rng.rand(32, 3).tolist()
        flood_headers = {
            "X-Gordo-Tenant": "flood",
            "X-Gordo-Priority": "best_effort",
        }

        async def probe_once():
            t0 = time.monotonic()
            resp = await client.post(
                "/gordo/v0/demo/demo-a/anomaly/prediction",
                json={"X": X_probe},
            )
            await resp.read()
            return resp.status, time.monotonic() - t0

        print(f"phase 1: unloaded interactive baseline ({baseline}) ...",
              flush=True)
        base_lat = []
        for i in range(baseline):
            status, dt = await probe_once()
            assert status == 200, status
            # the first probes pay one-off JIT compiles; counting them
            # would inflate the baseline p99 and flatter the flood ratio
            if i >= 5:
                base_lat.append(dt)

        print(
            f"phase 2: best_effort flood ({flood_workers} workers, "
            f"{flood_seconds:.0f}s) + interactive probe ...",
            flush=True,
        )
        stop = asyncio.Event()
        flood_statuses = {}

        async def flood_worker():
            while not stop.is_set():
                resp = await client.post(
                    "/gordo/v0/demo/demo-b/anomaly/prediction",
                    json={"X": X_flood},
                    headers=flood_headers,
                )
                await resp.read()
                key = str(resp.status)
                flood_statuses[key] = flood_statuses.get(key, 0) + 1

        workers = [
            asyncio.get_running_loop().create_task(flood_worker())
            for _ in range(flood_workers)
        ]
        flood_lat = []
        probe_statuses = {}
        deadline = time.monotonic() + flood_seconds
        try:
            while time.monotonic() < deadline:
                status, dt = await probe_once()
                probe_statuses[str(status)] = (
                    probe_statuses.get(str(status), 0) + 1
                )
                if status == 200:
                    flood_lat.append(dt)
        finally:
            stop.set()
            await asyncio.gather(*workers, return_exceptions=True)

        qos = await (await client.get("/gordo/v0/demo/qos")).json()
        slo = await (await client.get("/gordo/v0/demo/slo?refresh=1")).json()

        shed = (qos.get("admission") or {}).get("shed") or {}
        shed_total = sum(shed.values())
        shed_be = sum(
            n for k, n in shed.items()
            if k.split("|")[1:2] == ["best_effort"]
        )
        tenants = (slo.get("goodput") or {}).get("tenants") or {}

        def class_goodput(cls):
            good = total = 0
            for key, cell in tenants.items():
                if key.rsplit("|", 1)[-1] != cls:
                    continue
                good += cell.get("goodput", 0)
                total += sum(cell.values())
            return round(good / total, 4) if total else None

        print_fairness_table(qos, slo, slo.get("goodput") or {})

        interactive_non_200 = sum(
            n for k, n in probe_statuses.items() if k != "200"
        )
        doc = {
            "interactive_p99_baseline_ms": p99_ms(base_lat),
            "interactive_p99_flood_ms": p99_ms(flood_lat),
            "interactive_p99_ratio": (
                round(p99_ms(flood_lat) / p99_ms(base_lat), 3)
                if base_lat and flood_lat
                else None
            ),
            "interactive_non_200": interactive_non_200,
            "interactive_statuses": probe_statuses,
            "flood_statuses": flood_statuses,
            "shed_total": shed_total,
            "shed_on_best_effort": shed_be,
            "shed_precision": (
                round(shed_be / shed_total, 4) if shed_total else None
            ),
            "goodput_ratio_interactive": class_goodput("interactive"),
            "goodput_ratio_best_effort": class_goodput("best_effort"),
            "unknown_tenants": (qos.get("admission") or {}).get(
                "unknown_tenants", 0
            ),
        }
        print()
        print(json.dumps(doc))
        return 0
    finally:
        await client.close()


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--flood-workers", type=int, default=10)
    parser.add_argument("--flood-seconds", type=float, default=8.0)
    parser.add_argument("--baseline", type=int, default=40)
    args = parser.parse_args()
    sys.exit(
        asyncio.run(
            main(
                flood_workers=args.flood_workers,
                flood_seconds=args.flood_seconds,
                baseline=args.baseline,
            )
        )
    )
