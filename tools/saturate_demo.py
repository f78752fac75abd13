#!/usr/bin/env python
"""saturate-demo: drive the same scoring batch over every serving-plane
transport and print rows/s + bytes/row side by side (``make saturate-demo``).

Builds a small fleet, serves it through the REAL multi-worker pool
(server/workers.py: ``--workers`` event loops behind one accept path)
with a Unix-domain-socket listener and the shared-memory scoring ring
armed, then measures:

- the in-process bank rate (the ceiling every transport chases);
- end-to-end rows/s over TCP, UDS, and the shm ring — after a bitwise
  parity gate (same ``GTNS`` body must yield identical bytes from all
  three, so the table can never be "fast but wrong");
- push mode (``GORDO_PUSH=1``): windows scored per second as ingest
  advances watermarks, with results fanned to a long-poll subscriber.

Prints one JSON doc last (same contract as the other demos).
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
# push-mode knobs must land before build_app constructs the streaming
# plane (it reads them at init)
os.environ.setdefault("GORDO_STREAM", "1")
os.environ.setdefault("GORDO_PUSH", "1")
os.environ.setdefault("GORDO_PUSH_INTERVAL_S", "0.05")

import numpy as np  # noqa: E402

N_FEATURES = 8


def build_artifacts(root: str, n_models: int) -> None:
    from gordo_components_tpu import serializer
    from gordo_components_tpu.models import (
        AutoEncoder,
        DiffBasedAnomalyDetector,
    )

    rng = np.random.RandomState(0)
    X = rng.rand(256, N_FEATURES).astype("float32")
    for i in range(n_models):
        det = DiffBasedAnomalyDetector(
            base_estimator=AutoEncoder(epochs=1, batch_size=128)
        )
        det.fit(X + 0.01 * i)
        serializer.dump(
            det, os.path.join(root, f"sat-{i}"), metadata={"name": f"sat-{i}"}
        )


async def timed_http_leg(base, url_path, body, posts, concurrency, connector):
    import aiohttp

    from gordo_components_tpu.utils.wire import TENSOR_CONTENT_TYPE

    sem = asyncio.Semaphore(concurrency)
    bytes_in = 0

    async with aiohttp.ClientSession(connector=connector) as session:

        async def one(count=True):
            nonlocal bytes_in
            async with sem:
                async with session.post(
                    f"{base}{url_path}",
                    data=body,
                    headers={"Content-Type": TENSOR_CONTENT_TYPE},
                ) as resp:
                    assert resp.status == 200, await resp.text()
                    data = await resp.read()
                    if count:
                        bytes_in += len(data)

        # warm the connection pool + any first-batch-shape compile
        # before the clock starts, same contract as the other legs
        await asyncio.gather(*(one(count=False) for _ in range(2)))
        t0 = time.perf_counter()
        await asyncio.gather(*(one() for _ in range(posts)))
        elapsed = time.perf_counter() - t0
    return elapsed, bytes_in


async def run(args) -> dict:
    import aiohttp

    from gordo_components_tpu.server import build_app
    from gordo_components_tpu.server.workers import ServerPool
    from gordo_components_tpu.utils.shm_ring import ShmRingClient
    from gordo_components_tpu.utils.wire import (
        TENSOR_CONTENT_TYPE,
        pack_frames,
    )

    rng = np.random.RandomState(1)
    X = rng.rand(args.rows, N_FEATURES).astype("float32")
    body = pack_frames([("X", X)])
    loop = asyncio.get_running_loop()

    with tempfile.TemporaryDirectory(prefix="saturate-demo-") as root:
        build_artifacts(root, args.models)
        uds_path = os.path.join(root, "gordo.sock")
        shm_name = f"gordo-sat-{os.getpid()}"
        app = build_app(root)
        pool = ServerPool(
            app, host="127.0.0.1", port=0, workers=args.workers,
            uds_path=uds_path, shm_ring=shm_name,
        )
        pool.start()
        base = f"http://127.0.0.1:{pool.port}"
        url_path = "/gordo/v0/demo/sat-0/anomaly/prediction"
        shm = ShmRingClient(shm_name)
        try:
            # ---- parity gate: identical bytes from all three transports
            async with aiohttp.ClientSession() as s:
                async with s.post(
                    f"{base}{url_path}", data=body,
                    headers={"Content-Type": TENSOR_CONTENT_TYPE},
                ) as r:
                    assert r.status == 200, await r.text()
                    tcp_bytes = await r.read()
            async with aiohttp.ClientSession(
                connector=aiohttp.UnixConnector(path=uds_path)
            ) as s:
                async with s.post(
                    f"http://localhost{url_path}", data=body,
                    headers={"Content-Type": TENSOR_CONTENT_TYPE},
                ) as r:
                    assert r.status == 200, await r.text()
                    uds_bytes = await r.read()
            status, shm_bytes = await loop.run_in_executor(
                None, shm.request, "sat-0", body
            )
            assert status == 200, shm_bytes[:200]
            assert tcp_bytes == uds_bytes == shm_bytes, "transport parity broke"

            # ---- in-process ceiling
            bank = app["bank"]
            reqs = [("sat-0", X, None)]
            bank.score_many(reqs)  # warm
            t0 = time.perf_counter()
            for _ in range(args.posts):
                bank.score_many(reqs)
            in_proc_elapsed = time.perf_counter() - t0
            in_proc_rate = args.rows * args.posts / in_proc_elapsed

            legs = {}
            # ---- tcp
            elapsed, bytes_in = await timed_http_leg(
                base, url_path, body, args.posts, args.concurrency,
                aiohttp.TCPConnector(limit=args.concurrency + 2),
            )
            legs["tcp"] = {
                "rows_per_sec": round(args.rows * args.posts / elapsed, 1),
                "request_bytes_per_row": round(len(body) / args.rows, 1),
                "response_bytes_per_row": round(
                    bytes_in / args.posts / args.rows, 1
                ),
            }
            # ---- uds
            elapsed, bytes_in = await timed_http_leg(
                "http://localhost", url_path, body, args.posts,
                args.concurrency, aiohttp.UnixConnector(path=uds_path),
            )
            legs["uds"] = {
                "rows_per_sec": round(args.rows * args.posts / elapsed, 1),
                "request_bytes_per_row": round(len(body) / args.rows, 1),
                "response_bytes_per_row": round(
                    bytes_in / args.posts / args.rows, 1
                ),
            }
            # ---- shm ring
            sem = asyncio.Semaphore(min(args.concurrency, 6))
            resp_bytes = 0

            async def shm_one():
                nonlocal resp_bytes
                async with sem:
                    st, data = await loop.run_in_executor(
                        None, shm.request, "sat-0", body
                    )
                    assert st == 200
                    resp_bytes += len(data)

            await asyncio.gather(*(shm_one() for _ in range(2)))  # warm
            resp_bytes = 0
            t0 = time.perf_counter()
            await asyncio.gather(*(shm_one() for _ in range(args.posts)))
            elapsed = time.perf_counter() - t0
            legs["shm"] = {
                "rows_per_sec": round(args.rows * args.posts / elapsed, 1),
                "request_bytes_per_row": round(len(body) / args.rows, 1),
                "response_bytes_per_row": round(
                    resp_bytes / args.posts / args.rows, 1
                ),
            }

            # ---- push mode: windows scored/s as watermarks advance
            plane = app["stream"]
            now = time.time()
            push_rows = 64
            async with aiohttp.ClientSession() as s:
                poll = asyncio.ensure_future(
                    s.get(
                        f"{base}/gordo/v0/demo/sat-0/results/stream"
                        "?subscriber=demo&timeout=10"
                    )
                )
                await asyncio.sleep(0.05)
                t0 = time.perf_counter()
                for b in range(args.push_batches):
                    for m in range(args.models):
                        ts = [
                            now + b * push_rows + j for j in range(push_rows)
                        ]
                        async with s.post(
                            f"{base}/gordo/v0/demo/sat-{m}/ingest",
                            data=pack_frames(
                                [
                                    ("rows", X[:push_rows]),
                                    ("timestamps", np.asarray(ts, np.float64)),
                                ]
                            ),
                            headers={"Content-Type": TENSOR_CONTENT_TYPE},
                        ) as r:
                            assert r.status == 200, await r.text()
                # wait for the push loop to drain the dirty set
                target_min = args.models  # every member scored at least once
                for _ in range(200):
                    if plane.push_stats["windows_scored"] >= target_min and not plane._push_dirty:
                        break
                    await asyncio.sleep(0.05)
                push_elapsed = time.perf_counter() - t0
                resp = await poll
                first = await resp.json()
            windows = plane.push_stats["windows_scored"]
            push = {
                "windows_scored": windows,
                "windows_per_sec": round(windows / push_elapsed, 1),
                "published": plane.broker.stats()["published_total"],
                "subscriber_got_results": len(first["results"]) > 0,
                "dropped": plane.broker.stats()["dropped_total"],
            }

            stats_body = None
            async with aiohttp.ClientSession() as s:
                async with s.get(f"{base}/gordo/v0/demo/stats") as r:
                    stats_body = await r.json()
            best = max(leg["rows_per_sec"] for leg in legs.values())
            gap = round(in_proc_rate / best, 2)
            return {
                "rows": args.rows,
                "posts_per_leg": args.posts,
                "workers": args.workers,
                "parity": "bitwise",
                "in_process_rows_per_sec": round(in_proc_rate, 1),
                "legs": legs,
                "uds_vs_tcp": round(
                    legs["uds"]["rows_per_sec"] / legs["tcp"]["rows_per_sec"], 2
                ),
                "shm_vs_tcp": round(
                    legs["shm"]["rows_per_sec"] / legs["tcp"]["rows_per_sec"], 2
                ),
                "end_to_end_gap_ratio": gap,
                "push": push,
                "server_workers_seen": stats_body["workers"],
                "server_shm_counters": stats_body.get("shm"),
            }
        finally:
            shm.close()
            pool.stop()


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--rows", type=int, default=500, help="rows per POST")
    parser.add_argument("--posts", type=int, default=40, help="POSTs per leg")
    parser.add_argument("--models", type=int, default=4)
    parser.add_argument("--workers", type=int, default=2)
    parser.add_argument("--concurrency", type=int, default=8)
    parser.add_argument("--push-batches", type=int, default=10)
    args = parser.parse_args()

    doc = asyncio.run(run(args))

    print()
    print(
        f"saturate demo: {args.rows} rows/POST x {args.posts} POSTs per leg, "
        f"{args.workers} workers"
    )
    print("=" * 68)
    header = f"{'transport':<10}{'rows/s':>12}{'req B/row':>12}{'resp B/row':>12}"
    print(header)
    print("-" * len(header))
    for name, leg in doc["legs"].items():
        print(
            f"{name:<10}{leg['rows_per_sec']:>12}"
            f"{leg['request_bytes_per_row']:>12}"
            f"{leg['response_bytes_per_row']:>12}"
        )
    print(f"\nin-process ceiling: {doc['in_process_rows_per_sec']} rows/s")
    print(
        f"end-to-end gap (in-process / best transport): "
        f"{doc['end_to_end_gap_ratio']}x"
    )
    print(
        f"push: {doc['push']['windows_scored']} windows scored "
        f"({doc['push']['windows_per_sec']}/s)"
    )
    print()
    print(json.dumps(doc, indent=1))


if __name__ == "__main__":
    main()
