#!/usr/bin/env python
"""wire-demo: post the SAME batch as JSON, parquet, and framed tensor
bodies and print rows/s + bytes/row side by side (``make wire-demo``).

Trains two tiny anomaly models into a temp dir, serves them through the
real ``build_app`` stack (bank + batching engine), then scores one fixed
batch many times per encoding through the raw HTTP surface: the pure
data-plane comparison. Also verifies bitwise JSON-vs-tensor
score parity on the batch before timing, so the rows/s table is never a
"fast but wrong" number, and prints the server's per-encoding
``gordo_server_request{,_bytes}_total`` counters at the end.

Prints one JSON doc last (same contract as the other demos) so the
numbers are machine-readable.
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

import numpy as np  # noqa: E402


def build_artifacts(root: str) -> None:
    from gordo_components_tpu import serializer
    from gordo_components_tpu.models import (
        AutoEncoder,
        DiffBasedAnomalyDetector,
    )

    rng = np.random.RandomState(0)
    X = rng.rand(256, 8).astype("float32")
    for i, name in enumerate(("wire-a", "wire-b")):
        det = DiffBasedAnomalyDetector(
            base_estimator=AutoEncoder(epochs=1, batch_size=128)
        )
        det.fit(X + 0.01 * i)
        serializer.dump(det, os.path.join(root, name), metadata={"name": name})


async def run(rows: int, posts: int) -> dict:
    from aiohttp.test_utils import TestClient, TestServer

    from gordo_components_tpu.server import build_app
    from gordo_components_tpu.utils import parquet_engine_available
    from gordo_components_tpu.utils.wire import (
        TENSOR_CONTENT_TYPE,
        pack_frames,
        unpack_frames,
    )

    rng = np.random.RandomState(1)
    X = rng.rand(rows, 8).astype("float32")

    with tempfile.TemporaryDirectory(prefix="wire-demo-") as root:
        build_artifacts(root)
        client = TestClient(TestServer(build_app(root)))
        await client.start_server()
        try:
            url = "/gordo/v0/demo/wire-a/anomaly/prediction"
            json_payload = {"X": X.tolist()}
            tensor_body = pack_frames([("X", X)])

            # ---- parity gate: same scores from both encodings, bitwise
            r = await client.post(url, json=json_payload)
            assert r.status == 200, await r.text()
            j = await r.json()
            r = await client.post(
                url, data=tensor_body,
                headers={"Content-Type": TENSOR_CONTENT_TYPE},
            )
            assert r.status == 200, await r.text()
            frames = unpack_frames(await r.read())
            json_total = np.asarray(j["data"]["total-anomaly-scaled"])
            bin_total = frames["total-anomaly-scaled"].astype(np.float64)
            assert np.array_equal(json_total, bin_total), "score parity broke"

            # ---- timed legs (request+response through the live app)
            async def leg(label, post):
                t0 = time.perf_counter()
                bytes_in = 0
                for _ in range(posts):
                    resp = await post()
                    assert resp.status == 200
                    bytes_in += len(await resp.read())
                elapsed = time.perf_counter() - t0
                return {
                    "rows_per_sec": round(rows * posts / elapsed, 1),
                    "request_bytes_per_row": round(
                        leg_request_bytes[label] / rows, 1
                    ),
                    "response_bytes_per_row": round(bytes_in / posts / rows, 1),
                }

            leg_request_bytes = {
                "json": len(json.dumps(json_payload).encode()),
                "tensor": len(tensor_body),
            }
            results = {}
            results["json"] = await leg(
                "json", lambda: client.post(url, json=json_payload)
            )
            if parquet_engine_available():
                import io

                import pandas as pd

                buf = io.BytesIO()
                pd.DataFrame(X).rename(columns=str).to_parquet(buf)
                pq_body = buf.getvalue()
                leg_request_bytes["parquet"] = len(pq_body)
                results["parquet"] = await leg(
                    "parquet",
                    lambda: client.post(
                        url, data=pq_body,
                        headers={"Content-Type": "application/x-parquet"},
                    ),
                )
            results["tensor"] = await leg(
                "tensor",
                lambda: client.post(
                    url, data=tensor_body,
                    headers={"Content-Type": TENSOR_CONTENT_TYPE},
                ),
            )

            # server-side per-encoding accounting (the stability-contract
            # series the ops dashboards read)
            stats = await (await client.get("/gordo/v0/demo/stats")).json()
            return {
                "rows": rows,
                "posts_per_leg": posts,
                "parity": "bitwise",
                "legs": results,
                "tensor_vs_json": round(
                    results["tensor"]["rows_per_sec"]
                    / results["json"]["rows_per_sec"],
                    2,
                ),
                "server_wire_counters": stats["wire"],
            }
        finally:
            await client.close()


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--rows", type=int, default=500, help="rows per POST")
    parser.add_argument("--posts", type=int, default=30, help="POSTs per leg")
    args = parser.parse_args()

    doc = asyncio.run(run(args.rows, args.posts))

    print()
    print(f"wire demo: {args.rows} rows/POST x {args.posts} POSTs per leg")
    print("=" * 68)
    header = f"{'encoding':<10}{'rows/s':>12}{'req B/row':>12}{'resp B/row':>12}"
    print(header)
    print("-" * len(header))
    for enc, leg in doc["legs"].items():
        print(
            f"{enc:<10}{leg['rows_per_sec']:>12}"
            f"{leg['request_bytes_per_row']:>12}"
            f"{leg['response_bytes_per_row']:>12}"
        )
    print(f"\ntensor vs json: {doc['tensor_vs_json']}x")
    print()
    print(json.dumps(doc, indent=1))


if __name__ == "__main__":
    main()
