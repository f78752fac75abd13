#!/usr/bin/env python
"""Declarative fleet compiler demo.

Compiles one fleet YAML spec (N machines across 2 feature-count buckets)
into the typed build -> bucket -> place -> canary -> promote DAG, then
walks the full rollout loop against a REAL in-process server:

1. offline executor run builds the fleet (gang vmap programs, register
   cache) and seeds the server's incumbent collection;
2. live run lands the generation through the zero-downtime swap with
   scoring traffic flowing through the canary window — the goodput
   judge promotes on measured health, and every data-plane response is
   collected (the zero-non-200 verdict);
3. ONE machine's config is edited and the spec re-run: the content-digest
   step keys re-execute exactly that machine's subgraph (build + bucket
   + rollout tail) while everything else serves from state — the
   incremental-recompile ratio is measured, not asserted;
4. a second edit runs with an injected SLO fast-burn (deadline 504s) in
   the canary window: the judge auto-rolls back to the incumbent and the
   incumbent's post-rollback scoring is verified 200.

Prints one JSON document. Run directly (``make fleet-demo``).
"""

import argparse
import asyncio
import json
import os
import shutil
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_DS = {
    "type": "RandomDataset",
    "train_start_date": "2017-12-25 06:00:00Z",
    "train_end_date": "2017-12-25 18:00:00Z",
}


def make_spec(members: int = 8, rev: int = 1, window_s: float = 0.6):
    wide = members - members // 3
    machines = [
        {
            "name": f"m-{i}",
            "dataset": dict(_DS, tag_list=[f"a{i}", f"b{i}", f"c{i}"]),
            "metadata": {"rev": rev if i == 0 else 1},
        }
        for i in range(wide)
    ]
    machines += [
        {"name": f"w-{i}", "dataset": dict(_DS, tag_list=[f"x{i}", f"y{i}"])}
        for i in range(members - wide)
    ]
    return {
        "machines": machines,
        "globals": {
            "model": {
                "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
                    "base_estimator": {
                        "sklearn.pipeline.Pipeline": {
                            "steps": [
                                "sklearn.preprocessing.MinMaxScaler",
                                {
                                    "gordo_components_tpu.models.AutoEncoder": {
                                        "kind": "feedforward_hourglass",
                                        "epochs": 1,
                                        "batch_size": 32,
                                    }
                                },
                            ]
                        }
                    }
                }
            }
        },
        "fleet": {
            "canary": {"window_s": window_s, "poll_s": 0.05, "min_requests": 1},
            "schedules": {"refit_every": "6h"},
        },
    }


class LiveServer:
    def __init__(self, collection_dir: str):
        from aiohttp import web

        from gordo_components_tpu.server import build_app

        self.web = web
        self.loop = asyncio.new_event_loop()
        self.app = build_app(collection_dir, devices=1)
        self.url = None
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        assert self._started.wait(60), "server failed to start"

    def _run(self):
        asyncio.set_event_loop(self.loop)

        async def go():
            self.runner = self.web.AppRunner(self.app)
            await self.runner.setup()
            site = self.web.TCPSite(self.runner, "127.0.0.1", 0)
            await site.start()
            port = site._server.sockets[0].getsockname()[1]
            self.url = f"http://127.0.0.1:{port}"
            self._started.set()

        self.loop.create_task(go())
        self.loop.run_forever()

    def stop(self):
        async def bye():
            await self.runner.cleanup()
            self.loop.stop()

        asyncio.run_coroutine_threadsafe(bye(), self.loop)
        self._thread.join(10)


def run_demo(members: int = 8, platform: "str | None" = None) -> dict:
    os.environ.setdefault("GORDO_SERVER_WARMUP", "0")
    os.environ.setdefault("GORDO_SLO_SAMPLE_S", "0.02")
    os.environ.setdefault(
        "GORDO_SLO_OBJECTIVES", '[{"name": "availability", "target": 0.999}]'
    )
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)
    import numpy as np
    import requests

    from gordo_components_tpu.workflow import FleetExecutor, compile_fleet

    out: dict = {"members": members}
    root = tempfile.mkdtemp(prefix="fleet-demo-")
    collection = os.path.join(root, "collection")
    os.makedirs(collection)

    # ---- 1. compile + offline seed build ----
    t0 = time.time()
    dag = compile_fleet(make_spec(members), "demo")
    out["compile_s"] = round(time.time() - t0, 4)
    out["step_counts"] = dag.counts()
    seed = FleetExecutor(dag, os.path.join(root, "seed"))
    t0 = time.time()
    seed_rep = seed.run()
    out["seed_build_s"] = round(time.time() - t0, 2)
    assert not seed_rep["failed"], seed_rep["failed"]
    for name in os.listdir(seed.artifact_dir):
        src = os.path.join(seed.artifact_dir, name)
        if os.path.isdir(src):
            shutil.copytree(src, os.path.join(collection, name))

    server = LiveServer(collection)
    codes: list = []
    X = np.random.RandomState(0).rand(8, 3).tolist()

    def traffic(url, headers=None):
        r = requests.post(
            f"{url}/gordo/v0/demo/m-0/anomaly/prediction",
            json={"X": X}, headers=headers or {}, timeout=10,
        )
        codes.append(r.status_code)

    def executor(rev):
        return FleetExecutor(
            compile_fleet(make_spec(members, rev=rev), "demo"),
            os.path.join(root, "state"),
            server_url=server.url,
            collection_dir=collection,
            register_dir=seed.register_dir,
            traffic_hook=traffic,
        )

    try:
        # ---- 2. live end-to-end rollout under traffic ----
        t0 = time.time()
        rep = executor(1).run()
        out["rollout"] = {
            "wall_s": round(time.time() - t0, 2),
            "promoted": rep["promoted"],
            "canary": rep["canary"]["decision"],
            "generation": rep["generation"],
            "non_200": sorted({c for c in codes if c != 200}),
        }
        assert rep["promoted"], rep

        # ---- 3. edit one machine -> incremental re-run ----
        codes.clear()
        t0 = time.time()
        rep2 = executor(2).run()
        out["incremental"] = {
            "wall_s": round(time.time() - t0, 2),
            "executed": rep2["executed"],
            "cached": len(rep2["cached"]),
            "incremental_ratio": rep2["incremental_ratio"],
            "promoted": rep2["promoted"],
            "non_200": sorted({c for c in codes if c != 200}),
        }

        # ---- 4. fast-burn canary -> auto-rollback ----
        codes.clear()
        ex3 = executor(3)
        ex3.traffic_hook = lambda url: traffic(
            url, headers={"X-Gordo-Deadline-Ms": "0.001"}
        )
        rep3 = ex3.run()
        r = requests.post(
            f"{server.url}/gordo/v0/demo/m-0/anomaly/prediction",
            json={"X": X}, timeout=10,
        )
        out["burn_rollback"] = {
            "canary": rep3["canary"]["decision"],
            "reason": rep3["canary"]["reason"],
            "rolled_back": rep3["rolled_back"],
            "post_rollback_scoring": r.status_code,
            "incumbent_rev": requests.get(
                f"{server.url}/gordo/v0/demo/m-0/metadata", timeout=10
            ).json()["endpoint-metadata"]["user-defined"]["rev"],
        }
        out["passed"] = bool(
            rep["promoted"]
            and rep2["promoted"]
            and not out["rollout"]["non_200"]
            and not out["incremental"]["non_200"]
            and rep3["rolled_back"]
            and r.status_code == 200
            and out["burn_rollback"]["incumbent_rev"] == 2
        )
    finally:
        server.stop()
        shutil.rmtree(root, ignore_errors=True)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--members", type=int, default=8)
    ap.add_argument("--platform", default=None)
    args = ap.parse_args()
    out = run_demo(members=args.members, platform=args.platform)
    print(json.dumps(out, indent=2, default=str))
    return 0 if out.get("passed") else 1


if __name__ == "__main__":
    sys.exit(main())
