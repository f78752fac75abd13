#!/usr/bin/env python
"""mesh-demo: stand up a REAL multi-process serving mesh and measure it
(``make mesh-demo``).

What it does, in order:

1. builds a small fleet of artifacts into a shared temp dir;
2. **baseline** — ONE server process owning every member; a bulk client
   posts tensor chunks round-robin over the members and records rows/s;
3. **mesh** — N (default 2) server processes, each booting its
   deterministic member partition (``GORDO_MESH_REPLICA_ID`` /
   ``GORDO_MESH_REPLICAS``), fronted by a live watchman whose
   ``GET /routing`` table the client consumes for partition-aware
   fan-out; aggregate rows/s over the SAME member set is recorded, plus
   per-replica request counts proving the fan-out actually split;
4. **parity gate** — the same tensor body posted to the mesh owner and
   the baseline server must answer byte-identically, so the table can
   never be "fast but wrong";
5. **migration under load** — while scoring load runs against the mesh,
   watchman migrates one member across replicas (acquire -> route ->
   release, both banks hot-swapping); every response during the window
   is counted and the demo FAILS on any non-200.

Prints one JSON doc last (same contract as the other demos).

Honesty note (docs/architecture.md "Multi-host serving"): the aggregate
speedup is real process parallelism — on a multi-core box 2 replicas
approach 2x; on a single-core container the OS timeshares one CPU and
the ratio hovers near 1x no matter how the software is shaped. The doc
records ``cpu_count`` next to the ratio so the number is never read out
of context.

``--serve`` is the child-process entry (one serving replica).
"""

import argparse
import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

N_FEATURES = 8
PROJECT = "mesh"


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
            det, os.path.join(root, f"mm-{i}"), metadata={"name": f"mm-{i}"}
        )


def serve(args) -> None:
    """Child entry: one serving replica (mesh identity from env)."""
    from gordo_components_tpu.server import run_server

    run_server(args.root, host="127.0.0.1", port=args.port)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def spawn_replica(root: str, port: int, mesh: "tuple | None") -> subprocess.Popen:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("GORDO_MESH_REPLICA_ID", None)
    env.pop("GORDO_MESH_REPLICAS", None)
    if mesh is not None:
        env["GORDO_MESH_REPLICA_ID"] = str(mesh[0])
        env["GORDO_MESH_REPLICAS"] = str(mesh[1])
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--serve",
         "--root", root, "--port", str(port)],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )


def wait_ready(port: int, timeout: float = 120.0) -> None:
    deadline = time.monotonic() + timeout
    url = f"http://127.0.0.1:{port}/gordo/v0/{PROJECT}/ready"
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2) as resp:
                if resp.status == 200:
                    return
        except Exception:
            pass
        time.sleep(0.25)
    raise RuntimeError(f"replica on port {port} never became ready")


async def measure_posts(
    bodies_by_url: "dict[str, list[tuple[str, bytes]]]",
    posts_per_member: int,
    concurrency: int,
) -> "tuple[float, int, int]":
    """POST every member's tensor body ``posts_per_member`` times to its
    assigned URL with bounded concurrency. Returns (elapsed_s, rows
    scored, non-200 count). One shared session: the keep-alive pool is
    the same for baseline and mesh, so the comparison is transport-fair."""
    import aiohttp

    from gordo_components_tpu.utils.wire import TENSOR_CONTENT_TYPE

    sem = asyncio.Semaphore(concurrency)
    bad = 0
    rows = 0
    jobs = []
    async with aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=concurrency + 4)
    ) as session:

        async def one(url, body, count=True):
            nonlocal bad, rows
            async with sem:
                async with session.post(
                    url, data=body,
                    headers={"Content-Type": TENSOR_CONTENT_TYPE},
                ) as resp:
                    data = await resp.read()
                    if count:
                        if resp.status != 200:
                            bad += 1
                        else:
                            rows += body_rows[body]
                    return resp.status, data

        body_rows = {}
        for pairs in bodies_by_url.values():
            for _url, body in pairs:
                from gordo_components_tpu.utils.wire import unpack_frames

                body_rows[body] = len(unpack_frames(body)["X"])
        # warm: TWO full rounds at the timed concurrency, so the batch
        # widths the engine will actually coalesce (and their XLA
        # programs, per pow2 rung) compile off the clock — warming one
        # request per replica would leave the first timed burst paying a
        # fresh batch-shape compile, a cost that lands once per PROCESS
        # and would bill the mesh twice what it bills the baseline
        for _ in range(2):
            await asyncio.gather(
                *(
                    one(url, body, count=False)
                    for pairs in bodies_by_url.values()
                    for url, body in pairs
                )
            )
        t0 = time.perf_counter()
        for pairs in bodies_by_url.values():
            for url, body in pairs:
                jobs.extend(one(url, body) for _ in range(posts_per_member))
        await asyncio.gather(*jobs)
        elapsed = time.perf_counter() - t0
    return elapsed, rows, bad


async def run(args) -> dict:
    import aiohttp

    from gordo_components_tpu.utils.wire import TENSOR_CONTENT_TYPE, pack_frames

    rng = np.random.RandomState(1)
    X = rng.rand(args.rows, N_FEATURES).astype("float32")
    members = [f"mm-{i}" for i in range(args.models)]

    def member_body(name: str) -> bytes:
        # per-member distinct rows: parity must compare real outputs,
        # not a shared constant the server could have cached
        i = int(name.split("-")[1])
        return pack_frames([("X", (X + 1e-3 * i).astype(np.float32))])

    bodies = {name: member_body(name) for name in members}

    def score_url(base: str, name: str) -> str:
        return f"{base}/gordo/v0/{PROJECT}/{name}/anomaly/prediction"

    with tempfile.TemporaryDirectory(prefix="mesh-demo-") as root:
        build_artifacts(root, args.models)
        doc: dict = {
            "models": args.models,
            "rows": args.rows,
            "posts_per_member": args.posts,
            "replicas": args.replicas,
            "cpu_count": os.cpu_count(),
        }
        procs = []
        try:
            # ---------------- baseline: one replica, all members ------- #
            p0 = free_port()
            procs.append(spawn_replica(root, p0, mesh=None))
            wait_ready(p0)
            base0 = f"http://127.0.0.1:{p0}"
            single_assign = {
                "single": [(score_url(base0, m), bodies[m]) for m in members]
            }
            elapsed, rows, bad = await measure_posts(
                single_assign, args.posts, args.concurrency
            )
            assert bad == 0, f"{bad} non-200s against the baseline replica"
            doc["single_replica"] = {
                "rows_per_sec": round(rows / elapsed, 1),
                "elapsed_s": round(elapsed, 3),
            }
            # parity reference: one body's exact response bytes
            async with aiohttp.ClientSession() as session:
                async with session.post(
                    score_url(base0, members[0]), data=bodies[members[0]],
                    headers={"Content-Type": TENSOR_CONTENT_TYPE},
                ) as resp:
                    assert resp.status == 200
                    parity_ref = await resp.read()
            procs[0].send_signal(signal.SIGTERM)
            procs[0].wait(timeout=30)
            procs.clear()

            # ---------------- mesh: N partitioned replicas ------------- #
            ports = [free_port() for _ in range(args.replicas)]
            for i, port in enumerate(ports):
                procs.append(
                    spawn_replica(root, port, mesh=(i, args.replicas))
                )
            for port in ports:
                wait_ready(port)
            bases = [f"http://127.0.0.1:{p}" for p in ports]

            # watchman (in-process, real port): the routing plane
            from aiohttp import web

            from gordo_components_tpu.watchman.server import (
                build_watchman_app,
            )

            wm_app = build_watchman_app(
                PROJECT, bases[0], refresh_interval=0.5,
                metrics_urls=[
                    b + f"/gordo/v0/{PROJECT}/metrics" for b in bases
                ],
            )
            runner = web.AppRunner(wm_app)
            await runner.setup()
            wm_port = free_port()
            site = web.TCPSite(runner, "127.0.0.1", wm_port)
            await site.start()
            wm_url = f"http://127.0.0.1:{wm_port}"

            async with aiohttp.ClientSession() as session:
                async with session.get(wm_url + "/routing") as resp:
                    table = await resp.json()
            owners = table["members"]
            assert set(owners) == set(members), (
                "routing table must cover the whole fleet", owners
            )
            doc["routing_version"] = table["version"]
            rep_urls = {r["replica"]: r["url"] for r in table["replicas"]}
            mesh_assign: dict = {}
            for m in members:
                url = score_url(rep_urls[owners[m]], m)
                mesh_assign.setdefault(owners[m], []).append((url, bodies[m]))
            doc["partition_sizes"] = {
                str(k): len(v) for k, v in sorted(mesh_assign.items())
            }
            elapsed, rows, bad = await measure_posts(
                mesh_assign, args.posts, args.concurrency
            )
            assert bad == 0, f"{bad} non-200s against the mesh"
            doc["mesh"] = {
                "rows_per_sec": round(rows / elapsed, 1),
                "elapsed_s": round(elapsed, 3),
            }
            doc["mesh_vs_single"] = round(
                doc["mesh"]["rows_per_sec"]
                / doc["single_replica"]["rows_per_sec"],
                3,
            )

            # parity: the mesh owner answers byte-identically
            async with aiohttp.ClientSession() as session:
                async with session.post(
                    score_url(rep_urls[owners[members[0]]], members[0]),
                    data=bodies[members[0]],
                    headers={"Content-Type": TENSOR_CONTENT_TYPE},
                ) as resp:
                    assert resp.status == 200
                    parity_mesh = await resp.read()
            assert parity_mesh == parity_ref, (
                "mesh owner's scores differ from the baseline replica's"
            )
            doc["parity"] = "bitwise"

            # per-replica fan-out proof from each replica's /stats
            fanout = {}
            async with aiohttp.ClientSession() as session:
                for i, b in enumerate(bases):
                    async with session.get(
                        b + f"/gordo/v0/{PROJECT}/stats"
                    ) as resp:
                        st = await resp.json()
                        fanout[str(i)] = st["requests"].get("anomaly", 0)
            doc["requests_per_replica"] = fanout
            assert all(v > 0 for v in fanout.values()), fanout

            # ------------- migration under concurrent load ------------- #
            victim = members[0]
            src = owners[victim]
            dst = (src + 1) % args.replicas
            statuses: list = []
            stop = asyncio.Event()

            async def load_loop():
                # keep scoring the migrating member (and a neighbor)
                # against the LIVE routing table for the whole window
                async with aiohttp.ClientSession() as session:
                    current = dict(owners)
                    while not stop.is_set():
                        async with session.get(wm_url + "/routing") as resp:
                            t = await resp.json()
                            current = t["members"]
                        for m in (victim, members[1 % len(members)]):
                            url = score_url(
                                rep_urls[current.get(m, src)], m
                            )
                            async with session.post(
                                url, data=bodies[m],
                                headers={
                                    "Content-Type": TENSOR_CONTENT_TYPE
                                },
                            ) as resp:
                                await resp.read()
                                statuses.append(resp.status)

            loader = asyncio.create_task(load_loop())
            await asyncio.sleep(0.3)
            async with aiohttp.ClientSession() as session:
                async with session.post(
                    wm_url + "/migrate",
                    json={"member": victim, "to": dst},
                ) as resp:
                    verdict = await resp.json()
                    assert resp.status == 200 and verdict["moved"], verdict
            await asyncio.sleep(0.5)
            stop.set()
            await loader
            non200 = [s for s in statuses if s != 200]
            doc["migration"] = {
                "member": victim,
                "src": src,
                "dst": dst,
                "requests_during": len(statuses),
                "non_200": len(non200),
                # "swap" can be present-but-None (already_owned retry,
                # bank disabled in the ambient env) — or-chain, not
                # .get defaults, so the demo reports null instead of
                # crashing after a migration that actually succeeded
                "acquire_swap_pause_ms": (
                    ((verdict.get("acquire") or {}).get("swap") or {})
                    .get("pause_ms")
                ),
                "release_swap_pause_ms": (
                    ((verdict.get("release") or {}).get("swap") or {})
                    .get("pause_ms")
                ),
                "routing_version": verdict.get("routing_version"),
            }
            assert len(non200) == 0, f"non-200s during migration: {non200}"
            assert len(statuses) > 0

            await runner.cleanup()
            return doc
        finally:
            for proc in procs:
                if proc.poll() is None:
                    proc.send_signal(signal.SIGTERM)
            for proc in procs:
                try:
                    proc.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    proc.kill()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--serve", action="store_true", help="child entry")
    ap.add_argument("--root", default=None)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--models", type=int, default=8)
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--posts", type=int, default=24,
                    help="timed posts per member per phase")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--concurrency", type=int, default=8)
    args = ap.parse_args()
    if args.serve:
        serve(args)
        return
    doc = asyncio.run(run(args))
    single = doc["single_replica"]["rows_per_sec"]
    meshed = doc["mesh"]["rows_per_sec"]
    print(
        f"single replica : {single:>10.1f} rows/s\n"
        f"{doc['replicas']}-replica mesh : {meshed:>10.1f} rows/s "
        f"aggregate ({doc['mesh_vs_single']}x, cpu_count="
        f"{doc['cpu_count']})\n"
        f"fan-out        : {doc['requests_per_replica']} requests/replica\n"
        f"migration      : {doc['migration']['requests_during']} requests "
        f"during move, {doc['migration']['non_200']} non-200"
    )
    print(json.dumps(doc, indent=2))


if __name__ == "__main__":
    main()
