"""Multi-host serving mesh suite (ISSUE 14; marker ``mesh``, ``make mesh``).

Covers the whole routing stack in-process (aiohttp TestServers are
separate apps, not separate processes — the multi-PROCESS path is
tools/mesh_demo.py and the subprocess
perf-guard below):

- the serving-side bootstrap (``parallel/distributed.py``): identity
  resolution/validation and the deterministic boot partition;
- ``ModelCollection`` ownership (owned filter, acquire/release);
- the mesh HTTP surface: ``GET /mesh``, artifact shipping,
  acquire/release landing through the zero-downtime swap;
- watchman's versioned routing table: content-keyed version bumps,
  ``ETag``/304 polling, health/staleness stamps in ``GET /``;
- routing-table edge cases the ISSUE names: member owned by NO replica
  (404 with the reason), member owned by TWO replicas mid-migration
  (both answer byte-identically), empty fleet (valid empty table);
- the client: partition-aware fan-out, stale-table refetch + reroute,
  hedging that skips degraded/quarantined replicas;
- the fleet placement tier: plan_fleet determinism + health gates, and
  the watchman-driven migration with zero non-200s under load;
- perf-guard (``perfguard``+``slow``): partition-aware fan-out >=
  single-URL client on a REAL 2-process mesh.
"""

import asyncio
import json
import os

import numpy as np
import pandas as pd
import pytest
from aiohttp.test_utils import TestClient, TestServer

from gordo_components_tpu import serializer
from gordo_components_tpu.models import AutoEncoder, DiffBasedAnomalyDetector
from gordo_components_tpu.parallel.distributed import (
    MeshIdentity,
    bootstrap_serving_mesh,
    partition_members,
    serving_mesh_identity,
)
from gordo_components_tpu.placement.planner import plan_fleet
from gordo_components_tpu.server import build_app
from gordo_components_tpu.server.model_io import (
    ModelCollection,
    pack_artifact_dir,
    scan_artifacts,
    unpack_artifact_dir,
)
from gordo_components_tpu.utils.wire import TENSOR_CONTENT_TYPE, pack_frames
from gordo_components_tpu.watchman.server import build_watchman_app

pytestmark = pytest.mark.mesh

N_FEATURES = 4
MEMBERS = ["mesh-0", "mesh-1", "mesh-2", "mesh-3"]


@pytest.fixture(scope="module")
def mesh_dir(tmp_path_factory):
    """Four anomaly members in one shared artifact dir (the mesh's
    shared-volume deploy shape)."""
    rng = np.random.RandomState(0)
    X = rng.rand(96, N_FEATURES).astype("float32")
    root = tmp_path_factory.mktemp("mesh-fleet")
    for i, name in enumerate(MEMBERS):
        det = DiffBasedAnomalyDetector(
            base_estimator=AutoEncoder(epochs=1, batch_size=64)
        )
        det.fit(X + 0.01 * i)
        serializer.dump(det, str(root / name), metadata={"name": name})
    return str(root)


def scoring_body(seed: int = 1, rows: int = 24) -> bytes:
    X = np.random.RandomState(seed).rand(rows, N_FEATURES).astype("float32")
    return pack_frames([("X", X)])


class MeshPair:
    """Two partitioned replica apps over one artifact dir + a watchman."""

    def __init__(self, replicas, watchman, urls):
        self.replicas = replicas  # TestClients
        self.watchman = watchman  # TestClient
        self.urls = urls
        self.wm_url = (
            f"http://{watchman.server.host}:{watchman.server.port}"
        )


async def start_mesh(mesh_dir, refresh_interval=0.1, replica_count=2):
    replicas = []
    urls = []
    for i in range(replica_count):
        os.environ["GORDO_MESH_REPLICA_ID"] = str(i)
        os.environ["GORDO_MESH_REPLICAS"] = str(replica_count)
        try:
            app = build_app(mesh_dir)
        finally:
            os.environ.pop("GORDO_MESH_REPLICA_ID", None)
            os.environ.pop("GORDO_MESH_REPLICAS", None)
        client = TestClient(TestServer(app))
        await client.start_server()
        replicas.append(client)
        urls.append(f"http://{client.server.host}:{client.server.port}")
    wm_app = build_watchman_app(
        "proj", urls[0], refresh_interval=refresh_interval,
        metrics_urls=[u + "/gordo/v0/proj/metrics" for u in urls],
    )
    wm = TestClient(TestServer(wm_app))
    await wm.start_server()
    return MeshPair(replicas, wm, urls)


async def stop_mesh(mesh: MeshPair):
    await mesh.watchman.close()
    for client in mesh.replicas:
        await client.close()


# ------------------------------------------------------------------ #
# bootstrap + collection units
# ------------------------------------------------------------------ #


def test_mesh_identity_env_resolution(monkeypatch):
    monkeypatch.delenv("GORDO_MESH_REPLICA_ID", raising=False)
    monkeypatch.delenv("GORDO_MESH_REPLICAS", raising=False)
    assert serving_mesh_identity() is None
    assert bootstrap_serving_mesh() is None
    monkeypatch.setenv("GORDO_MESH_REPLICA_ID", "1")
    monkeypatch.setenv("GORDO_MESH_REPLICAS", "3")
    ident = serving_mesh_identity()
    assert ident == MeshIdentity(replica_id=1, replica_count=3)
    # half-configured fails loudly (a wrong partition is worse than a crash)
    monkeypatch.delenv("GORDO_MESH_REPLICAS")
    with pytest.raises(ValueError, match="BOTH"):
        serving_mesh_identity()
    monkeypatch.setenv("GORDO_MESH_REPLICAS", "2")
    monkeypatch.setenv("GORDO_MESH_REPLICA_ID", "2")
    with pytest.raises(ValueError, match="not in"):
        serving_mesh_identity()
    monkeypatch.setenv("GORDO_MESH_REPLICA_ID", "nope")
    with pytest.raises(ValueError, match="integer"):
        serving_mesh_identity()


def test_mesh_partition_is_disjoint_and_exhaustive():
    names = [f"x-{i}" for i in range(11)]
    parts = [
        MeshIdentity(i, 3).partition(names) for i in range(3)
    ]
    flat = [n for p in parts for n in p]
    assert sorted(flat) == sorted(names)
    assert len(set(flat)) == len(names)
    # same split the training-side partitioner computes: one rule fleet-wide
    assert parts[0] == partition_members(names, 0, 3)


def test_collection_owned_filter_and_acquire_release(mesh_dir):
    col = ModelCollection(mesh_dir, owned=MEMBERS[:2])
    assert col.names() == MEMBERS[:2]
    # acquire an on-disk member the partition excluded
    col.acquire(MEMBERS[2])
    assert MEMBERS[2] in col.models
    # release keeps the artifact on disk but stops serving it
    col.release(MEMBERS[2])
    assert MEMBERS[2] not in col.models
    assert MEMBERS[2] in scan_artifacts(mesh_dir)
    with pytest.raises(KeyError):
        col.release("never-owned")
    with pytest.raises(FileNotFoundError):
        col.acquire("no-such-artifact")
    # an owned-but-empty partition is legal (no startup raise)
    empty = ModelCollection(mesh_dir, owned=[])
    assert empty.names() == []


def test_artifact_pack_unpack_roundtrip_and_traversal_guard(mesh_dir, tmp_path):
    src = os.path.join(mesh_dir, MEMBERS[0])
    raw = pack_artifact_dir(src)
    dest = tmp_path / "landed"
    unpack_artifact_dir(raw, str(dest))
    assert sorted(os.listdir(dest)) == sorted(os.listdir(src))
    # a hostile archive must not escape the member dir
    import io
    import tarfile

    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w:gz") as tar:
        info = tarfile.TarInfo("../evil.txt")
        payload = b"boom"
        info.size = len(payload)
        tar.addfile(info, io.BytesIO(payload))
    with pytest.raises(ValueError, match="unsafe"):
        unpack_artifact_dir(buf.getvalue(), str(tmp_path / "guarded"))


def test_plan_fleet_determinism_gates_and_health():
    mbr = {0: ["a", "b", "c"], 1: ["d", "e", "f"]}
    loads = {"a": 4000, "b": 4000, "c": 200, "d": 200, "e": 200, "f": 200}
    p1 = plan_fleet(mbr, loads, threshold=1.2, min_rows=100)
    p2 = plan_fleet(mbr, loads, threshold=1.2, min_rows=100)
    assert p1.summary() == p2.summary()  # determinism contract
    assert p1.should_apply and p1.moves[0].src == 0
    # every move strictly improves: no thrash (the member whose load
    # equals the whole gap must not just swap the hot replica)
    assert p1.skew_after < p1.skew_before
    # degraded/burning/unreachable replicas are never destinations
    for status in ("degraded", "unhealthy", "unreachable", "burning"):
        p = plan_fleet(
            mbr, loads, replica_health={1: status}, threshold=1.2,
            min_rows=100,
        )
        assert not any(m.dst == 1 for m in p.moves)
        assert p.eligible == [0]
    # signal floor
    p = plan_fleet(mbr, loads, threshold=1.2, min_rows=10**9)
    assert not p.should_apply and "insufficient load signal" in p.reason
    # degenerate fleets
    assert not plan_fleet({0: ["a"]}, {"a": 5}).should_apply
    assert not plan_fleet({}, {}).should_apply


# ------------------------------------------------------------------ #
# routing table + edge cases
# ------------------------------------------------------------------ #


async def test_routing_table_versioning_etag_and_replica_stamps(mesh_dir):
    mesh = await start_mesh(mesh_dir)
    try:
        resp = await mesh.watchman.get("/routing")
        assert resp.status == 200
        table = await resp.json()
        etag = resp.headers["ETag"]
        assert table["version"] >= 1
        # the table covers the whole fleet, disjointly
        assert sorted(table["members"]) == MEMBERS
        assert table["migrating"] == {}
        owners = set(table["members"].values())
        assert owners == {0, 1}
        # unchanged fleet re-observed: version stays, 304 on the etag
        resp = await mesh.watchman.get(
            "/routing?refresh=1", headers={"If-None-Match": etag}
        )
        assert resp.status == 304
        # GET / replicas entries carry the satellite's stamps
        resp = await mesh.watchman.get("/")
        body = await resp.json()
        assert len(body["replicas"]) == 2
        for i, entry in enumerate(body["replicas"]):
            assert entry["replica"] == i
            assert entry["url"] == mesh.urls[i]
            assert entry["routing_version"] == table["version"]
            assert entry["status"] == "ok" and entry["reachable"]
            assert "routing_age_s" in entry
        assert body["routing"]["members"] == len(MEMBERS)
        assert body["routing"]["stale"] is False
        # the bare-URL consumer contract still holds (dual-form)
        from gordo_components_tpu.client import Client

        assert Client.replicas_from_watchman(body) == mesh.urls
    finally:
        await stop_mesh(mesh)


async def test_routing_member_owned_by_no_replica_404_with_reason(mesh_dir):
    mesh = await start_mesh(mesh_dir)
    try:
        resp = await mesh.watchman.get("/routing")
        table = await resp.json()
        assert "ghost-member" not in table["members"]
        # a client falling back to any replica gets a 404 NAMING the
        # member — "wrong replica" and "typo" must be distinguishable
        resp = await mesh.replicas[0].post(
            "/gordo/v0/proj/ghost-member/prediction",
            data=scoring_body(),
            headers={"Content-Type": TENSOR_CONTENT_TYPE},
        )
        assert resp.status == 404
        assert "ghost-member" in (await resp.json())["error"]
    finally:
        await stop_mesh(mesh)


async def test_routing_empty_fleet_serves_valid_empty_table():
    # watchman pointed at nothing reachable: version-0 empty table, not
    # an error — the client downgrades to single-URL mode
    wm_app = build_watchman_app(
        "proj", "http://127.0.0.1:1", refresh_interval=0.1,
        metrics_urls=["http://127.0.0.1:1/gordo/v0/proj/metrics"],
    )
    wm = TestClient(TestServer(wm_app))
    await wm.start_server()
    try:
        resp = await wm.get("/routing")
        assert resp.status == 200
        table = await resp.json()
        assert table["members"] == {}
        (rep,) = table["replicas"]
        assert rep["reachable"] is False and rep["status"] == "unreachable"
    finally:
        await wm.close()


async def test_dual_ownership_mid_migration_bitwise_identical(mesh_dir):
    mesh = await start_mesh(mesh_dir)
    try:
        resp = await mesh.watchman.get("/routing")
        table = await resp.json()
        member = next(m for m, o in table["members"].items() if o == 0)
        # acquire on replica 1 WITHOUT releasing replica 0: the
        # mid-migration overlap, frozen
        resp = await mesh.replicas[1].post(
            "/gordo/v0/proj/mesh/acquire",
            json={"member": member, "source": mesh.urls[0]},
        )
        assert resp.status == 200, await resp.text()
        body = scoring_body(seed=7)
        answers = []
        for client in mesh.replicas:
            resp = await client.post(
                f"/gordo/v0/proj/{member}/anomaly/prediction",
                data=body,
                headers={"Content-Type": TENSOR_CONTENT_TYPE},
            )
            assert resp.status == 200
            answers.append(await resp.read())
        # both owners answer, bitwise identically: the overlap window
        # cannot change any client's results
        assert answers[0] == answers[1]
        # the table reports the overlap + a single routed owner
        resp = await mesh.watchman.get("/routing?refresh=1")
        table = await resp.json()
        assert table["migrating"].get(member) == [0, 1]
        assert table["members"][member] in (0, 1)
        # idempotent re-acquire: no second bank rebuild
        resp = await mesh.replicas[1].post(
            "/gordo/v0/proj/mesh/acquire", json={"member": member}
        )
        assert (await resp.json())["already_owned"] is True
    finally:
        await stop_mesh(mesh)


async def test_release_unknown_member_404_and_mesh_view(mesh_dir):
    mesh = await start_mesh(mesh_dir)
    try:
        resp = await mesh.replicas[0].post(
            "/gordo/v0/proj/mesh/release", json={"member": "ghost"}
        )
        assert resp.status == 404
        assert "ghost" in (await resp.json())["error"]
        resp = await mesh.replicas[0].get("/gordo/v0/proj/mesh")
        view = await resp.json()
        assert view["enabled"] and view["replica_count"] == 2
        assert view["owned"] == sorted(view["owned"])
        resp = await mesh.replicas[0].post(
            "/gordo/v0/proj/mesh/acquire", json={"member": 3}
        )
        assert resp.status == 400
        # traversal-shaped member names never reach the filesystem: the
        # acquire endpoint unpacks a network-supplied archive under
        # root/<member>, so a separator or ".." in the name is an attack
        for evil in ("../evil", "a/b", "..", "/abs", ""):
            resp = await mesh.replicas[0].post(
                "/gordo/v0/proj/mesh/acquire",
                json={"member": evil, "source": "http://127.0.0.1:1"},
            )
            assert resp.status == 400, evil
    finally:
        await stop_mesh(mesh)


# ------------------------------------------------------------------ #
# watchman-driven migration under load (the acceptance edge)
# ------------------------------------------------------------------ #


async def test_watchman_migration_zero_non_200_under_load(mesh_dir):
    mesh = await start_mesh(mesh_dir)
    try:
        resp = await mesh.watchman.get("/routing")
        table = await resp.json()
        v0 = table["version"]
        member = next(m for m, o in table["members"].items() if o == 0)
        body = scoring_body(seed=3)
        statuses = []
        stop = asyncio.Event()

        async def load_loop():
            while not stop.is_set():
                resp = await mesh.watchman.get("/routing")
                owners = (await resp.json())["members"]
                owner = owners.get(member, 0)
                resp = await mesh.replicas[owner].post(
                    f"/gordo/v0/proj/{member}/anomaly/prediction",
                    data=body,
                    headers={"Content-Type": TENSOR_CONTENT_TYPE},
                )
                await resp.read()
                statuses.append(resp.status)

        loader = asyncio.create_task(load_loop())
        await asyncio.sleep(0.1)
        resp = await mesh.watchman.post(
            "/migrate", json={"member": member, "to": 1}
        )
        verdict = await resp.json()
        assert resp.status == 200 and verdict["moved"], verdict
        # both halves landed through the hot swap
        assert verdict["acquire"]["swap"]["pause_ms"] is not None
        assert verdict["release"]["swap"]["pause_ms"] is not None
        await asyncio.sleep(0.2)
        stop.set()
        await loader
        assert statuses and all(s == 200 for s in statuses), statuses
        resp = await mesh.watchman.get("/routing?refresh=1")
        table = await resp.json()
        assert table["members"][member] == 1
        assert member not in table["migrating"]
        assert table["version"] > v0  # a rebalance is a detectable step
        # migration counters render in the watchman exposition
        resp = await mesh.watchman.get("/metrics")
        text = await resp.text()
        assert "gordo_fleet_migrations_total 1" in text
        assert "gordo_fleet_routing_version" in text
    finally:
        await stop_mesh(mesh)


async def test_migrate_validation_and_conflict(mesh_dir):
    mesh = await start_mesh(mesh_dir)
    try:
        resp = await mesh.watchman.post("/migrate", json={"member": "x"})
        assert resp.status == 400
        resp = await mesh.watchman.get("/routing")
        member, owner = next(iter((await resp.json())["members"].items()))
        resp = await mesh.watchman.post(
            "/migrate", json={"member": member, "to": owner}
        )
        assert resp.status == 409  # already at destination
        resp = await mesh.watchman.post(
            "/migrate", json={"member": member, "to": 99}
        )
        assert resp.status == 409
    finally:
        await stop_mesh(mesh)


async def test_fleet_rebalance_dry_run_and_forced_move(mesh_dir):
    mesh = await start_mesh(mesh_dir)
    try:
        # generate a skewed load signal: score replica 0's members hard
        resp = await mesh.watchman.get("/routing")
        table = await resp.json()
        hot = [m for m, o in table["members"].items() if o == 0]
        body = scoring_body(seed=5, rows=48)
        for _ in range(6):
            for m in hot:
                resp = await mesh.replicas[0].post(
                    f"/gordo/v0/proj/{m}/anomaly/prediction",
                    data=body,
                    headers={"Content-Type": TENSOR_CONTENT_TYPE},
                )
                assert resp.status == 200
        resp = await mesh.watchman.post("/fleet-rebalance?dry_run=1")
        preview = await resp.json()
        assert preview["applied"] == 0 and preview["dry_run"]
        # min-rows floor (1024 default) not met -> force applies anyway
        resp = await mesh.watchman.post(
            "/fleet-rebalance", json={"force": True}
        )
        result = await resp.json()
        assert result["plan"]["moves"], result
        assert result["applied"] >= 1, result
        move = result["moves"][0]
        assert move["moved"] and move["dst"] == 1
        # ownership really changed end to end
        resp = await mesh.watchman.get("/routing?refresh=1")
        table = await resp.json()
        assert table["members"][move["member"]] == move["dst"]
    finally:
        await stop_mesh(mesh)


# ------------------------------------------------------------------ #
# client fan-out
# ------------------------------------------------------------------ #


def _routed_client(mesh, **kw):
    from gordo_components_tpu.client import Client

    fallback = {
        "type": "RandomDataset",
        "tag_list": [f"t-{j}" for j in range(N_FEATURES)],
        "resolution": "1min",
    }
    return Client(
        "proj", base_url=mesh.urls[0], routing_url=mesh.wm_url,
        metadata_fallback_dataset=fallback, batch_size=60,
        parallelism=4, **kw,
    )


async def test_client_partition_aware_fanout(mesh_dir):
    mesh = await start_mesh(mesh_dir)
    try:
        client = _routed_client(mesh)
        start = pd.Timestamp("2020-01-01T00:00:00Z")
        results = await client.predict_async(
            start, start + pd.Timedelta(minutes=120)
        )
        # target discovery came from the TABLE: all four members, not
        # just the base replica's partition
        assert sorted(r.name for r in results) == MEMBERS
        assert all(r.ok for r in results), [
            r.error_messages for r in results if not r.ok
        ]
        assert client._fanout_stats["routed_chunks"] > 0
        assert client.routing_version >= 1
        # every replica actually served scoring traffic (the fan-out
        # split, not a broadcast to one URL)
        for rep in mesh.replicas:
            resp = await rep.get("/gordo/v0/proj/stats")
            stats = await resp.json()
            assert stats["requests"].get("anomaly", 0) > 0
    finally:
        await stop_mesh(mesh)


async def test_client_stale_table_refetches_and_reroutes(mesh_dir):
    # watchman cache pinned LONG so its table goes stale the moment the
    # fleet changes under it; the client's 404 must force a refresh
    mesh = await start_mesh(mesh_dir, refresh_interval=300.0)
    try:
        client = _routed_client(mesh)
        start = pd.Timestamp("2020-01-01T00:00:00Z")
        end = start + pd.Timedelta(minutes=60)
        results = await client.predict_async(start, end)
        assert all(r.ok for r in results)
        v1 = client.routing_version
        # migrate a member directly on the replicas — watchman's cached
        # table (and the client's) now lies
        resp = await mesh.watchman.get("/routing")
        table = await resp.json()
        member = next(m for m, o in table["members"].items() if o == 0)
        resp = await mesh.replicas[1].post(
            "/gordo/v0/proj/mesh/acquire",
            json={"member": member, "source": mesh.urls[0]},
        )
        assert resp.status == 200
        resp = await mesh.replicas[0].post(
            "/gordo/v0/proj/mesh/release", json={"member": member}
        )
        assert resp.status == 200
        results = await client.predict_async(start, end, targets=[member])
        assert results[0].ok, results[0].error_messages
        assert client._fanout_stats["reroutes"] > 0
        assert client.routing_version > v1
    finally:
        await stop_mesh(mesh)


async def test_replica_dark_steps_version_and_emits_mesh_events(mesh_dir):
    """ISSUE 17 satellite: a replica going dark is a routing event in
    its own right — the reachable True->False transition MUST step the
    table version (clients polling the version stop posting at the dead
    owner) and emit ``mesh.replica_unreachable``; the heal steps the
    version again and emits ``mesh.replica_recovered``."""
    from gordo_components_tpu import resilience

    mesh = await start_mesh(mesh_dir, refresh_interval=300.0)
    try:
        resp = await mesh.watchman.get("/routing?refresh=1")
        v0 = (await resp.json())["version"]
        # transport-partition every probe for exactly one rebuild round
        # (2 replicas = 2 probes)
        resilience.configure_from_env("watchman.probe=refuse,times=2")
        resp = await mesh.watchman.get("/routing?refresh=1")
        dark = await resp.json()
        assert dark["version"] > v0
        assert all(not r["reachable"] for r in dark["replicas"])
        # fault budget exhausted: the next rebuild observes the heal
        resp = await mesh.watchman.get("/routing?refresh=1")
        healed = await resp.json()
        assert healed["version"] > dark["version"]
        assert all(r["reachable"] for r in healed["replicas"])
        assert sorted(healed["members"]) == MEMBERS
        # both transitions are timeline events the incident stack reads
        resp = await mesh.watchman.get(
            "/events?type=mesh.replica_unreachable,mesh.replica_recovered"
        )
        events = (await resp.json())["events"]
        types = [e["type"] for e in events]
        assert "mesh.replica_unreachable" in types
        assert "mesh.replica_recovered" in types
        assert types.index("mesh.replica_unreachable") < types.index(
            "mesh.replica_recovered"
        )
        down = next(
            e for e in events if e["type"] == "mesh.replica_unreachable"
        )
        assert down["severity"] == "error"
    finally:
        resilience.reset()
        await stop_mesh(mesh)


async def test_forced_refresh_rate_limited_per_member(mesh_dir):
    """ISSUE 17 satellite: stale-table forced refreshes are rate-limited
    per member — a migration storm of 404s must not stampede watchman —
    and suppressed calls count
    ``gordo_client_routing_refreshes_throttled_total``."""
    import aiohttp

    from gordo_components_tpu.observability import get_registry

    mesh = await start_mesh(mesh_dir)
    try:
        client = _routed_client(mesh, routing_refresh_window_s=60.0)
        async with aiohttp.ClientSession() as session:
            assert await client._fetch_routing(session) is True  # install
            # the member's FIRST forced refresh is entitled to hit the
            # network (stale-table recovery must work)
            await client._fetch_routing(session, force=True, member="mesh-0")
            assert client._fanout_stats["refreshes_throttled"] == 0
            fetched = client._fanout_stats["routing_refreshes"]
            # a second within the window is suppressed network-free
            assert (
                await client._fetch_routing(
                    session, force=True, member="mesh-0"
                )
                is False
            )
            assert client._fanout_stats["refreshes_throttled"] == 1
            assert client._fanout_stats["routing_refreshes"] == fetched
            # a different member owns its own window
            await client._fetch_routing(session, force=True, member="mesh-1")
            assert client._fanout_stats["refreshes_throttled"] == 1
        text = get_registry().render()
        assert "gordo_client_routing_refreshes_throttled_total" in text
        snap = get_registry().snapshot()
        vals = snap["gordo_client_routing_refreshes_throttled_total"]["values"]
        assert any(v["value"] == 1 for v in vals)
    finally:
        await stop_mesh(mesh)


def test_hedge_skips_degraded_and_quarantining_replicas():
    """The satellite fix: a hedge must never land on the replica the
    table marks sick — the OLD client hedged to any other replica, which
    could be exactly the degraded one it was escaping."""
    from gordo_components_tpu.client import Client

    def table(status1="ok", quarantined1=()):
        return {
            "version": 1,
            "members": {"m": 0},
            "migrating": {"m": [0, 1]},
            "replicas": [
                {"replica": 0, "url": "http://a:1", "status": "ok",
                 "reachable": True, "quarantined": []},
                {"replica": 1, "url": "http://b:2", "status": status1,
                 "reachable": True, "quarantined": list(quarantined1)},
            ],
        }

    healthy = Client(
        "proj", base_url="http://a:1", hedge=True, routing=table()
    )
    urls = healthy._chunk_urls("m", "prediction")
    assert len(urls) == 2 and urls[1].startswith("http://b:2/")
    for bad in (
        table(status1="degraded"),
        table(status1="unhealthy"),
        table(quarantined1=["m"]),
    ):
        c = Client("proj", base_url="http://a:1", hedge=True, routing=bad)
        assert len(c._chunk_urls("m", "prediction")) == 1
    # a replica that does not SERVE the member is no hedge target either
    partitioned = table()
    partitioned["migrating"] = {}
    c = Client(
        "proj", base_url="http://a:1", hedge=True, routing=partitioned
    )
    assert len(c._chunk_urls("m", "prediction")) == 1


def test_client_rejects_malformed_routing_table():
    from gordo_components_tpu.client import Client

    with pytest.raises(ValueError, match="members"):
        Client("proj", routing={"version": 1})


# ------------------------------------------------------------------ #
# perf-guard: partition-aware fan-out >= single-URL on a REAL mesh
# ------------------------------------------------------------------ #


@pytest.mark.perfguard
@pytest.mark.slow
def test_perfguard_routed_fanout_no_slower_than_single_url():
    """The routing path must never regress below naive single-URL
    posting. Subprocess (tools/mesh_demo.py): real processes, so on
    multi-core hosts the guard also demands the parallel win — on a
    single-core container (N processes timesharing one CPU cannot beat
    one process; docs/architecture.md records the measured ~0.6x) the
    guard holds the STRUCTURAL line instead: fan-out split across every
    replica, bitwise parity, and a zero-non-200 migration."""
    import subprocess
    import sys

    tool = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "mesh_demo.py",
    )
    out = subprocess.run(
        [sys.executable, tool, "--models", "6", "--rows", "300",
         "--posts", "10", "--concurrency", "16"],
        capture_output=True, text=True, timeout=420,
    )
    assert out.returncode == 0, (out.stdout or "") + (out.stderr or "")
    lines = out.stdout.splitlines()
    start = max(i for i, ln in enumerate(lines) if ln.strip() == "{")
    doc = json.loads("\n".join(lines[start:]))
    assert doc["parity"] == "bitwise"
    assert all(v > 0 for v in doc["requests_per_replica"].values())
    assert doc["migration"]["non_200"] == 0
    if (doc.get("cpu_count") or 1) >= 2:
        assert doc["mesh_vs_single"] >= 1.0, doc["mesh_vs_single"]
