"""Goodput accounting, utilization attribution, and SLO burn rates
(observability/goodput.py + observability/slo.py; ISSUE 7).

The acceptance story this file proves: under a chaos run mixing tight
deadlines (``GORDO_FAULTS`` latency on ``engine.queue``) with normal
traffic, ``gordo_goodput_ratio`` demonstrably drops while
``gordo_slo_burn_rate{objective=availability,window=5m}`` rises; ``GET
/slo``, the watchman rollup, and the registry snapshot agree (the
no-drift contract); and the per-request stage attribution sums to
within 5% of each traced request's wall time.
"""

import asyncio
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from gordo_components_tpu import resilience, serializer
from gordo_components_tpu.models import AutoEncoder, DiffBasedAnomalyDetector
from gordo_components_tpu.observability import MetricsRegistry
from gordo_components_tpu.observability.goodput import (
    GoodputLedger,
    attribute_trace,
)
from gordo_components_tpu.observability.slo import (
    SLOTracker,
    merge_slo_snapshots,
    parse_objectives,
    parse_windows,
)
from gordo_components_tpu.server import build_app
from gordo_components_tpu.server.bank import ModelBank

pytestmark = pytest.mark.slo


@pytest.fixture(autouse=True)
def _clean_faults():
    resilience.reset()
    yield
    resilience.reset()


@pytest.fixture(scope="module")
def bankable_models():
    rng = np.random.RandomState(0)
    X3 = rng.rand(160, 3).astype("float32")
    models = {}
    for i, name in enumerate(("gp-a", "gp-b")):
        det = DiffBasedAnomalyDetector(
            base_estimator=AutoEncoder(epochs=1, batch_size=64)
        )
        det.fit(X3 + 0.01 * i)
        models[name] = det
    return models


@pytest.fixture(scope="module")
def artifact_dir(tmp_path_factory, bankable_models):
    root = tmp_path_factory.mktemp("goodput-collection")
    for name, det in bankable_models.items():
        serializer.dump(det, str(root / name), metadata={"name": name})
    return str(root)


def _x_payload(rows=24, cols=3, seed=7):
    rng = np.random.RandomState(seed)
    return {"X": rng.rand(rows, cols).tolist()}


async def _serve(artifact_dir, **kwargs):
    kwargs.setdefault("devices", 1)
    client = TestClient(TestServer(build_app(artifact_dir, **kwargs)))
    await client.start_server()
    return client


# ------------------------------------------------------------------ #
# ledger units
# ------------------------------------------------------------------ #


def test_ledger_request_classification():
    led = GoodputLedger()
    led.finish_request(200, 0.010, 0.004)
    led.finish_request(200, 0.020, 0.006, scores_finite=False)  # NaN 200
    led.finish_request(504, 0.030, 0.0)
    led.finish_request(500, 0.040, 0.002)
    led.finish_request(429, 0.001, 0.0)
    assert led.requests == {"goodput": 1, "wasted": 3, "expired": 1}
    # availability errors: 5xx (incl. the 504) + the non-finite 200
    assert led.errors_5xx == 3
    assert led.wall_goodput_s == pytest.approx(0.010)
    assert led.wall_wasted_s == pytest.approx(0.091)
    assert led.device_goodput_s == pytest.approx(0.004)
    assert led.device_wasted_s == pytest.approx(0.008)
    assert led.goodput_ratio() == pytest.approx(0.010 / 0.101)
    snap = led.snapshot()
    assert snap["goodput_ratio"] == pytest.approx(led.goodput_ratio())
    # latency histogram counts SERVED (status < 400) requests only — the
    # two 200s here — so a fast-failing outage can't flatter the p99 SLI
    assert snap["latency"]["count"] == 2


def test_ledger_account_group_splits_device_window():
    led = GoodputLedger()
    # 75% real rows: a 40ms window splits 30ms useful / 10ms padded
    led.account_group(
        "bucket-x", 0.040, 0.030, 0.010, ok=True,
        coalesce_s=0.001, pad_s=0.002, postprocess_s=0.003,
        shard_rows=[("0", 300, 100), ("1", 0, 400)],
    )
    # a failed group wastes its useful share outright
    led.account_group("bucket-x", 0.020, 0.015, 0.005, ok=False)
    assert led.device_padded_s == pytest.approx(0.015)
    assert led.device_failed_s == pytest.approx(0.015)
    assert led.stage_s["coalesce"] == pytest.approx(0.001)
    snap = led.snapshot()
    bx = snap["per_bucket"]["bucket-x"]
    assert bx["useful_s"] == pytest.approx(0.030)
    assert bx["failed_s"] == pytest.approx(0.015)
    assert bx["padded_s"] == pytest.approx(0.015)
    assert snap["per_shard"]["1"]["padded_ratio"] == 1.0
    assert snap["per_shard"]["0"]["routed_rows"] == 300
    # padded waste ratio over all device time booked so far
    assert led.padded_waste_ratio() == pytest.approx(0.015 / 0.030)


def test_ledger_registry_emission_matches_snapshot():
    registry = MetricsRegistry()
    led = GoodputLedger(registry=registry)
    led.finish_request(200, 0.010, 0.004)
    led.finish_request(503, 0.010, 0.001)
    led.account_group("b", 0.010, 0.008, 0.002, ok=True)
    snap = registry.snapshot()
    ratio = snap["gordo_goodput_ratio"]["values"][0]["value"]
    assert ratio == pytest.approx(led.goodput_ratio(), abs=1e-6)
    classes = {
        v["labels"]["class"]: v["value"]
        for v in snap["gordo_goodput_requests_total"]["values"]
    }
    assert classes == {"goodput": 1, "wasted": 1, "expired": 0}
    dev = {
        v["labels"]["class"]: v["value"]
        for v in snap["gordo_goodput_device_seconds_total"]["values"]
    }
    assert dev["goodput"] == pytest.approx(0.004)
    assert dev["padded"] == pytest.approx(0.002)
    stages = {
        v["labels"]["stage"]: v["value"]
        for v in snap["gordo_goodput_stage_seconds_total"]["values"]
    }
    assert set(stages) == {"queue_wait", "coalesce", "pad", "postprocess"}
    # the exposition text renders the same families (parser round-trip)
    text = registry.render()
    assert "gordo_goodput_ratio" in text
    assert "gordo_padded_row_waste_ratio" in text


def test_ledger_from_env_disable(monkeypatch):
    monkeypatch.setenv("GORDO_SLO", "0")
    assert GoodputLedger.from_env() is None
    monkeypatch.setenv("GORDO_SLO", "1")
    assert GoodputLedger.from_env() is not None
    monkeypatch.delenv("GORDO_SLO")
    assert GoodputLedger.from_env() is not None  # default: enabled


# ------------------------------------------------------------------ #
# trace attribution
# ------------------------------------------------------------------ #


def _span(name, start_ms, dur_ms, children=()):
    return {
        "name": name,
        "start_ms": start_ms,
        "duration_ms": dur_ms,
        "children": list(children),
    }


def test_attribute_trace_synthetic():
    trace = {
        "duration_ms": 100.0,
        "spans": _span(
            "anomaly", 0.0, 100.0,
            [
                _span("queue_wait", 0.0, 10.0),
                # two overlapping device spans (multi-chunk request)
                # must merge, not double-count
                _span("device_execute", 20.0, 30.0),
                _span("device_execute", 40.0, 20.0),
                _span("postprocess", 60.0, 15.0),
                # non-stage spans (pipeline_overlap, deadline_expired)
                # never count toward a stage
                _span("pipeline_overlap", 0.0, 90.0),
            ],
        ),
    }
    out = attribute_trace(trace)
    assert out["wall_ms"] == 100.0
    stages = out["stages_ms"]
    assert stages["queue_wait"] == 10.0
    assert stages["device_execute"] == 40.0  # [20,50)+[40,60) merged
    assert stages["postprocess"] == 15.0
    assert stages["other"] == pytest.approx(100.0 - 65.0)
    assert sum(stages.values()) == pytest.approx(out["wall_ms"])
    assert out["coverage"] == pytest.approx(0.65)


def test_attribute_trace_clamps_overlong_spans():
    # a span stretching past the root wall clamps; sum still == wall
    trace = {
        "duration_ms": 10.0,
        "spans": _span(
            "prediction", 0.0, 10.0, [_span("device_execute", 5.0, 50.0)]
        ),
    }
    out = attribute_trace(trace)
    assert out["stages_ms"]["device_execute"] == 5.0
    assert sum(out["stages_ms"].values()) == pytest.approx(10.0)


# ------------------------------------------------------------------ #
# SLO engine units
# ------------------------------------------------------------------ #


def test_parse_objectives_defaults_and_errors(monkeypatch):
    objs = parse_objectives("")
    assert [o.name for o in objs] == [
        "availability", "p99_latency_ms", "goodput_ratio",
    ]
    assert objs[1].quantile == 0.99 and objs[1].budget == pytest.approx(0.01)
    objs = parse_objectives(
        '[{"name": "p95_latency_ms", "target": 20}]'
    )
    assert objs[0].quantile == 0.95
    for bad in (
        "not json",
        '{"name": "availability"}',  # not a list
        '[{"name": "availability", "target": 2.0}]',  # ratio out of range
        '[{"name": "nonsense", "target": 0.5}]',
        '[{"name": "availability", "target": 0.9},'
        ' {"name": "availability", "target": 0.99}]',  # duplicate
    ):
        with pytest.raises(ValueError):
            parse_objectives(bad)


def test_parse_windows():
    assert parse_windows("") == [("5m", 300.0), ("1h", 3600.0), ("6h", 21600.0)]
    assert parse_windows("30s,2m") == [("30s", 30.0), ("2m", 120.0)]
    # sorted ascending regardless of input order (first = fast window)
    assert parse_windows("1h,5m")[0] == ("5m", 300.0)
    with pytest.raises(ValueError):
        parse_windows("5 minutes")


def test_burn_rate_math_with_fake_clock():
    led = GoodputLedger()
    now = {"t": 1000.0}
    tracker = SLOTracker(
        led,
        objectives=[
            {"name": "availability", "target": 0.99},
            {"name": "p99_latency_ms", "target": 50.0},
        ],
        windows=[("10s", 10.0), ("1m", 60.0)],
        sample_interval_s=1.0,
        clock=lambda: now["t"],
    )
    # t=1000: clean baseline — 90 fast requests
    for _ in range(90):
        led.finish_request(200, 0.005, 0.0)
    tracker.sample(force=True)
    # t=1005: 5 fast server errors + 5 slow-but-served 200s in-window
    now["t"] = 1005.0
    for _ in range(5):
        led.finish_request(500, 0.005, 0.0)
    for _ in range(5):
        led.finish_request(200, 0.2, 0.0)
    tracker.sample(force=True)
    snap = tracker.snapshot()
    avail = next(o for o in snap["objectives"] if o["name"] == "availability")
    w = avail["windows"]["10s"]
    # windowed: 5 errors / 10 total -> error rate 0.5, budget 0.01
    assert w["total"] == 10 and w["good"] == 5
    assert w["burn_rate"] == pytest.approx(50.0)
    assert avail["fast_burn"] is True
    lat = next(o for o in snap["objectives"] if o["name"] == "p99_latency_ms")
    # latency rates over SERVED requests only: the 5 fast 500s are
    # excluded (a fast-failing outage must not read as a healthy p99) —
    # the 5 served requests all took 200ms > the 50ms target
    assert lat["windows"]["10s"]["total"] == 5
    assert lat["windows"]["10s"]["ratio"] == pytest.approx(0.0)
    assert lat["windows"]["10s"]["burn_rate"] == pytest.approx(100.0)
    assert snap["worst"]["burn_rate"] == pytest.approx(100.0)
    # t=1100: the errors age out of the 10s window (clean sample after)
    now["t"] = 1100.0
    for _ in range(20):
        led.finish_request(200, 0.005, 0.0)
    tracker.sample(force=True)
    snap = tracker.snapshot()
    avail = next(o for o in snap["objectives"] if o["name"] == "availability")
    assert avail["windows"]["10s"]["burn_rate"] == 0.0


def test_tracker_snapshot_cached_between_samples():
    """The no-drift mechanism: between samples, every reader gets the
    SAME object — /slo, /stats, and the registry gauges cannot
    disagree."""
    led = GoodputLedger()
    registry = MetricsRegistry()
    tracker = SLOTracker(
        led, sample_interval_s=3600.0, registry=registry
    )
    led.finish_request(200, 0.01, 0.0)
    led.finish_request(500, 0.01, 0.0)
    tracker.sample(force=True)
    time.sleep(0.01)
    led.finish_request(500, 0.01, 0.0)
    tracker.sample(force=True)
    snap1 = tracker.snapshot()
    led.finish_request(500, 0.01, 0.0)  # cells move, but no new sample
    snap2 = tracker.snapshot()
    assert snap1 is snap2
    # registry gauges render from the same cached snapshot
    burn = {
        (v["labels"]["objective"], v["labels"]["window"]): v["value"]
        for v in registry.snapshot()["gordo_slo_burn_rate"]["values"]
        # the family also carries {tenant,class} rows (multi-tenant QoS)
        if "objective" in v["labels"]
    }
    for obj in snap1["objectives"]:
        for wname, w in obj["windows"].items():
            assert burn[(obj["name"], wname)] == pytest.approx(
                w["burn_rate"]
            )


def test_merge_slo_snapshots_fleet_math():
    def body(err, total, burn):
        return {
            "enabled": True,
            "objectives": [
                {
                    "name": "availability",
                    "target": 0.99,
                    "budget": 0.01,
                    "windows": {
                        "5m": {
                            "good": total - err,
                            "total": total,
                            "ratio": (total - err) / total,
                            "burn_rate": burn,
                        }
                    },
                }
            ],
        }

    merged = merge_slo_snapshots(
        [body(0, 100, 0.0), body(10, 100, 10.0), None, {"enabled": False}]
    )
    assert merged["replicas_scraped"] == 2
    (obj,) = merged["objectives"]
    w = obj["windows"]["5m"]
    assert w["good"] == 190 and w["total"] == 200
    # fleet burn recomputes from the summed ratio: 5% errors / 1% budget
    assert w["burn_rate"] == pytest.approx(5.0)
    # worst-burn attribution names the hot replica
    assert merged["worst_burn"]["replica"] == 1
    assert merged["worst_burn"]["burn_rate"] == 10.0
    # no replicas at all -> empty, never an error
    empty = merge_slo_snapshots([None, None])
    assert empty["replicas_scraped"] == 0 and empty["objectives"] == []


# ------------------------------------------------------------------ #
# HTTP surface: /slo, /stats, /metrics (no-drift) + stage attribution
# ------------------------------------------------------------------ #


async def test_http_slo_and_stats_and_metrics_agree(artifact_dir, monkeypatch):
    monkeypatch.setenv("GORDO_SLO_SAMPLE_S", "3600")  # samples only on refresh
    client = await _serve(artifact_dir)
    try:
        for i in range(6):
            resp = await client.post(
                f"/gordo/v0/proj/gp-{'ab'[i % 2]}/prediction",
                json=_x_payload(),
            )
            assert resp.status == 200
        slo = await (await client.get("/gordo/v0/proj/slo?refresh=1")).json()
        assert slo["enabled"] is True
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
        # no-drift 1: /stats embeds the same snapshot /slo serves
        assert stats["slo"]["objectives"] == slo["objectives"]
        # no-drift 2: the ledger block matches the registry's ratio gauge
        reg = stats["metrics"]
        ratio = reg["gordo_goodput_ratio"]["values"][0]["value"]
        assert ratio == pytest.approx(stats["goodput"]["goodput_ratio"])
        assert stats["goodput"]["requests"]["goodput"] == 6
        assert stats["goodput"]["device"]["total_s"] > 0
        # no-drift 3: the burn gauges equal the /slo body per (obj, window)
        burn = {
            (v["labels"]["objective"], v["labels"]["window"]): v["value"]
            for v in reg["gordo_slo_burn_rate"]["values"]
            # per-objective rows only — {tenant,class} rows ride along
            if "objective" in v["labels"]
        }
        for obj in slo["objectives"]:
            for wname, w in obj["windows"].items():
                assert burn[(obj["name"], wname)] == pytest.approx(
                    w["burn_rate"]
                )
        # the Prometheus text exposition carries the same families
        text = await (await client.get("/gordo/v0/proj/metrics")).text()
        assert "gordo_goodput_ratio" in text
        assert 'gordo_slo_burn_rate{objective="availability",window="5m"}' in text
    finally:
        await client.close()


async def test_stage_attribution_within_5pct(artifact_dir, monkeypatch):
    """Acceptance: per-request stage attribution sums to within 5% of
    each traced request's wall time (the 'other' residual is part of the
    attribution — the check catches cross-stage double-counting)."""
    monkeypatch.setenv("GORDO_TRACE_SAMPLE", "1")
    client = await _serve(artifact_dir)
    try:
        for i in range(10):
            resp = await client.post(
                f"/gordo/v0/proj/gp-{'ab'[i % 2]}/anomaly/prediction",
                json=_x_payload(rows=48),
            )
            assert resp.status == 200
        body = await (await client.get("/gordo/v0/proj/traces?n=0")).json()
        scoring = [t for t in body["traces"] if t["name"] == "anomaly"]
        assert len(scoring) >= 8
        for t in scoring:
            attr = attribute_trace(t)
            total = sum(attr["stages_ms"].values())
            assert total == pytest.approx(attr["wall_ms"], rel=0.05), (
                t["trace_id"], attr,
            )
            # the hot path's named stages must actually appear
            assert attr["stages_ms"]["device_execute"] > 0, attr
            assert attr["stages_ms"]["queue_wait"] >= 0, attr
    finally:
        await client.close()


@pytest.mark.chaos
async def test_chaos_goodput_drops_and_burn_rises(artifact_dir, monkeypatch):
    """THE acceptance scenario: an ``engine.queue`` latency fault plus
    tight deadlines on half the traffic -> expired requests burn wall
    time with no goodput, so ``gordo_goodput_ratio`` drops while
    ``gordo_slo_burn_rate{objective=availability,window=5m}`` rises —
    and /slo, the watchman rollup, and the registry snapshot agree."""
    from gordo_components_tpu.watchman.server import build_watchman_app

    monkeypatch.setenv("GORDO_SLO_SAMPLE_S", "3600")  # refresh-driven only
    client = await _serve(artifact_dir)
    try:
        # ---- phase 1: healthy traffic ----
        for i in range(10):
            resp = await client.post(
                f"/gordo/v0/proj/gp-{'ab'[i % 2]}/prediction",
                json=_x_payload(),
            )
            assert resp.status == 200
        slo1 = await (await client.get("/gordo/v0/proj/slo?refresh=1")).json()
        g1 = slo1["goodput"]["goodput_ratio"]
        assert g1 == pytest.approx(1.0)

        def burn(slo, objective, window):
            obj = next(o for o in slo["objectives"] if o["name"] == objective)
            return obj["windows"][window]["burn_rate"]

        assert burn(slo1, "availability", "5m") == 0.0

        # ---- phase 2: latency fault + tight deadlines on half the load ----
        resilience.arm("engine.queue", delay_s=0.05, exc=None)
        statuses = []
        for i in range(10):
            headers = {"X-Gordo-Deadline-Ms": "10"} if i % 2 == 0 else {}
            resp = await client.post(
                f"/gordo/v0/proj/gp-{'ab'[i % 2]}/prediction",
                json=_x_payload(),
                headers=headers,
            )
            statuses.append(resp.status)
        resilience.reset()
        assert statuses.count(504) >= 4, statuses  # tight budgets expired
        assert statuses.count(200) >= 4, statuses  # normal traffic survived

        slo2 = await (await client.get("/gordo/v0/proj/slo?refresh=1")).json()
        g2 = slo2["goodput"]["goodput_ratio"]
        assert g2 < g1, (g1, g2)  # goodput demonstrably dropped
        b2 = burn(slo2, "availability", "5m")
        assert b2 > 0.0, slo2  # the budget is burning
        assert slo2["goodput"]["requests"]["expired"] >= 4

        # ---- no-drift: /slo == /stats embed == registry snapshot ----
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
        assert stats["slo"]["objectives"] == slo2["objectives"]
        reg_burn = {
            (v["labels"]["objective"], v["labels"]["window"]): v["value"]
            for v in stats["metrics"]["gordo_slo_burn_rate"]["values"]
            # per-objective rows only — {tenant,class} rows ride along
            if "objective" in v["labels"]
        }
        assert reg_burn[("availability", "5m")] == pytest.approx(b2)
        assert stats["metrics"]["gordo_goodput_ratio"]["values"][0][
            "value"
        ] == pytest.approx(g2)

        # ---- watchman rollup agrees with the single replica ----
        base = f"http://{client.server.host}:{client.server.port}"
        wapp = build_watchman_app(
            "proj", base,
            metrics_urls=[f"{base}/gordo/v0/proj/metrics"],
        )
        wclient = TestClient(TestServer(wapp))
        await wclient.start_server()
        try:
            rollup = await (await wclient.get("/slo")).json()
            assert rollup["replicas_scraped"] == 1
            avail = next(
                o for o in rollup["objectives"] if o["name"] == "availability"
            )
            assert avail["windows"]["5m"]["burn_rate"] == pytest.approx(b2)
            assert rollup["worst_burn"]["replica"] == 0
            assert rollup["worst_burn"]["burn_rate"] > 0.0
        finally:
            await wclient.close()
    finally:
        await client.close()


async def test_watchman_slo_rollup_multi_replica(artifact_dir, monkeypatch):
    """Two replicas — one clean, one burning — merge into fleet windows
    whose good/total are the sums, with worst-burn attributed to the
    burning replica; a dead replica degrades, never errors."""
    from gordo_components_tpu.watchman.server import build_watchman_app

    monkeypatch.setenv("GORDO_SLO_SAMPLE_S", "3600")
    clean = await _serve(artifact_dir)
    burning = await _serve(artifact_dir)
    try:
        for _ in range(6):
            resp = await clean.post(
                "/gordo/v0/proj/gp-a/prediction", json=_x_payload()
            )
            assert resp.status == 200
        # real 5xx pressure via tight deadlines + an engine.queue latency
        # fault: the fault makes the 1 ms budgets expire however fast the
        # host serves, so the burn does not hang on a scheduling race
        resilience.arm("engine.queue", delay_s=0.05, exc=None)
        statuses = []
        for i in range(6):
            resp = await burning.post(
                "/gordo/v0/proj/gp-a/prediction",
                json=_x_payload(),
                headers={"X-Gordo-Deadline-Ms": "1"} if i % 2 == 0 else {},
            )
            statuses.append(resp.status)
        resilience.reset()
        assert statuses.count(504) >= 3, statuses  # tight budgets expired
        await clean.get("/gordo/v0/proj/slo?refresh=1")
        await burning.get("/gordo/v0/proj/slo?refresh=1")

        def url(c):
            return f"http://{c.server.host}:{c.server.port}/gordo/v0/proj/metrics"

        wapp = build_watchman_app(
            "proj",
            f"http://{clean.server.host}:{clean.server.port}",
            metrics_urls=[
                url(clean), url(burning),
                "http://127.0.0.1:1/gordo/v0/proj/metrics",  # dead
            ],
        )
        wclient = TestClient(TestServer(wapp))
        await wclient.start_server()
        try:
            rollup = await (await wclient.get("/slo")).json()
            assert rollup["replicas_scraped"] == 2
            assert [r["scraped"] for r in rollup["replicas"]] == [
                True, True, False,
            ]
            avail = next(
                o for o in rollup["objectives"] if o["name"] == "availability"
            )
            w = avail["windows"]["5m"]
            assert w["total"] >= 10  # both replicas' traffic summed
            assert w["burn_rate"] > 0.0  # the burning replica shows fleet-wide
            assert rollup["worst_burn"]["replica"] == 1
        finally:
            await wclient.close()
    finally:
        await clean.close()
        await burning.close()


async def test_nonfinite_input_does_not_burn_availability(artifact_dir):
    """NaN-in-NaN-out is the client's data, not wasted server work: the
    request classifies as goodput and burns no availability budget (the
    same exemption the quarantine breaker applies). Finite-input ->
    non-finite-output would still classify wasted."""
    client = await _serve(artifact_dir)
    try:
        payload = _x_payload(rows=24)
        payload["X"][0][0] = float("nan")
        resp = await client.post(
            "/gordo/v0/proj/gp-a/prediction", json=payload
        )
        assert resp.status == 200
        snap = (await (await client.get("/gordo/v0/proj/stats")).json())[
            "goodput"
        ]
        assert snap["requests"]["goodput"] == 1
        assert snap["requests"]["wasted"] == 0
        led = client.app["goodput"]
        assert led.errors_5xx == 0
    finally:
        await client.close()


async def test_slo_disabled_by_env(artifact_dir, monkeypatch):
    """GORDO_SLO=0: no ledger object exists, /slo reports disabled, and
    scoring still works untouched (the near-free-when-off contract)."""
    monkeypatch.setenv("GORDO_SLO", "0")
    client = await _serve(artifact_dir)
    try:
        assert client.app["goodput"] is None
        assert client.app.get("slo") is None
        resp = await client.post(
            "/gordo/v0/proj/gp-a/prediction", json=_x_payload()
        )
        assert resp.status == 200
        body = await (await client.get("/gordo/v0/proj/slo")).json()
        assert body == {"enabled": False}
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
        assert "goodput" not in stats and "slo" not in stats
        text = await (await client.get("/gordo/v0/proj/metrics")).text()
        assert "gordo_goodput_ratio" not in text
        assert "gordo_slo_burn_rate" not in text
    finally:
        await client.close()


async def test_reload_keeps_ledger_monotonic(artifact_dir):
    """A /reload swaps the bank but the app-level ledger persists — the
    counters must not reset (the same monotonicity contract the metric
    registry keeps across reloads)."""
    client = await _serve(artifact_dir)
    try:
        for _ in range(3):
            resp = await client.post(
                "/gordo/v0/proj/gp-a/prediction", json=_x_payload()
            )
            assert resp.status == 200
        before = (await (await client.get("/gordo/v0/proj/stats")).json())[
            "goodput"
        ]["requests"]["goodput"]
        assert (await client.post("/gordo/v0/proj/reload")).status == 200
        resp = await client.post(
            "/gordo/v0/proj/gp-a/prediction", json=_x_payload()
        )
        assert resp.status == 200
        after = (await (await client.get("/gordo/v0/proj/stats")).json())[
            "goodput"
        ]
        assert after["requests"]["goodput"] == before + 1
        # the reloaded bank kept feeding device time into the SAME ledger
        assert after["device"]["total_s"] > 0
    finally:
        await client.close()


# ------------------------------------------------------------------ #
# hot-loop overhead guard (CI lanes: make slo / make hotloop)
# ------------------------------------------------------------------ #


@pytest.mark.hotloop
def test_goodput_ledger_overhead_within_5pct(bankable_models):
    """The ledger's accounting on the scoring path must stay within 5%
    of the ledger-free configuration (which is the GORDO_SLO=0 path:
    bank.ledger is None and every call site skips on that one check).
    Interleaved best-of-N so machine drift hits both sides."""
    rng = np.random.RandomState(6)
    bank = ModelBank.from_models(bankable_models, registry=False)
    ledger = GoodputLedger()
    requests = [
        (name, rng.rand(64, 3).astype("float32"), None)
        for name in bankable_models
    ]
    bank.score_many(requests)  # warm/compile

    def timed(led, iters=40):
        bank.ledger = led
        t0 = time.perf_counter()
        for _ in range(iters):
            results = bank.score_many(requests)
            if led is not None:
                for r in results:
                    led.finish_request(200, 0.001, r.device_s)
        bank.ledger = None
        return time.perf_counter() - t0

    rounds, ratios = 7, []
    for _ in range(rounds):
        control = timed(None)
        instrumented = timed(ledger)
        ratios.append(instrumented / control)
    assert min(ratios) <= 1.05, ratios


def test_score_result_device_s_assigned(bankable_models):
    """With a ledger attached, every ScoreResult carries its share of
    the group's useful device window, apportioned by row count; without
    one, device_s stays 0.0 (no accounting machinery runs)."""
    rng = np.random.RandomState(3)
    bank = ModelBank.from_models(bankable_models, registry=False)
    requests = [
        ("gp-a", rng.rand(96, 3).astype("float32"), None),
        ("gp-b", rng.rand(32, 3).astype("float32"), None),
    ]
    results = bank.score_many(requests)
    assert all(r.device_s == 0.0 for r in results)
    ledger = GoodputLedger()
    bank.ledger = ledger
    results = bank.score_many(requests)
    assert all(r.device_s > 0.0 for r in results)
    # row-proportional split: the 96-row request carries 3x the 32-row one
    assert results[0].device_s == pytest.approx(3 * results[1].device_s)
    # the group's padded+useful split landed in the ledger
    snap = ledger.snapshot()
    assert snap["device"]["padded_s"] > 0  # 96+32 rows pad to pow2 shapes
    assert snap["per_bucket"], snap
