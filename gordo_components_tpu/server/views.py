"""HTTP views.

Reference parity: gordo_components/server/views/ (unverified; SURVEY.md §2
"server", §3.2) — REST surface per target:

- ``GET  /gordo/v0/{project}/{target}/healthcheck``
- ``GET  /gordo/v0/{project}/{target}/metadata``
- ``POST /gordo/v0/{project}/{target}/prediction``
- ``POST /gordo/v0/{project}/{target}/anomaly/prediction``
- ``GET  /gordo/v0/{project}/{target}/download-model``

plus collection-level ``GET /gordo/v0/{project}/models``. Implemented on
aiohttp; model compute runs in a thread-pool executor so the event loop
stays responsive while XLA executes.
"""

import asyncio
import functools
import json
import logging
import math
import os
import time
from typing import Any, Dict, Optional

import numpy as np
import pandas as pd
from aiohttp import web
from aiohttp.payload import Payload

from gordo_components_tpu import __version__, serializer
from gordo_components_tpu.observability.tracing import chrome_trace, stage
from gordo_components_tpu.qos.admission import QosShed
from gordo_components_tpu.qos.classify import classify_meta
from gordo_components_tpu.resilience.deadline import DeadlineExceeded
from gordo_components_tpu.server.bank import EngineOverloaded
from gordo_components_tpu.server.model_io import (
    anomaly_frame_arrays,
    anomaly_frames,
    decode_tensor_request_ex,
    prediction_frames,
)
from gordo_components_tpu.server.utils import (
    extract_x_y,
    frame_to_dict,
    get_reload_lock,
)
from gordo_components_tpu.utils import parquet_engine_available
from gordo_components_tpu.utils.wire import (
    TENSOR_CONTENT_TYPE,
    WireFormatError,
    encoding_of,
    frame_segments,
    rows_as_f32,
    unpack_frames,
)

logger = logging.getLogger(__name__)

routes = web.RouteTableDef()


def _collection(request: web.Request):
    return request.app["collection"]


_PARQUET_OK = parquet_engine_available()


def _get_model(request: web.Request):
    target = request.match_info["target"]
    collection = _collection(request)
    try:
        # one-state read: a concurrent /reload swapping the collection
        # must not let the existence check and the metadata lookup see
        # different states
        return collection.entry(target)
    except KeyError:
        raise web.HTTPNotFound(
            text=json.dumps({"error": f"No such model: {target}"}),
            content_type="application/json",
        )


def _bank_engine(request: web.Request):
    """The continuous-batching engine, if the target is bank-resident.

    Under the worker pool each parse loop owns a LOCAL engine over the
    shared bank (server/workers.py) — scoring must use it, never the
    primary's: a cross-loop hop per request costs GIL-switch stalls and
    breaks the local loop's batch coalescing."""
    engine = getattr(request.app, "gordo_engine", None) or request.app.get(
        "bank_engine"
    )
    if engine is not None and request.match_info["target"] in engine.bank:
        return engine
    return None


def _engine_score(engine):
    """The engine's any-loop scoring entry: ``submit`` hops to the
    engine's own loop when the handler runs on a multi-worker parse loop
    (server/workers.py) and is a pure pass-through on the primary loop.
    Test stubs that only implement ``score`` keep working."""
    return getattr(engine, "submit", None) or engine.score


def _quarantine_gate(request: web.Request) -> None:
    """410 Gone (with the recorded reason) for a quarantined target — the
    model EXISTS but was evicted from routing by the failure breaker
    (resilience/quarantine.py); a 404 would lie to the operator and a
    crash-retry loop would keep burning capacity on a poisoned model."""
    quarantine = request.app.get("quarantine")
    target = request.match_info["target"]
    if quarantine is None or target not in quarantine:
        return
    info = quarantine.reason(target) or {}
    raise web.HTTPGone(
        text=json.dumps(
            {
                "error": f"Model {target!r} is quarantined",
                "reason": info.get("reason"),
                "failures": info.get("failures"),
                "since": info.get("since"),
                "clear": f"POST /gordo/v0/{request.match_info['project']}"
                         "/quarantine/clear",
            }
        ),
        content_type="application/json",
    )


def _request_encoding(request: web.Request) -> str:
    """The scoring-POST body encoding, from the content type alone — the
    binary path's OPT-IN switch (the shared rule in utils/wire.py, also
    what the middleware's per-encoding counters classify by)."""
    return encoding_of(request.content_type)


def _note_scoring_result(
    request: web.Request, target: str, X_arr: np.ndarray, values
) -> None:
    """Record a completed score with the quarantine breaker: finite
    output resets the failure streak; non-finite output (NaN/Inf anywhere
    in ``values``) counts as a failure — UNLESS the request's own input
    was non-finite, which is the client's data, not the model's fault.
    The input scan only runs on the (rare) non-finite path. The
    finiteness verdict is also stashed for the goodput ledger: a 200
    carrying NaN scores is wasted work, not goodput.

    ``X_arr`` is the float32 array the model actually scored — the
    handlers validate/convert the request payload ONCE and reuse that
    one array here, instead of the old second
    ``np.asarray(X.values, dtype="float64")`` shadow copy per non-finite
    check (and the verdict is now about the values the model truly saw:
    a float64 payload the float32 cast turned infinite IS non-finite
    input from the model's point of view)."""
    quarantine = request.app.get("quarantine")
    ledger = request.app.get("goodput")
    if quarantine is None and ledger is None:
        return
    arr = np.asarray(values)
    finite = bool(np.all(np.isfinite(arr)))
    input_finite = True
    if not finite:
        input_finite = bool(np.all(np.isfinite(X_arr)))
    if ledger is not None:
        # same exemption the breaker applies: NaN-in-NaN-out is the
        # client's data — the server did its work, so it is not wasted
        # and must not burn the availability budget. Only finite input
        # producing non-finite output counts against goodput.
        request["scores_finite"] = finite or not input_finite
    if quarantine is None:
        return
    if finite:
        quarantine.record_success(target)
    elif input_finite:
        if quarantine.record_failure(
            target, "non-finite scores in model output"
        ):
            _emit_event(
                request.app,
                "quarantine.enter",
                severity="error",
                target=target,
                reason="non-finite scores in model output",
            )


def _note_scoring_error(request: web.Request, target: str, exc: Exception) -> None:
    """Count a scoring exception against the quarantine breaker.
    Input-shape complaints (ValueError/KeyError) are the request's fault,
    not the model's, and a blown deadline is the clock's — neither ever
    counts (expired requests are handled before this is reached; the
    exclusion is belt-and-braces for future call sites)."""
    quarantine = request.app.get("quarantine")
    if quarantine is None or isinstance(
        exc, (ValueError, KeyError, DeadlineExceeded)
    ):
        return
    if quarantine.record_failure(target, f"{type(exc).__name__}: {exc}"):
        _emit_event(
            request.app,
            "quarantine.enter",
            severity="error",
            target=target,
            reason=f"{type(exc).__name__}: {exc}",
        )


def _emit_event(
    app: web.Application, etype: str, severity: str = "info", **attrs
) -> None:
    """Stamp a state transition onto the flight-recorder timeline
    (observability/events.py), tagged with the current bank generation.
    Absent log (apps built before the recorder, bare test apps) = no-op."""
    events = app.get("events")
    if events is not None:
        events.emit(
            etype,
            severity=severity,
            generation=app.get("bank_generation"),
            **attrs,
        )


def _http_overloaded(exc: EngineOverloaded) -> web.HTTPTooManyRequests:
    """429 with a drain-estimate Retry-After for a shed request."""
    return web.HTTPTooManyRequests(
        text=json.dumps(
            {
                "error": str(exc),
                "reason": "engine_overloaded",
                "retry_after_s": round(exc.retry_after_s, 2),
            }
        ),
        content_type="application/json",
        headers={"Retry-After": str(max(1, math.ceil(exc.retry_after_s)))},
    )


def _http_qos_shed(exc: QosShed) -> web.HTTPTooManyRequests:
    """429 for an admission refusal (qos/admission.py): same honest
    Retry-After contract as the engine shed, plus the machine-readable
    reason/tenant/class so a client (or operator) can see WHICH rule
    refused it — a rate-limited tenant backs off differently than a
    class under queue pressure."""
    return web.HTTPTooManyRequests(
        text=json.dumps(
            {
                "error": str(exc),
                "reason": exc.reason,
                "tenant": exc.tenant,
                "class": exc.qos_class,
                "retry_after_s": round(exc.retry_after_s, 2),
            }
        ),
        content_type="application/json",
        headers={"Retry-After": str(max(1, math.ceil(exc.retry_after_s)))},
    )


def _qos_admit(request: web.Request, engine) -> tuple:
    """Run QoS admission for a scoring request; returns the
    ``(tenant_label, qos_class)`` to stamp on the engine call. Raises
    the 429 itself on refusal. No controller / no QoS identity -> the
    defaults, zero extra work."""
    qos = request.get("qos")
    admission = request.app.get("qos_admission")
    if admission is None:
        return ("default", qos.qos_class if qos is not None else "interactive")
    if qos is None:
        from gordo_components_tpu.qos.classify import DEFAULT_REQUEST_CLASS

        qos = DEFAULT_REQUEST_CLASS
    depth = max_queue = 0
    drain_s = 0.05
    if engine is not None:
        max_queue = getattr(engine, "max_queue", 0)
        queue = getattr(engine, "_queue", None)
        depth = queue.qsize() if queue is not None else 0
        est = getattr(engine, "drain_estimate", None)
        if est is not None:
            drain_s = est(depth)
    try:
        label = admission.admit(
            qos, queue_depth=depth, max_queue=max_queue, drain_s=drain_s
        )
    except QosShed as exc:
        raise _http_qos_shed(exc)
    request["qos_label"] = label
    return (label, qos.qos_class)


def _note_deadline_expired_per_model(request: web.Request) -> None:
    """Observability for a per-model-path expiry (engine expiries count
    themselves): bump the engine's counter when one exists — a bank
    server's non-banked targets share the same
    ``gordo_engine_deadline_expired_total`` series the 504 runbook
    alerts on — and record the ``deadline_expired`` span."""
    engine = request.app.get("bank_engine")
    if engine is not None:
        engine.stats["deadline_expired"] += 1
    trace = request.get("trace")
    if trace is not None:
        now = time.monotonic()
        trace.add_span(
            "deadline_expired", now, now, error=True, where="per-model"
        )


def _http_deadline_exceeded(
    request: web.Request, exc: Optional[DeadlineExceeded] = None
) -> web.HTTPGatewayTimeout:
    """504 for a request whose time budget ran out before (or during)
    scoring. The body names the request id — the ONE request a client
    most wants to correlate is the one it already gave up on — and the
    middleware stamps the usual X-Request-Id/traceparent echo on the
    HTTPException headers, matching the 500/410 paths. Retrying an
    expired request verbatim is pointless (the same budget expires the
    same way), so unlike the 429 there is no Retry-After hint: raise
    the deadline or shed load instead."""
    rid = request.get("request_id")
    return web.HTTPGatewayTimeout(
        text=json.dumps(
            {
                "error": str(exc) if exc is not None else "deadline exceeded",
                "request_id": rid,
            }
        ),
        content_type="application/json",
    )


def _bank_coverage(request: web.Request, names) -> Any:
    """Operator-facing coverage: which models score through the HBM bank
    vs the per-model fallback path, and why (server/bank.py). None when
    the bank is disabled."""
    bank = request.app.get("bank")
    if bank is None:
        return None
    cov = bank.coverage()
    return {
        "banked": sorted(n for n in names if n in bank),
        "fallback": {
            n: cov["fallback"].get(n, "not bankable")
            for n in names
            if n not in bank
        },
        "n_buckets": cov["n_buckets"],
        "devices": cov["devices"],
        "kernel": cov["kernel"],
        "device": cov["device"],
    }


@routes.get("/gordo/v0/{project}/models")
async def list_models(request: web.Request) -> web.Response:
    body = {
        "project": request.match_info["project"],
        "models": _collection(request).names(),
        # advertised request encodings, in the server's preference order:
        # the bulk client upgrades its POST bodies to the best one it
        # also speaks (client/client.py). Tensor first — the framed
        # binary format (utils/wire.py) upgrades BOTH directions of the
        # wire and needs only numpy; parquet is deliberately demoted
        # below it (it only ever covered the request body, so it never
        # moved the bulk ratio — docs/architecture.md "Wire protocol")
        # and advertised only when a parse engine is importable, or
        # every advertised-then-posted body would 500.
        "accepts": ["application/json", TENSOR_CONTENT_TYPE]
        + (["application/x-parquet"] if _PARQUET_OK else []),
    }
    # local zero-copy transports (server/workers.py + utils/shm_ring.py):
    # the negotiation ladder a co-located client's transport="auto"
    # climbs — shm > uds > tcp, each rung verified locally before use
    transports = request.app.get("transports")
    if transports:
        body["transports"] = dict(transports)
    bank = _bank_coverage(request, body["models"])
    if bank is not None:
        body["bank"] = bank
    return web.json_response(body)


@routes.get("/gordo/v0/{project}/ready")
async def readiness(request: web.Request) -> web.Response:
    """O(1) readiness: the K8s probe fires every few seconds, and
    ``/models`` returns the full name list + per-model bank coverage —
    ~340 KB per probe at the 10k north star. This returns counts only;
    503 until the collection has loaded at least one model (matching
    the probe's previous effective gate on ``/models``)."""
    n = len(_collection(request).models)
    # a mesh replica with an EMPTY partition is ready: it owns nothing
    # right now (small fleet, or everything migrated away) but is a
    # healthy acquire target — 503ing it would get it restarted by the
    # probe exactly when the placement plane wants to hand it members
    ok = n > 0 or request.app.get("mesh") is not None
    body = {"ready": ok, "models": n}
    return web.json_response(body, status=200 if ok else 503)


def _healthz_body(app: web.Application) -> tuple:
    """Tri-state process health: ``ok`` | ``degraded`` | ``unhealthy``.

    ``degraded`` (still HTTP 200 — a liveness/readiness probe must NOT
    flap and restart a process that is serving its healthy majority)
    means a subset is impaired: models quarantined by the failure
    breaker, or artifacts the collection could not load on its latest
    scan. ``unhealthy`` (503) means nothing is servable — or the bank's
    background warm-up compile failed: the programs it compiles are the
    ones requests dispatch, so a replica whose warm-up raised must not
    pass for a serving one. The body always says WHY, so "degraded" is a
    pager link, not a mystery."""
    collection = app.get("collection")
    quarantine = app.get("quarantine")
    bank = app.get("bank")
    models = len(collection.models) if collection is not None else 0
    load_failures = dict(collection.load_failures) if collection is not None else {}
    quarantined = quarantine.snapshot()["quarantined"] if quarantine is not None else {}
    finalize_failures = dict(getattr(bank, "finalize_failures", None) or {})
    warmup = app.get("warmup_future")
    warmup_error = None
    if warmup is not None and warmup.done() and not warmup.cancelled():
        exc = warmup.exception()
        if exc is not None:
            warmup_error = f"{type(exc).__name__}: {exc}"
    if warmup_error is not None or (models == 0 and app.get("mesh") is None):
        status, http = "unhealthy", 503
    elif quarantined or load_failures or finalize_failures:
        status, http = "degraded", 200
    else:
        status, http = "ok", 200
    return {
        "status": status,
        "models": models,
        "quarantined": quarantined,
        "load_failures": load_failures,
        "bank_finalize_failures": finalize_failures,
        "bank_warmup_error": warmup_error,
    }, http


@routes.get("/healthz")
@routes.get("/gordo/v0/{project}/healthz")
async def healthz(request: web.Request) -> web.Response:
    body, status = _healthz_body(request.app)
    return web.json_response(body, status=status)


@routes.get("/gordo/v0/{project}/quarantine")
async def quarantine_list(request: web.Request) -> web.Response:
    quarantine = request.app.get("quarantine")
    if quarantine is None:
        return web.json_response({"enabled": False})
    return web.json_response({"enabled": True, **quarantine.snapshot()})


@routes.post("/gordo/v0/{project}/quarantine/clear")
async def quarantine_clear(request: web.Request) -> web.Response:
    """Operator action (see docs/operations.md runbook): re-admit
    quarantined models to routing. Body ``{"targets": [...]}`` clears the
    named models; an empty/absent body clears everything."""
    quarantine = request.app.get("quarantine")
    if quarantine is None:
        return web.json_response({"enabled": False, "cleared": []})
    targets = None
    if request.can_read_body:
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": "expected a JSON body"}),
                content_type="application/json",
            )
        if body:
            targets = body.get("targets")
            if targets is not None and not isinstance(targets, list):
                raise web.HTTPBadRequest(
                    text=json.dumps({"error": "targets must be a list"}),
                    content_type="application/json",
                )
    cleared = quarantine.clear(targets)
    if cleared:
        _emit_event(request.app, "quarantine.clear", targets=cleared)
    return web.json_response({"enabled": True, "cleared": cleared})


@routes.get("/gordo/v0/{project}/metrics")
async def metrics_exposition(request: web.Request) -> web.Response:
    """Prometheus text-format exposition of the app's metrics registry
    (observability/): request counters/latency histograms, the batching
    engine's queue state, the bank router's per-shard routed/padded-row
    counters and per-bucket coalescing histograms, and live HBM gauges.
    The generated manifests annotate pods with this path for scraping;
    watchman scrapes it to build the fleet-wide rollup."""
    registry = request.app.get("metrics")
    if registry is None:
        raise web.HTTPNotFound(
            text=json.dumps({"error": "metrics registry not enabled"}),
            content_type="application/json",
        )
    return web.Response(
        body=registry.render().encode("utf-8"),
        headers={"Content-Type": "text/plain; version=0.0.4; charset=utf-8"},
    )


def _tracer_or_disabled(request: web.Request):
    tracer = request.app.get("tracer")
    if tracer is None or not tracer.enabled:
        return None
    return tracer


def _query_n(request: web.Request, default: str) -> Any:
    """``?n=`` as a non-negative int (0 = unbounded), else 400 — a
    negative value must not silently slice away the newest/slowest
    traces (``list[:-n]``), which are the ones the caller wants."""
    try:
        n = int(request.query.get("n", default))
    except ValueError:
        n = -1
    if n < 0:
        raise web.HTTPBadRequest(
            text=json.dumps({"error": "n must be a non-negative integer"}),
            content_type="application/json",
        )
    return n or None


def _traces_response(request: web.Request, traces) -> web.Response:
    """Shared tail for the trace endpoints: ``?format=chrome`` exports
    Chrome trace-event JSON (opens directly in chrome://tracing /
    Perfetto), the default is the summary+span-tree JSON."""
    if request.query.get("format") == "chrome":
        return web.json_response(chrome_trace(traces))
    return web.json_response(
        {"enabled": True, "traces": [t.summary() for t in traces]}
    )


@routes.get("/gordo/v0/{project}/traces")
async def traces_recent(request: web.Request) -> web.Response:
    """Recent retained traces (newest first), from the tracer's bounded
    ring. ``?id=<trace_id>`` retrieves one trace (ring + slow reservoir),
    ``?n=<count>`` bounds the list, ``?format=chrome`` exports the Trace
    Event Format. Sampling: head-sampled by ``GORDO_TRACE_SAMPLE``; a
    request carrying a ``traceparent`` with the sampled flag is always
    retained."""
    tracer = _tracer_or_disabled(request)
    if tracer is None:
        return web.json_response({"enabled": False, "traces": []})
    trace_id = request.query.get("id")
    if trace_id:
        return _traces_response(request, tracer.find(trace_id))
    return _traces_response(
        request, tracer.recent(_query_n(request, default="50"))
    )


@routes.get("/gordo/v0/{project}/traces/slow")
async def traces_slow(request: web.Request) -> web.Response:
    """The slow-request flight recorder: worst-N traces by duration,
    slowest first — retained regardless of head sampling, so the tail is
    always explorable. Same ``?n=``/``?format=chrome`` options."""
    tracer = _tracer_or_disabled(request)
    if tracer is None:
        return web.json_response({"enabled": False, "traces": []})
    return _traces_response(
        request, tracer.slow(_query_n(request, default="0"))
    )


@routes.get("/gordo/v0/{project}/slo")
async def slo_view(request: web.Request) -> web.Response:
    """Rolling multi-window SLO state (observability/slo.py): per
    configured objective (availability / p99 latency / goodput ratio),
    the windowed good/total deltas, ratios, and burn rates over the
    5m/1h/6h windows, plus the worst burn across all of them.

    The body is the SAME cached snapshot the registry's
    ``gordo_slo_burn_rate`` gauges render and ``/stats`` embeds (the
    no-drift contract — byte-identical between samples). ``?refresh=1``
    forces a fresh sample first (operator / test hook; the background
    cadence is ``GORDO_SLO_SAMPLE_S``). Watchman's ``GET /slo`` merges
    this body fleet-wide."""
    tracker = request.app.get("slo")
    if tracker is None:
        return web.json_response({"enabled": False})
    if request.query.get("refresh", "").lower() in ("1", "true", "yes"):
        tracker.sample(force=True)
    body = {"enabled": True, **tracker.snapshot()}
    ledger = request.app.get("goodput")
    if ledger is not None:
        body["goodput"] = ledger.snapshot()
    return web.json_response(body)


@routes.get("/gordo/v0/{project}/qos")
async def qos_view(request: web.Request) -> web.Response:
    """Multi-tenant QoS state (qos/): the admission controller's tenant
    buckets / per-class shed thresholds / admitted+shed counters, and
    the engine's weighted-fair queue (class weights, per-class depth,
    virtual clocks, dequeue counts) plus per-class engine counters —
    the page an operator reads during overload triage to answer "which
    class is shedding, and why" (docs/operations.md runbook). Counters
    are the SAME dicts the registry renders (no-drift)."""
    admission = request.app.get("qos_admission")
    body: Dict[str, Any] = {
        "enabled": admission is not None,
        "admission": admission.snapshot() if admission is not None else {},
    }
    engine = request.app.get("bank_engine")
    if engine is not None and hasattr(engine, "qos_snapshot"):
        body["engine"] = engine.qos_snapshot()
    return web.json_response(body)


@routes.get("/gordo/v0/{project}/heat")
async def heat_view(request: web.Request) -> web.Response:
    """Per-member access heat (observability/heat.py): the decayed
    routed-row rate accountant's tier counts, per-bucket breakdown, and
    rate histogram, plus the ``?top=N`` hottest/coldest member rankings
    (default 10 — the ONLY per-member surface; the registry exports
    bounded tier/histogram series, never per-member ones).

    The body is the SAME cached snapshot the registry's
    ``gordo_heat_*`` series render and ``/stats`` embeds (no-drift);
    ``?refresh=1`` forces a fold first (operator/test hook — the normal
    cadence is ``GORDO_HEAT_SAMPLE_S``). Watchman's ``GET /heat`` sums
    these bodies into one fleet-ranked list."""
    heat = request.app.get("heat")
    if heat is None:
        return web.json_response({"enabled": False})
    if request.query.get("refresh", "").lower() in ("1", "true", "yes"):
        heat.sample(force=True)
    body = {"enabled": True, **heat.snapshot()}
    top = _query_float(request, "top")
    body.update(heat.ranked(10 if top is None else int(top)))
    return web.json_response(body)


@routes.get("/gordo/v0/{project}/costs")
async def costs_view(request: web.Request) -> web.Response:
    """Per-bucket device-cost attribution (observability/cost.py):
    analytic FLOPs/row × the goodput ledger's measured device seconds
    and real-vs-padded row split, per bucket — MFU, device-seconds-per-
    1k-rows, pad-waste score — plus the ``ranking`` list ordering
    buckets by wasted device time (pad waste × device share).

    The body is the SAME cached join the registry's ``gordo_bucket_*``
    cost series render and ``/stats`` embeds (no-drift); ``?refresh=1``
    forces a fresh join. Watchman's ``GET /costs`` sums the raw tallies
    fleet-wide and recomputes through the same arithmetic."""
    cost = request.app.get("cost")
    if cost is None:
        return web.json_response({"enabled": False})
    if request.query.get("refresh", "").lower() in ("1", "true", "yes"):
        cost.sample(force=True)
    return web.json_response({"enabled": True, **cost.snapshot()})


def _query_float(request: web.Request, name: str) -> Optional[float]:
    raw = request.query.get(name)
    if raw in (None, ""):
        return None
    try:
        return float(raw)
    except ValueError:
        raise web.HTTPBadRequest(
            text=json.dumps({"error": f"{name} must be a number, got {raw!r}"}),
            content_type="application/json",
        )


@routes.get("/gordo/v0/{project}/history")
async def history_view(request: web.Request) -> web.Response:
    """Retained metric history (observability/timeseries.py): the
    flight recorder's time axis. Without ``?series=``, the store meta +
    retained series names; with ``?series=a,b`` (plus optional
    ``since``/``until`` epoch seconds and ``step`` seconds), the points
    from the finest tier that covers the range. Disabled
    (``GORDO_HISTORY`` unset) answers ``{"enabled": false}`` — the
    watchman rollup counts such replicas out instead of erroring."""
    store = request.app.get("history")
    if store is None:
        return web.json_response({"enabled": False})
    body: Dict[str, Any] = store.snapshot()
    series_raw = request.query.get("series", "")
    names = [s for s in series_raw.split(",") if s]
    if names:
        body["series"] = store.query(
            names,
            since=_query_float(request, "since"),
            until=_query_float(request, "until"),
            step=_query_float(request, "step"),
        )
    else:
        body["names"] = store.series_names()
    return web.json_response(body)


@routes.get("/gordo/v0/{project}/events")
async def events_view(request: web.Request) -> web.Response:
    """Structured event timeline (observability/events.py): every state
    transition this replica performed — swaps, reloads, quarantine
    flips, mesh moves, canary/fault activity — oldest-first. Filters:
    ``?since=<seq>`` (resume a tail), ``?since_wall=<epoch s>``,
    ``?type=a,b`` (comma-separated), ``?limit=n`` (newest n)."""
    events = request.app.get("events")
    if events is None:
        return web.json_response({"enabled": False, "events": []})
    types_raw = request.query.get("type", "")
    types = [t for t in types_raw.split(",") if t] or None
    since_seq = _query_float(request, "since") or 0
    limit = _query_float(request, "limit")
    body = {"enabled": True, **events.snapshot()}
    body["events"] = events.events(
        since_seq=int(since_seq),
        types=types,
        since_wall=_query_float(request, "since_wall"),
        limit=None if limit is None else int(limit),
    )
    return web.json_response(body)


@routes.get("/gordo/v0/{project}/stats")
async def server_stats(request: web.Request) -> web.Response:
    """Serving-process observability (SURVEY.md §5 metrics): request
    counters by endpoint kind, error count, uptime, and the continuous
    -batching engine's coalescing effectiveness (avg rolled-up batch
    size is THE number that explains bank throughput)."""
    stats = request.app.get("stats") or {}
    body: Any = {
        "uptime_seconds": round(
            time.time() - stats.get("started_at", time.time()), 1
        ),
        "requests": dict(stats.get("requests", {})),
        "errors": int(stats.get("errors", 0)),
        "models": len(_collection(request).models),
        # per-endpoint-kind service time percentiles (SLO evidence: the
        # tail under coalescing, not just throughput — VERDICT r3 #4)
        "latency": {
            kind: hist.snapshot()
            for kind, hist in stats.get("latency", {}).items()
        },
        # exemplar-style links from latency buckets to traces: per
        # endpoint kind, the last trace id to land in each histogram
        # bucket (keyed by the bucket's le edge) — paste the trace_id
        # into GET .../traces?id=... to see where that request's time
        # went (metric spike -> offending trace in two clicks)
        "exemplars": stats.get("exemplars", {}),
        # the data plane by encoding (json|parquet|tensor): scoring and
        # ingest POST counts, request body bytes, response body bytes and
        # those of them written by reference — the same cells the
        # gordo_server_{requests,request_bytes,response_bytes,
        # response_bytes_by_reference}_total{encoding} series render
        "wire": {
            key: dict(cells)
            for key, cells in stats.get("wire", {}).items()
        },
        # multi-worker accept balance (server/workers.py): requests
        # parsed per worker loop — empty outside pool mode
        "workers": dict(stats.get("workers", {})),
    }
    shm = stats.get("shm")
    if shm is not None:
        # the shared-memory ring's data plane (utils/shm_ring.py)
        body["shm"] = dict(shm)
    transports = request.app.get("transports")
    if transports:
        body["transports"] = dict(transports)
    engine = request.app.get("bank_engine")
    if engine is not None:
        es = dict(engine.stats)
        if es.get("batches"):
            es["avg_batch"] = round(es["requests"] / es["batches"], 2)
        # the flush_ms trade, quantified: how long requests sat waiting
        # for their batch vs total submit->result service time
        es["queue_wait"] = engine.queue_wait.snapshot()
        es["service"] = engine.service.snapshot()
        # backpressure visibility: bound, live depth, and sheds (the
        # "shed" counter rides in from engine.stats)
        es["max_queue"] = engine.max_queue
        es["queue_depth"] = engine._queue.qsize()
        # per-class attribution (ISSUE 19): requests/sheds/expiries by
        # priority class, the same dicts /metrics renders
        if getattr(engine, "class_stats", None):
            es["by_class"] = {
                c: dict(cs) for c, cs in engine.class_stats.items()
            }
        body["bank_engine"] = es
    worker_engines = request.app.get("worker_engines")
    if worker_engines:
        # the per-worker-loop engines of the multi-worker pool: their
        # coalescing/shed state, next to the primary engine's above
        body["worker_engines"] = {
            wid: {
                **dict(weng.stats),
                "queue_depth": weng._queue.qsize(),
            }
            for wid, weng in worker_engines
        }
    bank = request.app.get("bank")
    if bank is not None:
        body["bank_models"] = len(bank)
        pipeline = getattr(bank, "pipeline_stats", None)
        if pipeline is not None:
            # the scoring pipeline's health at a glance: in-flight
            # window, padded-buffer arena hit rate, and the measured
            # host/device overlap ratio across multi-group calls
            body["bank_pipeline"] = pipeline()
        capacity = getattr(bank, "capacity_stats", None)
        if capacity is not None:
            # the HBM capacity picture: storage dtype, weight bytes per
            # member, models-per-GB, and any buckets whose quantization
            # fell back to fp32 (docs/observability.md contract)
            body["bank_capacity"] = capacity()
        if getattr(bank, "shared_stats", None):
            # what the buckets with shared leaves observed, dispatch by
            # dispatch: rows and tokens, tokens routed an expert (sum,
            # max), (query, key) selections made
            body["bank_shared"] = dict(bank.shared_stats)
    quarantine = request.app.get("quarantine")
    if quarantine is not None:
        # the degraded-mode surface: which models the breaker evicted
        # (and why), plus the pre-quarantine failure streaks in flight
        body["quarantine"] = quarantine.snapshot()
    ledger = request.app.get("goodput")
    if ledger is not None:
        # the goodput ledger: wall/device time by class (goodput vs
        # wasted vs padded), host-stage overhead, per-bucket/per-shard
        # breakdowns — the same cells /metrics renders
        body["goodput"] = ledger.snapshot()
    tracker = request.app.get("slo")
    if tracker is not None:
        # the SLO state GET .../slo serves, embedded verbatim (no-drift)
        body["slo"] = tracker.snapshot()
    heat = request.app.get("heat")
    if heat is not None:
        # the access-heat tiers GET .../heat serves, embedded verbatim
        # (no-drift; the per-member rankings stay on /heat?top=)
        body["heat"] = heat.snapshot()
    cost = request.app.get("cost")
    if cost is not None:
        # the per-bucket MFU/cost join GET .../costs serves (no-drift)
        body["costs"] = cost.snapshot()
    collection = request.app.get("collection")
    if collection is not None:
        body["load_failures"] = {
            "current": dict(collection.load_failures),
            "total": collection.load_failed_total,
        }
    registry = request.app.get("metrics")
    if registry is not None:
        # the registry's JSON view: the SAME cells /metrics renders (per-
        # shard routed/padded counters, engine shed/queue-depth, ...), so
        # the human-readable endpoint and the scrape endpoint cannot drift
        body["metrics"] = registry.snapshot()
    return web.json_response(body)


@routes.get("/gordo/v0/{project}/metadata-all")
async def metadata_all(request: web.Request) -> web.Response:
    """Every target's health + metadata in ONE response.

    The reference's watchman had to poll one pod per model; against a
    collection server that per-target pattern costs O(2N) HTTP requests
    per snapshot (20k requests/30s at the 10k-model north star) hammering
    the same process that serves scoring traffic. A model present in the
    collection is loaded and servable, so ``healthy`` mirrors what
    per-target ``/healthcheck`` (200 iff present) would report.

    ``?digest=1``: per-target health + a bounded metadata digest
    (utils/digest.py) instead of full metadata — O(1) requests AND
    O(small) bytes for watchman's periodic polling; full metadata stays
    available without the flag and per-target."""
    from gordo_components_tpu.utils.digest import metadata_digest

    want_digest = (
        request.query.get("digest", "").lower() in ("1", "true", "yes")
    )
    # ONE consistent (models, metadata) state: a concurrent /reload swaps
    # the collection atomically, so reading both sides from one snapshot
    # can neither 500 nor drop a target mid-reload
    models, metadata = _collection(request).snapshot()
    names = sorted(models)
    targets = {}
    for name in names:
        entry = {"healthy": True}
        meta = metadata.get(name)
        if meta is not None:
            if want_digest:
                entry["digest"] = metadata_digest(meta)
            else:
                entry["endpoint-metadata"] = meta
        targets[name] = entry
    body = {"project": request.match_info["project"], "targets": targets}
    bank = _bank_coverage(request, names)
    if bank is not None:
        body["bank"] = bank
    resp = web.json_response(body)
    if want_digest:
        # digest bodies are highly repetitive JSON (same keys per target);
        # gzip takes a 10k-fleet snapshot from a few MB to a few hundred
        # KB on the wire. DELIBERATELY digest-only: aiohttp compresses
        # synchronously on the event loop, and gzipping a tens-of-MB full
        # body would stall every concurrent scoring request — full-body
        # consumers (the bulk client) are rare and throughput-bound, not
        # wire-bound
        resp.enable_compression()
    return resp


async def _swap_collection_bank(app: web.Application, loop) -> tuple:
    """Rebuild the HBM bank from the collection's CURRENT models and land
    it through the zero-downtime swap primitive (placement/swap.py): the
    replacement builds + warm-compiles off to the side (same mesh/
    registry/pipeline/precision config and goodput ledger the app booted
    with, so counters stay monotonic and tuning never silently resets),
    then one generation flip moves serving over — in-flight batches
    drain on the old bank, so there is no 5xx window. Shared by /reload
    and the mesh acquire/release endpoints (one swap discipline, not
    three). Caller MUST hold the reload lock. Returns
    ``(bank_models, swap_info)`` — ``(None, None)`` when the bank is
    disabled."""
    if not app.get("bank_enabled"):
        return None, None
    from gordo_components_tpu.placement.swap import (
        _restore_collectors,
        build_bank,
        snapshot_collectors,
        swap_bank,
    )

    collection = app["collection"]
    prev_collectors = snapshot_collectors(app.get("metrics"))
    try:
        bank = await loop.run_in_executor(
            None, functools.partial(build_bank, app, collection.models)
        )
    except Exception:
        # a stillborn build must not leave the registry pointing at its
        # dead collectors — the serving bank's series keep rendering
        # (swap_bank handles the flip-failure case itself)
        _restore_collectors(app.get("metrics"), prev_collectors)
        raise
    result = swap_bank(app, bank, prev_collectors=prev_collectors)
    controller = app.get("placement")
    if controller is not None:
        # every swap path shares the controller's stats/pause histogram:
        # the generation GET /placement reports must agree with whoever
        # bumped it (reload, rebalance, or a mesh ownership change)
        controller.record_swap(result)
    return result.bank_models, {
        "generation": result.generation,
        "pause_ms": round(result.pause_s * 1e3, 3),
    }


@routes.post("/gordo/v0/{project}/reload")
async def reload_models(request: web.Request) -> web.Response:
    """Rescan the artifact dir and serve new/updated models without a
    restart: the builder writes artifacts, then POSTs here (the reference
    rolled a new pod per model instead). Rebuilds the HBM bank when
    enabled.

    Serialized with an app-level lock: concurrent reloads would otherwise
    run ``collection.refresh()`` on separate executor threads (mutating
    models/metadata under readers) and each would rebuild the full HBM
    bank — making repeated POSTs a cheap DoS on device memory/compute."""
    app = request.app
    lock = get_reload_lock(app)
    collection = _collection(request)
    loop = asyncio.get_running_loop()
    async with lock:
        changes = await loop.run_in_executor(None, collection.refresh)
        quarantine = app.get("quarantine")
        if quarantine is not None:
            # a replaced or removed artifact gets a clean slate: the
            # quarantine verdict belonged to the OLD bytes
            for name in changes["updated"] + changes["removed"]:
                quarantine.drop(name)
        bank_models, swap_info = await _swap_collection_bank(app, loop)
    _emit_event(
        app,
        "models.reload",
        added=len(changes.get("added", ())),
        updated=len(changes.get("updated", ())),
        removed=len(changes.get("removed", ())),
    )
    body = {
        "changes": changes,
        "models": collection.names(),
        "bank_models": bank_models,
    }
    if swap_info is not None:
        body["swap"] = swap_info
    return web.json_response(body)


@routes.get("/gordo/v0/{project}/placement")
async def placement_view(request: web.Request) -> web.Response:
    """The live model->shard placement (placement control plane): per
    bucket, the members in stack order with their per-shard observed
    window loads, the current bank generation, the controller's knobs
    and counters, and — with ``?dry_run=1`` — a full plan preview
    (what ``POST /rebalance`` would do right now, without doing it)."""
    controller = request.app.get("placement")
    if controller is None:
        return web.json_response({"enabled": False})
    dry_run = request.query.get("dry_run", "").lower() in ("1", "true", "yes")
    return web.json_response(controller.placement_view(dry_run=dry_run))


@routes.post("/gordo/v0/{project}/rebalance")
async def rebalance(request: web.Request) -> web.Response:
    """Evaluate the rebalance planner and apply the plan via the
    zero-downtime swap. Body (optional JSON): ``{"force": true}``
    applies a skew-reducing plan even below the improvement threshold
    (operator override). ``?dry_run=1`` evaluates without applying.
    A failed swap rolls back to the old generation (the old bank keeps
    serving every request) and answers 500 with ``rolled_back``."""
    controller = request.app.get("placement")
    if controller is None:
        raise web.HTTPNotFound(
            text=json.dumps({"error": "placement control plane not enabled"}),
            content_type="application/json",
        )
    force = False
    if request.can_read_body:
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": "expected a JSON body"}),
                content_type="application/json",
            )
        if isinstance(body, dict):
            force = bool(body.get("force", False))
        elif body:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": "expected a JSON object body"}),
                content_type="application/json",
            )
    dry_run = request.query.get("dry_run", "").lower() in ("1", "true", "yes")
    try:
        result = await controller.rebalance(force=force, dry_run=dry_run)
    except Exception as exc:
        # swap_bank's rollback contract already ran: the old generation
        # is serving, nothing was dropped — the 500 reports the failed
        # ATTEMPT, not a degraded server
        logger.exception("rebalance failed (rolled back)")
        return web.json_response(
            {
                "error": f"{type(exc).__name__}: {exc}",
                "rolled_back": True,
                "generation": int(request.app.get("bank_generation", 0)),
                "request_id": request.get("request_id"),
            },
            status=500,
        )
    if not dry_run:
        _emit_event(
            request.app,
            "rebalance.applied" if result.get("applied") else "rebalance.plan",
            moves=len((result.get("plan") or {}).get("moves") or ()),
            applied=bool(result.get("applied")),
        )
    return web.json_response(result)


# ---------------------------------------------------------------------- #
# multi-host serving mesh (parallel/distributed.py + watchman routing):
# ownership introspection, artifact shipping, and the acquire/release
# halves of a cross-replica member migration. Every ownership change
# lands through the SAME zero-downtime swap /reload uses, so a migration
# has no 5xx window on either side.
# ---------------------------------------------------------------------- #


@routes.get("/gordo/v0/{project}/mesh")
async def mesh_view(request: web.Request) -> web.Response:
    """This replica's mesh identity + live ownership: which members it
    serves right now (the boot partition plus/minus any acquire/release
    since). Watchman's routing table is built from exactly this truth
    (via ``/models`` — same collection), so the view exists for
    operators and tests to see the partition without joining metrics."""
    identity = request.app.get("mesh")
    collection = _collection(request)
    body: Any = {
        "enabled": identity is not None,
        "owned": collection.names(),
        "generation": int(request.app.get("bank_generation", 0)),
    }
    if identity is not None:
        body.update(
            {
                "replica_id": identity.replica_id,
                "replica_count": identity.replica_count,
                "distributed": identity.distributed,
                "coordinator": identity.coordinator,
            }
        )
    return web.json_response(body)


def _member_artifact_dir(request: web.Request, target: str) -> str:
    """The on-disk artifact dir for an OWNED member, or 404 with the
    reason (never a bare 404: a migration driver must be able to tell
    "wrong replica" from "typo'd member")."""
    from gordo_components_tpu.server.model_io import scan_artifacts

    collection = _collection(request)
    if target not in collection:
        raise web.HTTPNotFound(
            text=json.dumps(
                {
                    "error": f"member {target!r} is not owned by this replica",
                    "owned": len(collection.models),
                }
            ),
            content_type="application/json",
        )
    path = scan_artifacts(collection.root, collection.target_name).get(target)
    if path is None:  # owned in memory but artifact vanished from disk
        raise web.HTTPNotFound(
            text=json.dumps(
                {"error": f"member {target!r} has no artifact dir on disk"}
            ),
            content_type="application/json",
        )
    return path


@routes.get("/gordo/v0/{project}/mesh/member/{target}/artifact")
async def mesh_member_artifact(request: web.Request) -> web.Response:
    """The member's artifact dir as a gzipped tar — the shipping half of
    a cross-replica migration (the acquiring replica pulls this, lands
    it under its own root, then loads + swaps). Packed on an executor
    thread: tar+gzip of a model artifact must not stall the event loop
    that is serving scoring traffic."""
    target = request.match_info["target"]
    path = _member_artifact_dir(request, target)
    from gordo_components_tpu.server.model_io import pack_artifact_dir

    data = await asyncio.get_running_loop().run_in_executor(
        None, pack_artifact_dir, path
    )
    return web.Response(
        body=data,
        content_type="application/gzip",
        headers={"X-Gordo-Member": target},
    )


async def _mesh_body(request: web.Request) -> dict:
    """The JSON object body every mesh mutation takes (400 otherwise).

    The member name is validated as a bare directory name: acquire joins
    it into the artifact root and unpacks a network-supplied archive
    there, so separators, ``..``, or an absolute path would let a
    hostile caller aim the write outside the root entirely (the archive
    guards in ``unpack_artifact_dir`` protect paths INSIDE the archive,
    not the destination)."""
    try:
        body = await request.json()
    except Exception:
        body = None
    member = (body or {}).get("member") if isinstance(body, dict) else None
    if (
        not isinstance(member, str)
        or not member
        or member != os.path.basename(member)
        or member in (".", "..")
        or os.path.isabs(member)
    ):
        raise web.HTTPBadRequest(
            text=json.dumps(
                {
                    "error": 'expected a JSON body {"member": "<name>", ...} '
                             "with a plain member name (no path separators)"
                }
            ),
            content_type="application/json",
        )
    return body


@routes.post("/gordo/v0/{project}/mesh/acquire")
async def mesh_acquire(request: web.Request) -> web.Response:
    """Take ownership of a member. Body: ``{"member": name}`` (artifact
    already on this replica's disk — the shared-volume deploy, and the
    replica-loss recovery path) or ``{"member": name, "source": url}``
    (pull the artifact from the source replica's ``.../artifact``
    endpoint first — the cross-host shipping path).

    Ordering contract (watchman's migration sequence): acquire runs
    BEFORE the source's release, so mid-migration the member is owned by
    BOTH replicas and either answers — the zero-non-200 window. The new
    bank generation lands through the same zero-downtime swap as
    ``/reload``. Idempotent: acquiring an already-owned member is a
    no-op 200 (a retried migration step must not rebuild the bank)."""
    app = request.app
    body = await _mesh_body(request)
    member = body["member"]
    source = body.get("source")
    if source is not None and not isinstance(source, str):
        raise web.HTTPBadRequest(
            text=json.dumps({"error": "source must be a URL string"}),
            content_type="application/json",
        )
    collection = _collection(request)
    loop = asyncio.get_running_loop()
    lock = get_reload_lock(app)
    async with lock:
        if member in collection:
            return web.json_response(
                {
                    "acquired": False,
                    "already_owned": True,
                    "member": member,
                    "generation": int(app.get("bank_generation", 0)),
                }
            )
        if source:
            # pull the artifact from the losing replica (bounded: a hung
            # source must not pin this replica's reload lock forever)
            import aiohttp as _aiohttp

            from gordo_components_tpu.resilience.deadline import Deadline
            from gordo_components_tpu.server.model_io import unpack_artifact_dir

            url = (
                f"{source.rstrip('/')}/gordo/v0/"
                f"{request.match_info['project']}/mesh/member/{member}/artifact"
            )

            async def fetch():
                async with _aiohttp.ClientSession() as session:
                    async with session.get(url) as resp:
                        if resp.status != 200:
                            raise ValueError(
                                f"source replied {resp.status}: "
                                f"{(await resp.text())[:300]}"
                            )
                        return await resp.read()

            try:
                raw = await Deadline(120.0).wait_for(fetch())
                await loop.run_in_executor(
                    None,
                    unpack_artifact_dir,
                    raw,
                    os.path.join(collection.root, member),
                )
            except Exception as exc:
                return web.json_response(
                    {
                        "acquired": False,
                        "member": member,
                        "error": f"artifact fetch from {source} failed: "
                                 f"{type(exc).__name__}: {exc}",
                    },
                    status=502,
                )
        try:
            changes = await loop.run_in_executor(
                None, collection.acquire, member
            )
        except FileNotFoundError as exc:
            raise web.HTTPNotFound(
                text=json.dumps(
                    {
                        "error": str(exc),
                        "hint": 'pass {"source": "<replica base url>"} to '
                                "ship the artifact first",
                    }
                ),
                content_type="application/json",
            )
        quarantine = app.get("quarantine")
        if quarantine is not None:
            # freshly shipped bytes get a clean breaker slate
            quarantine.drop(member)
        try:
            bank_models, swap_info = await _swap_collection_bank(app, loop)
        except Exception as exc:
            # roll ownership back: serving a member the bank rebuild
            # rejected would route its traffic into per-model fallbacks
            # nobody planned for — the old generation keeps serving and
            # the migration driver sees a clean failure to retry
            await loop.run_in_executor(None, collection.release, member)
            logger.exception("mesh acquire of %r failed at bank swap", member)
            return web.json_response(
                {
                    "acquired": False,
                    "member": member,
                    "rolled_back": True,
                    "error": f"{type(exc).__name__}: {exc}",
                    "generation": int(app.get("bank_generation", 0)),
                },
                status=500,
            )
    _emit_event(
        app, "mesh.acquire", member=member, shipped=bool(source)
    )
    return web.json_response(
        {
            "acquired": True,
            "member": member,
            "shipped": bool(source),
            "changes": changes,
            "bank_models": bank_models,
            "swap": swap_info,
            "owned": collection.names(),
        }
    )


@routes.post("/gordo/v0/{project}/mesh/release")
async def mesh_release(request: web.Request) -> web.Response:
    """Drop ownership of a member (the source's half of a migration,
    AFTER the target acquired and the routing table moved). The artifact
    stays on disk — a failed migration re-acquires locally instead of
    re-shipping — and the new (smaller) bank generation lands through
    the zero-downtime swap. 404 with the reason for a member this
    replica does not own."""
    app = request.app
    body = await _mesh_body(request)
    member = body["member"]
    collection = _collection(request)
    loop = asyncio.get_running_loop()
    lock = get_reload_lock(app)
    async with lock:
        try:
            changes = await loop.run_in_executor(
                None, collection.release, member
            )
        except KeyError as exc:
            raise web.HTTPNotFound(
                text=json.dumps({"error": str(exc.args[0])}),
                content_type="application/json",
            )
        quarantine = app.get("quarantine")
        if quarantine is not None:
            quarantine.drop(member)
        try:
            bank_models, swap_info = await _swap_collection_bank(app, loop)
        except Exception as exc:
            # re-acquire locally (the artifact is still on disk): a
            # failed rebuild must not leave the member unowned ANYWHERE
            # while the routing table still points here. Off the event
            # loop (it re-loads the artifact), and guarded: if the
            # re-acquire ALSO fails (artifact corrupt — likely the same
            # root cause) the 500 must still answer, flagged so the
            # migration driver knows the member truly has no owner here
            reacquired = True
            try:
                await loop.run_in_executor(None, collection.acquire, member)
            except Exception:
                reacquired = False
                logger.exception(
                    "mesh release rollback could not re-acquire %r; the "
                    "member is NOT served by this replica", member,
                )
            logger.exception("mesh release of %r failed at bank swap", member)
            return web.json_response(
                {
                    "released": False,
                    "member": member,
                    "rolled_back": reacquired,
                    "reacquire_failed": not reacquired,
                    "error": f"{type(exc).__name__}: {exc}",
                    "generation": int(app.get("bank_generation", 0)),
                },
                status=500,
            )
    _emit_event(app, "mesh.release", member=member)
    return web.json_response(
        {
            "released": True,
            "member": member,
            "changes": changes,
            "bank_models": bank_models,
            "swap": swap_info,
            "owned": collection.names(),
        }
    )


def _stream_plane(request: web.Request):
    """The streaming adaptation plane, or a 404 naming the knob — a
    plain 404 would read as a typo'd URL, not a disabled feature."""
    plane = request.app.get("stream")
    if plane is None:
        raise web.HTTPNotFound(
            text=json.dumps(
                {"error": "streaming plane not enabled (GORDO_STREAM=0)"}
            ),
            content_type="application/json",
        )
    return plane


@routes.get("/gordo/v0/{project}/drift")
async def drift_view(request: web.Request) -> web.Response:
    """Per-member drift state over the streaming window buffers
    (streaming/drift.py): EWMA reconstruction-error drift vs the
    train-time thresholds, input out-of-training-range fraction,
    watermark lag and staleness, plus the currently drifted member list.
    ``?refresh=1`` runs a fresh evaluation sweep first (device work, off
    the event loop); the default serves the last sweep's state."""
    plane = request.app.get("stream")
    if plane is None:
        return web.json_response({"enabled": False})
    if request.query.get("refresh", "").lower() in ("1", "true", "yes"):
        await plane.evaluate()
    return web.json_response({"enabled": True, **plane.drift_view()})


@routes.post("/gordo/v0/{project}/{target}/ingest")
async def ingest_rows(request: web.Request) -> web.Response:
    """Streaming ingestion: append fresh rows to the target's window
    buffer. Body: ``{"rows": [[...], ...], "timestamps": [...]}`` —
    timestamps are epoch seconds or ISO-8601 strings (optional: absent
    means "arrived now"); ``null`` cells mark sensor dropout. Late rows
    (behind the watermark by more than ``GORDO_STREAM_LATENESS_S``) are
    counted and dropped, out-of-order rows within the allowance are
    accepted — the response reports both.

    Binary bodies (``application/x-gordo-tensor``, the scoring plane's
    frame format) carry a float32 ``rows`` frame (NaN cells = dropout —
    the wire needs no null boxing) and an optional float64 epoch-seconds
    ``timestamps`` frame; live windows stream at the same zero-copy cost
    as scoring."""
    plane = _stream_plane(request)
    _get_model(request)  # 404 for unknown targets, same as scoring
    target = request.match_info["target"]
    if _request_encoding(request) == "tensor":
        raw = await request.read()
        try:
            frames = unpack_frames(raw)
            if "rows" not in frames:
                raise WireFormatError(
                    f"tensor ingest body must carry a 'rows' frame "
                    f"(got {sorted(frames)})"
                )
            values = rows_as_f32(frames["rows"], "rows")
            ts = frames.get("timestamps")
            if ts is None:
                # "arrived now" on the plane's clock seam: under replay
                # this is the replayed now, not the compressing wall
                event_ts = np.full((len(values),), plane.clock.time())
            else:
                event_ts = np.asarray(ts, np.float64).reshape(-1)
                if len(event_ts) != len(values):
                    raise WireFormatError(
                        f"{len(event_ts)} timestamps for {len(values)} rows"
                    )
            counts = plane.ingest(target, event_ts, values)
        except (WireFormatError, ValueError) as exc:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": str(exc)}),
                content_type="application/json",
            )
        return web.json_response({"target": target, **counts})
    try:
        body = await request.json()
    except Exception:
        raise web.HTTPBadRequest(
            text=json.dumps({"error": "expected a JSON body with rows"}),
            content_type="application/json",
        )
    rows = body.get("rows") if isinstance(body, dict) else None
    if not isinstance(rows, list) or not rows:
        raise web.HTTPBadRequest(
            text=json.dumps({"error": "rows must be a non-empty list of lists"}),
            content_type="application/json",
        )
    try:
        values = np.asarray(
            [[np.nan if v is None else v for v in r] for r in rows],
            dtype=np.float32,
        )
        raw_ts = body.get("timestamps")
        if raw_ts is None:
            event_ts = np.full((len(values),), plane.clock.time())
        elif not isinstance(raw_ts, list):
            raise ValueError("timestamps must be a list")
        elif len(raw_ts) != len(values):
            raise ValueError(
                f"{len(raw_ts)} timestamps for {len(values)} rows"
            )
        elif raw_ts and isinstance(raw_ts[0], str):
            # asi8 is in the index's own unit (ns/us/ms/s in pandas 2.x
            # — see dataset/resample.py); normalize to ns first
            event_ts = (
                pd.to_datetime(raw_ts, utc=True).as_unit("ns").asi8 / 1e9
            )
        else:
            event_ts = np.asarray(raw_ts, np.float64)
        counts = plane.ingest(target, event_ts, values)
    except ValueError as exc:
        raise web.HTTPBadRequest(
            text=json.dumps({"error": str(exc)}), content_type="application/json"
        )
    return web.json_response({"target": target, **counts})


@routes.get("/gordo/v0/{project}/{target}/results/stream")
async def results_stream(request: web.Request) -> web.Response:
    """Push-mode long poll (streaming/push.py): scored-window results
    for the target since the subscriber's last poll, waiting up to
    ``?timeout=`` (default 10s, max 60) for the first one. Pass a stable
    ``?subscriber=`` id to keep one bounded queue across polls (absent:
    a fresh id is minted and echoed — results published BEFORE the
    first poll with it are not replayed). The response's ``dropped``
    counts results this subscriber lost to its bounded queue
    (drop-oldest — the backpressure rule); 429 past
    ``GORDO_PUSH_SUBSCRIBERS_MAX`` subscribers."""
    plane = _stream_plane(request)
    broker = getattr(plane, "broker", None)
    if broker is None:
        raise web.HTTPNotFound(
            text=json.dumps(
                {"error": "push mode not enabled (GORDO_PUSH=0)"}
            ),
            content_type="application/json",
        )
    _get_model(request)  # unknown targets 404, same as scoring
    target = request.match_info["target"]
    subscriber = request.query.get("subscriber", "")[:128]
    if not subscriber:
        import uuid

        subscriber = uuid.uuid4().hex[:12]
    try:
        timeout = float(request.query.get("timeout", "10"))
    except ValueError:
        raise web.HTTPBadRequest(
            text=json.dumps({"error": "timeout must be a number"}),
            content_type="application/json",
        )
    timeout = min(max(timeout, 0.0), 60.0)
    if not broker.subscribe(subscriber, target):
        # consistent shed contract (ISSUE 19 satellite): every 429 in
        # the serving plane carries Retry-After + a machine-readable
        # retry_after_s. A full subscriber table drains on the poll
        # timeout cadence — a vacated slot appears within one long-poll
        # window, so that IS the honest retry hint.
        retry_s = max(timeout, 1.0)
        raise web.HTTPTooManyRequests(
            text=json.dumps(
                {
                    "error": "push subscriber table full "
                    "(GORDO_PUSH_SUBSCRIBERS_MAX)",
                    "reason": "push_subscribers_full",
                    "retry_after_s": round(retry_s, 2),
                }
            ),
            content_type="application/json",
            headers={"Retry-After": str(max(1, math.ceil(retry_s)))},
        )
    # the wait parks on the push plane's DEDICATED poll pool (sized to
    # the subscriber bound), never the event loop and never the default
    # executor the batching engine dispatches through — parked polls
    # must not starve the scoring that would wake them
    results, dropped = await asyncio.get_running_loop().run_in_executor(
        plane.poll_executor, broker.poll, subscriber, target, timeout
    )
    return web.json_response(
        {
            "subscriber": subscriber,
            "target": target,
            "results": results,
            "dropped": dropped,
        }
    )


@routes.post("/gordo/v0/{project}/adapt")
async def adapt(request: web.Request) -> web.Response:
    """Apply the online adaptation: recalibrate (default) or
    incrementally refit the drifted members (or an explicit ``targets``
    list) and land the result as a new bank generation through the
    zero-downtime swap. Body (optional JSON):
    ``{"mode": "recalibrate"|"refit", "targets": ["name", ...]}``.
    A failed adaptation rolls back completely — the serving generation
    is untouched — and answers 500 with ``rolled_back``."""
    plane = _stream_plane(request)
    mode, targets = "recalibrate", None
    if request.can_read_body:
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": "expected a JSON body"}),
                content_type="application/json",
            )
        if isinstance(body, dict):
            mode = body.get("mode", "recalibrate")
            targets = body.get("targets")
        elif body:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": "expected a JSON object body"}),
                content_type="application/json",
            )
    if mode not in ("recalibrate", "refit"):
        raise web.HTTPBadRequest(
            text=json.dumps({"error": f"mode must be recalibrate|refit, got {mode!r}"}),
            content_type="application/json",
        )
    if targets is not None and not isinstance(targets, list):
        raise web.HTTPBadRequest(
            text=json.dumps({"error": "targets must be a list"}),
            content_type="application/json",
        )
    try:
        result = await plane.adapt(mode, targets=targets)
    except Exception as exc:
        # the rollback contract already ran (streaming/adapt.py): the
        # serving generation and the published models are untouched
        logger.exception("adaptation failed (rolled back)")
        return web.json_response(
            {
                "error": f"{type(exc).__name__}: {exc}",
                "rolled_back": True,
                "generation": int(request.app.get("bank_generation", 0)),
                "request_id": request.get("request_id"),
            },
            status=500,
        )
    return web.json_response(result)


@routes.get("/gordo/v0/{project}/{target}/healthcheck")
async def healthcheck(request: web.Request) -> web.Response:
    _get_model(request)
    return web.json_response({"gordo-server-version": __version__})


@routes.get("/gordo/v0/{project}/{target}/metadata")
async def metadata(request: web.Request) -> web.Response:
    _, meta = _get_model(request)
    return web.json_response(
        {"endpoint-metadata": meta, "env": {"model_collection_dir": _collection(request).root}}
    )


@routes.get("/gordo/v0/{project}/{target}/download-model")
async def download_model(request: web.Request) -> web.Response:
    model, _ = _get_model(request)
    data = serializer.dumps(model)
    return web.Response(
        body=data, content_type="application/octet-stream"
    )


async def _parse_request(request: web.Request):
    content_type = request.content_type or "application/json"
    if "parquet" in content_type:
        if not _PARQUET_OK:
            # a clean 415 (instead of an ImportError 500) lets the bulk
            # client downgrade the run to JSON
            raise web.HTTPUnsupportedMediaType(
                text=json.dumps(
                    {"error": "no parquet engine installed on this server"}
                ),
                content_type="application/json",
            )
        raw = await request.read()
        return extract_x_y(None, raw, content_type)
    try:
        body = await request.json()
    except Exception:
        raise web.HTTPBadRequest(
            text=json.dumps({"error": "Expected JSON body with an X entry"}),
            content_type="application/json",
        )
    return extract_x_y(body)


async def _parse_scoring(request: web.Request):
    """Parse a scoring POST once, by encoding.

    Returns ``(encoding, X, y, Xf, yf)``: ``Xf``/``yf`` are the float32
    arrays scoring consumes (validated ONCE and reused by the finiteness
    breaker — the old second float64 copy in ``_note_scoring_result`` is
    gone); ``X``/``y`` DataFrames exist only on the JSON/parquet paths
    (``None`` for tensor — its fast path never builds one). The ``parse``
    stage span carries the encoding, so per-encoding parse cost is
    visible in traces (docs/observability.md). A tensor body's ``parse``
    holds ``receive``: the wait for the rest of the body and its join
    (the handler starts once the headers are in)."""
    encoding = _request_encoding(request)
    trace = request.get("trace")
    t_parse = time.monotonic()
    X = y = yf = None
    if encoding == "tensor":
        raw = await request.read()
        t_read = time.monotonic()
        try:
            # bytes -> frombuffer views -> float32 rows; no DataFrame,
            # no per-value boxing (server/model_io.py, utils/wire.py)
            Xf, yf, meta = decode_tensor_request_ex(raw)
        except WireFormatError as exc:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": f"tensor body: {exc}"}),
                content_type="application/json",
            )
        if meta:
            # binary-path QoS identity: the __meta__ sidecar overrides
            # the headers (qos/classify.py) — the FINAL value here is
            # what admission gates on and the ledger attributes
            request["qos"] = classify_meta(meta, request.get("qos"))
    else:
        try:
            X, y = await _parse_request(request)
        except ValueError as exc:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": str(exc)}),
                content_type="application/json",
            )
        # no-copy when the parse already produced float32 (the old
        # .astype("float32") unconditionally copied per request)
        Xf = np.asarray(X.values, dtype="float32")
        if y is not None:
            yf = np.asarray(y.values, dtype="float32")
    if trace is not None:
        parse = trace.add_span("parse", t_parse, time.monotonic(), encoding=encoding)
        if encoding == "tensor":
            trace.add_span("receive", t_parse, t_read, parent=parse, bytes=len(raw))
    return encoding, X, y, Xf, yf


def _span_engine_edges(trace) -> None:
    """The two spans of a banked request whose ends lie on either side of
    the engine, recorded where the view coroutine resumes after
    ``await engine.score(...)``: ``admit`` (end of ``parse`` -> the
    request enqueued: quarantine and QoS admission) and ``resolve`` (the
    bank's ``postprocess`` done on the executor thread -> now: the
    hand-off back to the event loop and the future's wake-up). Both are
    observed across an ``await``, so neither is a profiler stage. A
    request that never queued, or that a retry scored untraced, gets
    neither."""
    now = time.monotonic()
    parsed = queued = done = None
    for span in trace.spans:
        if span.name == "parse":
            parsed = span.end
        elif span.name == "queue_wait":
            queued = span.start
        elif span.name == "postprocess":
            done = span.end
    if parsed is not None and queued is not None:
        trace.add_span("admit", parsed, queued)
    if done is not None:
        trace.add_span("resolve", done, now)


class TensorBody(Payload):
    """A tensor answer as its connection writes it: the frames' segments
    (``utils.wire.frame_segments``) one after another, each array from
    its own memory. No joined body exists; ``size`` is the segments' sum,
    so the response carries ``Content-Length`` and is not chunked. The
    segments, and through them the arrays, are held until the last byte
    has left: the transport keeps what a ``send`` did not take by
    reference.

    Given the request's ``trace``, the write records its ``send`` span
    there: the first segment handed to the connection's writer (the
    headers go with it) → the last ``writer.write`` returned, with
    ``bytes`` and ``segments`` handed; ``error: true`` and closed at the
    failure where the connection failed first. The middleware publishes
    such a trace only after this write (``server._stats_middleware``)."""

    def __init__(self, frames, trace=None) -> None:
        segments = frame_segments(frames)
        super().__init__(segments, content_type=TENSOR_CONTENT_TYPE)
        self._size = sum(len(seg) for seg in segments)
        # what the middleware's response counters read (stats["wire"])
        self.by_reference = sum(
            len(seg) for seg in segments if isinstance(seg, memoryview)
        )
        self.trace = trace

    def decode(self, encoding: str = "utf-8", errors: str = "strict") -> str:
        return b"".join(self._value).decode(encoding, errors)

    async def write(self, writer) -> None:
        start = time.monotonic()
        handed = 0
        try:
            for segment in self._value:
                await writer.write(segment)
                handed += 1
        finally:
            trace = self.trace
            if trace is not None and not trace.published:
                trace.add_span(
                    "send", start, time.monotonic(),
                    error=handed < len(self._value),
                    bytes=sum(len(seg) for seg in self._value[:handed]),
                    segments=handed,
                )


@routes.post("/gordo/v0/{project}/{target}/prediction")
async def prediction(request: web.Request) -> web.Response:
    model, _ = _get_model(request)
    _quarantine_gate(request)
    target = request.match_info["target"]
    encoding, X, _y, Xf, _yf = await _parse_scoring(request)
    engine = _bank_engine(request)
    tenant_label, qos_class = _qos_admit(request, engine)
    trace = request.get("trace")
    deadline = request.get("deadline")
    try:
        if engine is not None:
            result = await _engine_score(engine)(
                target,
                Xf,
                request_id=request.get("request_id"),
                trace=trace,
                deadline=deadline,
                tenant=tenant_label,
                qos_class=qos_class,
            )
            if trace is not None:
                _span_engine_edges(trace)
            output = result.model_output
            # goodput: the request's share of its group's device window
            # (bank-attributed), committed by the middleware on response
            request["device_s"] = result.device_s
        else:
            if deadline is not None and deadline.expired():
                # per-model path: the executor job can't be cancelled
                # once submitted, so the expiry check runs before it
                _note_deadline_expired_per_model(request)
                raise DeadlineExceeded("deadline expired before dispatch")
            loop = asyncio.get_running_loop()
            t0 = time.monotonic()
            output = await loop.run_in_executor(None, model.predict, Xf)
            request["device_s"] = time.monotonic() - t0
            if trace is not None:
                # per-model fallback path: no coalescing stages, but the
                # device work still gets its named span
                trace.add_span(
                    "device_execute", t0, t0 + request["device_s"],
                    path="per-model",
                )
    except EngineOverloaded as exc:
        raise _http_overloaded(exc)
    except DeadlineExceeded as exc:
        # NOT a scoring error: the model is healthy, the clock ran out —
        # never counted against the quarantine breaker
        raise _http_deadline_exceeded(request, exc)
    except Exception as exc:  # surface model errors as 400s with detail
        _note_scoring_error(request, target, exc)
        logger.exception("prediction failed")
        raise web.HTTPBadRequest(
            text=json.dumps({"error": f"{type(exc).__name__}: {exc}"}),
            content_type="application/json",
        )
    _note_scoring_result(request, target, Xf, output)
    if encoding == "tensor":
        # binary out for binary in: the output array is framed, not
        # copied — no tolist, no index stringification (the client trims
        # its own index by the offset in __meta__)
        with stage("encode", trace, stage="to_wire"):
            body = TensorBody(prediction_frames(output, len(Xf)), trace)
        return web.Response(body=body, content_type=TENSOR_CONTENT_TYPE)
    with stage("encode", trace, stage="to_json"):
        out_index = X.index[len(X) - len(output):]
        return web.json_response(
            {
                "data": np.asarray(output).tolist(),
                "index": [str(i) for i in out_index],
            }
        )


@routes.post("/gordo/v0/{project}/{target}/anomaly/prediction")
async def anomaly_prediction(request: web.Request) -> web.Response:
    model, _ = _get_model(request)
    if not hasattr(model, "anomaly"):
        raise web.HTTPUnprocessableEntity(
            text=json.dumps({"error": "Model does not support anomaly scoring"}),
            content_type="application/json",
        )
    _quarantine_gate(request)
    target = request.match_info["target"]
    encoding, X, y, Xf, yf = await _parse_scoring(request)
    engine = _bank_engine(request)
    tenant_label, qos_class = _qos_admit(request, engine)
    trace = request.get("trace")
    deadline = request.get("deadline")
    frame = None
    try:
        if engine is not None:
            result = await _engine_score(engine)(
                target,
                Xf,
                yf,
                request_id=request.get("request_id"),
                trace=trace,
                deadline=deadline,
                tenant=tenant_label,
                qos_class=qos_class,
            )
            if trace is not None:
                _span_engine_edges(trace)
            request["device_s"] = result.device_s
            if encoding == "tensor":
                # the banked fast path end-to-end: fetched device buffers
                # -> ScoreResult arrays -> the connection, each array
                # from its own memory. No DataFrame and no joined body is
                # ever constructed on this path.
                with stage("encode", trace, stage="to_wire"):
                    body = TensorBody(
                        anomaly_frames(
                            result.tags, result.to_arrays(), result.offset
                        ),
                        trace,
                    )
                total_scaled = result.total_scaled
            else:
                with stage("encode", trace, stage="to_frame"):
                    frame = result.to_frame(index=X.index)
        else:
            if deadline is not None and deadline.expired():
                _note_deadline_expired_per_model(request)
                raise DeadlineExceeded("deadline expired before dispatch")
            if X is None:
                # per-model fallback wants DataFrames (model.anomaly's
                # contract); tensor callers pay one cheap wrap here —
                # the hot banked path above never does
                X = pd.DataFrame(Xf)
                y = None if yf is None else pd.DataFrame(yf)
            loop = asyncio.get_running_loop()
            t0 = time.monotonic()
            frame = await loop.run_in_executor(None, model.anomaly, X, y)
            request["device_s"] = time.monotonic() - t0
            if trace is not None:
                trace.add_span(
                    "device_execute", t0, t0 + request["device_s"],
                    path="per-model",
                )
            if encoding == "tensor":
                body = TensorBody(
                    anomaly_frames(
                        frame["model-input"].columns,
                        anomaly_frame_arrays(frame),
                        len(Xf) - len(frame),
                    ),
                    trace,
                )
    except EngineOverloaded as exc:
        raise _http_overloaded(exc)
    except DeadlineExceeded as exc:
        raise _http_deadline_exceeded(request, exc)
    except Exception as exc:
        _note_scoring_error(request, target, exc)
        logger.exception("anomaly scoring failed")
        raise web.HTTPBadRequest(
            text=json.dumps({"error": f"{type(exc).__name__}: {exc}"}),
            content_type="application/json",
        )
    # NaN anywhere in the model's reconstruction propagates into the
    # total columns (sums of NaN), so the totals are a cheap O(rows)
    # whole-frame finiteness proxy for the breaker
    if frame is not None:
        total_scaled = frame[("total-anomaly-scaled", "")].to_numpy()
    _note_scoring_result(request, target, Xf, total_scaled)
    if encoding == "tensor":
        return web.Response(body=body, content_type=TENSOR_CONTENT_TYPE)
    with stage("encode", trace, stage="to_json"):
        return web.json_response(frame_to_dict(frame))
