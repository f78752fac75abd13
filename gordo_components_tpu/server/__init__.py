"""Model server.

Reference parity: gordo_components/server/ (unverified; SURVEY.md §2
"server") — the reference runs one Flask+gunicorn process per model. The
TPU-native server is one aiohttp process serving a *collection* of models
(a fleet shard resident in a chip's HBM), with the same per-target REST
surface, so Ambassador-style routing by ``{target}`` still works.
"""

import asyncio
import contextlib
import itertools
import logging
import os
import sys
import time
from typing import Optional

from aiohttp import web

from gordo_components_tpu.observability import MetricsRegistry, Tracer
from gordo_components_tpu.observability.goodput import GoodputLedger
from gordo_components_tpu.observability.slo import SLOTracker
from gordo_components_tpu.observability.tracing import format_traceparent
from gordo_components_tpu.resilience import (
    QuarantineSet,
    configure_from_env,
    faultpoint,
)
from gordo_components_tpu.resilience.deadline import (
    DEADLINE_HEADER,
    Deadline,
    default_deadline_ms,
    parse_deadline_ms,
)
from gordo_components_tpu.server.bank import BatchingEngine, ModelBank
from gordo_components_tpu.server.model_io import ModelCollection
from gordo_components_tpu.server.stats import LatencyHistogram
from gordo_components_tpu.server.views import TensorBody, routes

logger = logging.getLogger(__name__)

# server-generated request-id sequence (used when the client sent none);
# process-wide so ids stay unique across app rebuilds in one process
_RID_SEQ = itertools.count(1)

# request-body size cap, shared with the worker pool (server/workers.py)
# so every accept path — primary, workers, UDS — enforces ONE limit
CLIENT_MAX_SIZE = 256 * 1024**2

# stats mutation guard for multi-worker serving: with workers=1 (the
# default) every mutation happens on one loop thread and stats["lock"]
# is absent — this shared nullcontext keeps that path allocation-free
# and lock-free. The worker pool (server/workers.py) installs a real
# threading.Lock so N worker loops can't lose counter increments.
_NO_LOCK = contextlib.nullcontext()


# transport-level chaos seam (mesh game days): armed with the
# connection-class fault kinds (refuse/reset/blackhole — resilience/
# faults.py), the middleware below ABORTS the raw socket instead of
# answering, so a real peer observes a real transport failure
# (ServerDisconnectedError / hang), not an in-band 500. Injection over
# subprocess boundaries rides GORDO_FAULTS, which build_app arms.
_FP_CONNECTION = faultpoint("server.connection")


@web.middleware
async def _chaos_transport_middleware(request, handler):
    """Outermost middleware: when ``server.connection`` fires, kill the
    TCP connection before any handler (or stats accounting) runs — the
    disarmed cost is one attribute read per request."""
    try:
        _FP_CONNECTION.fire()
    except asyncio.CancelledError:
        raise
    except BaseException:
        transport = request.transport
        if transport is not None:
            transport.abort()
        raise
    return await handler(request)


def _trace_headers(headers, rid: str, trace) -> None:
    """Stamp the id headers every response must carry: the gordo request
    id, the generic ``X-Request-Id`` (the trace id when traced, so an
    operator pastes it straight into ``GET /traces?id=``), and the W3C
    ``traceparent`` continuing the request's trace context downstream."""
    headers["X-Gordo-Request-Id"] = rid
    headers["X-Request-Id"] = trace.trace_id if trace is not None else rid
    if trace is not None:
        headers["traceparent"] = format_traceparent(
            trace.trace_id, trace.root.span_id
        )


def _publish_trace(trace, stats, lock, kind: str, hist, elapsed: float) -> None:
    """Publish a request's closed trace and, where it was retained, link
    it from the latency bucket its root landed in: an exemplar, the LAST
    trace to land in each bucket, keyed by the bucket's le edge
    (formatted EXACTLY as the Prometheus exposition formats it, so the
    strings join against the scraped histogram), bounded at O(buckets)
    per kind and surfaced through /stats so "p99 spiked" resolves to
    "this trace" in two clicks. Only RETAINED traces publish an exemplar
    (``retained`` is set by the commit): a head-sample drop must not
    leave a dangling id the /traces lookup can't resolve."""
    trace.publish()
    if trace.retained:
        from gordo_components_tpu.observability.metrics import _fmt

        # _fmt renders inf as "+Inf", matching the bucket labels
        with lock:
            stats.setdefault("exemplars", {}).setdefault(kind, {})[
                _fmt(hist.bucket_le(elapsed))
            ] = {
                "trace_id": trace.trace_id,
                "value_ms": round(elapsed * 1e3, 3),
                "at": round(time.time(), 3),
            }


@web.middleware
async def _stats_middleware(request, handler):
    """Per-endpoint-kind request/error counters + service-time histograms
    for ``GET .../stats``, plus request-id/trace propagation: the client's
    ``X-Gordo-Request-Id`` header (or a server-generated id) is stashed on
    the request, echoed on the response, and logged in the access line —
    so a latency-histogram outlier or an engine-batch failure is traceable
    back to one request. When the app carries a tracer
    (observability/tracing.py), a request-scoped trace opens here (W3C
    ``traceparent`` in, root span = endpoint kind), rides the request
    through the engine/bank stage spans, and closes with the response —
    its id echoed in ``X-Request-Id``/``traceparent`` and attached as an
    exemplar on the request-latency bucket it landed in, so a histogram
    spike resolves to one retrievable trace. The root ends at the
    handler's return; a tensor answer's trace is published once its body
    has been written (the ``send`` span), every other trace there and
    then. Single event-loop thread:
    plain dict/int mutation is safe. Counter keys come from the matched
    route TEMPLATE (a bounded set) — keying on raw paths would let a
    scanner probing random URLs grow the dict without bound."""
    stats = request.app["stats"]
    resource = getattr(request.match_info.route, "resource", None)
    canonical = getattr(resource, "canonical", None)
    if canonical is None:
        kind = "other"  # unmatched route (404 scanners land here)
    elif canonical.endswith("/anomaly/prediction"):
        kind = "anomaly"
    else:
        kind = canonical.rsplit("/", 1)[-1] or "/"
    # multi-worker serving: stats["lock"] exists only when the worker
    # pool installed it (workers > 1) — the default path stays the
    # lock-free single-loop mutation it always was
    lock = stats.get("lock") or _NO_LOCK
    # which worker loop parsed this request (server/workers.py tags each
    # worker app); absent (None) outside pool mode — no per-worker
    # series render, the stability contract's default-off rule
    worker = getattr(request.app, "gordo_worker", None)
    enc = None
    with lock:
        stats["requests"][kind] = stats["requests"].get(kind, 0) + 1
        if worker is not None:
            w = stats["workers"]
            w[worker] = w.get(worker, 0) + 1
        if request.method == "POST" and kind in ("prediction", "anomaly", "ingest"):
            # per-encoding data-plane accounting (stability contract:
            # gordo_server_requests_total{encoding} + request_bytes_total):
            # which wire format the fleet's clients actually negotiate, and
            # the bytes each moves — the numbers the bytes-per-row
            # dashboards read. ONE classification
            # rule shared with the scoring handlers (utils/wire.py), so the
            # metrics can never disagree with the path a request took.
            from gordo_components_tpu.utils.wire import encoding_of

            enc = encoding_of(request.content_type)
            wire = stats["wire"]
            wire["requests"][enc] = wire["requests"].get(enc, 0) + 1
            wire["bytes"][enc] = (
                wire["bytes"].get(enc, 0) + (request.content_length or 0)
            )
        hist = stats["latency"].get(kind)
        if hist is None:
            hist = stats["latency"][kind] = LatencyHistogram()
    # bounded: a hostile header must not become an unbounded log/label blob
    rid = request.headers.get("X-Gordo-Request-Id", "")[:128] or (
        f"srv-{next(_RID_SEQ):x}"
    )
    request["request_id"] = rid
    # per-request time budget (resilience/deadline.py): the client's
    # X-Gordo-Deadline-Ms header, or the operator default
    # (GORDO_DEFAULT_DEADLINE_MS, resolved once at build_app). The
    # engine drops entries whose deadline passes before device dispatch
    # (504). No header + no default is the common case and costs one
    # dict read — held to the <=5% hotloop guard in tests/test_deadline.py
    raw_deadline = request.headers.get(DEADLINE_HEADER)
    deadline_ms = parse_deadline_ms(raw_deadline) if raw_deadline else None
    if deadline_ms is None:
        deadline_ms = request.app.get("default_deadline_ms")
    request["deadline"] = (
        Deadline.after_ms(deadline_ms) if deadline_ms else None
    )
    # QoS identity (qos/classify.py): headers here, possibly overridden
    # by the binary body's __meta__ sidecar in _parse_scoring — the
    # FINAL value on the request is what the ledger attributes below.
    # Untagged traffic gets the shared default instance (no allocation).
    if kind in ("prediction", "anomaly"):
        from gordo_components_tpu.qos.classify import classify_headers

        request["qos"] = classify_headers(request.headers)
    tracer = request.app.get("tracer")
    trace = None
    if tracer is not None:
        trace = tracer.start_trace(
            kind,
            traceparent=request.headers.get("traceparent"),
            request_id=rid,
        )
        if trace is not None:
            request["trace"] = trace
    t0 = time.monotonic()
    status = 500  # a non-HTTP handler crash surfaces as a 500
    counted = False
    resp = None
    try:
        resp = await handler(request)
        status = resp.status
    except web.HTTPException as exc:
        status = exc.status
        _trace_headers(exc.headers, rid, trace)
        if exc.status >= 400:
            with lock:
                stats["errors"] += 1
        raise
    except Exception:
        # a handler crash is a 500; the counter must see exactly the
        # failures an operator most needs to — and the response we build
        # here (instead of re-raising into aiohttp's default handler)
        # still carries the request-id echo, so the one request a client
        # most wants to trace is the one that stays traceable
        with lock:
            stats["errors"] += 1
        counted = True
        logger.exception(
            "unhandled error serving %s %s (rid=%s)",
            request.method, request.path, rid,
        )
        resp = web.json_response(
            {"error": "internal server error", "request_id": rid}, status=500
        )
    finally:
        # errored requests count too: a timeout-then-500 pattern is
        # exactly what a tail-latency histogram exists to surface
        elapsed = time.monotonic() - t0
        with lock:
            hist.record(elapsed)
        # goodput classification (observability/goodput.py): every
        # SCORING request commits its wall time + attributed device time
        # to the ledger with its final outcome — 504s are expired work,
        # other >=400s (and non-finite scores behind a 200) wasted work.
        # One dict read when disabled (GORDO_SLO=0 -> no ledger at all).
        # A cancellation (client disconnect, or a hedge win cancelling
        # the losing replica's request — PR 4's NORMAL operation) is not
        # a server failure: it must not classify as a 500 and burn the
        # availability budget, so it skips the ledger entirely.
        if kind in ("prediction", "anomaly") and not isinstance(
            sys.exc_info()[1], asyncio.CancelledError
        ):
            ledger = request.app.get("goodput")
            if ledger is not None:
                # per-class attribution: the tenant label is the
                # cardinality-BOUNDED one (known tenants + default +
                # "other") — stamped by admission when it ran, derived
                # here otherwise, never the raw header string
                qos = request.get("qos")
                tenant_label = request.get("qos_label")
                if qos is not None and tenant_label is None:
                    adm = request.app.get("qos_admission")
                    tenant_label = qos.label_tenant(
                        adm.known_tenants if adm is not None else None
                    )
                # under the pool, finish_request callers multiply (one
                # per worker loop) — the ledger's single-writer cell
                # contract is restored by the same stats lock
                with lock:
                    ledger.finish_request(
                        status=status,
                        elapsed_s=elapsed,
                        device_s=request.get("device_s", 0.0),
                        scores_finite=request.get("scores_finite", True),
                        tenant=tenant_label or "default",
                        qos_class=(
                            qos.qos_class if qos is not None else "interactive"
                        ),
                    )
        if trace is not None:
            # the root ends here, at the handler's return, as the latency
            # histogram does
            trace.finish(error=status >= 400, publish=False, status=status)
            if isinstance(getattr(resp, "body", None), TensorBody):
                # a tensor answer is written after this returns, by
                # aiohttp's finish_response in THIS task (the body's
                # `send` span): the trace is published when the task
                # ends, whether the write completed, failed or never began
                asyncio.current_task().add_done_callback(
                    lambda _task: _publish_trace(
                        trace, stats, lock, kind, hist, elapsed
                    )
                )
            else:
                _publish_trace(trace, stats, lock, kind, hist, elapsed)
        logger.debug(
            "access rid=%s trace=%s %s %s %d %.1fms",
            rid, trace.trace_id if trace is not None else "-",
            request.method, request.path, status, elapsed * 1e3,
        )
    _trace_headers(resp.headers, rid, trace)
    if not counted and resp.status >= 400:
        with lock:
            stats["errors"] += 1
    if enc is not None:
        # the answer's bytes, under the REQUEST's encoding (a tensor
        # request is answered in tensor frames; json and parquet ones in
        # JSON), counted as the response is handed to its connection:
        # all of them, and those the connection will write from the
        # arrays' own memory (a tensor answer's payloads, views.TensorBody)
        body = getattr(resp, "body", None)
        size = getattr(body, "size", None)
        if size is None:
            size = len(body) if body else 0
        by_reference = getattr(body, "by_reference", 0)
        with lock:
            wire = stats["wire"]
            wire["response_bytes"][enc] = (
                wire["response_bytes"].get(enc, 0) + size
            )
            wire["response_bytes_by_reference"][enc] = (
                wire["response_bytes_by_reference"].get(enc, 0) + by_reference
            )
    return resp


def _server_collector(app: web.Application):
    """Read-through exposition of the middleware's stats dict: the scrape
    endpoint reads the same integers /stats reports, so they cannot
    drift."""

    def collect():
        stats = app["stats"]
        yield (
            "gordo_server_uptime_seconds", "gauge",
            "Seconds since server start", {},
            time.time() - stats["started_at"],
        )
        for kind, n in stats["requests"].items():
            yield (
                "gordo_server_requests_total", "counter",
                "HTTP requests by endpoint kind", {"kind": kind}, n,
            )
        yield (
            "gordo_server_errors_total", "counter",
            "HTTP responses with status >= 400", {}, stats["errors"],
        )
        # the data plane by encoding (stability contract): scoring/ingest
        # POSTs and their body bytes, labeled json|parquet|tensor. NOTE
        # for aggregators: these share the requests_total family with the
        # {kind} samples, so a scoring POST appears under BOTH label
        # dimensions — sum() by one label, never over the whole family
        # (docs/observability.md spells this out)
        for enc, n in stats["wire"]["requests"].items():
            yield (
                "gordo_server_requests_total", "counter",
                "Scoring/ingest POSTs by wire encoding "
                "(second label dimension of requests_total)",
                {"encoding": enc}, n,
            )
        for enc, n in stats["wire"]["bytes"].items():
            yield (
                "gordo_server_request_bytes_total", "counter",
                "Scoring/ingest request body bytes by wire encoding",
                {"encoding": enc}, n,
            )
        for enc, n in stats["wire"]["response_bytes"].items():
            yield (
                "gordo_server_response_bytes_total", "counter",
                "Scoring/ingest response body bytes by the request's "
                "wire encoding",
                {"encoding": enc}, n,
            )
        for enc, n in stats["wire"]["response_bytes_by_reference"].items():
            yield (
                "gordo_server_response_bytes_by_reference_total", "counter",
                "Those of the response body bytes written to the "
                "connection from the arrays' own memory (never copied "
                "into a joined body)",
                {"encoding": enc}, n,
            )
        # multi-worker accept-path balance (stability contract): which
        # worker loop parsed each request — a worker starving while the
        # others saturate is the SO_REUSEPORT/acceptor skew this series
        # exists to show. Absent (no samples) outside pool mode.
        for worker, n in sorted(stats.get("workers", {}).items()):
            yield (
                "gordo_server_worker_requests_total", "counter",
                "HTTP requests parsed per worker event loop",
                {"worker": worker}, n,
            )
        # local zero-copy transport counters (utils/shm_ring.py installs
        # the cell when GORDO_SHM_RING arms the ring; absent otherwise)
        shm = stats.get("shm")
        if shm is not None:
            yield (
                "gordo_shm_requests_total", "counter",
                "Scoring requests served over the shared-memory ring",
                {}, shm["requests"],
            )
            yield (
                "gordo_shm_errors_total", "counter",
                "Shared-memory ring requests answered with an error "
                "status", {}, shm["errors"],
            )
        for kind, hist in stats["latency"].items():
            yield (
                "gordo_server_request_seconds", "histogram",
                "Service time by endpoint kind", {"kind": kind}, hist,
            )
        collection = app.get("collection")
        if collection is not None:
            yield (
                "gordo_server_models", "gauge",
                "Models loaded in the collection", {},
                len(collection.models),
            )
            # corrupt-artifact visibility (the healthy-subset fallback
            # used to be invisible to operators): total failed load
            # attempts (counter; nonzero rate = an artifact is STILL
            # failing every refresh) + the current failed set's size
            yield (
                "gordo_models_load_failed_total", "counter",
                "Artifact load attempts that failed (corrupt/mid-write)",
                {}, collection.load_failed_total,
            )
            yield (
                "gordo_models_load_failed", "gauge",
                "Artifacts failing to load as of the latest scan", {},
                len(collection.load_failures),
            )
        quarantine = app.get("quarantine")
        if quarantine is not None:
            yield (
                "gordo_quarantined_models", "gauge",
                "Models evicted from routing by the scoring-failure "
                "breaker (410 until cleared)", {}, len(quarantine),
            )

    return collect


def _hbm_collector():
    """Device HBM usage as gauges, read fresh per scrape — the same
    numbers ``utils/profiling.device_memory_stats`` records into build
    metadata, republished live so memory headroom is scrapeable."""
    from gordo_components_tpu.utils.profiling import device_memory_stats

    def collect():
        for dev, st in device_memory_stats().items():
            for key in ("bytes_in_use", "bytes_limit", "peak_bytes_in_use"):
                if key in st:
                    yield (
                        f"gordo_device_hbm_{key}", "gauge",
                        "Per-device HBM memory (bytes)", {"device": dev},
                        st[key],
                    )

    return collect


def build_app(
    model_dir: str,
    target_name: Optional[str] = None,
    use_bank: Optional[bool] = None,
    bank_flush_ms: float = 0.0,
    bank_max_batch: int = 64,
    bank_max_queue: Optional[int] = None,
    devices: Optional[int] = None,
    quarantine_threshold: Optional[int] = None,
    bank_inflight: Optional[int] = None,
    arena_max_mb: Optional[float] = None,
    bank_dtype: Optional[str] = None,
    bank_kernel: Optional[str] = None,
    clock=None,
) -> web.Application:
    """App factory: loads the artifact(s) under ``model_dir`` once.

    When ``use_bank`` (default: env ``GORDO_SERVER_BANK`` != "0"), every
    bankable model is additionally stacked into an HBM-resident
    :class:`ModelBank` and requests are continuously batched through it;
    non-bankable models keep the per-model scoring path.

    ``devices`` (default: env ``GORDO_SERVER_DEVICES``; 0/unset = all
    available when >1, else single-device) shards the bank over a
    ``models``-axis mesh so a multi-chip server slice holds each model
    once and routes requests to the owning chip — the layout the
    generated manifests' ``server_devices`` request assumes.

    Hot-path pipeline knobs (docs/operations.md "Hot-path pipeline &
    tuning"): ``bank_inflight`` (env ``GORDO_BANK_INFLIGHT``) bounds how
    many bucket groups ``score_many`` keeps in flight on the device;
    ``arena_max_mb`` (env ``GORDO_ARENA_MAX_MB``) bounds the
    padded-buffer arena. The persistent XLA compilation cache is placed
    (``utils.profiling.resolve_compile_cache``) before the bank's bucket
    programs build, so a restarted replica re-warms from disk instead of
    recompiling.

    ``clock`` is the wall-time seam (replay/clock.py): the streaming
    plane's lateness/staleness accounting and the SLO tracker's window
    aging read it, so the replay harness can compress event time
    without distorting their semantics. Default (None) is the real
    clock — production never passes this.
    """
    def env_int(
        name: str, default: Optional[str] = None, hint: str = ""
    ) -> Optional[int]:
        """Integer env knob with an actionable error: these deploy to
        every replica, and a bare int() traceback would crashloop the
        fleet with no hint which knob is malformed."""
        raw = os.environ.get(name, default)
        if raw is None:
            return None
        try:
            return int(raw)
        except ValueError:
            raise ValueError(
                f"{name} must be an integer, got {raw!r}"
                + (f" ({hint})" if hint else "")
            ) from None

    # chaos/fault config: arms any GORDO_FAULTS sites before the first
    # artifact load / bucket compile can hit them; no-op when unset
    configure_from_env()
    # persistent XLA compilation cache (same knob the builder CLI wires):
    # armed BEFORE the bank compiles its bucket programs, so a restarted
    # or rolling-deployed replica loads them from the shared volume
    # instead of stalling its first requests on recompiles
    from gordo_components_tpu.utils.profiling import resolve_compile_cache

    try:
        resolve_compile_cache()
    except OSError:
        logger.warning(
            "could not create the persistent compilation cache directory; "
            "serving continues without it",
            exc_info=True,
        )
    if use_bank is None:
        use_bank = os.environ.get("GORDO_SERVER_BANK", "1") != "0"
    if devices is None:
        devices = env_int(
            "GORDO_SERVER_DEVICES", "0", hint="0/unset = all available devices"
        )
    mesh = None
    if use_bank and devices != 1:
        import jax

        from gordo_components_tpu.parallel.mesh import fleet_mesh

        avail = len(jax.devices())
        want = avail if devices in (0, -1) else min(devices, avail)
        if devices > avail:
            logger.warning(
                "GORDO_SERVER_DEVICES=%d but only %d device(s) present; "
                "sharding the bank over %d",
                devices, avail, want,
            )
        if want > 1:
            mesh = fleet_mesh(want)
    app = web.Application(
        client_max_size=CLIENT_MAX_SIZE,
        middlewares=[_chaos_transport_middleware, _stats_middleware],
    )
    # the wall-time seam: every component whose semantics are defined in
    # wall time (streaming lateness/staleness, SLO windows) reads THIS
    # clock, so replay can swap in a compressed timeline app-wide
    from gordo_components_tpu.replay.clock import SYSTEM_CLOCK

    app["clock"] = clock if clock is not None else SYSTEM_CLOCK
    app["stats"] = {
        "started_at": time.time(),
        "requests": {},
        "errors": 0,
        "latency": {},
        "exemplars": {},
        # per-encoding data-plane counters (json|parquet|tensor): scoring
        # /ingest POST counts + request body bytes, fed by the middleware
        # and, once a response is handed over, its body bytes and how
        # many of them the connection writes by reference
        "wire": {
            "requests": {},
            "bytes": {},
            "response_bytes": {},
            "response_bytes_by_reference": {},
        },
        # per-worker request counters (server/workers.py tags each worker
        # loop's app): empty — and emitting no series — outside pool mode
        "workers": {},
    }
    # operator default request budget (ms): applied by the middleware to
    # every request that carries no X-Gordo-Deadline-Ms header; None
    # (unset) keeps the pre-deadline behavior of never expiring
    app["default_deadline_ms"] = default_deadline_ms()
    # per-app request tracer (observability/tracing.py): the middleware
    # opens a trace per request, the engine/bank record stage spans into
    # it, and ``GET .../traces`` serves the ring + slow reservoir.
    # ``GORDO_TRACE_SAMPLE=0`` disables tracing entirely (start_trace
    # returns None and every call site skips on that one check)
    app["tracer"] = Tracer()
    # per-app metrics registry (observability/): the bank router and the
    # batching engine record per-shard/per-bucket series here; ``GET
    # .../metrics`` renders it as Prometheus text and ``GET .../stats``
    # embeds the same registry's JSON snapshot — one source, two views.
    # Per-app (not process-global) so test suites building many apps in
    # one process don't bleed series into each other.
    registry = MetricsRegistry()
    app["metrics"] = registry
    registry.collector(_server_collector(app), key="server")
    registry.collector(_hbm_collector(), key="hbm")
    # goodput ledger + SLO burn-rate tracker (observability/goodput.py,
    # observability/slo.py): the middleware classifies every scoring
    # request's outcome, the engine/bank feed stage + device-window
    # seconds, and GET .../slo serves the multi-window burn rates the
    # same registry renders as gordo_slo_burn_rate{objective,window}.
    # GORDO_SLO=0 disables the whole plane (no ledger object exists; the
    # call sites pay one None check — the hot-loop guard's contract)
    ledger = GoodputLedger.from_env(registry)
    app["goodput"] = ledger
    if ledger is not None:
        # SLO window ages ride the seam: under replay a "5m" burn
        # window spans 5 replayed minutes, not 5 real ones
        app["slo"] = SLOTracker(
            ledger, registry=registry, clock=app["clock"].monotonic
        )
    # multi-tenant QoS admission (qos/admission.py): per-tenant token
    # buckets + per-class shed thresholds in front of the engine, wired
    # to the SLO tracker's per-class fast-window burn so overload sheds
    # the class already burning budget fastest. Always constructed —
    # with no GORDO_QOS_TENANTS it is default-open and the scoring path
    # pays one depth comparison per request.
    from gordo_components_tpu.qos.admission import AdmissionController

    admission = AdmissionController.from_env()
    app["qos_admission"] = admission
    admission.install_collector(registry)
    slo_tracker = app.get("slo")
    if slo_tracker is not None and hasattr(slo_tracker, "class_burn"):
        admission.burn_for = slo_tracker.class_burn
    # access-heat accountant + device-cost attribution (observability/
    # heat.py, cost.py): heat is APP-level state — every bank generation
    # feeds the same accountant, so the decayed per-member history
    # survives /reload and rebalance swaps; cost joins the bank's static
    # FLOPs table to the ledger's measured device seconds on a sampling
    # cadence. GORDO_HEAT=0 / GORDO_COST=0 disable each plane (the
    # object is None; the bank pays one None check — the hot-loop
    # guard's contract). Both decay/sample on the replay-aware clock.
    from gordo_components_tpu.observability.cost import cost_from_env
    from gordo_components_tpu.observability.heat import heat_from_env

    app["heat"] = heat_from_env(registry, clock=app["clock"])
    app["cost"] = cost_from_env(
        ledger, lambda: app.get("bank"), registry=registry, clock=app["clock"]
    )
    # multi-host serving mesh (parallel/distributed.py): with
    # GORDO_MESH_REPLICA_ID/GORDO_MESH_REPLICAS set, this process is one
    # replica of a fleet mesh and loads ONLY its deterministic member
    # partition from the (typically shared) artifact dir — watchman's
    # routing table points clients at the owning replica, and the mesh
    # acquire/release endpoints (views.py) move members between replicas
    # live. Unset (the default): unpartitioned, zero new code runs.
    from gordo_components_tpu.parallel.distributed import bootstrap_serving_mesh
    from gordo_components_tpu.server.model_io import scan_artifacts

    mesh_identity = bootstrap_serving_mesh()
    owned = None
    if mesh_identity is not None:
        roster = sorted(scan_artifacts(model_dir, target_name))
        owned = mesh_identity.partition(roster)
        logger.info(
            "mesh replica %d/%d owns %d of %d member(s)",
            mesh_identity.replica_id, mesh_identity.replica_count,
            len(owned), len(roster),
        )
    app["mesh"] = mesh_identity
    # flight recorder (docs/observability.md "Flight recorder"): the
    # structured event log is ALWAYS on — state transitions are rare
    # (swaps, reloads, quarantines), so one locked deque append per
    # transition is noise and the timeline is there when the incident
    # hits. The metric history store is GORDO_HISTORY-gated and None
    # when off; call sites pay one `is None` check (the disabled
    # contract the hot-loop guard enforces).
    from gordo_components_tpu.observability.events import EventLog
    from gordo_components_tpu.observability.timeseries import history_from_env

    replica_name = (
        f"replica-{mesh_identity.replica_id}" if mesh_identity is not None else None
    )
    events = EventLog(clock=app["clock"], replica=replica_name)
    events.attach_registry(registry)
    app["events"] = events
    history = history_from_env(registry, clock=app["clock"])
    app["history"] = history
    if history is not None:

        async def _start_history_sampler(app: web.Application) -> None:
            store = app["history"]
            store.sample()  # boot baseline: rates start on the 2nd pass

            async def _tick():
                # cadence in seam seconds, like the SLO sampler: a replay
                # clock compresses the real sleep so samples land every
                # interval_s of REPLAYED time
                real_sleep = store.interval_s / max(1.0, app["clock"].timescale)
                while True:
                    await asyncio.sleep(real_sleep)
                    store.sample()

            app["history_sampler"] = asyncio.get_running_loop().create_task(_tick())

        async def _stop_history_sampler(app: web.Application) -> None:
            import contextlib

            task = app.get("history_sampler")
            if task is not None:
                task.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await task

        app.on_startup.append(_start_history_sampler)
        app.on_cleanup.append(_stop_history_sampler)

    # fault fires land on the timeline (armed sites only — the disarmed
    # hot path never reaches the listener). Process-global seam, so the
    # most recently built app owns it; uninstall on cleanup only if it
    # is still ours (many short-lived apps per test process)
    from gordo_components_tpu.resilience.faults import set_fire_listener

    def _on_fault_fire(site: str, spec) -> None:
        events.emit(
            "fault.fired",
            severity="warning",
            generation=app.get("bank_generation"),
            site=site,
            fired=spec.fired,
        )

    async def _install_fault_listener(app: web.Application) -> None:
        set_fire_listener(_on_fault_fire)

    async def _uninstall_fault_listener(app: web.Application) -> None:
        from gordo_components_tpu.resilience import faults as _faults

        if _faults._FIRE_LISTENER is _on_fault_fire:
            set_fire_listener(None)

    app.on_startup.append(_install_fault_listener)
    app.on_cleanup.append(_uninstall_fault_listener)
    collection = ModelCollection(model_dir, target_name=target_name, owned=owned)
    app["collection"] = collection
    # per-model scoring-failure breaker (resilience/quarantine.py): a
    # model that keeps failing or emitting NaN is evicted from routing
    # (410 + reason) instead of crash-looping requests; /healthz reports
    # the tri-state (ok/degraded/unhealthy) over quarantine + load state
    if quarantine_threshold is None:
        from gordo_components_tpu.resilience.quarantine import DEFAULT_THRESHOLD

        quarantine_threshold = env_int(
            "GORDO_QUARANTINE_THRESHOLD",
            str(DEFAULT_THRESHOLD),
            hint="consecutive scoring failures before eviction; <=0 disables",
        )
    app["quarantine"] = QuarantineSet(threshold=quarantine_threshold)
    app["bank_enabled"] = use_bank
    if bank_max_queue is None and os.environ.get("GORDO_BANK_MAX_QUEUE"):
        # operator backpressure knob: how deep the scoring queue may grow
        # before requests shed with 429 (default 8 * max_batch)
        bank_max_queue = env_int("GORDO_BANK_MAX_QUEUE")
    app["bank_config"] = {
        "max_batch": bank_max_batch,
        "flush_ms": bank_flush_ms,
        "max_queue": bank_max_queue,
        # pipeline knobs, remembered so /reload rebuilds the bank with
        # the same window/arena budget the app booted with (None = the
        # env/default resolution inside ModelBank)
        "inflight": bank_inflight,
        "arena_max_mb": arena_max_mb,
        # precision/capacity knobs (docs/operations.md "Precision &
        # capacity tuning"): storage dtype for the stacked weights (env
        # GORDO_BANK_DTYPE) and the banked-epilogue dispatch mode (env
        # GORDO_BANK_KERNEL) — remembered so /reload rebuilds the bank
        # at the same precision the app booted with
        "bank_dtype": bank_dtype,
        "bank_kernel": bank_kernel,
    }
    app["bank_mesh"] = mesh  # reload (views.py) rebuilds under the same mesh
    if use_bank:
        bank = ModelBank.from_models(
            collection.models,
            mesh=mesh,
            registry=registry,
            inflight=bank_inflight,
            arena_max_mb=arena_max_mb,
            bank_dtype=bank_dtype,
            bank_kernel=bank_kernel,
            ledger=ledger,
            heat=app["heat"],
        )
        # expose the bank even when nothing banked: /models reports the
        # coverage (banked vs per-model fallback, with reasons)
        app["bank"] = bank
        # store the RESOLVED precision/kernel, not the requested (often
        # None) values: a /reload must rebuild at what the app actually
        # booted with, even if the env changed underneath it since
        app["bank_config"]["bank_dtype"] = bank.bank_dtype
        app["bank_config"]["bank_kernel"] = bank.kernel_mode
        # placement control plane (placement/): GET /placement and
        # POST /rebalance work in every mode; GORDO_REBALANCE=auto adds
        # the background evaluator. Generation 0 is the boot bank; every
        # applied swap (rebalance or /reload) bumps it.
        from gordo_components_tpu.placement.controller import (
            PlacementController,
        )

        app["bank_generation"] = 0
        app["placement"] = PlacementController(app)

        async def _start_placement(app: web.Application) -> None:
            app["placement"].start()

        async def _stop_placement(app: web.Application) -> None:
            await app["placement"].stop()

        app.on_startup.append(_start_placement)
        app.on_cleanup.append(_stop_placement)
        if len(bank):

            async def _start_engine(app: web.Application) -> None:
                engine = BatchingEngine(
                    bank,
                    max_batch=bank_max_batch,
                    flush_ms=bank_flush_ms,
                    max_queue=bank_max_queue,
                    # present only under the worker pool: serializes this
                    # engine's bank dispatches with the per-worker engines
                    dispatch_lock=app.get("bank_dispatch_lock"),
                )
                engine.start()
                app["bank_engine"] = engine
                # pre-compile scoring programs off the request path so the
                # first request doesn't pay the XLA compile — in the
                # BACKGROUND: awaiting here would hold the port closed for
                # the whole compile loop and fail readiness probes on
                # large fleets
                if os.environ.get("GORDO_SERVER_WARMUP", "1") != "0":
                    fut = asyncio.get_running_loop().run_in_executor(
                        None, bank.warmup
                    )
                    # a warm-up compile failure is not a warning: it stays
                    # in the future, where /healthz reads it (unhealthy)
                    fut.add_done_callback(_log_warmup_failure)
                    app["warmup_future"] = fut

            app.on_startup.append(_start_engine)

    # streaming ingestion & online adaptation plane (streaming/):
    # DEFAULT OFF (GORDO_STREAM=0) — the scoring hot path is untouched
    # and no gordo_stream_*/gordo_drift_* series appear (the contract
    # tests/test_streaming.py's hot-loop guard holds). When enabled, the
    # server accumulates fresh windows via POST .../{target}/ingest,
    # detects drift (GET .../drift), and recalibrates/refits through the
    # zero-downtime swap; GORDO_STREAM_ADAPT=auto arms the background loop
    if os.environ.get("GORDO_STREAM", "0") not in ("0", "", "false"):
        from gordo_components_tpu.streaming import StreamingPlane

        app["stream"] = StreamingPlane(app)

        async def _start_stream(app: web.Application) -> None:
            app["stream"].start()

        async def _stop_stream(app: web.Application) -> None:
            await app["stream"].stop()

        app.on_startup.append(_start_stream)
        app.on_cleanup.append(_stop_stream)

    if ledger is not None:
        # background SLO sampling cadence: the tracker also samples
        # lazily on reads, but a replica nobody is scraping must still
        # age its windows so the first scrape after an incident sees the
        # burn, not a flat line ending at the last visitor
        async def _start_slo_sampler(app: web.Application) -> None:
            tracker = app["slo"]
            tracker.sample(force=True)  # boot baseline sample

            async def _tick():
                # cadence in seam seconds: a replay clock compresses
                # the real sleep so samples land every
                # sample_interval_s of REPLAYED time
                real_sleep = tracker.sample_interval_s / max(
                    1.0, app["clock"].timescale
                )
                while True:
                    await asyncio.sleep(real_sleep)
                    tracker.sample()

            app["slo_sampler"] = asyncio.get_running_loop().create_task(_tick())

        app.on_startup.append(_start_slo_sampler)

        async def _stop_slo_sampler(app: web.Application) -> None:
            import contextlib

            task = app.get("slo_sampler")
            if task is not None:
                task.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await task

        app.on_cleanup.append(_stop_slo_sampler)

    async def _stop_engine(app: web.Application) -> None:
        engine = app.get("bank_engine")
        if engine is not None:
            await engine.stop()
        fut = app.get("warmup_future")
        if fut is not None and not fut.done():
            # executor jobs can't be interrupted; just don't tear the app
            # down from under a still-running compile
            import contextlib

            with contextlib.suppress(Exception):
                await fut
        bank = app.get("bank")
        if bank is not None:
            bank.release()  # the app may outlive its clean-up; its device memory must not

    app.on_cleanup.append(_stop_engine)
    app.add_routes(routes)
    return app


def _log_warmup_failure(fut: "asyncio.Future") -> None:
    if not fut.cancelled() and fut.exception() is not None:
        logger.error(
            "Model bank warm-up FAILED: the bucket programs requests "
            "dispatch did not compile; /healthz reports unhealthy",
            exc_info=fut.exception(),
        )


def run_server(
    model_dir: str,
    host: str = "0.0.0.0",
    port: int = 5555,
    target_name: Optional[str] = None,
    devices: Optional[int] = None,
    workers: Optional[int] = None,
    uds_path: Optional[str] = None,
    shm_ring: Optional[str] = None,
) -> None:
    """Blocking server entrypoint (reference: ``run_server`` /
    ``Dockerfile-ModelServer`` CMD).

    Saturation knobs (docs/operations.md "Saturating the serving
    plane"): ``workers`` / ``GORDO_SERVER_WORKERS`` runs N parse loops
    behind one accept path (server/workers.py); ``uds_path`` /
    ``GORDO_UDS`` adds a Unix-domain-socket listener speaking the same
    HTTP surface; ``shm_ring`` / ``GORDO_SHM_RING`` arms the
    shared-memory scoring ring for co-located producers
    (utils/shm_ring.py). All default OFF: with none set, this is the
    exact single-loop ``web.run_app`` serving it always was.
    """
    from gordo_components_tpu.server.workers import ServerPool, resolve_workers

    workers = resolve_workers(workers)
    if uds_path is None:
        uds_path = os.environ.get("GORDO_UDS") or None
    if shm_ring is None:
        shm_ring = os.environ.get("GORDO_SHM_RING") or None
    app = build_app(model_dir, target_name=target_name, devices=devices)
    logger.info(
        "Serving %d model(s) on %s:%d", len(app["collection"].models), host, port
    )
    if workers == 1 and not uds_path and not shm_ring:
        web.run_app(app, host=host, port=port)
        return
    pool = ServerPool(
        app, host=host, port=port, workers=workers,
        uds_path=uds_path, shm_ring=shm_ring,
    )
    pool.start()
    try:
        pool.wait()
    finally:
        pool.stop()


__all__ = ["build_app", "run_server", "ModelCollection", "ModelBank", "BatchingEngine"]
