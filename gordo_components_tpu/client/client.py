"""Bulk prediction client.

Reference parity: ``Client`` (gordo_components/client/client.py, unverified;
SURVEY.md §2 "client", §3.3): discover the project's endpoints (watchman or
the server's collection listing), rebuild each machine's dataset config from
metadata, chunk the requested time range, POST batches with bounded
concurrency (the THROUGHPUT HOT LOOP), and optionally forward results to a
prediction store.
"""

import asyncio
import contextlib
import functools
import hashlib
import itertools
import json
import logging
import random
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import aiohttp
import numpy as np
import pandas as pd

from gordo_components_tpu.client.io import (
    fetch_json,
    fetch_json_hedged,
    fetch_metadata_all,
)
from gordo_components_tpu.observability import get_registry
from gordo_components_tpu.observability.tracing import format_traceparent
from gordo_components_tpu.dataset import get_dataset
from gordo_components_tpu.resilience.deadline import Deadline, DeadlineExceeded
from gordo_components_tpu.resilience.retry_budget import RetryBudget
from gordo_components_tpu.server.utils import dict_to_frame
from gordo_components_tpu.utils import parquet_engine_available
from gordo_components_tpu.utils.encoding import parquet_engine
from gordo_components_tpu.utils.wire import (
    ANOMALY_FRAME_NAMES,
    TENSOR_CONTENT_TYPE,
    pack_frames,
    unpack_frames,
)

logger = logging.getLogger(__name__)

# below this many targets, per-target /metadata GETs beat downloading the
# whole fleet's metadata in one metadata-all response
_PREFETCH_MIN_TARGETS = 8

# latency samples needed before the hedge delay switches from the
# configured initial value to the observed p95
_HEDGE_MIN_SAMPLES = 16


class _LatencyTracker:
    """Bounded record of observed chunk latencies; p95 drives the hedge
    delay so only the slowest ~5% of requests ever pay a duplicate."""

    def __init__(self, maxlen: int = 256):
        self._samples: "deque[float]" = deque(maxlen=maxlen)

    def record(self, seconds: float) -> None:
        self._samples.append(seconds)

    def __len__(self) -> int:
        return len(self._samples)

    def p95(self) -> Optional[float]:
        if not self._samples:
            return None
        ordered = sorted(self._samples)
        return ordered[min(len(ordered) - 1, int(0.95 * len(ordered)))]


@dataclass
class PredictionResult:
    """Per-machine outcome of a bulk run (reference: ``PredictionResult``)."""

    name: str
    predictions: Optional[pd.DataFrame]
    error_messages: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.predictions is not None and not self.error_messages


class Client:
    """Score time ranges against every model of a project."""

    def __init__(
        self,
        project: str,
        host: str = "localhost",
        port: int = 5555,
        scheme: str = "http",
        *,
        base_url: Optional[str] = None,
        batch_size: int = 1000,
        parallelism: int = 10,
        forwarder=None,
        use_anomaly: bool = True,
        metadata_fallback_dataset: Optional[Dict[str, Any]] = None,
        use_parquet="auto",
        use_tensor="auto",
        transport: str = "auto",
        uds_path: Optional[str] = None,
        shm_ring: Optional[str] = None,
        retries: int = 3,
        backoff: float = 0.5,
        retry_budget: Optional[RetryBudget] = None,
        retry_budget_ratio: Optional[float] = None,
        deadline_ms: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
        hedge: bool = False,
        replica_urls: Optional[List[str]] = None,
        hedge_delay_init_s: float = 1.0,
        routing_url: Optional[str] = None,
        routing: Optional[Dict[str, Any]] = None,
        routing_refresh_window_s: float = 5.0,
    ):
        self.project = project
        # normalized (no trailing slash) so the hedge target exclusion
        # compares like with like against replica_urls below
        self.base_url = (base_url or f"{scheme}://{host}:{port}").rstrip("/")
        self.batch_size = int(batch_size)
        self.parallelism = int(parallelism)
        self.forwarder = forwarder
        self.use_anomaly = use_anomaly
        self.metadata_fallback_dataset = metadata_fallback_dataset
        # multi-tenant QoS identity (qos/classify.py): stamped on every
        # scoring POST as X-Gordo-Tenant / X-Gordo-Priority and, on the
        # binary/shm paths, in the __meta__ tensor sidecar — proxies may
        # strip custom headers and shm envelopes never had any. The
        # class also picks the client's own overload posture below
        # (retry ratio, hedging): a best-effort client must not amplify
        # the very overload that is shedding it.
        from gordo_components_tpu.qos.classify import (
            normalize_class,
            normalize_tenant,
        )

        self.tenant = normalize_tenant(tenant) if tenant else None
        self.qos_class = (
            normalize_class(priority) if priority else "interactive"
        )
        # transport citizenship knobs (previously hardcoded in io.py):
        # bounded retries with decorrelated-jitter backoff, all gated by
        # ONE shared token-bucket retry budget — a thousand chunks
        # failing together can re-offer at most ~ratio x the offered
        # load, not 3x (the synchronized-retry overload recipe)
        self.retries = int(retries)
        self.backoff = float(backoff)
        if retry_budget_ratio is None:
            # per-class retry appetite: lower classes re-offer less of
            # their failed load — they are the first to be shed, so
            # their retries are the likeliest to be pure overload fuel
            retry_budget_ratio = {
                "batch": 0.05, "best_effort": 0.02
            }.get(self.qos_class, 0.1)
        self.retry_budget = (
            retry_budget
            if retry_budget is not None
            else RetryBudget(ratio=retry_budget_ratio)
        )
        # per-chunk time budget (ms), stamped on every scoring POST as
        # X-Gordo-Deadline-Ms so a saturated server drops the work once
        # this client has given up; also bounds the dataset build
        self.deadline_ms = None if deadline_ms is None else float(deadline_ms)
        # tail-latency hedging: after a p95-derived delay, re-issue a
        # slow chunk POST to one other replica (from watchman's target
        # list — see replicas_from_watchman) and take the first success.
        # best_effort NEVER hedges: a hedge is a second copy of the load
        # the fleet is most willing to shed, and tail latency is not
        # part of that class's contract anyway.
        self.hedge = bool(hedge) and self.qos_class != "best_effort"
        self.replica_urls = [
            u.rstrip("/") for u in (replica_urls or []) if u.rstrip("/")
        ]
        self.hedge_delay_init_s = float(hedge_delay_init_s)
        self._latency = _LatencyTracker()
        self._hedge_stats: Dict[str, int] = {"hedges": 0, "hedge_wins": 0}
        self._hedge_rng = random.Random()
        # partition-aware fan-out (multi-host serving mesh): with a
        # routing table — fetched from watchman's GET /routing when
        # ``routing_url`` names the watchman base, or passed verbatim as
        # ``routing`` — every member's chunks POST to the replica that
        # OWNS it instead of one base URL, and hedges/fallbacks skip
        # replicas the table marks degraded/unreachable (or that
        # quarantine the member). Neither set: classic single-URL client,
        # zero new code on the chunk path.
        self.routing_url = (routing_url or "").rstrip("/") or None
        self._routing: Optional[Dict[str, Any]] = None
        self._routing_etag: Optional[str] = None
        if routing is not None:
            self._install_routing(routing)
        self._fanout_stats: Dict[str, int] = {
            "routed_chunks": 0, "routing_refreshes": 0, "reroutes": 0,
            "refreshes_throttled": 0,
        }
        # stale-table forced-refresh rate limit: ONE forced /routing
        # refetch per member per window. During a migration storm with a
        # dead replica, every chunk of every displaced member would
        # otherwise force its own refresh — a refresh stampede against
        # watchman exactly when it is busiest. Throttled attempts keep
        # their 404 (the bounded-retry contract is per-window, not gone).
        self.routing_refresh_window_s = float(routing_refresh_window_s)
        self._forced_refresh_at: Dict[str, float] = {}
        # request-body encoding for scoring POSTs: "auto" upgrades to
        # parquet when the server advertises it (JSON float-list
        # encode/decode dominates at fleet-backfill scale — the reference's
        # client used parquet for the same reason); True forces parquet,
        # False forces JSON. A mid-run parquet rejection (foreign server)
        # downgrades the rest of an "auto" run to JSON. Normalized here so
        # truthy non-True values (1, "yes") can't get auto-mode downgrade
        # semantics while claiming forced mode.
        if use_parquet not in (True, False, "auto"):
            raise ValueError(
                f"use_parquet must be True, False or 'auto', got {use_parquet!r}"
            )
        self.use_parquet = use_parquet
        self._parquet_active = False
        # framed binary tensor bodies (utils/wire.py) — the preferred
        # encoding when the server advertises application/x-gordo-tensor:
        # it upgrades BOTH wire directions (request rows and the 4x-larger
        # anomaly response), where parquet only ever covered the request.
        # Same negotiation contract as parquet: "auto" upgrades on the
        # advertisement and downgrades for the rest of the run when a
        # foreign server rejects a tensor body that JSON then accepts.
        if use_tensor not in (True, False, "auto"):
            raise ValueError(
                f"use_tensor must be True, False or 'auto', got {use_tensor!r}"
            )
        self.use_tensor = use_tensor
        self._tensor_active = False
        # local zero-copy transport negotiation (server/workers.py +
        # utils/shm_ring.py): "auto" climbs the ladder shm > uds > tcp
        # using the server's /models ``transports`` advertisement, each
        # rung verified LOCALLY (shm attachable, socket path present)
        # before use — a remote server's advertisement never breaks a
        # remote client, it just resolves to tcp. Explicit "uds"/"shm"
        # try exactly that rung and degrade to tcp with a warning
        # (graceful fallback); "tcp" is the classic path untouched.
        if transport not in ("auto", "tcp", "uds", "shm"):
            raise ValueError(
                f"transport must be auto|tcp|uds|shm, got {transport!r}"
            )
        self.transport = transport
        self.uds_path = uds_path
        self.shm_ring = shm_ring
        # resolved per run (predict_async): which rung actually carried
        # the scoring chunks — the demos report this next to rows/s
        self.transport_used = "tcp"
        self._shm_client = None
        self._data_session = None  # UDS session for scoring POSTs
        # sessions retired mid-run by _drop_uds: closed at run end, not
        # at retirement — sibling chunks may still have requests in
        # flight on them, and an immediate close would turn their clean
        # ClientConnectionError into an unhandled "Session is closed"
        self._dead_sessions: List[Any] = []
        # per-encoding wire accounting (gordo_client_request_bytes_total):
        # body bytes out and rows
        # posted for every scoring POST that got a 2xx back
        self._wire_stats: Dict[str, Dict[str, int]] = {}
        self._metadata_all: Dict[str, Any] = {}
        # request-id propagation: every scoring POST carries a unique
        # X-Gordo-Request-Id the server threads through its access log and
        # engine queue, so a slow/failed chunk in a fleet backfill is
        # traceable end to end (client log line <-> server histogram entry)
        self._rid_prefix = uuid.uuid4().hex[:12]
        self._rid_seq = itertools.count(1)
        # streaming-forwarder accounting (ingest_async): rows accepted by
        # the server's window buffers, exposed as
        # gordo_client_ingest_rows_total through the collector below
        self._ingest_stats: Dict[str, int] = {"rows": 0, "chunks": 0}
        # after _rid_prefix: the metric series are labeled by it
        self._register_metrics()

    def _next_request_id(self) -> str:
        return f"{self._rid_prefix}-{next(self._rid_seq):x}"

    def _register_metrics(self) -> None:
        """Read-through exposition of the client's overload-citizenship
        counters in the process registry. Weakref: the process registry
        must not pin a discarded client. Series are labeled by the client's rid
        prefix and registered under a per-instance key, so two clients
        in one process (one per project, or a fresh client per run)
        neither replace each other's collectors nor emit colliding
        unlabeled samples; a discarded client's collector yields
        nothing through the dead weakref."""
        import weakref

        ref = weakref.ref(self)
        labels = {"client": self._rid_prefix}

        def collect():
            c = ref()
            if c is None:
                return
            b = c.retry_budget.snapshot()
            yield (
                "gordo_client_retries_total", "counter",
                "Retries the shared budget admitted", labels,
                b["retries_allowed"],
            )
            yield (
                "gordo_client_retries_denied_total", "counter",
                "Retries refused because the budget was exhausted "
                "(failed fast instead of re-offering load)", labels,
                b["retries_denied"],
            )
            yield (
                "gordo_client_retry_budget_tokens", "gauge",
                "Retry tokens currently banked", labels, b["tokens"],
            )
            yield (
                "gordo_client_hedges_total", "counter",
                "Hedge requests issued (primary slower than the hedge "
                "delay)", labels, c._hedge_stats["hedges"],
            )
            yield (
                "gordo_client_hedge_wins_total", "counter",
                "Hedged requests answered by the hedge replica first",
                labels, c._hedge_stats["hedge_wins"],
            )
            yield (
                "gordo_client_ingest_rows_total", "counter",
                "Stream rows the ingestion forwarder posted and the "
                "server accepted", labels, c._ingest_stats["rows"],
            )
            yield (
                "gordo_client_routed_chunks_total", "counter",
                "Scoring chunks routed to their member's owning replica "
                "via the mesh routing table", labels,
                c._fanout_stats["routed_chunks"],
            )
            yield (
                "gordo_client_routing_refreshes_total", "counter",
                "Routing-table fetches that installed a new table "
                "(200s; 304 not-modified polls excluded)", labels,
                c._fanout_stats["routing_refreshes"],
            )
            yield (
                "gordo_client_reroutes_total", "counter",
                "Chunks re-posted after a stale-table 404 forced a "
                "routing refresh", labels, c._fanout_stats["reroutes"],
            )
            yield (
                "gordo_client_routing_refreshes_throttled_total", "counter",
                "Forced stale-table refreshes suppressed by the "
                "per-member rate limit (refresh-stampede guard)",
                labels, c._fanout_stats["refreshes_throttled"],
            )
            for enc, st in list(c._wire_stats.items()):
                yield (
                    "gordo_client_request_bytes_total", "counter",
                    "Scoring request body bytes posted, by wire encoding",
                    {**labels, "encoding": enc}, st["bytes_out"],
                )

        get_registry().collector(collect, key=f"bulk_client:{self._rid_prefix}")

    def _note_wire(self, encoding: str, bytes_out: int, rows: int) -> None:
        """Count a successfully posted scoring chunk against its wire
        encoding (single event-loop thread: plain dict mutation)."""
        st = self._wire_stats.setdefault(
            encoding, {"posts": 0, "bytes_out": 0, "rows": 0}
        )
        st["posts"] += 1
        st["bytes_out"] += int(bytes_out)
        st["rows"] += int(rows)

    @property
    def wire_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-encoding wire accounting: POSTs, body bytes out, and rows
        for every scoring chunk that succeeded (bytes/row per encoding
        is bytes over rows)."""
        return {enc: dict(st) for enc, st in self._wire_stats.items()}

    @staticmethod
    def replicas_from_watchman(snapshot: Dict[str, Any]) -> List[str]:
        """Replica base URLs from a watchman ``GET /`` snapshot body
        (the ``replicas`` list watchman derives from its scrape
        targets) — the hedging target list, fetched from the component
        that already tracks which replicas exist. Accepts both forms:
        bare URL strings (pre-mesh watchman) and the stamped entry
        objects (``{"url": ..., "routing_version": ..., "status": ...}``)
        the routing plane serves now."""
        out: List[str] = []
        for entry in snapshot.get("replicas") or []:
            if isinstance(entry, dict):
                entry = entry.get("url")
            if isinstance(entry, str) and entry.rstrip("/"):
                out.append(entry.rstrip("/"))
        return out

    # ------------------------------------------------------------------ #
    # partition-aware fan-out (the mesh routing table)
    # ------------------------------------------------------------------ #

    def _install_routing(self, table: Dict[str, Any]) -> None:
        """Validate + index a routing table body (watchman ``GET
        /routing``): member -> owner index, index -> replica entry."""
        if not isinstance(table, dict) or not isinstance(
            table.get("members"), dict
        ):
            raise ValueError(
                "routing table must be a dict with a 'members' map "
                "(watchman GET /routing body)"
            )
        replicas = {
            int(r["replica"]): {**r, "url": str(r["url"]).rstrip("/")}
            for r in table.get("replicas") or []
            if isinstance(r, dict) and "replica" in r and r.get("url")
        }
        self._routing = {
            "version": int(table.get("version", 0)),
            "members": dict(table["members"]),
            # member -> ALL replica indices serving it right now (the
            # table's multi-owner view: mid-migration overlap, or a
            # fully replicated fleet) — the hedge candidate set
            "owners": {
                str(k): [int(i) for i in v]
                for k, v in (table.get("migrating") or {}).items()
                if isinstance(v, (list, tuple))
            },
            "replicas": replicas,
        }

    @property
    def routing_version(self) -> Optional[int]:
        return self._routing["version"] if self._routing else None

    async def _fetch_routing(
        self, session, force: bool = False, member: Optional[str] = None
    ) -> bool:
        """Fetch/refresh the routing table from watchman. ETag-
        conditional: an unchanged table costs a 304 and keeps the local
        index. Returns True when the local table CHANGED. Best-effort —
        a watchman outage downgrades the run to single-URL posting (the
        configured base_url) rather than failing it.

        ``member`` (stale-table callers only) engages the per-member
        forced-refresh rate limit: at most one forced refetch per member
        per ``routing_refresh_window_s``; throttled calls return False
        without touching the network and count
        ``gordo_client_routing_refreshes_throttled_total``."""
        if self.routing_url is None:
            return False
        if force and member is not None:
            now = time.monotonic()
            last = self._forced_refresh_at.get(member)
            if (
                last is not None
                and now - last < self.routing_refresh_window_s
            ):
                self._fanout_stats["refreshes_throttled"] += 1
                return False
            # stamped BEFORE the attempt: a watchman that is down (the
            # storm case) must not be hammered by failed-refresh retries
            self._forced_refresh_at[member] = now
        headers = {}
        if self._routing_etag and not force:
            headers["If-None-Match"] = self._routing_etag
        try:
            async with session.get(
                f"{self.routing_url}/routing",
                params={"refresh": "1"} if force else None,
                headers=headers,
            ) as resp:
                if resp.status == 304:
                    return False
                if resp.status != 200:
                    logger.warning(
                        "routing fetch answered %d; keeping %s",
                        resp.status,
                        "previous table" if self._routing else "single-URL mode",
                    )
                    return False
                body = await resp.json()
                etag = resp.headers.get("ETag")
        except Exception as exc:
            logger.warning(
                "routing fetch from %s failed (%s); %s", self.routing_url,
                exc,
                "keeping previous table" if self._routing
                else "single-URL mode",
            )
            return False
        before = self._routing["version"] if self._routing else None
        try:
            # best-effort by contract: a 200 with an unexpected shape (a
            # proxy's JSON error page, a pre-mesh watchman) must downgrade
            # like any other fetch failure, not abort the scoring run —
            # and must NOT record the ETag, or conditional 304s would pin
            # the client table-less forever while it believes it is polling
            self._install_routing(body)
        except ValueError as exc:
            logger.warning(
                "routing body from %s unusable (%s); %s", self.routing_url,
                exc,
                "keeping previous table" if self._routing
                else "single-URL mode",
            )
            return False
        self._routing_etag = etag
        self._fanout_stats["routing_refreshes"] += 1
        return self._routing["version"] != before

    def _member_base_url(self, target: str) -> Optional[str]:
        """The owning replica's base URL for a member, or None (member
        unknown to the table, owner entry missing, or no table) — the
        caller falls back to the configured base_url, whose server
        answers 404 with the reason if truly nobody serves it."""
        if self._routing is None:
            return None
        idx = self._routing["members"].get(target)
        if idx is None:
            return None
        rep = self._routing["replicas"].get(int(idx))
        return rep["url"] if rep else None

    def _replica_healthy_for(self, rep: Dict[str, Any], target: str) -> bool:
        """Hedge/fallback eligibility from the routing table's stamps: a
        replica marked unreachable, degraded, or unhealthy — or one that
        QUARANTINES this member — must never receive a hedge (the old
        behavior hedged to any other replica, so a hedge could land on
        exactly the sick replica it was escaping)."""
        if not rep.get("reachable", True):
            return False
        if rep.get("status", "ok") not in ("ok",):
            return False
        return target not in (rep.get("quarantined") or ())

    def _connector_limit(self) -> int:
        """Keep-alive pool size for the scoring session. Hedged chunks
        open a SECOND in-flight socket while the primary is still
        running (client/io.py:fetch_json_hedged) — sizing the pool to
        ``parallelism`` alone made hedges queue behind the very sockets
        they were meant to bypass, so the slowest ~5% of chunks paid the
        hedge delay and then waited anyway. ``parallelism * (1 + hedge)``
        lanes plus a little control-plane headroom."""
        lanes = self.parallelism * (2 if self.hedge else 1)
        return max(lanes + 4, 8)

    def _hedge_delay_s(self) -> float:
        """Hedge after the observed p95 (only the slowest ~5% of chunks
        duplicate work); until enough samples exist, the configured
        initial delay applies."""
        if len(self._latency) >= _HEDGE_MIN_SAMPLES:
            p95 = self._latency.p95()
            if p95 is not None:
                return max(p95, 1e-3)
        return self.hedge_delay_init_s

    def _chunk_urls(self, target: str, endpoint: str) -> List[str]:
        """Primary URL plus (hedging only) ONE alternate replica's URL
        for the same path.

        With a routing table the primary is the member's OWNING replica
        (partition-aware fan-out: each chunk goes where the model's
        weights are resident), and the hedge alternate is drawn only
        from replicas the table marks healthy that also serve the member
        — in a partitioned fleet that usually means a mid-migration
        dual owner; a replica that doesn't hold the member, is
        degraded/unreachable, or quarantines it can only lose (or
        mis-404) the hedge."""
        if self._data_session is not None:
            # UDS session: the path is the address (the connector owns
            # the socket); hedging is TCP-replica machinery and a local
            # socket has no replicas — one URL, no hedge
            return [
                f"http://localhost/gordo/v0/{self.project}/{target}/{endpoint}"
            ]
        path = f"gordo/v0/{self.project}/{target}/{endpoint}"
        if self._routing is not None:
            primary = self._member_base_url(target) or self.base_url
            urls = [f"{primary}/{path}"]
            if self.hedge:
                # healthy replicas that actually SERVE this member (the
                # table's multi-owner set: mid-migration overlap, or a
                # replicated fleet) — never the sick-replica or
                # wrong-partition hedge the pre-routing client could
                # issue
                candidates = [
                    rep["url"]
                    for idx in self._routing["owners"].get(target, ())
                    if (rep := self._routing["replicas"].get(idx)) is not None
                    and rep["url"] != primary
                    and self._replica_healthy_for(rep, target)
                ]
                if candidates:
                    urls.append(f"{self._hedge_rng.choice(candidates)}/{path}")
            return urls
        urls = [self._url(target, endpoint)]
        if self.hedge:
            others = [u for u in self.replica_urls if u != self.base_url]
            if others:
                alt = self._hedge_rng.choice(others)
                urls.append(f"{alt}/{path}")
        return urls

    def _trace_headers(self, rid: str) -> Dict[str, str]:
        """Scoring-POST id headers: the gordo request id plus a W3C
        ``traceparent`` whose trace id is DERIVED from the request id
        (md5 — identity, not security), so a client log line and the
        server-side trace are the same identifier family and either one
        recovers the other. The sampled flag is set: a request the
        client bothered to stamp is one the operator wants retrievable
        at ``GET .../traces`` regardless of server head sampling.

        The QoS identity rides here too (when configured): the server's
        middleware classifies every scoring request from these headers,
        so one header pair covers the JSON, parquet, and tensor-over-
        HTTP encodings alike."""
        trace_id = hashlib.md5(rid.encode()).hexdigest()
        headers = {
            "X-Gordo-Request-Id": rid,
            "traceparent": format_traceparent(trace_id, trace_id[:16]),
        }
        if self.tenant:
            headers["X-Gordo-Tenant"] = self.tenant
        if self.qos_class != "interactive":
            headers["X-Gordo-Priority"] = self.qos_class
        return headers

    # ------------------------------------------------------------------ #
    # local zero-copy transports (docs/architecture.md "Serving
    # saturation"): negotiation + the shm scoring path
    # ------------------------------------------------------------------ #

    async def _resolve_transport(self, models_body) -> None:
        """Pick the scoring transport for this run. The ladder (shm >
        uds > tcp) combines local hints (``shm_ring=``/``uds_path=``)
        with the server's ``/models`` ``transports`` advertisement, and
        each rung must prove itself locally — attachable segment,
        present + connectable socket path — before it carries chunks.
        Every failure degrades one rung and logs why; tcp always
        works."""
        import os

        self.transport_used = "tcp"
        self._shm_client = None
        self._data_session = None
        if self.transport == "tcp":
            return
        adv = (models_body or {}).get("transports") or {}
        if self.transport in ("auto", "shm"):
            name = self.shm_ring or adv.get("shm")
            if name:
                try:
                    from gordo_components_tpu.utils.shm_ring import (
                        ShmRingClient,
                    )

                    self._shm_client = ShmRingClient(name)
                    self.transport_used = "shm"
                    logger.info("scoring over shm ring %r", name)
                    return
                except Exception as exc:
                    logger.warning(
                        "shm ring %r not attachable (%s); trying the next "
                        "transport", name, exc,
                    )
            elif self.transport == "shm":
                logger.warning(
                    "transport='shm' but no ring name (pass shm_ring= or "
                    "serve with GORDO_SHM_RING); falling back to tcp"
                )
        if self.transport in ("auto", "uds"):
            path = self.uds_path or adv.get("uds")
            if path and os.path.exists(path):
                try:
                    self._data_session = aiohttp.ClientSession(
                        timeout=aiohttp.ClientTimeout(total=600),
                        connector=aiohttp.UnixConnector(
                            path=path, limit=self._connector_limit()
                        ),
                    )
                    self.transport_used = "uds"
                    logger.info("scoring over unix socket %s", path)
                    return
                except Exception as exc:
                    logger.warning(
                        "unix socket %s not usable (%s); falling back to "
                        "tcp", path, exc,
                    )
            elif self.transport == "uds":
                logger.warning(
                    "transport='uds' but socket path %r does not exist; "
                    "falling back to tcp", path,
                )

    async def _drop_uds(self, exc) -> None:
        """Retire a dead unix-socket session mid-run (idempotent under
        concurrent chunks: first caller wins, the rest see tcp). The
        session object is parked for end-of-run closing — see
        ``_dead_sessions``."""
        s, self._data_session = self._data_session, None
        self.transport_used = "tcp"
        if s is not None:
            logger.warning(
                "unix-socket transport failed mid-run (%s); remaining "
                "chunks go over tcp", exc,
            )
            self._dead_sessions.append(s)

    async def _post_shm(
        self, target: str, endpoint: str, chunk: pd.DataFrame,
        chunk_y: Optional[pd.DataFrame],
        deadline: Optional[Deadline] = None,
    ) -> pd.DataFrame:
        """One chunk over the shared-memory ring: same tensor body, same
        response bytes, no socket. The ring wait runs on an executor
        thread — the event loop keeps pumping the other chunks.

        Same transient-failure citizenship as the HTTP path
        (client/io.py): 408/429/5xx retry on decorrelated jitter
        (honoring a 429 body's ``retry_after_s`` drain estimate as a
        lower bound) through the shared retry budget; other non-200s
        raise ``ValueError`` with the server's error document. The
        chunk's ``deadline`` bounds the whole exchange CLIENT-side (ring
        wait capped at the remaining budget, no retry sleep past
        expiry, ``DeadlineExceeded`` once spent) — the slot envelope
        carries no deadline field, so server-side expiry dropping is
        the one HTTP nicety the shm rung does not replicate."""
        from gordo_components_tpu.resilience.retry_budget import (
            decorrelated_jitter,
        )

        body = await asyncio.get_running_loop().run_in_executor(
            None, self._encode_tensor, chunk, chunk_y
        )
        kind = "anomaly" if endpoint.startswith("anomaly") else "prediction"
        self.retry_budget.note_request()
        retries = max(1, self.retries)
        prev_delay = self.backoff
        for attempt in range(retries):
            if deadline is not None and deadline.expired():
                raise DeadlineExceeded(
                    f"deadline expired before shm attempt {attempt + 1}"
                )
            ring_timeout = 60.0
            if deadline is not None:
                ring_timeout = max(1e-3, min(ring_timeout, deadline.remaining_s()))
            status, resp = await asyncio.get_running_loop().run_in_executor(
                None,
                functools.partial(
                    self._shm_client.request, target, body, kind,
                    timeout=ring_timeout,
                ),
            )
            if status < 400:
                self._note_wire("tensor", len(body), len(chunk))
                return self._decode_tensor_scoring_body(
                    resp, chunk, anomaly=kind == "anomaly"
                )
            if status not in (408, 429) and status < 500:
                break  # genuine request error: retrying cannot help
            if attempt + 1 >= retries or not self.retry_budget.try_spend():
                break
            delay = prev_delay = decorrelated_jitter(
                self.backoff, prev_delay
            )
            if status == 429:
                try:  # the shed response's queue-drain estimate
                    hinted = float(json.loads(resp).get("retry_after_s", 0))
                    delay = max(delay, min(hinted, 60.0))
                except (ValueError, AttributeError):
                    pass
            if deadline is not None:
                # never sleep past our own expiry (same rule as io.py)
                delay = min(delay, deadline.remaining_s())
            await asyncio.sleep(delay)
        raise ValueError(
            f"shm status {status}: {resp[:500].decode('utf-8', 'replace')}"
        )

    # ------------------------------------------------------------------ #

    def _url(self, target: str, endpoint: str) -> str:
        # control-plane lookups follow the routing table too: in a
        # partitioned mesh only the OWNER can answer a member's
        # /metadata (the configured base_url would 404 the other
        # partitions' members)
        base = self._member_base_url(target) or self.base_url
        return f"{base}/gordo/v0/{self.project}/{target}/{endpoint}"

    async def _get_metadata(self, session, target: str) -> Dict[str, Any]:
        meta = self._metadata_all.get(target)
        if meta is not None:
            return meta

        async def fetch():
            body = await fetch_json(
                session,
                self._url(target, "metadata"),
                retries=self.retries,
                backoff=self.backoff,
                retry_budget=self.retry_budget,
            )
            return body.get("endpoint-metadata", {})

        try:
            return await fetch()
        except ValueError as exc:
            # routed 404: the member may have MOVED since our table
            # (stale-table detection, same rule as the scoring path) —
            # one forced refetch, one retry against the new owner
            if self._routing is None or "404" not in str(exc):
                raise
            if not await self._fetch_routing(session, force=True, member=target):
                raise
            logger.warning(
                "routing table was stale (now v%s); refetching metadata "
                "for %s", self.routing_version, target,
            )
            self._fanout_stats["reroutes"] += 1
            return await fetch()

    async def _prefetch_metadata(self, session) -> None:
        """Prefetch every target's metadata in ONE request via the
        collection server's batched control-plane endpoint — at fleet
        scale the per-target ``/metadata`` round-trips otherwise cost N
        requests before any scoring starts. Best-effort with a short
        deadline and shape validation (shared helper, client/io.py):
        foreign servers keep the per-target path.

        Partitioned mesh: ONE metadata-all per replica (each holds only
        its partition's metadata), merged — still O(replicas), not
        O(members), requests."""
        bases = [self.base_url]
        if self._routing is not None:
            routed = [
                rep["url"]
                for rep in self._routing["replicas"].values()
                if rep.get("reachable", True)
            ]
            bases = routed or bases
        bodies = await asyncio.gather(
            *(fetch_metadata_all(session, b, self.project) for b in bases)
        )
        merged: Dict[str, Any] = {}
        for body in bodies:
            if body is None:
                continue
            merged.update(
                {
                    name: entry["endpoint-metadata"]
                    for name, entry in body["targets"].items()
                    if isinstance(entry, dict) and "endpoint-metadata" in entry
                }
            )
        self._metadata_all = merged

    def _dataset_config_from_metadata(self, meta, start, end) -> Dict[str, Any]:
        ds_meta = meta.get("dataset", {})
        config = self.metadata_fallback_dataset or {"type": "RandomDataset"}
        if ds_meta:
            # Tag dicts ({name, asset}) pass through whole: dropping asset
            # would break providers with asset-scoped layouts; row_filter and
            # aggregation must match training or scored rows diverge from
            # what the model saw.
            config = {
                "type": ds_meta.get("type", "TimeSeriesDataset"),
                "tag_list": ds_meta.get("tag_list", []),
                "resolution": ds_meta.get("resolution", "10min"),
                "aggregation_method": ds_meta.get("aggregation_method", "mean"),
                "row_filter": ds_meta.get("row_filter", ""),
                "data_provider": ds_meta.get("data_provider"),
            }
            if ds_meta.get("target_tag_list"):
                config["target_tag_list"] = ds_meta["target_tag_list"]
            if not isinstance(config["data_provider"], dict):
                # only a provider dict can be re-instantiated by the
                # dataset layer; a repr string cannot
                config.pop("data_provider", None)
        return {
            **config,
            "train_start_date": str(start),
            "train_end_date": str(end),
        }

    # ------------------------------------------------------------------ #

    def predict(
        self, start: pd.Timestamp, end: pd.Timestamp, targets: Optional[List[str]] = None
    ) -> List[PredictionResult]:
        """Synchronous entrypoint (reference CLI semantics)."""
        return asyncio.run(self.predict_async(start, end, targets))

    async def predict_async(
        self, start, end, targets: Optional[List[str]] = None
    ) -> List[PredictionResult]:
        timeout = aiohttp.ClientTimeout(total=600)
        sem = asyncio.Semaphore(self.parallelism)
        # keep-alive connections bounded a little above the chunk
        # concurrency: every chunk POST reuses a warm socket instead of
        # paying handshake latency per request. Sized for HEDGES too
        # (_connector_limit): a hedged chunk holds two sockets at once,
        # and a pool pinned to bare parallelism made hedges queue behind
        # the primaries they were escaping.
        connector = aiohttp.TCPConnector(limit=self._connector_limit())
        async with aiohttp.ClientSession(
            timeout=timeout, connector=connector
        ) as session:
            # partition-aware fan-out: learn the routing table BEFORE
            # discovery — in a mesh the configured base_url is one
            # replica and its /models lists only its own partition, so
            # the table (union over the fleet) is the real target roster
            if self.routing_url is not None:
                await self._fetch_routing(session)
            models_body = None
            if (
                targets is None
                or self.use_parquet == "auto"
                or self.use_tensor == "auto"
                or self.transport in ("auto", "uds", "shm")
            ):
                try:
                    models_body = await fetch_json(
                        session,
                        f"{self.base_url}/gordo/v0/{self.project}/models",
                        retries=self.retries,
                        backoff=self.backoff,
                        retry_budget=self.retry_budget,
                    )
                except Exception:
                    if targets is None and not (
                        self._routing and self._routing["members"]
                    ):
                        raise  # discovery is mandatory without a table
                    models_body = None  # encoding probe is best-effort
            if targets is None:
                if self._routing is not None and self._routing["members"]:
                    targets = sorted(self._routing["members"])
                else:
                    # a VALID-but-empty table (fleet still booting,
                    # replicas momentarily unreachable) must not quietly
                    # score nothing: the base replica's /models is live
                    # discovery truth we already fetched
                    targets = models_body["models"]
            # fresh per run: stale cached metadata must never outlive a
            # server-side /reload (a failed re-prefetch then falls back to
            # per-target fetches, not to last run's cache)
            self._metadata_all = {}
            if len(targets) >= _PREFETCH_MIN_TARGETS:
                # below that, per-target GETs are cheaper than pulling the
                # whole fleet's metadata for a handful of lookups
                await self._prefetch_metadata(session)
            if self.use_tensor == "auto":
                # tensor-first negotiation: exact content-type match (a
                # substring test would let a foreign "x-gordo-tensor-v9"
                # advertisement negotiate a format we don't speak)
                self._tensor_active = any(
                    a == TENSOR_CONTENT_TYPE
                    for a in (models_body or {}).get("accepts", [])
                )
            else:
                self._tensor_active = bool(self.use_tensor)
            if self.use_parquet == "auto":
                self._parquet_active = parquet_engine_available() and any(
                    "parquet" in a
                    for a in (models_body or {}).get("accepts", [])
                )
            else:
                self._parquet_active = bool(self.use_parquet)
                if self._parquet_active and not parquet_engine_available():
                    # forced mode fails loudly up front, not one opaque
                    # to_parquet ImportError per chunk
                    raise ImportError(
                        "use_parquet=True but no parquet engine "
                        "(pyarrow/fastparquet) is installed"
                    )
            if (
                self._routing is not None
                and len(self._routing["replicas"]) > 1
            ):
                # fan-out across replicas rides TCP: the uds/shm rungs
                # address ONE co-located server, and pinning every
                # routed chunk to a local socket would undo the
                # partition routing the table exists for
                self.transport_used = "tcp"
                self._shm_client = None
                self._data_session = None
            else:
                await self._resolve_transport(models_body)
            try:
                results = await asyncio.gather(
                    *(
                        self._predict_single(session, sem, t, start, end)
                        for t in targets
                    )
                )
            finally:
                if self._data_session is not None:
                    await self._data_session.close()
                    self._data_session = None
                for dead in self._dead_sessions:
                    with contextlib.suppress(Exception):
                        await dead.close()
                self._dead_sessions = []
                if self._shm_client is not None:
                    self._shm_client.close()
                    self._shm_client = None
        if self.forwarder is not None:
            for result in results:
                if result.ok:
                    self.forwarder.forward(result)
        return list(results)

    @staticmethod
    def _encode_parquet(chunk: pd.DataFrame, chunk_y) -> bytes:
        """Serialize one chunk as parquet bytes (runs on an executor
        thread: CPU-bound encoding must not stall the event loop that is
        pumping the in-flight POSTs — the overlap half of the data-plane
        win). Engine pinned once (utils/encoding.py) so pandas' per-call
        "auto" resolution never rides the chunk loop."""
        import io

        frame = chunk
        if chunk_y is not None:
            # indices are identical by construction (iloc slices of the
            # same row range), so this is a pure column concat
            frame = pd.concat([chunk, chunk_y.add_prefix("__y__")], axis=1)
        buf = io.BytesIO()
        frame.to_parquet(buf, engine=parquet_engine() or "auto")
        return buf.getvalue()

    async def _post_parquet(
        self, session, target, endpoint, chunk: pd.DataFrame,
        chunk_y: Optional[pd.DataFrame] = None,
        request_id: Optional[str] = None,
        deadline: Optional[Deadline] = None,
    ):
        """POST one chunk as a parquet body (index rides inside the file,
        so timestamps round-trip without the JSON string lists). Target
        columns for supervised machines are embedded under a ``__y__``
        prefix; the server splits them back out (server/utils.py)."""
        body = await asyncio.get_running_loop().run_in_executor(
            None, self._encode_parquet, chunk, chunk_y
        )
        headers = {"Content-Type": "application/x-parquet"}
        if request_id:
            headers.update(self._trace_headers(request_id))
        resp = await fetch_json_hedged(
            session,
            self._chunk_urls(target, endpoint),
            hedge_delay_s=self._hedge_delay_s(),
            hedge_stats=self._hedge_stats,
            method="POST",
            data=body,
            headers=headers,
            retries=self.retries,
            backoff=self.backoff,
            retry_budget=self.retry_budget,
            deadline=deadline,
        )
        self._note_wire("parquet", len(body), len(chunk))
        return resp

    def _encode_tensor(self, chunk: pd.DataFrame, chunk_y) -> bytes:
        """One chunk as a framed tensor body (utils/wire.py): the float32
        rows in C order, one memory copy total. Runs on an executor
        thread so chunk k+1 serializes while chunk k's POST is in flight
        (with tensor framing the encode is ~µs — the executor hop is for
        symmetry with the other encoders and for very large chunks).

        When a QoS identity is configured it rides in a ``__meta__``
        sidecar frame (JSON bytes): the shm ring has no headers and
        proxies may strip custom ones, so the framed body itself must
        carry tenant + priority for fairness to hold on every
        transport."""
        frames = [("X", np.ascontiguousarray(chunk.values, dtype=np.float32))]
        if chunk_y is not None:
            frames.append(
                ("y", np.ascontiguousarray(chunk_y.values, dtype=np.float32))
            )
        meta: Dict[str, str] = {}
        if self.tenant:
            meta["tenant"] = self.tenant
        if self.qos_class != "interactive":
            meta["priority"] = self.qos_class
        if meta:
            frames.append((
                "__meta__",
                np.frombuffer(
                    json.dumps(meta).encode("utf-8"), dtype=np.uint8
                ),
            ))
        return pack_frames(frames)

    def _decode_tensor_scoring_body(
        self, body: bytes, chunk: pd.DataFrame, anomaly: bool
    ) -> pd.DataFrame:
        """Tensor response -> the SAME DataFrame the JSON path builds
        (column-for-column, value-for-value: float32 -> float64 is exact,
        so frames from either encoding are bitwise interchangeable). The
        index is the client's own chunk index trimmed by the server's
        ``offset`` — no stringified-timestamp round trip."""
        frames = unpack_frames(body)
        meta = json.loads(bytes(frames.pop("__meta__")))
        offset = int(meta.get("offset", 0))
        if anomaly:
            tags = meta["tags"]
            cols: Dict[Any, np.ndarray] = {}
            for top in ANOMALY_FRAME_NAMES[:4]:
                arr = frames[top].astype(np.float64)
                for i, tag in enumerate(tags):
                    cols[(top, tag)] = arr[:, i]
            for top in ANOMALY_FRAME_NAMES[4:]:
                cols[(top, "")] = frames[top].astype(np.float64)
            df = pd.DataFrame(cols)
            df.columns = pd.MultiIndex.from_tuples(df.columns)
        else:
            df = pd.DataFrame(frames["data"].astype(np.float64))
        df.index = chunk.index[offset : offset + len(df)]
        return df

    async def _post_tensor(
        self, session, target, endpoint, chunk: pd.DataFrame,
        chunk_y: Optional[pd.DataFrame] = None,
        request_id: Optional[str] = None,
        deadline: Optional[Deadline] = None,
    ) -> pd.DataFrame:
        """POST one chunk as a framed tensor body and decode the binary
        response straight into the result frame."""
        body = await asyncio.get_running_loop().run_in_executor(
            None, self._encode_tensor, chunk, chunk_y
        )
        headers = {"Content-Type": TENSOR_CONTENT_TYPE}
        if request_id:
            headers.update(self._trace_headers(request_id))
        resp = await fetch_json_hedged(
            session,
            self._chunk_urls(target, endpoint),
            hedge_delay_s=self._hedge_delay_s(),
            hedge_stats=self._hedge_stats,
            method="POST",
            data=body,
            headers=headers,
            retries=self.retries,
            backoff=self.backoff,
            retry_budget=self.retry_budget,
            deadline=deadline,
        )
        if not isinstance(resp, (bytes, bytearray)):
            # a 200 with a JSON body to a tensor POST is a foreign server
            # that ignored the content type; surface it like a rejection
            # so auto mode downgrades instead of mis-parsing
            raise ValueError(
                f"server answered a tensor POST with {type(resp).__name__}, "
                "not a tensor body"
            )
        self._note_wire("tensor", len(body), len(chunk))
        return self._decode_tensor_scoring_body(
            resp, chunk, anomaly=endpoint.startswith("anomaly")
        )

    async def _predict_single(
        self, session, sem, target: str, start, end
    ) -> PredictionResult:
        try:
            meta = await self._get_metadata(session, target)
            config = self._dataset_config_from_metadata(meta, start, end)
            dataset = get_dataset(config)
        except Exception as exc:
            logger.exception("Failed to resolve dataset config for %s", target)
            return PredictionResult(target, None, [f"dataset: {exc}"])
        try:
            fetch = asyncio.get_running_loop().run_in_executor(
                None, dataset.get_data
            )
            if self.deadline_ms is not None:
                # a hung data provider must not stall a backfill slot
                # forever: the dataset build gets the same budget as a
                # chunk POST (the executor job itself can't be
                # interrupted, but the slot moves on and reports).
                # Deliberately its OWN try block: a metadata-fetch
                # timeout above must not land in this handler
                fetch = asyncio.wait_for(fetch, timeout=self.deadline_ms / 1e3)
                try:
                    X, y = await fetch
                except asyncio.TimeoutError:
                    logger.error(
                        "Dataset build for %s exceeded the %.0fms deadline",
                        target, self.deadline_ms,
                    )
                    return PredictionResult(
                        target, None,
                        [
                            f"dataset: build exceeded "
                            f"{self.deadline_ms:.0f}ms deadline"
                        ],
                    )
            else:
                X, y = await fetch
        except Exception as exc:
            logger.exception("Failed to build dataset for %s", target)
            return PredictionResult(target, None, [f"dataset: {exc}"])

        endpoint = "anomaly/prediction" if self.use_anomaly else "prediction"
        frames: List[pd.DataFrame] = []
        errors: List[str] = []

        async def post_chunk(chunk: pd.DataFrame, chunk_y: Optional[pd.DataFrame]):
            async with sem:
                # routed-chunk accounting lives HERE, once per chunk
                # attempt — _chunk_urls runs once per encoding rung
                # (tensor -> parquet -> JSON downgrades), which would
                # count one chunk several times and skew the
                # routed-vs-fallback split the replica-loss runbook
                # reads. A no-owner fallback to base_url never counts.
                if (
                    self._routing is not None
                    and self._member_base_url(target) is not None
                ):
                    self._fanout_stats["routed_chunks"] += 1
                # one id per chunk, reused across the tensor/parquet ->
                # JSON downgrade re-posts: every attempt is the SAME
                # request. Likewise ONE deadline: a downgrade re-post
                # spends what remains of the chunk's budget, not a fresh
                # one.
                rid = self._next_request_id()
                deadline = (
                    Deadline.after_ms(self.deadline_ms)
                    if self.deadline_ms is not None
                    else None
                )
                t0 = asyncio.get_running_loop().time()
                tensor_exc = parquet_exc = None
                # captured ONCE per chunk: when the unix socket dies
                # mid-run, every in-flight sibling fails with the same
                # ClientConnectionError, and each must know it was on
                # the (now-retired) uds session — reading
                # self._data_session after the first sibling nulled it
                # would make the rest give up instead of retrying tcp
                data_sess = self._data_session
                if self._shm_client is not None and self._tensor_active:
                    # the shared-memory rung: same tensor body, same
                    # response bytes, zero sockets. A ring-level failure
                    # degrades the RUN to the HTTP rungs below; a 4xx is
                    # a genuine request error (the ring only ever faces
                    # a gordo server, so there is no foreign-server
                    # ambiguity to disambiguate).
                    try:
                        frame = await self._post_shm(
                            target, endpoint, chunk, chunk_y,
                            deadline=deadline,
                        )
                        self._latency.record(
                            asyncio.get_running_loop().time() - t0
                        )
                        return frame
                    except DeadlineExceeded as exc:
                        errors.append(
                            f"chunk {chunk.index[0]} (rid={rid}): deadline: {exc}"
                        )
                        return None
                    except ValueError as exc:
                        errors.append(f"chunk {chunk.index[0]} (rid={rid}): {exc}")
                        return None
                    except Exception as exc:
                        logger.warning(
                            "shm transport failed (%s); falling back to "
                            "HTTP for the rest of the run", exc,
                        )
                        shm, self._shm_client = self._shm_client, None
                        self.transport_used = (
                            "uds" if self._data_session is not None else "tcp"
                        )
                        with contextlib.suppress(Exception):
                            shm.close()
                if self._tensor_active:
                    try:
                        frame = await self._post_tensor(
                            data_sess or session, target, endpoint,
                            chunk, chunk_y, request_id=rid, deadline=deadline,
                        )
                        self._latency.record(
                            asyncio.get_running_loop().time() - t0
                        )
                        return frame
                    except aiohttp.ClientConnectionError as exc:
                        if data_sess is None:
                            errors.append(
                                f"chunk {chunk.index[0]} (rid={rid}): {exc}"
                            )
                            return None
                        # mid-run unix-socket death (server restarted
                        # without its UDS listener, path unlinked):
                        # degrade the run to tcp and retry THIS chunk —
                        # a transport failure must not masquerade as an
                        # encoding rejection and cost the run its
                        # tensor upgrade
                        await self._drop_uds(exc)
                        try:
                            frame = await self._post_tensor(
                                session, target, endpoint, chunk, chunk_y,
                                request_id=rid, deadline=deadline,
                            )
                            self._latency.record(
                                asyncio.get_running_loop().time() - t0
                            )
                            return frame
                        except Exception as exc2:
                            errors.append(
                                f"chunk {chunk.index[0]} (rid={rid}): {exc2}"
                            )
                            return None
                    except ValueError as exc:
                        # 4xx on the tensor body: foreign server (or a
                        # genuine model error that any encoding would
                        # 400). The fallback posts below disambiguate —
                        # forced mode never downgrades, same contract as
                        # parquet.
                        if self.use_tensor is True:
                            errors.append(
                                f"chunk {chunk.index[0]} (rid={rid}): {exc}"
                            )
                            return None
                        tensor_exc = exc
                    except Exception as exc:
                        errors.append(f"chunk {chunk.index[0]} (rid={rid}): {exc}")
                        return None
                if self._parquet_active:
                    try:
                        body = await self._post_parquet(
                            data_sess or session, target, endpoint,
                            chunk, chunk_y, request_id=rid, deadline=deadline,
                        )
                        self._latency.record(
                            asyncio.get_running_loop().time() - t0
                        )
                        if tensor_exc is not None:
                            # parquet succeeded where tensor 4xx'd: an
                            # encoding problem — downgrade the run
                            logger.warning(
                                "tensor body rejected (%s) but parquet "
                                "succeeded; downgrading run", tensor_exc,
                            )
                            self._tensor_active = False
                        return body
                    except aiohttp.ClientConnectionError as exc:
                        if data_sess is None:
                            errors.append(
                                f"chunk {chunk.index[0]} (rid={rid}): {exc}"
                            )
                            return None
                        # unix socket died mid-run: degrade to tcp and
                        # fall through to the JSON rung below — a
                        # transport failure is not an encoding verdict
                        await self._drop_uds(exc)
                        data_sess = None
                    except ValueError as exc:
                        # 4xx on the parquet body. Ambiguous: the server
                        # may reject the ENCODING (foreign pod, no parse
                        # engine) or this chunk may hit a genuine model
                        # error that would 400 under any encoding. The
                        # JSON re-post below disambiguates; forced mode
                        # never downgrades (documented contract).
                        if self.use_parquet is True:
                            errors.append(f"chunk {chunk.index[0]} (rid={rid}): {exc}")
                            return None
                        parquet_exc = exc
                    except Exception as exc:
                        errors.append(f"chunk {chunk.index[0]} (rid={rid}): {exc}")
                        return None
                payload = {
                    "X": chunk.values.tolist(),
                    "index": [str(i) for i in chunk.index],
                }
                if chunk_y is not None:
                    payload["y"] = chunk_y.values.tolist()
                # encode off the event loop (same overlap contract as the
                # binary encoders: a 500-row float-list dumps() is
                # milliseconds the in-flight POSTs shouldn't stall on),
                # and as bytes so the wire accounting sees real sizes
                json_body = await asyncio.get_running_loop().run_in_executor(
                    None,
                    functools.partial(json.dumps, payload, ensure_ascii=False),
                )
                json_body = json_body.encode("utf-8")

                async def _post_json(sess):
                    return await fetch_json_hedged(
                        sess,
                        self._chunk_urls(target, endpoint),
                        hedge_delay_s=self._hedge_delay_s(),
                        hedge_stats=self._hedge_stats,
                        method="POST",
                        data=json_body,
                        headers={
                            "Content-Type": "application/json",
                            **self._trace_headers(rid),
                        },
                        retries=self.retries,
                        backoff=self.backoff,
                        retry_budget=self.retry_budget,
                        deadline=deadline,
                    )

                try:
                    body = await _post_json(data_sess or session)
                    self._latency.record(asyncio.get_running_loop().time() - t0)
                    self._note_wire("json", len(json_body), len(chunk))
                except aiohttp.ClientConnectionError as exc:
                    if data_sess is None:
                        errors.append(
                            f"chunk {chunk.index[0]} (rid={rid}): {exc}"
                        )
                        return None
                    # same mid-run unix-socket death handling as the
                    # tensor rung: degrade to tcp and retry this chunk
                    await self._drop_uds(exc)
                    try:
                        body = await _post_json(session)
                        self._latency.record(
                            asyncio.get_running_loop().time() - t0
                        )
                        self._note_wire("json", len(json_body), len(chunk))
                    except Exception as exc2:
                        errors.append(
                            f"chunk {chunk.index[0]} (rid={rid}): {exc2}"
                        )
                        return None
                except DeadlineExceeded as exc:
                    errors.append(
                        f"chunk {chunk.index[0]} (rid={rid}): deadline: {exc}"
                    )
                    return None
                except Exception as exc:
                    errors.append(f"chunk {chunk.index[0]} (rid={rid}): {exc}")
                    return None
                if tensor_exc is not None:
                    logger.warning(
                        "tensor body rejected (%s) but JSON succeeded; "
                        "downgrading run", tensor_exc,
                    )
                    self._tensor_active = False
                if parquet_exc is not None:
                    # JSON succeeded where parquet 4xx'd: an encoding
                    # problem, not a model error — downgrade the rest of
                    # the run (a model error would have failed both and
                    # must NOT cost the whole fleet its parquet win)
                    logger.warning(
                        "parquet body rejected (%s) but JSON succeeded; "
                        "downgrading run to JSON", parquet_exc,
                    )
                    self._parquet_active = False
                return body

        # y rides along for supervised machines (target_tag_list): the
        # anomaly diff must be computed against the TRAINED target, not
        # X->X — silently dropping y here would score the wrong objective
        chunks = [
            (
                X.iloc[i : i + self.batch_size],
                None if y is None else y.iloc[i : i + self.batch_size],
            )
            for i in range(0, len(X), self.batch_size)
        ]
        bodies = await asyncio.gather(*(post_chunk(cx, cy) for cx, cy in chunks))
        if (
            self._routing is not None
            and any(b is None for b in bodies)
            and any("No such model" in e for e in errors)
        ):
            # stale-table detection: a routed chunk 404ing means the
            # member moved since our table (watchman stamps the version
            # for exactly this). Refetch once; a CHANGED table re-posts
            # every failed chunk to the new owner — one bounded retry,
            # not a loop (an unchanged table means the member truly has
            # no owner, and the 404-with-reason stands as the answer)
            if await self._fetch_routing(session, force=True, member=target):
                retry = [i for i, b in enumerate(bodies) if b is None]
                self._fanout_stats["reroutes"] += len(retry)
                logger.warning(
                    "routing table was stale (now v%s); re-posting %d "
                    "chunk(s) for %s", self.routing_version, len(retry),
                    target,
                )
                errors.clear()
                fresh = await asyncio.gather(
                    *(post_chunk(*chunks[i]) for i in retry)
                )
                for i, body in zip(retry, fresh):
                    bodies[i] = body
        for body in bodies:
            if body is None:
                continue
            if isinstance(body, pd.DataFrame):
                # the tensor path decodes straight to the result frame
                frames.append(body)
            elif "data" in body and isinstance(body["data"], dict):
                frames.append(dict_to_frame(body))
            elif "data" in body:
                df = pd.DataFrame(body["data"])
                if body.get("index") and len(body["index"]) == len(df):
                    df.index = pd.to_datetime(body["index"], utc=True)
                frames.append(df)
        predictions = pd.concat(frames) if frames else None
        return PredictionResult(target, predictions, errors)

    # ------------------------------------------------------------------ #
    # streaming forwarder
    # ------------------------------------------------------------------ #

    def ingest(
        self, target: str, X, timestamps=None, tensor: bool = False
    ) -> Dict[str, int]:
        """Synchronous wrapper over :meth:`ingest_async`."""
        return asyncio.run(self.ingest_async(target, X, timestamps, tensor=tensor))

    async def ingest_async(
        self, target: str, X, timestamps=None, tensor: bool = False
    ) -> Dict[str, int]:
        """Streaming forwarder: POST fresh rows to the server's
        ``.../{target}/ingest`` window buffer in ``batch_size``-row
        chunks, reusing the scoring path's transport citizenship — the
        per-chunk deadline rides the wire as ``X-Gordo-Deadline-Ms``
        (restamped per retry attempt) and every retry spends the SAME
        shared :class:`RetryBudget` the scoring POSTs draw from, so an
        ingest storm cannot re-offer unbounded load either. NaN cells
        (sensor dropout) serialize as JSON ``null``.

        ``X``: DataFrame (index supplies event timestamps unless
        ``timestamps`` is given) or (rows, features) array.
        Returns the summed server accounting
        (``accepted``/``late``/``dropped`` rows + chunks posted) and
        feeds ``gordo_client_ingest_rows_total``.

        ``tensor=True`` posts each chunk as a framed tensor body (the
        scoring plane's wire format, utils/wire.py): float32 ``rows``
        (NaN cells ARE the dropout markers — no null boxing) plus a
        float64 epoch-seconds ``timestamps`` frame. Explicit opt-in
        because the ingest path does no ``/models`` negotiation — use it
        against gordo servers, not foreign ones.

        Delivery is AT-LEAST-ONCE: a chunk the server ingested whose
        response was lost gets retried and its rows ingested twice.
        That is the right trade for a drift window (a few duplicated
        rows barely move an EWMA/quantile; silently LOSING fresh rows
        starves detection) — but it means ``rows_total`` is an upper
        bound on distinct rows, not an exact count."""
        if isinstance(X, pd.DataFrame):
            values = X.values
            if timestamps is None and isinstance(X.index, pd.DatetimeIndex):
                # only a datetime index carries event times; a default
                # RangeIndex would serialize as unparseable "0","1",...
                # — omit instead, the server stamps arrival time
                timestamps = [str(i) for i in X.index]
        else:
            values = np.asarray(X)
        epoch_ts = None
        if tensor and timestamps is not None:
            # the wire frame wants epoch seconds; string/Timestamp forms
            # are normalized once up front (ns -> s, matching the server)
            ts_list = list(timestamps)
            if ts_list and isinstance(
                ts_list[0], (int, float, np.integer, np.floating)
            ):
                epoch_ts = np.asarray(ts_list, np.float64)
            else:  # ISO strings / Timestamps: one vectorized parse
                epoch_ts = (
                    pd.to_datetime(ts_list, utc=True).as_unit("ns").asi8 / 1e9
                )
        totals = {"accepted": 0, "late": 0, "dropped": 0, "chunks": 0}
        url = self._url(target, "ingest")
        timeout = aiohttp.ClientTimeout(total=600)
        async with aiohttp.ClientSession(timeout=timeout) as session:
            for i in range(0, len(values), self.batch_size):
                chunk = values[i : i + self.batch_size]
                rid = self._next_request_id()
                deadline = (
                    Deadline.after_ms(self.deadline_ms)
                    if self.deadline_ms is not None
                    else None
                )
                if tensor:
                    frames = [
                        ("rows", np.ascontiguousarray(chunk, dtype=np.float32))
                    ]
                    if epoch_ts is not None:
                        frames.append(
                            ("timestamps", epoch_ts[i : i + self.batch_size])
                        )
                    data = pack_frames(frames)
                    body = await fetch_json(
                        session,
                        url,
                        method="POST",
                        data=data,
                        headers={
                            "Content-Type": TENSOR_CONTENT_TYPE,
                            **self._trace_headers(rid),
                        },
                        retries=self.retries,
                        backoff=self.backoff,
                        retry_budget=self.retry_budget,
                        deadline=deadline,
                    )
                    # its own bucket: mixing ingest traffic into the
                    # scoring "tensor" cell would skew the bytes-per-row
                    # comparison
                    self._note_wire("ingest-tensor", len(data), len(chunk))
                else:
                    rows = [
                        [None if v != v else float(v) for v in row]
                        for row in chunk.tolist()
                    ]
                    payload: Dict[str, Any] = {"rows": rows}
                    if timestamps is not None:
                        ts = list(timestamps[i : i + self.batch_size])
                        payload["timestamps"] = [
                            t if isinstance(t, (int, float, str)) else str(t)
                            for t in ts
                        ]
                    data = json.dumps(payload).encode("utf-8")
                    body = await fetch_json(
                        session,
                        url,
                        method="POST",
                        data=data,
                        headers={
                            "Content-Type": "application/json",
                            **self._trace_headers(rid),
                        },
                        retries=self.retries,
                        backoff=self.backoff,
                        retry_budget=self.retry_budget,
                        deadline=deadline,
                    )
                    # symmetric with the tensor branch: ingest bytes in
                    # their own bucket, never the scoring cells
                    self._note_wire("ingest-json", len(data), len(chunk))
                totals["chunks"] += 1
                for key in ("accepted", "late", "dropped"):
                    totals[key] += int(body.get(key, 0))
                self._ingest_stats["rows"] += int(body.get("accepted", 0))
                self._ingest_stats["chunks"] += 1
        return totals
