"""Goodput accounting: where did the fleet's time actually go?

PRs 1 and 3 gave the stack raw signals (per-shard routed/padded-row
counters, per-request stage spans, deadline-expiry counters) but nothing
computed the quantity the ROADMAP's next moves need: *what fraction of
serving time is deadline-met useful work, and where is the rest going?*
"ML Productivity Goodput" (PAPERS.md #5) frames exactly this accounting
for TPU fleets; this module is the serving-side ledger.

The :class:`GoodputLedger` attributes every scoring request's wall time
across the existing span stages (``queue_wait`` / ``coalesce`` / ``pad``
/ ``device_execute`` / ``postprocess``) and classifies time three ways:

- **goodput** — device + wall time of requests that met their deadline
  with finite scores (the only time anyone was paid for);
- **wasted** — time burned on requests that produced nothing: 504s
  (before OR after dispatch), failed bucket groups, quarantine-grade
  non-finite outputs, shed 429s, and the device FLOPs spent on padded
  rows;
- **overhead** — host-side stage time (queueing, coalescing, padding,
  postprocess) that is the price of batching, not the product.

Two ratios answer the fleet questions directly (stability contract,
docs/observability.md "Goodput & SLO"):

- ``gordo_goodput_ratio`` = goodput wall seconds / total classified wall
  seconds. Wall-weighted deliberately: under a deadline storm the
  dominant waste is *admission-time* (requests that expire before the
  device ever sees them), which a device-time-only ratio is blind to.
- ``gordo_device_busy_ratio`` = device-busy seconds / process uptime —
  how much of the chip an operator is paying for is executing at all.
- ``gordo_padded_row_waste_ratio`` = padded device seconds / device-busy
  seconds — the routing-skew FLOP waste, fleet-readable.

Threading contract (mirrors the metrics layer): each cell has ONE
writer. The bank's scoring executor thread writes the group-level cells
(``account_group``: device windows, padded split, per-bucket/per-shard
breakdowns, coalesce/pad/postprocess stage seconds); the aiohttp event
loop writes the request-level cells (``finish_request``: outcome
classes, wall seconds, the latency histogram, plus ``record_queue_wait``
from the engine's dispatch loop). Readers (snapshot/render) may observe
a mid-update value, never a corrupt one. Disabled (``GORDO_SLO=0``)
means the ledger simply does not exist — every call site guards on one
``None`` check, the same near-free-when-off contract as tracing, held
to the <=5% hot-loop guard in tests/test_goodput.py.
"""

import os
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from gordo_components_tpu.observability.metrics import (
    LATENCY_BINS_PER_DECADE,
    Histogram,
)
from gordo_components_tpu.observability.tracing import union_length

__all__ = ["GoodputLedger", "STAGES", "attribute_trace"]

# the span stages wall time attributes across: a request's top-level
# spans, in the order it passes through them (docs/observability.md's
# span-name stability contract); "other" is the residual attribute_trace
# reports for time no named stage covers (the middleware's own work, the
# response write)
STAGES = (
    "parse", "admit", "queue_wait", "handoff", "coalesce", "pad",
    "device_execute", "postprocess", "resolve", "encode",
)

_ENV_ENABLE = "GORDO_SLO"

# tenant_cells list layout: [goodput, wasted, expired]
_TENANT_IDX = {"goodput": 0, "wasted": 1, "expired": 2}
_TENANT_OUTCOMES = ("goodput", "wasted", "expired")


class GoodputLedger:
    """Cumulative goodput/waste/overhead accounting for one serving app.

    All cells are monotonic accumulators (counter semantics — the SLO
    tracker computes windowed rates from periodic samples); the ratios
    are derived at read time so ``/stats``, ``/metrics`` and ``/slo``
    cannot drift from each other.
    """

    def __init__(self, registry=None):
        self.started = time.monotonic()
        # ---- event-loop cells (finish_request / record_queue_wait) ----
        self.requests = {"goodput": 0, "wasted": 0, "expired": 0}
        self.errors_5xx = 0  # availability SLO feed (includes the 504s)
        self.wall_goodput_s = 0.0
        self.wall_wasted_s = 0.0  # wasted + expired requests' wall time
        self.device_goodput_s = 0.0
        self.device_wasted_s = 0.0  # device time of requests that failed
        # SERVED (status < 400) scoring-request service time, for the
        # latency SLO objectives — failed/shed/expired requests are
        # excluded on purpose: a deadline storm fails in milliseconds,
        # and counting those would read p99 as healthiest exactly while
        # the service is down (conventional latency SLIs measure
        # successful requests only; failures burn the availability
        # objective instead). Finer low-ms bins than the generic default:
        # ms-scale deadline budgets live where coarse bins blur
        # percentiles (same resolution as server/stats.LatencyHistogram).
        self.latency = Histogram(bins_per_decade=LATENCY_BINS_PER_DECADE)
        # ---- per-(tenant, priority-class) cells (ISSUE 19) ----
        # (tenant_label, qos_class) -> [goodput, wasted, expired].
        # Callers pass the cardinality-BOUNDED tenant label (known
        # tenants + "default" + "other" — qos/classify.py), so the dict
        # stays O(tenants x 3); the 256-key cap below is defense in
        # depth for direct callers that skip classification, matching
        # the PR 18 registry guard's never-unbounded rule.
        self.tenant_cells: Dict[Tuple[str, str], List[int]] = {}
        self._stage_queue_wait_s = 0.0
        # ---- scoring-executor cells (account_group) ----
        self.device_padded_s = 0.0  # device window spent on pad rows
        self.device_failed_s = 0.0  # device window of failed bucket groups
        self.stage_s = {"coalesce": 0.0, "pad": 0.0, "postprocess": 0.0}
        # bucket label -> [useful_s, padded_s, failed_s]
        self.per_bucket: Dict[str, List[float]] = {}
        # bucket label -> [routed_rows, padded_rows] — summed from the
        # same shard_rows tuples the per-shard cells consume; the cost
        # model's real-vs-padded row split per bucket (observability/
        # cost.py) without a second hot-path tally
        self.bucket_rows: Dict[str, List[float]] = {}
        # shard label -> [routed_rows, padded_rows]
        self.per_shard: Dict[str, List[float]] = {}
        if registry is not None:
            registry.collector(self._collect, key="goodput")

    # ------------------------------------------------------------------ #
    # construction
    # ------------------------------------------------------------------ #

    @classmethod
    def from_env(cls, registry=None) -> Optional["GoodputLedger"]:
        """A ledger, or ``None`` when ``GORDO_SLO=0`` — absence IS the
        disabled state, so every call site pays one ``None`` check."""
        if os.environ.get(_ENV_ENABLE, "1") == "0":
            return None
        return cls(registry=registry)

    # ------------------------------------------------------------------ #
    # writers
    # ------------------------------------------------------------------ #

    def record_queue_wait(self, seconds: float) -> None:
        """Engine dispatch loop: one request's submit -> dispatch wait."""
        self._stage_queue_wait_s += seconds

    def account_group(
        self,
        bucket: str,
        window_s: float,
        useful_s: float,
        padded_s: float,
        ok: bool,
        coalesce_s: float = 0.0,
        pad_s: float = 0.0,
        postprocess_s: float = 0.0,
        shard_rows: Iterable[Tuple[str, int, int]] = (),
    ) -> None:
        """One bucket group's trip through the scoring pipeline
        (executor thread). ``useful_s``/``padded_s`` split the group's
        device window by real vs pad rows; a failed group's useful share
        is wasted outright (nobody got its answers). The per-REQUEST
        useful shares ride out on ``ScoreResult.device_s`` and commit to
        the goodput/wasted cells when the request classifies
        (:meth:`finish_request`)."""
        self.device_padded_s += padded_s
        if not ok:
            self.device_failed_s += useful_s
        self.stage_s["coalesce"] += coalesce_s
        self.stage_s["pad"] += pad_s
        self.stage_s["postprocess"] += postprocess_s
        cells = self.per_bucket.get(bucket)
        if cells is None:
            cells = self.per_bucket[bucket] = [0.0, 0.0, 0.0]
        if ok:
            cells[0] += useful_s
        else:
            cells[2] += useful_s
        cells[1] += padded_s
        brows = self.bucket_rows.get(bucket)
        if brows is None:
            brows = self.bucket_rows[bucket] = [0.0, 0.0]
        for shard, routed, padded in shard_rows:
            rows = self.per_shard.get(shard)
            if rows is None:
                rows = self.per_shard[shard] = [0.0, 0.0]
            rows[0] += routed
            rows[1] += padded
            brows[0] += routed
            brows[1] += padded

    def finish_request(
        self,
        status: int = 200,
        elapsed_s: float = 0.0,
        device_s: float = 0.0,
        scores_finite: bool = True,
        tenant: str = "default",
        qos_class: str = "interactive",
    ) -> None:
        """Classify one finished scoring request (event loop; the server
        middleware calls this — the north-star check drives it directly).

        goodput: status < 400 with finite scores. expired: 504 (the
        deadline ran out — before dispatch the common case, after
        dispatch when a mid-pipeline expiry discarded the group).
        wasted: everything else (5xx, shed 429s, quarantine 410s, bad
        input 4xxs, non-finite output behind a 200). ``tenant`` /
        ``qos_class`` additionally attribute the outcome to the
        request's QoS identity (qos/classify.py; tenant must be the
        bounded label)."""
        if status == 504:
            cls = "expired"
        elif status < 400 and scores_finite:
            cls = "goodput"
        else:
            cls = "wasted"
        self.requests[cls] += 1
        key = (tenant, qos_class)
        cell = self.tenant_cells.get(key)
        if cell is None:
            if len(self.tenant_cells) >= 256 and key not in self.tenant_cells:
                key = ("other", qos_class)
                cell = self.tenant_cells.get(key)
            if cell is None:
                cell = self.tenant_cells[key] = [0, 0, 0]
        cell[_TENANT_IDX[cls]] += 1
        if status >= 500 or (status < 400 and not scores_finite):
            self.errors_5xx += 1
        if status < 400:
            self.latency.record(elapsed_s)
        if cls == "goodput":
            self.wall_goodput_s += elapsed_s
            self.device_goodput_s += device_s
        else:
            self.wall_wasted_s += elapsed_s
            self.device_wasted_s += device_s

    # ------------------------------------------------------------------ #
    # reads
    # ------------------------------------------------------------------ #

    def _device_total_s(self) -> float:
        return (
            self.device_goodput_s
            + self.device_wasted_s
            + self.device_failed_s
            + self.device_padded_s
        )

    def goodput_ratio(self) -> Optional[float]:
        """Goodput wall seconds / total classified wall seconds (None
        before any request classifies)."""
        total = self.wall_goodput_s + self.wall_wasted_s
        return (self.wall_goodput_s / total) if total > 0 else None

    def device_busy_ratio(self) -> float:
        return self._device_total_s() / max(1e-9, time.monotonic() - self.started)

    def padded_waste_ratio(self) -> Optional[float]:
        total = self._device_total_s()
        return (self.device_padded_s / total) if total > 0 else None

    def snapshot(self) -> Dict[str, Any]:
        """JSON view (served in ``/stats`` as ``goodput``; the north-star
        check records it). The SAME derivations the registry
        collector renders, so the two surfaces cannot drift."""
        device_total = self._device_total_s()
        ratio = self.goodput_ratio()
        dev_ratio = (
            self.device_goodput_s / device_total if device_total > 0 else None
        )
        padded = self.padded_waste_ratio()
        stages = dict(self.stage_s)
        stages["queue_wait"] = self._stage_queue_wait_s
        return {
            "uptime_s": round(time.monotonic() - self.started, 3),
            "requests": dict(self.requests),
            "goodput_ratio": None if ratio is None else round(ratio, 6),
            "wall": {
                "goodput_s": round(self.wall_goodput_s, 6),
                "wasted_s": round(self.wall_wasted_s, 6),
            },
            "device": {
                "total_s": round(device_total, 6),
                "goodput_s": round(self.device_goodput_s, 6),
                "wasted_s": round(
                    self.device_wasted_s + self.device_failed_s, 6
                ),
                "padded_s": round(self.device_padded_s, 6),
                "goodput_ratio": (
                    None if dev_ratio is None else round(dev_ratio, 6)
                ),
                "busy_ratio": round(self.device_busy_ratio(), 6),
                "padded_waste_ratio": (
                    None if padded is None else round(padded, 6)
                ),
            },
            "stages_s": {k: round(v, 6) for k, v in sorted(stages.items())},
            # per-(tenant, class) outcome counts, "tenant|class" keyed
            # (same atomic-snapshot idiom as per_bucket below)
            "tenants": {
                f"{tenant}|{cls}": dict(zip(_TENANT_OUTCOMES, cell))
                for (tenant, cls), cell in sorted(
                    list(self.tenant_cells.items())
                )
            },
            "latency": self.latency.snapshot(),
            # list() first: the scoring executor inserts a first-seen
            # bucket/shard key mid-read; snapshot the dict atomically
            # before iterating (the same idiom MetricFamily.samples uses)
            "per_bucket": {
                label: {
                    "useful_s": round(u, 6),
                    "padded_s": round(p, 6),
                    "failed_s": round(f, 6),
                    "routed_rows": int(self.bucket_rows.get(label, (0, 0))[0]),
                    "padded_rows": int(self.bucket_rows.get(label, (0, 0))[1]),
                }
                for label, (u, p, f) in sorted(list(self.per_bucket.items()))
            },
            "per_shard": {
                shard: {
                    "routed_rows": int(routed),
                    "padded_rows": int(padded_rows),
                    "padded_ratio": (
                        round(padded_rows / (routed + padded_rows), 6)
                        if (routed + padded_rows) > 0
                        else None
                    ),
                }
                for shard, (routed, padded_rows) in sorted(
                    list(self.per_shard.items())
                )
            },
        }

    def _collect(self):
        """Read-through registry exposition of the same cells."""
        ratio = self.goodput_ratio()
        if ratio is not None:
            yield (
                "gordo_goodput_ratio", "gauge",
                "Goodput wall seconds / total classified wall seconds "
                "(deadline-met finite-score work over everything served)",
                {}, round(ratio, 6),
            )
        yield (
            "gordo_device_busy_ratio", "gauge",
            "Device-busy seconds / process uptime", {},
            round(self.device_busy_ratio(), 6),
        )
        padded = self.padded_waste_ratio()
        if padded is not None:
            yield (
                "gordo_padded_row_waste_ratio", "gauge",
                "Padded-row device seconds / device-busy seconds (the "
                "routing-skew FLOP waste)", {}, round(padded, 6),
            )
        for cls, n in sorted(self.requests.items()):
            yield (
                "gordo_goodput_requests_total", "counter",
                "Scoring requests by goodput class", {"class": cls}, n,
            )
        # per-(tenant, priority-class) outcomes (ISSUE 19): a separate
        # family — "class" here is the PRIORITY class; the outcome gets
        # its own label so it can't collide with the family above
        for (tenant, cls), cell in sorted(list(self.tenant_cells.items())):
            for outcome, n in zip(_TENANT_OUTCOMES, cell):
                yield (
                    "gordo_goodput_tenant_requests_total", "counter",
                    "Scoring requests by tenant, priority class, and "
                    "goodput outcome",
                    {"tenant": tenant, "class": cls, "outcome": outcome}, n,
                )
        for cls, v in (
            ("goodput", self.device_goodput_s),
            ("wasted", self.device_wasted_s + self.device_failed_s),
            ("padded", self.device_padded_s),
        ):
            yield (
                "gordo_goodput_device_seconds_total", "counter",
                "Device window seconds by goodput class", {"class": cls},
                round(v, 6),
            )
        stages = dict(self.stage_s)
        stages["queue_wait"] = self._stage_queue_wait_s
        for stage, v in sorted(stages.items()):
            yield (
                "gordo_goodput_stage_seconds_total", "counter",
                "Host-side stage seconds (batching overhead) by stage",
                {"stage": stage}, round(v, 6),
            )
        # list() first: a first-seen bucket/shard key can land from the
        # scoring executor mid-render (see snapshot)
        for label, (useful, padded_s, failed) in sorted(
            list(self.per_bucket.items())
        ):
            for cls, v in (
                ("useful", useful), ("padded", padded_s), ("failed", failed)
            ):
                yield (
                    "gordo_goodput_bucket_device_seconds_total", "counter",
                    "Device window seconds per bucket, split useful / "
                    "padded / failed-group", {"bucket": label, "class": cls},
                    round(v, 6),
                )
        for shard, (routed, padded_rows) in sorted(list(self.per_shard.items())):
            total = routed + padded_rows
            if total > 0:
                yield (
                    "gordo_goodput_shard_padded_row_ratio", "gauge",
                    "Pad rows / dispatched rows per shard (per-shard "
                    "padding waste share)", {"shard": shard},
                    round(padded_rows / total, 6),
                )


# ---------------------------------------------------------------------- #
# per-request stage attribution from a trace
# ---------------------------------------------------------------------- #


def _flatten_spans(node: Dict[str, Any], out: List[Dict[str, Any]]) -> None:
    out.append(node)
    for child in node.get("children", ()):
        _flatten_spans(child, out)


def attribute_trace(trace) -> Dict[str, Any]:
    """Attribute one request's wall time across the stage spans.

    ``trace`` is a :class:`~gordo_components_tpu.observability.tracing.
    Trace` or the summary dict ``GET .../traces`` serves. Returns
    ``{"wall_ms", "stages_ms": {stage: ms, ..., "other": ms},
    "coverage"}`` where per-stage time is the union of that stage's
    intervals (a multi-chunk request records several spans per stage;
    overlaps must not double-count), ``other`` is the residual no named
    stage covers (the middleware's own work; a tensor answer's ``send``
    lies after the root and is clamped out), and ``coverage``
    is the named-stage share of the wall. The acceptance contract
    (tests/test_goodput.py): the attribution sums to within 5% of the
    request's wall time."""
    if hasattr(trace, "summary"):
        trace = trace.summary()
    root = trace.get("spans") or {}
    wall_ms = float(trace.get("duration_ms") or root.get("duration_ms") or 0.0)
    flat: List[Dict[str, Any]] = []
    if root:
        _flatten_spans(root, flat)
    by_stage: Dict[str, List[Tuple[float, float]]] = {s: [] for s in STAGES}
    all_intervals: List[Tuple[float, float]] = []
    for span in flat:
        name = span.get("name")
        if name not in by_stage:
            continue
        start = max(0.0, float(span.get("start_ms", 0.0)))
        end = min(wall_ms, start + float(span.get("duration_ms", 0.0)))
        if end <= start:
            continue
        by_stage[name].append((start, end))
        all_intervals.append((start, end))
    stages_ms = {
        stage: round(union_length(iv), 3) for stage, iv in by_stage.items()
    }
    covered = union_length(all_intervals)
    stages_ms["other"] = round(max(0.0, wall_ms - covered), 3)
    return {
        "wall_ms": round(wall_ms, 3),
        "stages_ms": stages_ms,
        "coverage": round(covered / wall_ms, 4) if wall_ms > 0 else 0.0,
    }
