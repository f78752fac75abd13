"""Request and fit tracing: spans, W3C context, Chrome export, and the
program's stages in the profiler's trace.

The metrics registry (observability/metrics.py) answers "how is the
fleet doing"; this module answers "where did THIS request's 200 ms go".
A :class:`Tracer` produces request-scoped :class:`Trace` objects whose
:class:`Span` records carry monotonic timestamps, so the serving hot
path (`parse` -> `admit` -> `queue_wait` -> `handoff` -> `coalesce` ->
`pad` -> `device_execute` -> `postprocess` -> `resolve` -> `encode`,
then a tensor answer's `send`) and a fit (`fit:<bucket>` over the
trainer's stages) become a timeline instead of one histogram bucket.

Design rules, mirroring the metrics layer:

- **Hot-path safe** — a disabled tracer (``GORDO_TRACE_SAMPLE=0``)
  returns ``None`` from ``start_trace`` and every call site guards on
  that one reference (a :class:`stage` skips it); recording a span is
  two ``time.monotonic()`` reads and a ``list.append`` (atomic under the
  GIL, so spans may be appended from the scoring executor thread while
  the event loop owns the trace). The requests of one coalesced batch
  hold ONE span object per shared stage (:func:`group_span`).
- **W3C context propagation** — ``traceparent`` headers
  (``00-<32hex trace-id>-<16hex span-id>-<2hex flags>``) parse on the
  way in and format on the way out, so the client -> server -> engine ->
  device chain shares one trace id end to end. An upstream ``sampled``
  flag (0x01) forces retention past head sampling: the caller asked to
  see this one.
- **Sampling** — ``GORDO_TRACE_SAMPLE`` (default 0.1) head-samples
  which completed traces enter the recent ring; the slow reservoir
  ALWAYS considers every completed trace, so the worst requests are
  retrievable even at low sample rates (the whole point of a flight
  recorder). ``<=0`` disables tracing entirely.
- **Bounded memory** — completed traces land in a ring
  (``GORDO_TRACE_RING``, default 128) plus a worst-N min-heap reservoir
  (``GORDO_TRACE_SLOW_KEEP``, default 16); nothing grows with traffic.
- **Chrome trace-event export** — ``chrome_trace(traces)`` emits the
  Trace Event Format JSON (``ph: "X"`` complete events, microsecond
  ``ts``/``dur``) that ``chrome://tracing`` and Perfetto open directly,
  with ``ts`` on the JAX profiler's clock (:func:`profiler_ns`).
- **One primitive for work the host does** — :class:`stage` records a
  span and a ``jax.profiler.TraceAnnotation`` (``gordo:<name>``) from the
  same two clock reads, so an open profiler session shows the program's
  stages beside the XLA ops they launched. Spans whose boundaries are
  observed across an ``await`` keep :meth:`Trace.add_span` and get no
  annotation (a profiler region must not straddle an ``await``). JAX's
  own compile timings (``jax.monitoring``) land as ``trace_lower`` /
  ``backend_compile`` / ``cache_load`` spans on the current trace. JAX
  is imported by the first :class:`stage`, not by this module.

Span names are a stability contract like metric names — see
docs/observability.md ("Tracing").
"""

import contextlib
import contextvars
import heapq
import itertools
import os
import random
import re
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional, Tuple

__all__ = [
    "Span",
    "Trace",
    "Tracer",
    "chrome_trace",
    "covered_seconds",
    "current_trace",
    "format_traceparent",
    "get_tracer",
    "group_span",
    "parse_traceparent",
    "profiler_ns",
    "stage",
    "union_length",
    "use_trace",
]

_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)


def parse_traceparent(header: Optional[str]) -> Optional[Tuple[str, str, bool]]:
    """``(trace_id, parent_span_id, sampled)`` from a W3C ``traceparent``
    header, or None for absent/malformed/all-zero ids (the spec says an
    invalid header is ignored, not an error)."""
    if not header:
        return None
    m = _TRACEPARENT_RE.match(header.strip().lower())
    if not m:
        return None
    version, trace_id, span_id, flags = m.groups()
    if version == "ff" or trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    try:
        sampled = bool(int(flags, 16) & 0x01)
    except ValueError:  # unreachable given the regex; belt and braces
        return None
    return trace_id, span_id, sampled


def format_traceparent(trace_id: str, span_id: str, sampled: bool = True) -> str:
    return f"00-{trace_id}-{span_id}-{'01' if sampled else '00'}"


# id generation: a urandom-seeded Mersenne generator, NOT uuid4 — ids are
# identity, not security, and uuid4's per-call urandom read costs ~18us
# where getrandbits costs <1us (a trace mints ~a dozen ids; uuid4 alone
# was half the measured enabled-tracing overhead on the hot loop).
# Module-level shared instance: getrandbits is a single C call, atomic
# under the GIL, so the event loop and the scoring executor thread can
# both mint ids without a lock.
_ID_RNG = random.Random(int.from_bytes(os.urandom(16), "big"))


def _new_trace_id() -> str:
    return f"{_ID_RNG.getrandbits(128):032x}"


def _new_span_id() -> str:
    return f"{_ID_RNG.getrandbits(64):016x}"


def _monotonic_to_profiler_ns() -> int:
    """What to add to ``time.monotonic_ns()`` to read the clock the JAX
    profiler stamps its host events with: the system's real-time clock in
    nanoseconds (an ``.xplane.pb`` event lies at its trace's
    ``profile_start_time`` plus the event's offset). Read once, between
    two monotonic reads; the kernel slews both clocks alike, so only a
    step of the real-time clock moves the offset."""
    before = time.monotonic_ns()
    wall = time.time_ns()
    after = time.monotonic_ns()
    return wall - (before + after) // 2


# one anchor for the whole process: every trace's spans, and the
# profiler's regions, on one time line
_PROFILER_OFFSET_NS = _monotonic_to_profiler_ns()


def profiler_ns(t: float) -> float:
    """A ``time.monotonic()`` reading (a span's ``start``/``end``) on the
    profiler's clock, in nanoseconds."""
    return _PROFILER_OFFSET_NS + t * 1e9


class Span:
    """One named, timed operation inside a trace.

    ``start``/``end`` are ``time.monotonic()`` seconds; a span may be
    created open (``end is None``) and closed later, or recorded whole
    with explicit timestamps (``Trace.add_span``) when the boundary
    events were measured elsewhere — the engine's ``queue_wait`` is
    enqueue -> dispatch, both observed before the span object exists.

    ``parent`` is the span it hangs under; ``None`` is the root of
    whichever trace holds it. That is what lets ONE span object stand in
    every traced request of a coalesced group (:func:`group_span`): the
    group's stages have the same boundaries for all of them. ``span_id``
    is minted when first read (export, ``traceparent``), not on the hot
    path."""

    __slots__ = ("name", "parent", "start", "end", "error", "attributes", "_span_id")

    def __init__(
        self,
        name: str,
        parent: Optional["Span"],
        start: float,
        end: Optional[float] = None,
        error: bool = False,
        attributes: Optional[Dict[str, Any]] = None,
    ):
        self.name = name
        self.parent = parent
        self.start = start
        self.end = end
        self.error = error
        self.attributes = attributes or {}
        self._span_id: Optional[str] = None

    @property
    def span_id(self) -> str:
        if self._span_id is None:
            self._span_id = _new_span_id()
        return self._span_id

    @property
    def duration_s(self) -> float:
        return max(0.0, (self.end if self.end is not None else self.start) - self.start)

    def close(self, error: bool = False) -> None:
        if self.end is None:
            self.end = time.monotonic()
        if error:
            self.error = True


def group_span(
    name: str,
    traces: Iterable[Optional["Trace"]],
    start: float,
    end: Optional[float] = None,
    parent: Optional[Span] = None,
    error: bool = False,
    **attributes: Any,
) -> Span:
    """One span held by every trace given (``None`` entries skipped):
    what the requests of one coalesced batch share. ``end=None`` leaves
    it open; set ``end`` (or ``close()``) once, for all of them."""
    span = Span(name, parent, start, end, error, attributes or None)
    for trace in traces:
        if trace is not None:
            trace.spans.append(span)
    return span


class Trace:
    """All spans of one request/build, rooted at a single root span.

    The root opens at construction and closes at :meth:`finish`, which
    commits the trace to its tracer's ring/reservoir, or leaves that to
    :meth:`publish` where work after the root (a response's ``send``)
    still has a span to record. Span appends are plain list appends
    (GIL-atomic): the event loop and the scoring executor thread both
    record into in-flight traces. Readers only see a trace once it is
    published, and a published trace gains no span.
    """

    __slots__ = (
        "tracer",
        "trace_id",
        "name",
        "request_id",
        "parent_span_id",
        "keep_recent",
        "retained",
        "spans",
        "root",
        "_finished",
        "_published",
    )

    def __init__(
        self,
        tracer: Optional["Tracer"],
        name: str,
        trace_id: Optional[str] = None,
        request_id: Optional[str] = None,
        parent_span_id: Optional[str] = None,
        keep_recent: bool = True,
    ):
        self.tracer = tracer
        self.trace_id = trace_id or _new_trace_id()
        self.name = name
        self.request_id = request_id
        self.parent_span_id = parent_span_id
        self.keep_recent = keep_recent
        # set by Tracer._commit: True iff the finished trace actually
        # landed in the ring or the slow reservoir — references to a
        # trace id (exemplars, logs) should only be published when this
        # is True, or they dangle on a head-sample drop
        self.retained = False
        self.root = Span(name, None, time.monotonic())
        self.spans: List[Span] = [self.root]
        self._finished = False
        self._published = False

    # --------------------------- recording ---------------------------- #

    def start_span(
        self, name: str, parent: Optional[Span] = None, **attributes: Any
    ) -> Span:
        """Open a span now; close it with ``span.close()``. Parent
        defaults to the root."""
        span = Span(name, parent, time.monotonic(), attributes=attributes or None)
        self.spans.append(span)
        return span

    def add_span(
        self,
        name: str,
        start: float,
        end: float,
        parent: Optional[Span] = None,
        error: bool = False,
        **attributes: Any,
    ) -> Span:
        """Record a completed span from boundary timestamps measured
        elsewhere (monotonic seconds)."""
        span = Span(name, parent, start, max(start, end), error, attributes or None)
        self.spans.append(span)
        return span

    @contextlib.contextmanager
    def span(self, name: str, parent: Optional[Span] = None, **attributes: Any):
        """Context manager: the span closes on exit, with ``error=True``
        when the block raised (the exception propagates)."""
        span = self.start_span(name, parent=parent, **attributes)
        try:
            yield span
        except BaseException:
            span.close(error=True)
            raise
        else:
            span.close()

    def finish(
        self, error: bool = False, publish: bool = True, **attributes: Any
    ) -> None:
        """Close the root and, unless ``publish=False``, publish the trace.
        Idempotent: retry paths and shutdown sweeps may race one request's
        natural completion."""
        if self._finished:
            return
        self._finished = True
        if attributes:
            self.root.attributes.update(attributes)
        # an abandoned child (its owner crashed between start and close)
        # must not export as a still-open span pinning "now" forever
        for span in self.spans:
            if span.end is None and span is not self.root:
                span.close(error=True)
        self.root.close(error=error)
        if publish:
            self.publish()

    def publish(self) -> None:
        """Commit the finished trace to its tracer's ring/reservoir, once.
        Nothing is appended to a published trace."""
        if self._published:
            return
        self._published = True
        if self.tracer is not None:
            self.tracer._commit(self)

    # ----------------------------- reads ------------------------------ #

    @property
    def finished(self) -> bool:
        return self._finished

    @property
    def published(self) -> bool:
        return self._published

    @property
    def duration_s(self) -> float:
        return self.root.duration_s

    @property
    def error(self) -> bool:
        return any(s.error for s in self.spans)

    def _span_dict(self, span: Span, children: Dict[int, List[Span]]) -> dict:
        out: Dict[str, Any] = {
            "name": span.name,
            "span_id": span.span_id,
            "start_ms": round((span.start - self.root.start) * 1e3, 3),
            "duration_ms": round(span.duration_s * 1e3, 3),
        }
        if span.error:
            out["error"] = True
        if span.attributes:
            out["attributes"] = dict(span.attributes)
        kids = children.get(id(span))
        if kids:
            out["children"] = [self._span_dict(k, children) for k in kids]
        return out

    def children(self, parent: Optional[Span] = None) -> List[Span]:
        """The spans directly under ``parent`` (default: the root), by
        start time."""
        if parent is self.root:
            parent = None
        return sorted(
            (s for s in self.spans if s.parent is parent and s is not self.root),
            key=lambda s: s.start,
        )

    def tree(self) -> dict:
        """Nested span tree (children sorted by start time)."""
        held = {id(s) for s in self.spans}
        children: Dict[int, List[Span]] = {}
        for span in self.spans:
            if span is self.root:
                continue
            parent = span.parent
            # top-level spans, and orphans (their parent was never
            # registered in this trace) re-root so they stay visible
            # rather than silently vanishing from the tree
            if parent is None or id(parent) not in held:
                parent = self.root
            children.setdefault(id(parent), []).append(span)
        for kids in children.values():
            kids.sort(key=lambda s: s.start)
        return self._span_dict(self.root, children)

    def summary(self, spans: bool = True) -> dict:
        out: Dict[str, Any] = {
            "trace_id": self.trace_id,
            "name": self.name,
            "request_id": self.request_id,
            "start_unix": round(profiler_ns(self.root.start) * 1e-9, 3),
            "duration_ms": round(self.duration_s * 1e3, 3),
            "error": self.error,
            "n_spans": len(self.spans),
        }
        if spans:
            out["spans"] = self.tree()
        return out


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length of the union of ``(start, end)`` intervals: nested or
    overlapping ones count once."""
    total, edge = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > edge:
            total += end - max(start, edge)
            edge = end
    return total


def covered_seconds(spans: Iterable[Span]) -> float:
    """Seconds the (closed) spans cover between them; JAX reports an outer
    function's tracing over its callees', and those count once."""
    return union_length((s.start, s.end) for s in spans if s.end is not None)


def chrome_trace(traces: Iterable[Trace]) -> dict:
    """Chrome trace-event JSON for one or more traces: complete events
    (``ph: "X"``) with microsecond ``ts``/``dur``, one ``pid`` per trace
    so multiple requests render side by side in Perfetto. ``ts`` is on the
    JAX profiler's clock (:func:`profiler_ns`), one anchor for every
    trace of the process: concurrent traces align, and a span lies where
    the same moment lies in a profiler session's ``.xplane.pb``."""
    events: List[dict] = []
    for pid, trace in enumerate(traces, start=1):
        events.append(
            {
                "ph": "M",
                "pid": pid,
                "name": "process_name",
                "args": {
                    "name": f"{trace.name} {trace.trace_id[:8]}"
                    + (f" rid={trace.request_id}" if trace.request_id else "")
                },
            }
        )
        for span in trace.spans:
            args: Dict[str, Any] = {"trace_id": trace.trace_id}
            if span.attributes:
                args.update(span.attributes)
            if span.error:
                args["error"] = True
            events.append(
                {
                    "ph": "X",
                    "pid": pid,
                    "tid": 1,
                    "name": span.name,
                    "cat": trace.name,
                    "ts": round(profiler_ns(span.start) * 1e-3, 3),
                    "dur": round(span.duration_s * 1e6, 3),
                    "args": args,
                }
            )
    return {"displayTimeUnit": "ms", "traceEvents": events}


class Tracer:
    """Process/app-scoped trace source, retention, and flight recorder.

    ``sample`` <= 0 disables tracing: ``start_trace`` returns ``None``
    and every instrumented call site skips on that single check (the
    near-free-when-disabled contract, guarded by the hot-loop overhead
    test). With ``0 < sample``, EVERY request records spans; ``sample``
    head-controls which completed traces enter the recent ring, while
    the slow reservoir (worst-N by duration) considers all of them —
    head-sampling for volume, always-sample-slow for the tail.
    """

    def __init__(
        self,
        sample: Optional[float] = None,
        ring: Optional[int] = None,
        slow_keep: Optional[int] = None,
    ):
        if sample is None:
            sample = _env_float("GORDO_TRACE_SAMPLE", 0.1)
        if ring is None:
            ring = int(_env_float("GORDO_TRACE_RING", 128))
        if slow_keep is None:
            slow_keep = int(_env_float("GORDO_TRACE_SLOW_KEEP", 16))
        self.sample = float(sample)
        self.slow_keep = max(1, slow_keep)
        self._recent: "deque[Trace]" = deque(maxlen=max(1, ring))
        self._slow: List[Tuple[float, int, Trace]] = []  # min-heap
        self._seq = itertools.count()
        self._rng = random.Random()
        self._lock = threading.Lock()  # commit path only, never recording
        self.started = 0
        self.finished = 0

    @property
    def enabled(self) -> bool:
        return self.sample > 0.0

    @property
    def inflight(self) -> int:
        """Traces started but not yet finished — a growing value under
        load means a code path leaks open traces (the chaos suite
        asserts this returns to zero)."""
        return self.started - self.finished

    def start_trace(
        self,
        name: str,
        traceparent: Optional[str] = None,
        request_id: Optional[str] = None,
        force: bool = False,
    ) -> Optional[Trace]:
        """New in-flight trace, or ``None`` when tracing is disabled.

        A valid ``traceparent`` continues the upstream trace id; its
        ``sampled`` flag (or ``force=True``) pins the trace into the
        recent ring regardless of head sampling."""
        if self.sample <= 0.0:
            return None
        trace_id = parent_span = None
        upstream_sampled = False
        parsed = parse_traceparent(traceparent)
        if parsed is not None:
            trace_id, parent_span, upstream_sampled = parsed
        keep = (
            force
            or upstream_sampled
            or self.sample >= 1.0
            or self._rng.random() < self.sample
        )
        self.started += 1
        return Trace(
            self,
            name,
            trace_id=trace_id,
            request_id=request_id,
            parent_span_id=parent_span,
            keep_recent=keep,
        )

    def _commit(self, trace: Trace) -> None:
        self.finished += 1
        with self._lock:
            if trace.keep_recent:
                self._recent.append(trace)
                trace.retained = True
            # the flight recorder: every completed trace competes for the
            # worst-N reservoir, so slow requests survive head sampling
            item = (trace.duration_s, next(self._seq), trace)
            if len(self._slow) < self.slow_keep:
                heapq.heappush(self._slow, item)
                trace.retained = True
            elif item[0] > self._slow[0][0]:
                heapq.heapreplace(self._slow, item)
                trace.retained = True

    # ----------------------------- reads ------------------------------ #

    def recent(self, n: Optional[int] = None) -> List[Trace]:
        """Completed retained traces, most recent first. ``n`` <= 0 (or
        None) returns everything — a negative slice must never silently
        drop the newest traces."""
        with self._lock:
            out = list(self._recent)
        out.reverse()
        return out[:n] if n is not None and n > 0 else out

    def slow(self, n: Optional[int] = None) -> List[Trace]:
        """The reservoir's worst traces, slowest first; same ``n``
        semantics as :meth:`recent`."""
        with self._lock:
            out = [t for _, _, t in sorted(self._slow, reverse=True)]
        return out[:n] if n is not None and n > 0 else out

    def find(self, trace_id: str) -> List[Trace]:
        """Retained traces matching ``trace_id`` (ring + reservoir)."""
        with self._lock:
            seen = []
            for t in list(self._recent) + [t for _, _, t in self._slow]:
                if t.trace_id == trace_id and t not in seen:
                    seen.append(t)
        return seen


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"{name} must be a number, got {raw!r}") from None


# process-default tracer (builder processes trace without plumbing;
# the server builds a per-app tracer, same split as the metrics registry)
_DEFAULT: Optional[Tracer] = None


def get_tracer() -> Tracer:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Tracer()
    return _DEFAULT


# ------------------------------------------------------------------ #
# current-trace propagation (builder path: build_fleet sets it, the
# fleet trainer's bucket loop and checkpoint writer read it — no
# parameter threading through six call layers)
# ------------------------------------------------------------------ #

_CURRENT: "contextvars.ContextVar[Optional[Trace]]" = contextvars.ContextVar(
    "gordo_current_trace", default=None
)
# the span new children of the current trace hang under (the trainer's
# open ``fit:<bucket>``); None = the root
_CURRENT_PARENT: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "gordo_current_parent", default=None
)


def current_trace() -> Optional[Trace]:
    return _CURRENT.get()


@contextlib.contextmanager
def use_trace(trace: Optional[Trace], parent: Optional[Span] = None):
    token = _CURRENT.set(trace)
    parent_token = _CURRENT_PARENT.set(parent)
    try:
        yield trace
    finally:
        _CURRENT_PARENT.reset(parent_token)
        _CURRENT.reset(token)


# ------------------------------------------------------------------ #
# stages: one span and one profiler annotation from the same clock reads
# ------------------------------------------------------------------ #

ANNOTATION_PREFIX = "gordo:"

# JAX's own duration events -> span names on the current trace. Both
# tracing events read as ``trace_lower``; ``cache_load`` (a persistent
# cache hit) falls inside the ``backend_compile`` that asked for it.
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_lower",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}

# JAX reports every eager primitive's first trace too (tens of
# microseconds each, hundreds in a process's first fit): noise in a tree
# whose job is to say which program recompiled
_MIN_COMPILE_SPAN_S = 1e-3

_annotation: Any = None  # jax.profiler.TraceAnnotation, bound on first use


def _on_jax_duration(event: str, duration_secs: float, **kwargs: Any) -> None:
    """``jax.monitoring`` listener: JAX reports a duration when the work
    ends, on the thread that did it, so the span is ``[now - d, now]`` on
    that thread's current trace. No current trace, nothing recorded."""
    name = _COMPILE_SPANS.get(event)
    if name is None or duration_secs < _MIN_COMPILE_SPAN_S:
        return
    trace = _CURRENT.get()
    if trace is None or trace.finished:
        return
    end = time.monotonic()
    trace.add_span(
        name, end - duration_secs, end, parent=_CURRENT_PARENT.get(), **kwargs
    )


def _bind_jax():
    """First :class:`stage` of the process: import JAX's profiler (this
    module stays importable without JAX) and register the compile
    listener, once."""
    global _annotation
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _annotation = jax.profiler.TraceAnnotation
    return _annotation


class stage:
    """``with stage("pad", *traces):`` — one stage of the program's work.

    Opens ``jax.profiler.TraceAnnotation("gordo:" + name)`` (a no-op in
    the runtime unless a profiler session is open) and, on exit, appends
    one span ``name`` with the same two ``time.monotonic()`` reads to each
    trace given (``None`` entries are skipped; no trace at all is fine).
    Always on: there is no flag. ``start``/``end``/``seconds`` stay
    readable after the block for callers that account the same interval
    elsewhere (the goodput ledger, ``epoch_seconds``).

    A span is the HOST's time in the stage: nothing is fenced, so a stage
    that launches device work asynchronously ends when the launch
    returns, and the annotation puts it beside the device ops it
    launched. Never wrap an ``await``: a profiler region must not
    straddle one (record such a span with :meth:`Trace.add_span`).

    ``parent``: the span the new one hangs under (default: each trace's
    root). One span object is held by every trace given
    (:func:`group_span`). Keyword arguments become span attributes, and
    ``attributes`` may be amended inside the block. The span is flagged
    as an error when the block raises, or when the block sets ``error``
    (a failure it handled itself)."""

    __slots__ = (
        "name", "traces", "parent", "attributes", "error", "start", "end",
        "_region",
    )

    def __init__(
        self,
        name: str,
        *traces: Optional[Trace],
        parent: Optional[Span] = None,
        **attributes: Any,
    ):
        self.name = name
        self.traces = traces
        self.parent = parent
        self.attributes = attributes
        self.error = False
        self._region = (_annotation or _bind_jax())(ANNOTATION_PREFIX + name)

    def __enter__(self) -> "stage":
        self._region.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end = time.monotonic()
        self._region.__exit__(exc_type, exc, tb)
        if self.traces:
            group_span(
                self.name,
                self.traces,
                self.start,
                self.end,
                self.parent,
                self.error or exc_type is not None,
                **self.attributes,
            )

    @property
    def seconds(self) -> float:
        return self.end - self.start
