"""Per-request deadlines, propagated end to end.

Under fleet-backfill saturation the failure mode is not "the server is
slow" but "the server is busy answering clients that gave up seconds
ago": admitted work never expired, so every queued request eventually
burned a device dispatch whether or not anyone was still waiting — the
metastable-overload recipe ("ML Productivity Goodput", PAPERS.md). The
fix is a budget that travels WITH the request:

- the client stamps ``X-Gordo-Deadline-Ms`` (its remaining patience) on
  every scoring POST;
- the server middleware parses it (or applies the operator default
  ``GORDO_DEFAULT_DEADLINE_MS``) into a :class:`Deadline` carried on the
  request;
- the batching engine drops already-expired entries *before* device
  dispatch, resolving their futures with :class:`DeadlineExceeded`
  (HTTP 504), so TPU time is spent only on answers someone still wants;
- ``ModelBank.score_many`` checks the remaining budget between bucket
  group dispatches, so a multi-group batch stops mid-way instead of
  finishing work nobody will read.

:class:`DeadlineExceeded` subclasses :class:`asyncio.TimeoutError` so
existing best-effort call sites (watchman scrapes, the shared
``fetch_metadata_all`` helper) that already catch timeouts degrade the
same way for a blown deadline — one exception family for "out of
time" everywhere.

Deadlines are monotonic-clock absolute instants: immune to wall-clock
steps, comparable across the event loop and executor threads in one
process, and deliberately NOT serialized across hosts (the header
carries a relative budget in ms; each hop re-anchors it on its own
clock, the standard cross-host propagation trick).
"""

import asyncio
import math
import os
import time
from typing import Any, Awaitable, Optional

__all__ = [
    "DEADLINE_HEADER",
    "Deadline",
    "DeadlineExceeded",
    "MAX_DEADLINE_MS",
    "default_deadline_ms",
    "parse_deadline_ms",
]

DEADLINE_HEADER = "X-Gordo-Deadline-Ms"
ENV_DEFAULT = "GORDO_DEFAULT_DEADLINE_MS"

# clamp ceiling for client-supplied budgets: the header is attacker
# adjacent (any HTTP peer sets it) and a near-infinite float must not
# produce a deadline that never expires where the operator expected one
MAX_DEADLINE_MS = 24 * 3600 * 1e3


class DeadlineExceeded(asyncio.TimeoutError):
    """The request's time budget ran out before the work completed.

    Maps to HTTP 504 at the serving layer (with the request id, like the
    500/410 paths). Subclasses ``asyncio.TimeoutError`` so generic
    timeout handling (retry loops, best-effort scrapes) needs no new
    catch clause.
    """


class Deadline:
    """An absolute monotonic expiry instant with its original budget.

    Cheap by design: construction is one ``time.monotonic()`` read, and
    :meth:`expired` is one read + one compare — it sits on the engine's
    per-pending dispatch path (see the hotloop guard in
    ``tests/test_deadline.py``).
    """

    __slots__ = ("expires_at", "budget_s")

    def __init__(self, seconds: float):
        self.budget_s = max(0.0, float(seconds))
        self.expires_at = time.monotonic() + self.budget_s

    @classmethod
    def after_ms(cls, ms: float) -> "Deadline":
        return cls(float(ms) / 1e3)

    def expired(self, now: Optional[float] = None) -> bool:
        """``now`` lets a batch loop reuse one clock read for N checks."""
        return (time.monotonic() if now is None else now) >= self.expires_at

    def remaining_s(self) -> float:
        """Seconds left; clamped at 0 (an expired deadline has no
        negative budget to hand downstream)."""
        return max(0.0, self.expires_at - time.monotonic())

    def remaining_ms(self) -> float:
        return self.remaining_s() * 1e3

    async def wait_for(self, awaitable: Awaitable[Any]) -> Any:
        """``asyncio.wait_for`` bounded by the REMAINING budget, raising
        :class:`DeadlineExceeded` — the shared helper behind watchman's
        scrape/refresh bounds and the client's per-attempt bound, so
        every "give up after" in the stack expires the same way."""
        try:
            return await asyncio.wait_for(awaitable, timeout=self.remaining_s())
        except asyncio.TimeoutError:
            raise DeadlineExceeded(
                f"deadline exceeded after {self.budget_s:.3f}s budget"
            ) from None

    def __repr__(self) -> str:
        return f"<Deadline budget={self.budget_s:.3f}s remaining={self.remaining_s():.3f}s>"


def parse_deadline_ms(raw: Optional[str]) -> Optional[float]:
    """Milliseconds from a ``X-Gordo-Deadline-Ms`` header value, or None.

    Malformed, non-finite, and non-positive values return None (the
    request proceeds under the server default) rather than 400: the
    header is best-effort metadata from heterogeneous clients/proxies,
    and rejecting the request over it would turn a telemetry hint into
    an outage. Values clamp to :data:`MAX_DEADLINE_MS`.
    """
    if not raw:
        return None
    try:
        ms = float(raw.strip())
    except (TypeError, ValueError):
        return None
    if not math.isfinite(ms) or ms <= 0:
        return None
    return min(ms, MAX_DEADLINE_MS)


def default_deadline_ms() -> Optional[float]:
    """Operator default budget from ``GORDO_DEFAULT_DEADLINE_MS``
    (milliseconds; unset/empty = no default). Malformed values RAISE —
    this deploys fleet-wide, and silently dropping a typo'd default
    would disable deadline protection with no signal (same contract as
    the server's other env knobs)."""
    raw = os.environ.get(ENV_DEFAULT, "").strip()
    if not raw:
        return None
    try:
        ms = float(raw)
    except ValueError:
        raise ValueError(
            f"{ENV_DEFAULT} must be a number of milliseconds, got {raw!r}"
        ) from None
    if not math.isfinite(ms) or ms <= 0:
        raise ValueError(
            f"{ENV_DEFAULT} must be a positive finite number of ms, got {raw!r}"
        )
    return min(ms, MAX_DEADLINE_MS)
