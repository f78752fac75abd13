"""Weighted-fair queueing for the batching engine.

:class:`WeightedFairQueue` is a drop-in replacement for the engine's
``asyncio.Queue[_Pending]`` (same ``put`` / ``put_nowait`` / ``get`` /
``get_nowait`` / ``qsize`` / ``empty`` surface) that dequeues across
priority classes by virtual time — classic WFQ/DRR, cost 1 per request:

- each class ``c`` keeps a virtual clock ``vtime[c]``; popping one of
  its requests advances it by ``1 / weight[c]``;
- ``get`` serves the nonempty class with the SMALLEST virtual clock, so
  over any busy interval class ``c`` receives ``weight[c] / sum(weights
  of backlogged classes)`` of the dequeues — a best-effort flood can
  delay interactive traffic by at most that ratio, never starve it;
- a class waking from idle has its clock caught up to the minimum
  backlogged clock first, so idleness never banks credit for a burst
  (standard virtual-time start rule).

Inside a class, requests pop in deadline order (earliest
``expires_at`` first; requests without a deadline keep FIFO order after
all deadlined ones with earlier expiry) — the "class-aware deadline
ordering inside a batch window" half of the tentpole: when the engine
can only fit part of a backlog into one batch, it takes the
entries closest to timing out first instead of whatever arrived first.

With every request in one class (the no-config default) behavior is
FIFO among no-deadline requests, exactly the pre-QoS queue.
"""

from __future__ import annotations

import asyncio
import heapq
import os
from typing import Any, Dict, Mapping, Optional

from gordo_components_tpu.qos.classify import CLASSES, DEFAULT_CLASS

#: Default class weights: interactive gets 8 dequeues for every 1 a
#: best-effort backlog gets while both are backlogged.
DEFAULT_WEIGHTS: Dict[str, float] = {
    "interactive": 8.0,
    "batch": 2.0,
    "best_effort": 1.0,
}

_ENV_WEIGHTS = "GORDO_QOS_WEIGHTS"


def parse_weights(spec: Optional[str] = None) -> Dict[str, float]:
    """Class weights from ``GORDO_QOS_WEIGHTS`` (``"interactive=8,
    batch=2,best_effort=1"``). Unknown classes and non-positive weights
    are ignored; missing classes keep their defaults — a malformed knob
    degrades to the shipped policy, never to a crash at boot."""
    weights = dict(DEFAULT_WEIGHTS)
    if spec is None:
        spec = os.environ.get(_ENV_WEIGHTS, "")
    for part in spec.split(","):
        if "=" not in part:
            continue
        name, _, raw = part.partition("=")
        name = name.strip().lower().replace("-", "_")
        if name not in weights:
            continue
        try:
            value = float(raw)
        except ValueError:
            continue
        if value > 0:
            weights[name] = value
    return weights


class WeightedFairQueue:
    """Duck-compatible ``asyncio.Queue`` with per-class WFQ dequeue.

    Internally an ``asyncio.Queue`` of wake-up tokens carries the
    blocking semantics (one token per enqueued item, so ``get`` awaits
    and ``wait_for`` cancellation behave exactly like the real queue),
    while items live in per-class heaps ordered by deadline."""

    def __init__(self, weights: Optional[Mapping[str, float]] = None):
        merged = dict(DEFAULT_WEIGHTS)
        if weights:
            for name, value in weights.items():
                if name in merged and value > 0:
                    merged[name] = float(value)
        self.weights = merged
        self._tokens: "asyncio.Queue[None]" = asyncio.Queue()
        self._heaps: Dict[str, list] = {c: [] for c in CLASSES}
        self._vtime: Dict[str, float] = {c: 0.0 for c in CLASSES}
        self._seq = 0  # FIFO tiebreak within equal deadlines
        # dequeues per class since construction — the fairness evidence
        # GET /qos and the starvation-bound test read
        self.dequeued: Dict[str, int] = {c: 0 for c in CLASSES}

    # -- asyncio.Queue surface ---------------------------------------- #

    def qsize(self) -> int:
        return sum(len(h) for h in self._heaps.values())

    def empty(self) -> bool:
        return self.qsize() == 0

    def put_nowait(self, item: Any) -> None:
        cls = getattr(item, "qos_class", None)
        if cls not in self._heaps:
            cls = DEFAULT_CLASS
        heap = self._heaps[cls]
        if not heap:
            # idle -> backlogged: catch the clock up so the idle period
            # didn't bank credit that would let this class burst ahead
            backlogged = [
                self._vtime[c] for c, h in self._heaps.items() if h
            ]
            if backlogged:
                self._vtime[cls] = max(self._vtime[cls], min(backlogged))
        deadline = getattr(item, "deadline", None)
        expires = (
            deadline.expires_at
            if deadline is not None and getattr(deadline, "expires_at", None) is not None
            else float("inf")
        )
        self._seq += 1
        heapq.heappush(heap, (expires, self._seq, item))
        self._tokens.put_nowait(None)

    async def put(self, item: Any) -> None:
        self.put_nowait(item)  # unbounded, like the engine's asyncio.Queue()

    def get_nowait(self) -> Any:
        self._tokens.get_nowait()  # raises asyncio.QueueEmpty when drained
        return self._pop()

    async def get(self) -> Any:
        await self._tokens.get()
        return self._pop()

    # -- WFQ core ------------------------------------------------------ #

    def _pop(self) -> Any:
        best = None
        for cls in CLASSES:  # class order is the deterministic tiebreak
            if not self._heaps[cls]:
                continue
            if best is None or self._vtime[cls] < self._vtime[best]:
                best = cls
        if best is None:  # token/heap desync would be a bug, not a state
            raise asyncio.QueueEmpty
        self._vtime[best] += 1.0 / self.weights[best]
        self.dequeued[best] += 1
        _, _, item = heapq.heappop(self._heaps[best])
        return item

    def depths(self) -> Dict[str, int]:
        """Live per-class backlog (for GET /qos and the engine gauge)."""
        return {c: len(h) for c, h in self._heaps.items()}

    def snapshot(self) -> dict:
        """Queue state for GET /qos: weights, per-class depth/virtual
        clock/served count."""
        return {
            "weights": dict(self.weights),
            "depth": self.depths(),
            "vtime": {c: round(v, 6) for c, v in self._vtime.items()},
            "dequeued": dict(self.dequeued),
        }
