"""BatchingEngine dispatch rule: work-conserving by default (no flush
window), so a lone request goes straight to the bank and a batch is
whatever queued while the last call was out; ``flush_ms`` > 0 still holds
a batch open for company. A stub bank records each call's size; nothing
here depends on how long anything takes."""

import asyncio
import threading

import numpy as np
import pytest

from gordo_components_tpu.server.bank import BatchingEngine


class _StubBank:
    """Minimal bank: records the size of every ``score_many`` call and,
    while ``gate`` is clear, holds the call on its executor thread."""

    def __init__(self, gated: bool = False):
        self.calls = []
        self.gate = threading.Event()
        if not gated:
            self.gate.set()

    def __contains__(self, name):
        return True

    def score_many(self, requests):
        self.calls.append(len(requests))
        self.gate.wait()
        return [float(X.sum()) for _name, X, _y in requests]

    def score(self, name, X, y=None):
        return self.score_many([(name, X, y)])[0]


def _x(i: int) -> np.ndarray:
    return np.full((2, 3), float(i), np.float32)


async def _until(predicate) -> None:
    # yields to the loop (and the executor thread) until the engine has
    # reached the state the test needs; no time bound is asserted
    while not predicate():
        await asyncio.sleep(0.001)


async def test_lone_request_dispatches_without_a_timer(monkeypatch):
    bank = _StubBank()
    engine = BatchingEngine(bank)
    assert engine.flush_s == 0
    guard = asyncio.wait_for  # the test's own timeout, saved before the patch

    def no_timer(*args, **kwargs):
        raise AssertionError("the default engine armed a flush timer")

    monkeypatch.setattr(asyncio, "wait_for", no_timer)
    try:
        result = await guard(engine.score("m", _x(1)), timeout=60)
    finally:
        await engine.stop()
    assert result == float(_x(1).sum())
    assert bank.calls == [1]
    assert engine.stats["requests_behind"] == 0


async def test_arrivals_during_a_call_form_one_next_batch():
    bank = _StubBank(gated=True)
    engine = BatchingEngine(bank, max_batch=8)
    try:
        first = asyncio.ensure_future(engine.score("m", _x(0)))
        await _until(lambda: bank.calls)  # the first call is out, held
        behind = [asyncio.ensure_future(engine.score("m", _x(i))) for i in (1, 2, 3)]
        await _until(lambda: engine._queue.qsize() == 3)
        bank.gate.set()
        results = await asyncio.gather(first, *behind)
    finally:
        await engine.stop()
    assert results == [float(_x(i).sum()) for i in range(4)]
    assert bank.calls == [1, 3]
    assert engine.stats["batches"] == 2
    assert engine.stats["requests"] == 4
    assert engine.stats["requests_behind"] == 3


@pytest.mark.parametrize(
    "flush_ms, calls",
    [(0.0, [1, 1]), (60_000.0, [2])],
    ids=["no-window", "window"],
)
async def test_staggered_arrivals_coalesce_only_inside_a_window(flush_ms, calls):
    """B arrives after the loop took A. Without a window A has already
    gone; with one (long enough never to close here) A waits, and the
    batch leaves the moment B fills it to ``max_batch``."""
    bank = _StubBank()
    engine = BatchingEngine(bank, max_batch=2, flush_ms=flush_ms)
    try:
        a = asyncio.ensure_future(engine.score("m", _x(1)))
        # the loop has taken A off the queue (and, with no window, sent it)
        await _until(lambda: engine._task is not None and engine._queue.qsize() == 0)
        b = asyncio.ensure_future(engine.score("m", _x(2)))
        assert await asyncio.gather(a, b) == [6.0, 12.0]
    finally:
        await engine.stop()
    assert bank.calls == calls


async def test_drain_estimate_is_positive_without_a_window():
    engine = BatchingEngine(_StubBank())
    assert engine.flush_s == 0
    assert engine.drain_estimate(0) > 0  # before any request was served
    try:
        await engine.score("m", _x(1))
    finally:
        await engine.stop()
    assert engine.service.count == 1
    assert engine.drain_estimate(0) > 0
    assert engine.drain_estimate(0) <= engine.drain_estimate(8 * engine.max_batch)


def test_requests_behind_is_exposed_as_a_counter():
    from gordo_components_tpu.observability.metrics import MetricsRegistry

    engine = BatchingEngine(_StubBank(), registry=MetricsRegistry())
    engine.stats["requests_behind"] = 5
    families = {m[0]: m for m in engine._collect_metrics()}
    name, kind, _help, labels, value = families["gordo_engine_requests_behind_total"]
    assert (kind, labels, value) == ("counter", {}, 5)
