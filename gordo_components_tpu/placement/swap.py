"""Zero-downtime double-buffered bank swap.

The bank's stacked device state is immutable after ``finalize()`` —
there is no in-place "move member i to shard d" (a scatter into a live
NamedSharding'd pytree would race in-flight XLA calls). Instead the
swap is double-buffered, the same discipline a GPU ring buffer uses:

1. **build** — a complete second :class:`ModelBank` (stacked, quantized,
   compiled) is constructed off to the side while the old one keeps
   serving. Peak HBM briefly holds both generations' weight stacks —
   the cost of never pausing (docs/operations.md budgets it).
2. **warm** — the new bank's bucket programs pre-compile off the
   request path, so the first post-swap request pays no XLA compile.
3. **flip** — one generation-pointer swing: ``app["bank"]`` and the
   batching engine's ``bank`` reference move to the new object. Batches
   already handed to the scoring executor captured the OLD bank object
   and drain on it untouched; batches dispatched after the flip score
   on the new generation. No request ever observes a half-built bank,
   so there is no 5xx window — the pause is the pointer swing itself,
   measured and exported as ``gordo_rebalance_swap_pause_seconds``.
4. **drop** — the old generation's device buffers free when its last
   in-flight batch completes and the final reference dies (GC), bounded
   by the observational drain wait (``GORDO_SWAP_DRAIN_S``).

``bank.swap`` is the chaos site: an injected fault mid-flip rolls the
pointer (and the registry's keyed collectors) back to the old
generation — requests keep scoring on the old bank as if the swap was
never attempted. ``/reload`` routes through the same primitive, so
model upgrades inherit the identical no-5xx guarantee.
"""

import logging
import os
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Mapping, MutableMapping, Optional

from gordo_components_tpu.resilience.faults import faultpoint
from gordo_components_tpu.server.bank import BatchingEngine, ModelBank

logger = logging.getLogger(__name__)

# chaos site (tests/test_placement.py): fired between the app-pointer
# and engine-pointer swings — the worst possible instant — so the
# rollback path is exercised exactly where a real crash would land
_FP_SWAP = faultpoint("bank.swap")

# registry collectors a bank registers under fixed keys; a rolled-back
# swap must restore the OLD bank's entries or its series would vanish
# from the exposition (a scrape gap Prometheus reads as churn).
# bank_heat / bank_cost are APP-level accountants (observability/heat.py
# and cost.py) that follow the live bank rather than belonging to one —
# snapshotting them alongside keeps a rolled-back swap's exposition
# byte-identical to the pre-swap one.
_BANK_COLLECTOR_KEYS = ("bank_pipeline", "bank_capacity", "bank_heat", "bank_cost")


def _loop_running() -> bool:
    import asyncio

    try:
        asyncio.get_running_loop()
    except RuntimeError:
        return False
    return True


@dataclass
class SwapResult:
    generation: int
    pause_s: float  # the flip critical section (the only serving pause)
    bank_models: int
    build_s: float = 0.0
    warmup_s: float = 0.0


def snapshot_collectors(registry) -> Optional[Dict[str, Any]]:
    """Capture the bank-owned registry collectors BEFORE building the
    replacement bank (whose construction overwrites them), so a failed
    swap can restore the old bank's exposition exactly."""
    if registry is None:
        return None
    return {
        key: registry.get_collector(key) for key in _BANK_COLLECTOR_KEYS
    }


def _restore_collectors(registry, prev: Optional[Dict[str, Any]]) -> None:
    if registry is None or not prev:
        return
    for key, fn in prev.items():
        if fn is not None:
            registry.collector(fn, key=key)


def ordered_models(
    models: Mapping[str, Any],
    member_order: Optional[Mapping[str, List[str]]],
) -> Dict[str, Any]:
    """Models dict in planned stacking order.

    Bucket membership is a function of each model's architecture, not of
    insertion order — only the order of members *within* a bucket (their
    stack index, hence their owning shard) follows insertion. Emitting
    the planned per-bucket orders first therefore realizes the plan
    exactly; models the plan doesn't mention keep their original
    relative order. Names no longer present are skipped (a reload may
    have removed them since the plan was computed)."""
    if not member_order:
        return dict(models)
    planned: List[str] = []
    seen = set()
    for names in member_order.values():
        for name in names:
            if name in models and name not in seen:
                planned.append(name)
                seen.add(name)
    out = {name: models[name] for name in planned}
    for name, model in models.items():
        if name not in seen:
            out[name] = model
    return out


def build_bank(
    app: MutableMapping[str, Any],
    models: Mapping[str, Any],
    member_order: Optional[Mapping[str, List[str]]] = None,
    warmup: Optional[bool] = None,
) -> ModelBank:
    """Stage 1+2 of the swap: the off-to-the-side build + warm compile.

    Blocking (runs XLA compiles) — call it from an executor thread, the
    way ``/reload`` and the controller do. ``app`` is the aiohttp app
    (or any mapping carrying the same keys): the new bank is built under
    the SAME mesh, registry, pipeline/precision config, and goodput
    ledger the app booted with, so a swap never silently changes
    tuning. The old bank's observed per-model routed rows carry over —
    the planner's load signal must survive its own swap."""
    t0 = time.monotonic()
    cfg = app.get("bank_config") or {}
    bank = ModelBank.from_models(
        ordered_models(models, member_order),
        mesh=app.get("bank_mesh"),
        registry=app.get("metrics"),
        inflight=cfg.get("inflight"),
        arena_max_mb=cfg.get("arena_max_mb"),
        bank_dtype=cfg.get("bank_dtype"),
        bank_kernel=cfg.get("bank_kernel"),
        ledger=app.get("goodput"),
        # the app-level heat accountant rides into every generation: the
        # decayed per-member history survives the swap, only the bank
        # feeding it changes (ModelBank.__init__ re-binds the
        # member->bucket attribution to the new bank)
        heat=app.get("heat"),
    )
    bank.build_s = time.monotonic() - t0
    old = app.get("bank")
    if old is not None and getattr(old, "model_rows", None) and (
        bank.model_rows is not None
    ):
        # .copy() is one C-level (GIL-atomic) operation: the old bank is
        # still SERVING while this builds, and iterating its live dict
        # directly could see a scoring thread's first-request insert
        # mid-iteration (RuntimeError: dict changed size)
        for name, rows in old.model_rows.copy().items():
            if name in bank:
                bank.model_rows[name] = rows
    if warmup is None:
        warmup = os.environ.get("GORDO_SERVER_WARMUP", "1") != "0"
    t1 = time.monotonic()
    if warmup and len(bank):
        bank.warmup()
    bank.warmup_s = time.monotonic() - t1
    return bank


def swap_bank(
    app: MutableMapping[str, Any],
    new_bank: ModelBank,
    prev_collectors: Optional[Dict[str, Any]] = None,
) -> SwapResult:
    """Stage 3: the atomic generation flip (event-loop thread only —
    the handlers that read these pointers all run on it, so the flip is
    one bytecode-level pointer swing per reader, never a torn state).

    On ANY failure inside the critical section (the ``bank.swap``
    faultpoint is armed exactly here) every pointer — app bank, engine
    bank, generation, registry collectors — rolls back to the old
    generation and the exception propagates; in-flight and future
    requests keep scoring on the old bank with no dropped request."""
    old_bank = app.get("bank")
    engine = app.get("bank_engine")
    old_engine_bank = getattr(engine, "bank", None)
    old_generation = int(app.get("bank_generation", 0))
    generation = old_generation + 1
    engine_created = False
    t0 = time.monotonic()
    try:
        new_bank.generation = generation
        app["bank"] = new_bank
        _FP_SWAP.fire()
        if engine is not None:
            # in-flight batches hold the old bank object and drain on it
            engine.bank = new_bank
            # multi-worker pool (server/workers.py): the per-worker-loop
            # engines front the same bank and must flip with it — a
            # worker still pointing at the old generation would split
            # the fleet's serving truth
            for _wid, weng in app.get("worker_engines") or ():
                weng.bank = new_bank
        elif len(new_bank) and _loop_running():
            # first generation with bankable members: the engine starts
            # here (the same path build_app's startup hook uses). Only
            # on an event loop — the north-star check drives the swap
            # synchronously against a bare bank and own their engines.
            cfg = app.get("bank_config") or {}
            engine = BatchingEngine(
                new_bank,
                max_batch=cfg.get("max_batch", 64),
                flush_ms=cfg.get("flush_ms", 0.0),
                max_queue=cfg.get("max_queue"),
            )
            engine.start()
            app["bank_engine"] = engine
            engine_created = True
        app["bank_generation"] = generation
    except BaseException:
        app["bank"] = old_bank
        if engine is not None:
            if engine_created:
                app.pop("bank_engine", None)
            elif old_engine_bank is not None:
                engine.bank = old_engine_bank
                for _wid, weng in app.get("worker_engines") or ():
                    weng.bank = old_engine_bank
        app["bank_generation"] = old_generation
        _restore_collectors(app.get("metrics"), prev_collectors)
        logger.error(
            "bank swap to generation %d FAILED mid-flip; rolled back to "
            "generation %d (old bank keeps serving)",
            generation, old_generation, exc_info=True,
        )
        events = app.get("events")
        if events is not None:
            events.emit(
                "bank.swap_failed",
                severity="error",
                generation=old_generation,
                attempted=generation,
            )
        raise
    pause_s = time.monotonic() - t0
    logger.info(
        "bank swapped to generation %d (%d model(s), flip pause %.3fms)",
        generation, len(new_bank), pause_s * 1e3,
    )
    events = app.get("events")
    if events is not None:
        # the ONE anchor every generation change shares (/reload,
        # rebalance, adapt, mesh acquire/release all land here), so the
        # timeline records every swap exactly once
        events.emit(
            "bank.swap",
            generation=generation,
            models=len(new_bank),
            pause_ms=round(pause_s * 1e3, 3),
        )
    return SwapResult(
        generation=generation,
        pause_s=pause_s,
        bank_models=len(new_bank),
        build_s=getattr(new_bank, "build_s", 0.0),
        warmup_s=getattr(new_bank, "warmup_s", 0.0),
    )


async def wait_drained(old_bank, timeout_s: float) -> bool:
    """Stage 4, observational: wait (bounded) for the old generation's
    in-flight pipeline groups to reach zero so "old buffers dropped" is
    a logged fact, not an assumption. The swap's correctness never
    depends on this — executor batches hold their own reference and the
    buffers free on GC regardless — but the rebalance trace should say
    when the old generation actually went quiet."""
    import asyncio

    if old_bank is None:
        return True
    deadline = time.monotonic() + max(0.0, timeout_s)
    while time.monotonic() < deadline:
        if getattr(old_bank, "_inflight_now", 0) == 0:
            return True
        await asyncio.sleep(0.01)
    return getattr(old_bank, "_inflight_now", 0) == 0
