"""The placement control loop.

One controller per serving app (``build_app`` attaches it as
``app["placement"]`` whenever the bank is enabled). It owns:

- the decision window: per-model routed rows are read as DELTAS since
  the last applied plan, so a week of balanced history can never bury a
  newly hot model (the same windowing watchman's fleet skew uses);
- plan evaluation + the swap pipeline (build in an executor thread,
  flip on the event loop, observational drain), serialized under the
  app's reload lock — a rebalance and a ``/reload`` both rebuild the
  bank and must never interleave;
- the ``GORDO_REBALANCE=auto`` background evaluator;
- the ``gordo_rebalance_*`` / ``gordo_bank_generation`` metric surface
  and the forced ``rebalance`` trace (span children: ``plan`` /
  ``build`` / ``swap`` / ``drain``).
"""

import asyncio
import functools
import logging
import os
import time
from typing import Any, Dict, Optional

from gordo_components_tpu.placement.planner import (
    RebalancePlan,
    default_threshold,
    plan_rebalance,
    skew_ratio,
)
from gordo_components_tpu.placement.swap import (
    build_bank,
    snapshot_collectors,
    swap_bank,
    wait_drained,
)

logger = logging.getLogger(__name__)


def _env_num(name: str, default, cast):
    raw = os.environ.get(name)
    if not raw:
        return default
    try:
        return cast(raw)
    except ValueError:
        raise ValueError(f"{name} must be a number, got {raw!r}") from None


class PlacementController:
    """Evaluates the planner against the live bank and applies plans
    through the zero-downtime swap primitive."""

    def __init__(
        self,
        app,
        threshold: Optional[float] = None,
        min_rows: Optional[int] = None,
        min_pad_ratio: Optional[float] = None,
        interval_s: Optional[float] = None,
        drain_timeout_s: Optional[float] = None,
    ):
        self.app = app
        self.threshold = (
            default_threshold() if threshold is None else float(threshold)
        )
        # don't plan on noise: a handful of warm-up requests is not a
        # traffic distribution (tests set it low)
        self.min_rows = (
            _env_num("GORDO_REBALANCE_MIN_ROWS", 4096, int)
            if min_rows is None
            else int(min_rows)
        )
        # goodput gate: skip planning while padding waste is negligible
        # (0 disables the gate; the planner documents the semantics)
        self.min_pad_ratio = (
            _env_num("GORDO_REBALANCE_MIN_PAD_RATIO", 0.0, float)
            if min_pad_ratio is None
            else float(min_pad_ratio)
        )
        self.interval_s = (
            _env_num("GORDO_REBALANCE_INTERVAL_S", 60.0, float)
            if interval_s is None
            else float(interval_s)
        )
        self.drain_timeout_s = (
            _env_num("GORDO_SWAP_DRAIN_S", 5.0, float)
            if drain_timeout_s is None
            else float(drain_timeout_s)
        )
        mode = os.environ.get("GORDO_REBALANCE", "").strip().lower()
        self.auto = mode == "auto"
        self._task: Optional[asyncio.Task] = None
        # routed-row baseline per member: the decision window is the
        # delta since the last APPLIED plan (or process start)
        self._load_baseline: Dict[str, float] = {}
        self.stats: Dict[str, Any] = {
            "evaluated": 0,
            "applied": 0,
            "noop": 0,
            "failed": 0,
            "last_reason": None,
            "last_improvement": None,
            "last_pause_ms": None,
            "last_generation": 0,
            "last_drained": None,
            "last_error": None,
        }
        registry = app.get("metrics")
        self._pause_hist = None
        if registry is not None:
            self._pause_hist = registry.histogram(
                "gordo_rebalance_swap_pause_seconds",
                "Generation-flip pause per applied swap (the only serving "
                "pause a rebalance or reload incurs)",
                lo=1e-6,
                hi=10.0,
            ).labels()
            registry.collector(self._collect, key="placement")

    def _collect(self):
        """Read-through exposition (stability contract,
        docs/observability.md): the same integers ``GET /placement``
        reports, so the scrape and the JSON view cannot drift."""
        s = self.stats
        yield (
            "gordo_bank_generation", "gauge",
            "Bank generation serving right now (bumps on every applied "
            "swap: rebalance or reload)", {},
            int(self.app.get("bank_generation", 0)),
        )
        yield (
            "gordo_rebalance_total", "counter",
            "Rebalance plans applied (bank swapped)", {}, s["applied"],
        )
        yield (
            "gordo_rebalance_noop_total", "counter",
            "Rebalance evaluations that decided not to swap", {}, s["noop"],
        )
        yield (
            "gordo_rebalance_failed_total", "counter",
            "Rebalance attempts that failed and rolled back to the old "
            "generation", {}, s["failed"],
        )
        yield (
            "gordo_rebalance_last_improvement", "gauge",
            "Predicted skew improvement factor of the last applied plan",
            {}, s["last_improvement"] or 0.0,
        )

    # ------------------------- load window ---------------------------- #

    def observed_loads(self) -> Dict[str, float]:
        """Per-member routed rows since the last applied plan."""
        bank = self.app.get("bank")
        rows = getattr(bank, "model_rows", None) or {}
        # GIL-atomic snapshot: scoring executor threads insert into the
        # live dict, and iterating it directly from the event loop could
        # raise mid-insert (dict changed size during iteration)
        rows = rows.copy()
        base = self._load_baseline
        return {
            name: delta
            for name, total in rows.items()
            if (delta := total - base.get(name, 0.0)) > 0
        }

    def observed_skew(self) -> Optional[float]:
        """Current-window skew over the live placement — what a plan
        would be judged against right now."""
        bank = self.app.get("bank")
        if bank is None:
            return None
        loads = self.observed_loads()
        placement = bank.placement()["buckets"]
        n_shards = max(
            (int(b["n_shards"]) for b in placement), default=0
        )
        if n_shards < 2:
            return None
        per_shard = [0.0] * n_shards
        for b in placement:
            size = int(b["shard_size"]) or len(b["members"])
            for i, name in enumerate(b["members"]):
                per_shard[min(i // size, n_shards - 1)] += loads.get(name, 0.0)
        return skew_ratio(per_shard)

    # --------------------------- planning ----------------------------- #

    def plan(self) -> RebalancePlan:
        bank = self.app.get("bank")
        if bank is None:
            return plan_rebalance([], {}, threshold=self.threshold)
        ledger = self.app.get("goodput")
        return plan_rebalance(
            bank.placement()["buckets"],
            self.observed_loads(),
            threshold=self.threshold,
            min_rows=self.min_rows,
            goodput=ledger.snapshot() if ledger is not None else None,
            min_pad_ratio=self.min_pad_ratio,
        )

    def placement_view(self, dry_run: bool = False) -> Dict[str, Any]:
        """The ``GET /placement`` body: live assignment + observed loads
        (+ a plan preview under ``?dry_run=1``)."""
        bank = self.app.get("bank")
        loads = self.observed_loads()
        body: Dict[str, Any] = {
            "enabled": True,
            "generation": int(self.app.get("bank_generation", 0)),
            "auto": self.auto,
            "threshold": self.threshold,
            "min_rows": self.min_rows,
            "interval_s": self.interval_s,
            "observed": {
                "rows": int(sum(loads.values())),
                "members_with_traffic": len(loads),
                "skew_ratio": self.observed_skew(),
            },
            # per-member window loads (routed rows since the last applied
            # plan): the FLEET placement tier's signal — watchman fetches
            # this from every replica and feeds plan_fleet, so which
            # replica owns each member is decided on the same windowed
            # counters the intra-host planner already uses. Only members
            # with traffic appear (bounded by the active set, not the
            # fleet roster).
            "member_rows": {name: int(v) for name, v in loads.items()},
            "stats": dict(self.stats),
        }
        if bank is not None:
            placement = bank.placement()
            # decorate each bucket with its per-shard observed window
            # loads so "which shard is hot and who lives there" is one
            # GET, not a metrics join
            for b in placement["buckets"]:
                size = int(b["shard_size"]) or len(b["members"])
                n_shards = max(1, int(b["n_shards"]))
                shard_loads = [0.0] * n_shards
                for i, name in enumerate(b["members"]):
                    shard_loads[min(i // size, n_shards - 1)] += loads.get(
                        name, 0.0
                    )
                b["shard_loads"] = [round(v, 1) for v in shard_loads]
            body.update(placement)
        if dry_run:
            body["plan"] = self.plan().summary()
        return body

    # ---------------------------- acting ------------------------------ #

    def record_swap(self, result) -> None:
        """Record an applied swap's flip — shared by the rebalance path
        and ``/reload`` (which rides the same primitive), so the stats
        ``GET /placement`` reports always agree with the generation it
        reports, whichever path bumped it."""
        self.stats["last_pause_ms"] = round(result.pause_s * 1e3, 3)
        self.stats["last_generation"] = result.generation
        if self._pause_hist is not None:
            self._pause_hist.record(result.pause_s)

    def _lock(self) -> asyncio.Lock:
        # the same lock /reload and the streaming plane serialize under:
        # every bank-rebuilding path shares it (server/utils.py)
        from gordo_components_tpu.server.utils import get_reload_lock

        return get_reload_lock(self.app)

    async def rebalance(
        self, force: bool = False, dry_run: bool = False
    ) -> Dict[str, Any]:
        """Evaluate the planner and (unless ``dry_run``) apply the plan
        through the swap. ``force`` overrides the improvement threshold
        and the min-rows gate — an operator override, not the loop's
        path — but never forces a plan with nothing to move."""
        async with self._lock():
            self.stats["evaluated"] += 1
            plan = self.plan()
            applicable = plan.should_apply or (
                force
                and plan.moved > 0
                and any(b.n_shards > 1 for b in plan.buckets)
            )
            if dry_run or not applicable:
                if not dry_run:
                    self.stats["noop"] += 1
                    self.stats["last_reason"] = plan.reason
                return {
                    "applied": False,
                    "dry_run": dry_run,
                    "plan": plan.summary(),
                }
            return await self._apply(plan, forced=force and not plan.should_apply)

    async def _apply(self, plan: RebalancePlan, forced: bool) -> Dict[str, Any]:
        app = self.app
        loop = asyncio.get_running_loop()
        tracer = app.get("tracer")
        trace = (
            tracer.start_trace("rebalance", force=True)
            if tracer is not None
            else None
        )
        t_plan = time.monotonic()
        old_bank = app.get("bank")
        # baseline snapshot BEFORE the swap: the applied plan consumed
        # exactly this window, so the next window starts here
        baseline = dict(getattr(old_bank, "model_rows", None) or {})
        registry = app.get("metrics")
        prev_collectors = snapshot_collectors(registry)
        try:
            t_build = time.monotonic()
            collection = app.get("collection")
            new_bank = await loop.run_in_executor(
                None,
                functools.partial(
                    build_bank,
                    app,
                    collection.models,
                    member_order=plan.member_order(),
                ),
            )
            t_swap = time.monotonic()
            result = swap_bank(app, new_bank, prev_collectors=prev_collectors)
            t_drain = time.monotonic()
            drained = await wait_drained(old_bank, self.drain_timeout_s)
        except Exception as exc:
            # a failed BUILD (not just a failed flip) may already have
            # replaced the registry's keyed bank collectors with the
            # stillborn bank's — restore the serving generation's so its
            # series keep rendering (swap_bank's own rollback handles
            # the flip-failure case before re-raising into here)
            from gordo_components_tpu.placement.swap import (
                _restore_collectors,
            )

            _restore_collectors(registry, prev_collectors)
            self.stats["failed"] += 1
            self.stats["last_error"] = f"{type(exc).__name__}: {exc}"
            if trace is not None:
                now = time.monotonic()
                trace.add_span("plan", t_plan, now, error=True)
                trace.finish(error=True)
            raise
        self._load_baseline = baseline
        self.stats["applied"] += 1
        self.stats["last_reason"] = plan.reason
        self.stats["last_improvement"] = plan.improvement
        self.stats["last_drained"] = drained
        self.stats["last_error"] = None
        self.record_swap(result)
        if trace is not None:
            t_end = time.monotonic()
            trace.add_span(
                "plan", t_plan, t_build,
                moved=plan.moved, improvement=plan.improvement,
            )
            trace.add_span(
                "build", t_build, t_swap, models=result.bank_models,
            )
            trace.add_span(
                "swap", t_swap, t_drain,
                generation=result.generation,
                pause_ms=round(result.pause_s * 1e3, 3),
            )
            trace.add_span("drain", t_drain, t_end, drained=drained)
            trace.finish(error=False, generation=result.generation)
        logger.info(
            "rebalance applied: %s (generation %d, pause %.3fms, "
            "drained=%s)",
            plan.reason, result.generation, result.pause_s * 1e3, drained,
        )
        return {
            "applied": True,
            "forced": forced,
            "plan": plan.summary(),
            "swap": {
                "generation": result.generation,
                "pause_ms": round(result.pause_s * 1e3, 3),
                "build_s": round(result.build_s, 3),
                "warmup_s": round(result.warmup_s, 3),
                "drained": drained,
            },
        }

    # ------------------------- the auto loop -------------------------- #

    def start(self) -> None:
        """Arm the ``GORDO_REBALANCE=auto`` background evaluator (no-op
        in manual mode — the endpoints still work either way)."""
        if self.auto and self._task is None:
            self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    async def _run(self) -> None:
        while True:
            await asyncio.sleep(self.interval_s)
            try:
                await self.rebalance()
            except asyncio.CancelledError:
                raise
            except Exception:
                # an auto-loop failure rolled back cleanly (swap_bank's
                # contract); the loop must survive to try again — the
                # failure is already counted and logged
                logger.warning(
                    "auto rebalance attempt failed; old generation keeps "
                    "serving", exc_info=True,
                )
