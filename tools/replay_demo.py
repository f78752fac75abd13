#!/usr/bin/env python
"""Time-compressed replay demo.

Trains a small heterogeneous fleet on the simulated provider's healthy
signal, then backtests the STANDARD incident library
(``replay/scenarios.py``) through the real ingest -> drift ->
recalibrate/refit -> hot-swap HTTP path on a :class:`ReplayClock` —
hours of event time per scenario in seconds of wall time.

Prints a per-scenario verdict table (detection latency, FP before/after
adaptation, adaptation count, rolled-back count, duplicates absorbed,
non-200 count, achieved compression) followed by one JSON document.
Run directly (``make replay-demo``).
"""

import argparse
import json
import logging
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_demo(
    epochs: int = 3,
    speed: float = 500.0,
    scenarios: list | None = None,
    platform: str | None = None,
) -> dict:
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)
    from gordo_components_tpu.replay.engine import ReplayEngine, train_fleet
    from gordo_components_tpu.replay.scenarios import (
        default_fleet,
        standard_scenarios,
    )

    members = default_fleet()
    picked = standard_scenarios()
    if scenarios:
        picked = [s for s in picked if s.name in scenarios]
        if not picked:
            # a typo'd --scenario must not report a vacuous green
            raise SystemExit(
                f"no scenario matches {scenarios!r}; valid names: "
                f"{[s.name for s in standard_scenarios()]}"
            )
    root = tempfile.mkdtemp(prefix="replay-demo-")
    t0 = time.monotonic()
    train_fleet(root, members, epochs=epochs)
    build_s = time.monotonic() - t0

    engine = ReplayEngine(root, members, speed=speed)
    doc: dict = {
        "members": len(members),
        "fleet_build_s": round(build_s, 3),
        "scenarios": {},
    }
    header = (
        f"{'scenario':28s} {'pass':4s} {'detect_s':>8s} {'fp_pre':>6s} "
        f"{'fp_post':>7s} {'adapt':>5s} {'rb':>2s} {'dup':>5s} "
        f"{'n200':>4s} {'x':>7s}"
    )
    print(header, file=sys.stderr)
    print("-" * len(header), file=sys.stderr)
    for scen in picked:
        v = engine.run_sync(scen)
        doc["scenarios"][scen.name] = v
        det = [
            e["detection_latency_s"]
            for e in v["incidents"].values()
            if e["detected"]
        ]
        fp_pre = max(v["fp_rate_before"].values(), default=0.0)
        fp_post = max(v["fp_rate_after"].values(), default=0.0)
        print(
            f"{scen.name:28s} {'ok' if v['passed'] else 'FAIL':4s} "
            f"{(min(det) if det else float('nan')):8.0f} {fp_pre:6.2f} "
            f"{fp_post:7.2f} {v['adaptations']:5d} {v['rolled_back']:2d} "
            f"{v['duplicate_rows_total']:5d} {v['non_200']:4d} "
            f"{v['speedup']:7.0f}",
            file=sys.stderr,
        )
        if v["failures"]:
            print(f"  failures: {v['failures']}", file=sys.stderr)
    doc["passed"] = all(v["passed"] for v in doc["scenarios"].values())
    doc["min_speedup"] = min(
        (v["speedup"] for v in doc["scenarios"].values()), default=0.0
    )
    doc["total_non_200"] = sum(
        v["non_200"] for v in doc["scenarios"].values()
    )
    return doc


def main() -> int:
    logging.basicConfig(level=logging.ERROR)
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--speed", type=float, default=500.0,
                    help="nominal event/wall compression factor")
    ap.add_argument("--scenario", action="append", default=None,
                    help="run only the named scenario(s)")
    ap.add_argument("--platform", default="cpu",
                    help="in-process jax platform pin")
    a = ap.parse_args()
    print(
        json.dumps(
            run_demo(
                epochs=a.epochs, speed=a.speed, scenarios=a.scenario,
                platform=a.platform,
            ),
            indent=1,
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
