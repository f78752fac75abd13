#!/usr/bin/env python
"""Placement rebalance demo.

Builds a deliberately skewed fleet on an 8-shard (virtual, CPU-safe)
``models`` mesh — the hot members clustered on shard 0, exactly the
placement a sorted artifact directory produces when one site's machines
run hot — drives the skewed traffic, plans with the LPT planner, applies
the plan through the zero-downtime swap, re-drives the SAME traffic, and
prints one JSON document: measured shard skew before/after, the planner's
predicted improvement, and the generation-flip pause.

Run directly (``make rebalance-demo``). ``--members 10000`` reproduces
the north-star-scale fixture.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pin_devices(n: int) -> None:
    """Virtual device count — must land before jax initializes."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def run_demo(
    members: int = 128,
    devices: int = 8,
    hot_weight: int = 8,
    request_rows: int = 64,
    tags: int = 10,
    platform: str | None = None,
) -> dict:
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)
    import jax
    import numpy as np

    from gordo_components_tpu.models import (
        AutoEncoder,
        DiffBasedAnomalyDetector,
    )
    from gordo_components_tpu.observability import MetricsRegistry
    from gordo_components_tpu.parallel.mesh import fleet_mesh
    from gordo_components_tpu.placement.planner import (
        plan_rebalance,
        skew_ratio,
    )
    from gordo_components_tpu.placement.swap import (
        build_bank,
        snapshot_collectors,
        swap_bank,
    )
    from gordo_components_tpu.server.bank import ModelBank

    if len(jax.devices()) < devices:
        raise SystemExit(
            f"need {devices} devices, have {len(jax.devices())}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={devices} "
            "before jax initializes (running this file's main() does it)"
        )

    rng = np.random.RandomState(0)
    X = rng.rand(120, tags).astype("float32")
    det = DiffBasedAnomalyDetector(
        base_estimator=AutoEncoder(epochs=1, batch_size=128)
    )
    det.fit(X)
    # identical weights across members: placement cares about names and
    # load only, and one fit keeps the 10k-member fixture tractable
    models = {f"machine-{i:05d}": det for i in range(members)}

    registry = MetricsRegistry()
    mesh = fleet_mesh(devices)
    t0 = time.monotonic()
    bank = ModelBank.from_models(models, mesh=mesh, registry=registry)
    build_s = time.monotonic() - t0

    placement = bank.placement()
    bucket = placement["buckets"][0]
    shard_size = bucket["shard_size"]
    # a set: the membership test runs per member per traffic pass, and
    # at --members 10000 a 1250-name list would cost ~12M comparisons
    hot = set(bucket["members"][:shard_size])  # all of shard 0 runs hot

    def traffic(b):
        reqs = []
        for name in bucket["members"]:
            w = hot_weight if name in hot else 1
            for _ in range(w):
                reqs.append(
                    (name, rng.rand(request_rows, tags).astype("float32"), None)
                )
        b.score_many(reqs)

    def shard_rows():
        snap = registry.snapshot()
        return {
            v["labels"]["shard"]: v["value"]
            for v in snap["gordo_bank_shard_routed_rows_total"]["values"]
        }

    traffic(bank)  # warm + record the skewed window
    base = shard_rows()
    traffic(bank)
    now = shard_rows()
    skew_before = skew_ratio([now[s] - base.get(s, 0.0) for s in sorted(now)])

    plan = plan_rebalance(
        placement["buckets"], dict(bank.model_rows), threshold=1.2, min_rows=1
    )
    app = {
        "bank": bank, "bank_mesh": mesh, "metrics": registry,
        "bank_config": {}, "goodput": None,
    }
    prev = snapshot_collectors(registry)
    t0 = time.monotonic()
    new_bank = build_bank(
        app, models, member_order=plan.member_order(), warmup=False
    )
    rebuild_s = time.monotonic() - t0
    result = swap_bank(app, new_bank, prev_collectors=prev)

    traffic(new_bank)  # warm the new placement's routed shapes
    base = shard_rows()
    traffic(new_bank)
    now = shard_rows()
    skew_after = skew_ratio([now[s] - base.get(s, 0.0) for s in sorted(now)])

    return {
        "members": members,
        "devices": devices,
        "hot_members": len(hot),
        "hot_weight": hot_weight,
        "bank_build_s": round(build_s, 3),
        "rebuild_s": round(rebuild_s, 3),
        "shard_skew_before": round(skew_before, 4),
        "shard_skew_after": round(skew_after, 4),
        "skew_reduction": round(skew_before / skew_after, 4),
        "plan": {
            "predicted_improvement": round(plan.improvement, 4),
            "moved": plan.moved,
            "reason": plan.reason,
        },
        "swap_generation": result.generation,
        "swap_pause_ms": round(result.pause_s * 1e3, 4),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--members", type=int, default=128)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--hot-weight", type=int, default=8)
    ap.add_argument("--request-rows", type=int, default=64)
    ap.add_argument("--tags", type=int, default=10)
    ap.add_argument("--platform", default="cpu",
                    help="in-process jax platform pin")
    a = ap.parse_args()
    if (a.platform or "") == "cpu":
        _pin_devices(a.devices)
    print(
        json.dumps(
            run_demo(
                members=a.members, devices=a.devices,
                hot_weight=a.hot_weight, request_rows=a.request_rows,
                tags=a.tags, platform=a.platform,
            ),
            indent=1,
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
