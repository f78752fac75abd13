#!/usr/bin/env python
"""gameday-demo: break the REAL multi-process mesh on purpose and judge
every failure with the SLO/incident stack (``make gameday-demo``).

Boots one game-day fleet per required mesh shape — N server
subprocesses plus a live watchman — and runs the scenario catalog
(``gordo_components_tpu/gameday/scenarios.py``) against it under
sustained scoring load: replica SIGKILL, watchman partition, migration
storm, gray slow-replica failure, thundering-herd reconnects,
correlated drift. Each drill's verdict is judged end-to-end by the
observability surfaces (detection latency, burn peak, causal event
order, non-200 containment, observed recovery) and printed as a table,
then as one JSON doc LAST (same contract as the other demos).

Honesty note: load-level bounds (hedge-win counts under real
parallelism) are waived on single-core hosts; structural bounds
(detection, containment, causal order, recovery) are asserted
everywhere, and ``cpu_count`` rides the doc so no number is read out of
context.
"""

import argparse
import asyncio
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    from gordo_components_tpu.gameday.harness import (
        render_verdict_table,
        run_gameday,
    )
    from gordo_components_tpu.gameday.scenarios import known_scenarios

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--scenario", "-s", action="append", default=None,
        metavar="NAME", choices=known_scenarios(),
        help="run only this scenario (repeatable; default: full catalog: "
             f"{', '.join(known_scenarios())})",
    )
    ap.add_argument(
        "--members", type=int, default=4,
        help="fleet size (members trained into the shared artifact dir)",
    )
    args = ap.parse_args()

    with tempfile.TemporaryDirectory(prefix="gordo-gameday-") as root:
        doc = asyncio.run(
            run_gameday(
                root,
                scenario_names=args.scenario,
                n_members=args.members,
                progress=lambda msg: print(f"[gameday] {msg}", flush=True),
            )
        )

    print()
    print(render_verdict_table(doc))
    print()
    # one compact JSON doc LAST, on one line — verdict "events" arrays
    # would break the consumers' last-"{"-line parse if pretty-printed
    print(json.dumps(doc, default=str))
    return 0 if doc["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
