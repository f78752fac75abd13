"""Mesh-scale game days: break the multi-host mesh ON PURPOSE, judge
every failure with the SLO/incident stack.

The reference system's value was surviving production at fleet scale;
this package is the drill hall. A :class:`~gameday.harness.GamedayMesh`
boots the REAL multi-process mesh (N server subprocesses + a live
watchman, the same shape ``tools/mesh_demo.py`` measures), puts it
under scoring load, injects a mesh-class failure from the scenario
catalog (``scenarios.py``), and judges the whole loop end to end with
the observability stack that production would use:

- **detect** — watchman's routing plane, SLO rollup and ``/incidents``
  correlation must see the failure (detection latency, burn peak,
  causal event ordering);
- **contain** — routing/hedging/quarantine must bound the blast radius
  (non-200s vs a DECLARED budget, no traffic to dead or gray replicas);
- **recover** — burn returns to zero, the routing version converges,
  subscribers re-attach.

Verdicts share the replay harness's envelope
(``replay/verdict.py``: ``failures``/``passed``), and the worst
scenarios gate fleet promotion (``gameday/gate.py`` + the ``gameday``
step kind in ``workflow/compiler.py``).

Fault injection over subprocess boundaries rides the ``GORDO_FAULTS``
env (``resilience/faults.py`` — including the transport-level
blackhole/refuse/reset kinds this PR adds); in-process injection uses
the same registry directly.
"""

from gordo_components_tpu.gameday.scenarios import (
    SCENARIOS,
    GamedayScenario,
    known_scenarios,
)

__all__ = ["SCENARIOS", "GamedayScenario", "known_scenarios"]
