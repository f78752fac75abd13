"""Game-day suite (ISSUE 17): the fleet chaos harness that breaks the
multi-process mesh on purpose and judges every failure with the
SLO/incident stack.

Fast legs (tier-1): the scenario catalog and its declarative judge
(every bound's pass/fail edge, the single-core honesty merge, the
unknown-bound guard), the harness's child-environment contract (mesh
identity and per-replica ``GORDO_FAULTS`` riding the subprocess env),
verdict-table rendering, and the gate's name validation. The real
multi-process drills — N server subprocesses + a live watchman,
SIGKILLed / partitioned / slowed on purpose — are marked ``slow`` and
run in the ``make gameday`` lane (the full catalog also runs as
tools/gameday_demo.py).
"""

import asyncio
import os

import pytest

from gordo_components_tpu.gameday.harness import (
    GAMEDAY_SCHEMA,
    RUNNERS,
    SHAPE_ORDER,
    GamedayMesh,
    render_verdict_table,
    run_gameday,
)
from gordo_components_tpu.gameday.scenarios import (
    GATE_DEFAULT,
    SCENARIOS,
    GamedayScenario,
    known_scenarios,
)

pytestmark = pytest.mark.gameday


# ---------------------------------------------------------------------- #
# catalog registry
# ---------------------------------------------------------------------- #


class TestCatalog:
    def test_every_scenario_has_a_runner_and_vice_versa(self):
        assert set(RUNNERS) == set(SCENARIOS)

    def test_at_least_six_mesh_class_scenarios(self):
        assert len(SCENARIOS) >= 6

    def test_every_scenario_declares_a_bootable_shape(self):
        for s in SCENARIOS.values():
            assert s.mesh in SHAPE_ORDER, s.name

    def test_gate_default_scenarios_are_gate_capable(self):
        assert GATE_DEFAULT
        for name in GATE_DEFAULT:
            assert SCENARIOS[name].gate_capable, name

    def test_known_scenarios_sorted(self):
        assert known_scenarios() == sorted(SCENARIOS)

    def test_every_scenario_bounds_detection_and_containment(self):
        """Each drill must be judged, not just run: every catalog entry
        declares a non-200 budget implicitly (judge default 0) and at
        least one observability bound."""
        for s in SCENARIOS.values():
            assert s.bounds, s.name


# ---------------------------------------------------------------------- #
# the judge (pure verdict edges)
# ---------------------------------------------------------------------- #


def _scenario(**kw):
    kw.setdefault("name", "t")
    kw.setdefault("description", "test scenario")
    kw.setdefault("mesh", "partitioned")
    return GamedayScenario(**kw)


class TestJudge:
    def test_detection_within_bound_passes(self):
        s = _scenario(bounds={"max_detection_latency_s": 5.0})
        v = {"detected": True, "detection_latency_s": 1.0, "non_200": 0}
        assert s.judge(v) == []

    def test_detection_missed_fails(self):
        s = _scenario(bounds={"max_detection_latency_s": 5.0})
        fails = s.judge({"detected": False, "non_200": 0})
        assert any("never detected" in f or "detect" in f for f in fails)

    def test_detection_too_slow_fails(self):
        s = _scenario(bounds={"max_detection_latency_s": 5.0})
        fails = s.judge(
            {"detected": True, "detection_latency_s": 9.0, "non_200": 0}
        )
        assert fails

    def test_non200_budget_enforced(self):
        s = _scenario(bounds={"max_non200": 1})
        assert s.judge({"non_200": 1}) == []
        assert s.judge({"non_200": 2})

    def test_non200_budget_defaults_to_zero(self):
        s = _scenario(bounds={})
        assert s.judge({"non_200": 0}) == []
        assert s.judge({"non_200": 1})

    def test_recovery_bound(self):
        s = _scenario(bounds={"max_recovery_s": 10.0})
        ok = {"non_200": 0, "recovered": True, "recovery_s": 2.0}
        assert s.judge(ok) == []
        assert s.judge({"non_200": 0, "recovered": False})
        assert s.judge(
            {"non_200": 0, "recovered": True, "recovery_s": 60.0}
        )

    def test_event_order_missing_event_fails(self):
        s = _scenario(
            bounds={"require_event_order": ["a.x", "b.y"]}
        )
        v = {"non_200": 0, "events": [{"type": "a.x"}]}
        fails = s.judge(v)
        assert any("b.y" in f and "missing" in f for f in fails)

    def test_event_order_out_of_order_fails(self):
        s = _scenario(bounds={"require_event_order": ["a.x", "b.y"]})
        v = {
            "non_200": 0,
            "events": [{"type": "b.y"}, {"type": "a.x"}],
        }
        fails = s.judge(v)
        assert any("causal order" in f for f in fails)

    def test_event_order_in_order_passes(self):
        s = _scenario(bounds={"require_event_order": ["a.x", "b.y"]})
        v = {
            "non_200": 0,
            "events": [
                {"type": "a.x"}, {"type": "noise"}, {"type": "b.y"},
            ],
        }
        assert s.judge(v) == []

    def test_routing_version_and_reroute_bounds(self):
        s = _scenario(
            bounds={
                "min_routing_version_steps": 2,
                "min_reroutes": 1,
                "max_routing_refreshes": 3,
            }
        )
        ok = {
            "non_200": 0, "routing_version_steps": 2, "reroutes": 2,
            "routing_refreshes": 3,
        }
        assert s.judge(ok) == []
        assert s.judge(dict(ok, routing_version_steps=1))
        assert s.judge(dict(ok, reroutes=0))
        assert s.judge(dict(ok, routing_refreshes=9))

    def test_herd_and_drift_bounds(self):
        s = _scenario(
            bounds={
                "min_distinct_reconnect_delays": 3,
                "require_all_subscribers_recovered": True,
                "min_drift_replicas": 2,
            }
        )
        ok = {
            "non_200": 0, "distinct_reconnect_delays": 4,
            "subscribers_lost": [], "drifted_replicas": [0, 1],
        }
        assert s.judge(ok) == []
        assert s.judge(dict(ok, distinct_reconnect_delays=1))
        assert s.judge(dict(ok, subscribers_lost=["herd-2"]))
        assert s.judge(dict(ok, drifted_replicas=[0]))

    def test_burn_peak_bound(self):
        s = _scenario(bounds={"min_burn_peak": 1.0})
        assert s.judge({"non_200": 0, "burn_peak": 3.2}) == []
        assert s.judge({"non_200": 0, "burn_peak": 0.1})
        assert s.judge({"non_200": 0, "burn_peak": None})

    def test_multicore_bounds_waived_on_single_core(self):
        s = _scenario(
            bounds={"min_hedge_wins": 1},
            multicore_bounds={"min_hedge_wins": 3},
        )
        v = {"non_200": 0, "hedge_wins": 1}
        assert s.judge(v, single_core=True) == []
        assert s.judge(v, single_core=False)  # needs 3 on multi-core

    def test_unknown_bound_fails_loudly(self):
        s = _scenario(bounds={"max_frobnication": 1})
        fails = s.judge({"non_200": 0})
        assert any("unknown bounds" in f for f in fails)

    def test_finalize_stamps_envelope(self):
        s = _scenario(bounds={})
        v = s.finalize({"non_200": 0}, single_core=True)
        assert v["schema"] == "gordo.scenario-verdict/v1"
        assert v["passed"] and v["failures"] == []
        assert v["scenario"] == "t" and v["single_core"] is True
        bad = s.finalize({"non_200": 5}, single_core=True)
        assert not bad["passed"] and bad["failures"]


# ---------------------------------------------------------------------- #
# harness: the subprocess environment contract
# ---------------------------------------------------------------------- #


class TestChildEnv:
    def test_partitioned_mesh_identity_rides_the_env(self, tmp_path):
        mesh = GamedayMesh(str(tmp_path), ["gd-0"], n_replicas=3)
        env = mesh._child_env(1)
        assert env["GORDO_MESH_REPLICA_ID"] == "1"
        assert env["GORDO_MESH_REPLICAS"] == "3"
        assert env["JAX_PLATFORMS"] == "cpu"

    def test_replicated_shape_has_no_mesh_identity(self, tmp_path):
        mesh = GamedayMesh(
            str(tmp_path), ["gd-0"], n_replicas=2, partitioned=False
        )
        env = mesh._child_env(0)
        assert "GORDO_MESH_REPLICA_ID" not in env

    def test_per_replica_faults_target_one_subprocess(self, tmp_path):
        """The fault boundary of the whole PR: GORDO_FAULTS armed for
        replica 1 must reach ONLY replica 1's environment."""
        mesh = GamedayMesh(
            str(tmp_path), ["gd-0"], n_replicas=2, partitioned=False,
            replica_env={1: {"GORDO_FAULTS": "engine.queue=latency:0.25"}},
        )
        assert "GORDO_FAULTS" not in mesh._child_env(0)
        assert (
            mesh._child_env(1)["GORDO_FAULTS"]
            == "engine.queue=latency:0.25"
        )

    def test_parent_faults_never_leak_into_children(self, tmp_path,
                                                    monkeypatch):
        """A GORDO_FAULTS armed in the PARENT (e.g. the test runner's
        own chaos lane) must not arm every child replica."""
        monkeypatch.setenv("GORDO_FAULTS", "bank.score=error")
        monkeypatch.setenv("GORDO_MESH_REPLICA_ID", "7")
        mesh = GamedayMesh(
            str(tmp_path), ["gd-0"], n_replicas=2, partitioned=False
        )
        env = mesh._child_env(0)
        assert "GORDO_FAULTS" not in env
        assert "GORDO_MESH_REPLICA_ID" not in env

    def test_common_env_applies_to_every_replica(self, tmp_path):
        mesh = GamedayMesh(
            str(tmp_path), ["gd-0"], n_replicas=2,
            common_env={"GORDO_STREAM": "1"},
        )
        assert mesh._child_env(0)["GORDO_STREAM"] == "1"
        assert mesh._child_env(1)["GORDO_STREAM"] == "1"


class TestRunValidation:
    def test_unknown_scenario_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="unknown scenario"):
            asyncio.run(
                run_gameday(str(tmp_path), scenario_names=["nope"])
            )

    def test_render_verdict_table_lists_every_scenario(self):
        doc = {
            "schema": GAMEDAY_SCHEMA,
            "scenarios": {
                "a_drill": {
                    "passed": True, "detection_latency_s": 0.5,
                    "non_200": 0, "recovery_s": 1.0, "failures": [],
                },
                "b_drill": {
                    "passed": False, "non_200": 3,
                    "failures": ["3 non-200(s) > budget 0"],
                },
            },
            "passed": False,
        }
        table = render_verdict_table(doc)
        assert "a_drill" in table and "b_drill" in table
        assert "PASS" in table and "FAIL" in table
        assert "non-200" in table


# ---------------------------------------------------------------------- #
# the real thing: multi-process drills (the `make gameday` lane)
# ---------------------------------------------------------------------- #


@pytest.mark.slow
class TestGamedayE2E:
    def test_partitioned_mesh_drills_end_to_end(self, tmp_path):
        """One real mesh boot (2 server subprocesses + live watchman),
        two drills against it: the SIGKILL crash/restart drill and the
        watchman transport partition — judged by detection latency,
        non-200 containment, causal event order and observed
        recovery."""
        doc = asyncio.run(
            run_gameday(
                str(tmp_path),
                scenario_names=[
                    "replica_crash_restart", "watchman_partition",
                ],
            )
        )
        assert doc["schema"] == GAMEDAY_SCHEMA
        assert set(doc["scenarios"]) == {
            "replica_crash_restart", "watchman_partition",
        }
        for name, v in doc["scenarios"].items():
            assert v["passed"], (name, v["failures"])
            assert v["schema"] == "gordo.scenario-verdict/v1"
            assert v["detected"] and v["non_200"] == 0
        crash = doc["scenarios"]["replica_crash_restart"]
        assert crash["recovered"] and crash["routing_version_steps"] >= 2
        types = [e["type"] for e in crash["events"]]
        assert "mesh.replica_unreachable" in types
        assert "mesh.replica_recovered" in types
        assert doc["passed"]
        assert doc["cpu_count"] == os.cpu_count()
