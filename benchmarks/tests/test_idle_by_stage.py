"""``tools/idle_by_stage.py``: the split of device idle time by program
stage on hand-made planes, and its taps on a tiny traced run."""

import os
import sys
import time

import pytest

from harness import refit, serve, trace
from test_trace import Ev, Line, Plane

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import idle_by_stage as tool  # noqa: E402


def test_idle_is_cut_at_stage_boundaries_and_the_innermost_stage_wins():
    device = Plane("/device:TPU:0", [Line(trace.OPS_LINE, [
        Ev("fusion.1", 2e9, 2e9),   # busy 2..4 s
        Ev("fusion.2", 7e9, 1e9),   # busy 7..8 s
    ])])
    host = Plane("/host:CPU", [
        Line("main", [
            Ev("bench:fit", 1e9, 9e9),           # the window: 1..10 s
            Ev("gordo:to_device", 1e9, 1.5e9),   # 1..2.5: the lead-in 1..2 is its
            Ev("gordo:epoch", 3e9, 2e9),         # 3..5: idle 4..5
            Ev("gordo:epoch_host", 5e9, 1e9),    # 5..6
            Ev("gordo:members", 8.5e9, 1e9),     # 8.5..9.5 of the tail 8..10
        ]),
        Line("worker", [Ev("gordo:checkpoint", 5.2e9, 0.3e9), Ev("other", 0, 10e9)]),
    ])
    out = tool.idle_by_stage([device, host])
    assert out["window_s"] == pytest.approx(9.0)
    assert out["idle_s"] == pytest.approx(6.0)  # 1..2, 4..7, 8..10
    assert out["by_stage"] == {
        "gordo:to_device": pytest.approx(1.0),
        "gordo:epoch": pytest.approx(1.0),
        "gordo:epoch_host": pytest.approx(0.7),
        "gordo:checkpoint": pytest.approx(0.3),   # inside epoch_host, shorter: wins
        "gordo:members": pytest.approx(1.0),
        tool.NO_STAGE: pytest.approx(2.0),        # 6..7, 8..8.5, 9.5..10
    }
    # the ledger's rule gives a whole gap to a stage only if it covers all of it
    whole = trace.reduce_planes([device, host], annotations_prefix="gordo:")["idle_gaps"]
    assert whole == [["unannotated", pytest.approx(3.0)]]


def test_no_device_plane_reads_nothing():
    assert tool.idle_by_stage([Plane("/host:CPU", [Line("x", [Ev("gordo:a", 0, 1)])])]) == {}


def test_outliers_name_what_overlaps_them():
    host = Plane("/host:CPU", [
        Line("loop", [Ev("gordo:encode", i * 10e6, 1e6) for i in range(9)]
             + [Ev("gordo:encode", 100e6, 50e6)]),
        Line("gc", [Ev("collect", 110e6, 30e6), Ev("far", 500e6, 1e6)]),
    ])
    (first, *_) = tool.stage_outliers([host], keep=2)
    assert first["stage"] == "gordo:encode" and first["ms"] == pytest.approx(50.0)
    assert first["stage_median_ms"] == pytest.approx(1.0)
    assert first["overlapping"] == [{"ms": pytest.approx(30.0), "event": "collect", "thread": "gc"}]


def test_report_and_files_of_a_device_trace(tmp_path, capsys):
    """What a chip run prints and brings back, from hand-made planes."""
    import json

    device = Plane("/device:TPU:0", [
        Line(trace.OPS_LINE, [Ev("fusion.1", 2e6, 6e6)]),
        Line(trace.MODULES_LINE, [Ev("jit_score(12)", 2e6, 6e6)]),
    ])
    host = Plane("/host:CPU", [Line("executor", [
        Ev("bench:window", 0, 20e6), Ev("gordo:enqueue", 1e6, 2e6),
        Ev("gordo:device_wait", 3e6, 9e6), Ev("PjitFunction(score)", 1e6, 1e6),
    ])])
    planes = [device, host]
    seen = {
        "idle": tool.idle_by_stage(planes), "outliers": tool.stage_outliers(planes),
        "timeline": tool.timeline(planes), "ledger_rule": [["unannotated", 0.012]],
        "module_calls": {"jit_score": 1}, "module_seconds": {"jit_score": 0.006},
        "cost": None, "span_ms": {"enqueue": {"count": 1, "median": 2.0, "mean": 2.0}},
        "slow": [{"root": "anomaly", "ms": 30.0, "stages_ms": {"device_execute": 11.0}}],
        "first_fit": {"wall_s": 12.0, "seconds_by_span": {"epoch": 9.0}, "longest_compile_spans": [
            {"s": 1.5, "span": "backend_compile", "fun_name": "jit(masked_epoch)", "at_s": 2.0}]},
    }
    assert seen["idle"]["by_stage"] == {
        tool.NO_STAGE: pytest.approx(0.009), "gordo:device_wait": pytest.approx(0.004),
        "gordo:enqueue": pytest.approx(0.001),
    }
    assert seen["idle"]["stage_regions"]["gordo:device_wait"] == {
        "count": 1, "total_s": pytest.approx(0.009), "median_ms": pytest.approx(9.0)}
    assert seen["timeline"] == [
        [0.0, 20.0, "bench:window", "executor"], [1.0, 2.0, "gordo:enqueue", "executor"],
        [2.0, 6.0, "jit_score", "/device:TPU:0"], [3.0, 9.0, "gordo:device_wait", "executor"],
    ]
    tool.report("dense300.live", 5, seen)
    printed = capsys.readouterr().out
    assert "64.3%  no stage" in printed and "of 0.0090 s the host spent there in 1 regions" in printed
    assert "jit(masked_epoch)" in printed and "slow anomaly 30.00 ms" in printed
    tool.write(str(tmp_path), "dense300.live", 5, seen)
    assert json.load(open(tmp_path / "dense300.live.5.json"))["idle"]["idle_s"] == pytest.approx(0.02 - 0.006)
    assert len(open(tmp_path / "dense300.live.5.timeline.csv").read().splitlines()) == 5


@pytest.mark.parametrize("name", ["dense300.refit", "dense300.live"])
def test_taps_on_a_tiny_traced_run(tiny_cell, capsys, name):
    """Off the chip there is no device plane, so no idle table; the taps
    still read the run: the new per-layer metrics, the session's cost, the
    stage regions of the host plane, the server's slowest requests."""
    cell = tiny_cell(name)
    driver = refit if cell.traffic["driver"] == "refit" else serve
    seen = tool.read_traced_run(
        lambda: driver.run(cell, 7, 1.0, True, time.time(), on_tpu=False) and 0
    )
    capsys.readouterr()
    assert seen["exit_code"] == 0 and seen["idle"] == {}
    assert {row["stage"] for row in seen["outliers"]} <= {
        "gordo:" + s for s in (
            "coalesce", "pad", "enqueue", "device_wait", "fetch", "reassemble", "encode",
            "stack_pad", "to_device", "scaler_fit", "init_state", "epoch", "epoch_host",
            "error_scalers", "unstack", "members",
        )
    } and seen["outliers"]
    metrics = set(seen["per_layer"])
    if name == "dense300.refit":
        assert {"prepare_ms.train", "epoch_host_ms.train", "finish_ms.train",
                "span_coverage.train"} <= metrics
        assert seen["per_layer"]["span_coverage.train"]["value"] > 90
        assert seen["cost"]["unit"] == "s a fit"
        assert seen["first_fit"]["seconds_by_span"]["epoch"] > 0
    else:
        assert {"server_ms.serve", "span_coverage.serve", "queue_behind_ms.serve",
                "enqueue_ms.serve", "device_wait_ms.serve", "resolve_ms.serve",
                "encode_ms.serve"} <= metrics
        assert seen["per_layer"]["span_coverage.serve"]["value"] > 85
        assert seen["slow"] and all(row["stages_ms"] for row in seen["slow"] if row["root"] == "anomaly")
    assert driver is not serve or serve._span_ms.__name__ == "_span_ms"  # taps restored
