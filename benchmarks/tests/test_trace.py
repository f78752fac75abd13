"""The trace reduction on hand-made planes and on a small recorded trace."""

import glob
import os
from dataclasses import dataclass, field
from typing import List

import pytest

from harness import trace


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclass
class Line:
    name: str
    events: List[Ev] = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)


def test_busy_is_the_union_and_gaps_take_the_host_span_that_covers_them():
    device = Plane("/device:TPU:0", [
        Line(trace.OPS_LINE, [
            Ev("fusion.1", 0, 4e9), Ev("copy.2", 3e9, 2e9),  # overlap: union 0..5 s
            Ev("fusion.1", 7e9, 1e9),                         # gap 5..7 s
        ]),
        Line(trace.MODULES_LINE, [Ev("jit_score(123)", 0, 5e9), Ev("jit_score(123)", 7e9, 1e9)]),
    ])
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench:window", 0, 10e9), Ev("bench:fit", 4e9, 4e9), Ev("other", 0, 10e9),
    ])])
    out = trace.reduce_planes([device, host])
    assert out["busy_s"] == pytest.approx(6.0)
    assert out["module_seconds"] == {"jit_score": pytest.approx(6.0)}
    assert out["module_calls"] == {"jit_score": 2}
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(5.0)]
    assert out["idle_gaps"] == [["bench:fit", pytest.approx(2.0)]]


def test_no_device_plane_reads_nothing():
    assert trace.reduce_planes([Plane("/host:CPU", [Line("x", [Ev("bench:a", 0, 1)])])]) == {}


def test_recorded_trace():
    """A trace recorded on the chip (``tools/record_small_trace.py``): one
    jitted matmul chain called 20 times under a ``bench:window`` span."""
    paths = glob.glob(os.path.join(os.path.dirname(__file__), "data", "*.xplane.pb"))
    if not paths:
        pytest.skip("no recorded trace in benchmarks/tests/data")
    out = trace.reduce_file(paths[0])
    assert out["n_devices"] == 1
    assert 0 < out["busy_s"] < 5.0
    (name,) = [n for n in out["module_seconds"] if n.startswith("jit_small_chain")]
    assert out["module_calls"][name] == 20
    assert out["module_seconds"][name] <= out["busy_s"] * 1.05
    assert out["idle_gaps"] and out["idle_gaps"][0][0] == "bench:window"
