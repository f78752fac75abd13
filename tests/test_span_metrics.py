"""The benchmark's readers of the program's spans
(``benchmarks/layer_metrics/<metric>.py``): each returns ``None`` where its
span is absent and the hand-computed value on a fixture. The ``.serve``
readers take the spans the serve driver pooled by name (milliseconds); the
``.train`` readers take the ``fleet_fit`` traces a fit leaves on the
process tracer."""

import json
import os
import sys

import pytest

from gordo_components_tpu.observability.tracing import Tracer, get_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")

SERVE = {
    "server_ms.serve": 30.0,          # median of the anomaly roots
    # parse, admit, queue_wait, handoff, coalesce, pad, device_execute,
    # postprocess, resolve, encode over the three roots; no child counted
    "span_coverage.serve": 100.0 * (1 + 2 + 6 + 9 + 1 + 1 + 40 + 3 + 3 + 4) / 80.0,
    "queue_behind_ms.serve": 4.0,
    "enqueue_ms.serve": 7.0,
    "device_wait_ms.serve": 9.0,
    "resolve_ms.serve": 1.0,
    "encode_ms.serve": 2.0,
    "receive_ms.serve": 0.5,          # under parse: in no sum of the top level
    "send_ms.serve": 4.0,             # after the root: in no sum of the top level
}
TRAIN = {
    "prepare_ms.train": 1e3 * 1.5,        # median of 1.0 and 2.0 s
    "epoch_host_ms.train": 1e3 * 0.3,     # median of 0.2 and 0.4 s
    "finish_ms.train": 1e3 * 0.75,        # median of 0.6 and 0.9 s
    "span_coverage.train": 100.0 * (1.0 + 6.0 + 0.2 + 0.6 + 2.0 + 6.0 + 0.4 + 0.9) / (8.0 + 10.0),
}


@pytest.fixture
def reader():
    """``harness.spec.load_reader``, as the benchmark's run finds a reader
    by its metric's name."""
    sys.path.insert(0, BENCH_DIR)
    try:
        from harness import spec

        yield spec.load_reader
    finally:
        sys.path.remove(BENCH_DIR)


def _serve_obs():
    return {"spans": {
        "anomaly": [20.0, 30.0, 30.0], "stats": [1.0],
        "parse": [0.5, 0.5], "admit": [1.0, 1.0],
        "queue_wait": [2.0, 4.0], "queue_behind": [1.0, 4.0, 5.0], "queue_flush": [2.0],
        "handoff": [9.0], "coalesce": [1.0], "pad": [1.0],
        "device_execute": [18.0, 22.0], "enqueue": [6.0, 7.0, 8.0], "device_wait": [9.0],
        "postprocess": [3.0], "fetch": [2.0], "reassemble": [1.0],
        "resolve": [0.5, 1.0, 1.5], "encode": [2.0, 2.0],
        "receive": [0.25, 0.5, 0.75], "send": [3.0, 5.0],
    }}


def _fit_trace(tracer, at, stages, bucket_s, name="fleet_fit"):
    """A finished fit trace starting at monotonic ``at``: one
    ``fit:<bucket>`` span of ``bucket_s`` seconds over ``stages``
    (name, seconds) laid end to end."""
    trace = tracer.start_trace(name, force=True)
    trace.root.start = at
    fit_span = trace.add_span("fit:f4x128", at, at + bucket_s)
    t = at
    for stage_name, seconds in stages:
        trace.add_span(stage_name, t, t + seconds, parent=fit_span)
        t += seconds
    trace.add_span("backend_compile", at, at + 0.5, parent=fit_span)  # not a stage
    trace.finish()


@pytest.fixture
def fit_traces(monkeypatch):
    """A process tracer holding a set-up fit and the window's two."""
    from gordo_components_tpu.observability import tracing

    tracer = Tracer(sample=1.0)
    monkeypatch.setattr(tracing, "_DEFAULT", tracer)
    assert get_tracer() is tracer
    first = [("stack_pad", 0.25), ("to_device", 0.25), ("scaler_fit", 0.25), ("init_state", 0.25),
             ("epoch", 3.0), ("epoch_host", 0.1), ("epoch", 3.0), ("epoch_host", 0.1),
             ("error_scalers", 0.2), ("unstack", 0.3), ("members", 0.1)]
    second = [("stack_pad", 0.5), ("to_device", 0.5), ("scaler_fit", 0.5), ("init_state", 0.5),
              ("epoch", 3.0), ("epoch_host", 0.2), ("epoch", 3.0), ("epoch_host", 0.2),
              ("error_scalers", 0.3), ("unstack", 0.3), ("members", 0.3)]
    # committed out of order: the readers sort by start time
    _fit_trace(tracer, 200.0, second, 10.0)
    _fit_trace(tracer, 0.0, [("stack_pad", 50.0), ("epoch_host", 50.0), ("members", 50.0)], 150.0)
    _fit_trace(tracer, 100.0, first, 8.0)
    _fit_trace(tracer, 300.0, [("stack_pad", 9.0)], 9.0, name="fleet_build")  # another root
    return {"fits": [{"wall_s": 8.1}, {"wall_s": 10.1}]}


@pytest.mark.parametrize("metric", sorted(SERVE))
def test_serve_reader(reader, metric):
    read = reader(metric)
    assert read({}) is None and read({"spans": {}}) is None
    if metric != "server_ms.serve":  # roots alone: no stage span to read
        assert read({"spans": {"anomaly": [30.0], "stats": [1.0]}}) is None
    assert read(_serve_obs()) == pytest.approx(SERVE[metric])


@pytest.mark.parametrize("metric", sorted(TRAIN))
def test_train_reader(reader, fit_traces, metric):
    read = reader(metric)
    assert read({}) is None and read({"fits": []}) is None
    assert read(fit_traces) == pytest.approx(TRAIN[metric])


@pytest.mark.parametrize("metric", sorted(TRAIN))
def test_train_reader_finds_nothing_without_fit_traces(reader, monkeypatch, metric):
    """A program whose fit keeps no ``fleet_fit`` trace (the parent of the
    PR that added the spans): the reader returns ``None``, it does not raise."""
    from gordo_components_tpu.observability import tracing

    monkeypatch.setattr(tracing, "_DEFAULT", Tracer(sample=1.0))
    assert reader(metric)({"fits": [{"wall_s": 4.3}]}) is None


FIRST_EPOCH_WAIT = "first_epoch_wait_ms.train"


@pytest.mark.parametrize(
    "fits,want",
    [
        (None, None),
        ([], None),
        ([{"wall_s": 1.0, "epoch_seconds": [0.5]}], None),  # one epoch: nothing to set it against
        ([{"wall_s": 1.0}, {"wall_s": 1.0, "epoch_seconds": []}], None),
        # per fit, first epoch less the median of the rest: 0.30 - 0.10, 0.12 - 0.11, 0.25 - 0.15
        # (the one-epoch fit is left out); the median of the three, in ms
        (
            [
                {"wall_s": 2.0, "epoch_seconds": [0.30, 0.10, 0.10, 0.40]},
                {"wall_s": 2.0, "epoch_seconds": [0.12, 0.11]},
                {"wall_s": 0.5, "epoch_seconds": [0.9]},
                {"wall_s": 2.0, "epoch_seconds": [0.25, 0.10, 0.20]},
            ],
            100.0,
        ),
        ([{"wall_s": 2.0, "epoch_seconds": [0.10, 0.15, 0.15]}], -50.0),  # no wait reads as none
    ],
)
def test_first_epoch_wait_reader(reader, fits, want):
    """It reads the driver's own ``fits`` (the trainer's ``epoch_seconds``),
    no span: a parent without this PR's staging is read the same way."""
    obs = {} if fits is None else {"fits": fits}
    got = reader(FIRST_EPOCH_WAIT)(obs)
    assert got is None if want is None else got == pytest.approx(want)


def test_every_new_reader_is_listed_with_its_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        per_layer = {m["name"]: m for m in json.load(fh)["per_layer"]}
    assert per_layer[FIRST_EPOCH_WAIT]["layer"] == "trainer, host side"
    for metric, cell in (
        [(m, "dense300.live") for m in SERVE]
        + [(m, "dense300.refit") for m in list(TRAIN) + [FIRST_EPOCH_WAIT]]
    ):
        entry = per_layer[metric]
        # its own cell first; later configurations append theirs (PR 28)
        assert entry["source"] == "program_span" and entry["workloads"][0] == cell
        assert os.path.exists(os.path.join(BENCH_DIR, "layer_metrics", metric + ".py"))
