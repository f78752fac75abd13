"""End-to-end request tracing (observability/tracing.py): traceparent
round-trip, span trees, Chrome trace-event export, ring/slow-reservoir
retention, the serving-path stage spans through a live ``build_app``, and
the tracing hot-loop overhead guard.
"""

import contextlib
import glob
import json
import os
import statistics
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from gordo_components_tpu import serializer
from gordo_components_tpu.models import AutoEncoder, DiffBasedAnomalyDetector
from gordo_components_tpu.observability.tracing import (
    Trace,
    Tracer,
    chrome_trace,
    covered_seconds,
    current_trace,
    format_traceparent,
    get_tracer,
    group_span,
    parse_traceparent,
    stage,
    use_trace,
)
from gordo_components_tpu.server import build_app

# ------------------------------------------------------------------ #
# W3C traceparent
# ------------------------------------------------------------------ #


def test_traceparent_parse_and_format_round_trip():
    tid, sid = "ab" * 16, "cd" * 8
    assert parse_traceparent(f"00-{tid}-{sid}-01") == (tid, sid, True)
    assert parse_traceparent(f"00-{tid}-{sid}-00") == (tid, sid, False)
    # flags are a bit field: 0x03 still carries sampled
    assert parse_traceparent(f"00-{tid}-{sid}-03")[2] is True
    # round trip through the formatter
    assert parse_traceparent(format_traceparent(tid, sid)) == (tid, sid, True)
    # malformed/forbidden forms are ignored per spec, never an error
    for bad in (
        None,
        "",
        "garbage",
        f"ff-{tid}-{sid}-01",  # version ff is forbidden
        f"00-{'0' * 32}-{sid}-01",  # all-zero trace id
        f"00-{tid}-{'0' * 16}-01",  # all-zero span id
        f"00-{tid[:-2]}-{sid}-01",  # short trace id
        f"00-{tid.upper()}-{sid}-XX",
    ):
        assert parse_traceparent(bad) is None, bad


# ------------------------------------------------------------------ #
# spans / trees / export
# ------------------------------------------------------------------ #


def test_span_tree_nesting_error_and_durations():
    tracer = Tracer(sample=1.0)
    trace = tracer.start_trace("request", request_id="rid-1")
    with trace.span("stage-a") as a:
        trace.add_span("child-of-a", a.start, a.start + 0.001, parent=a)
    with pytest.raises(RuntimeError):
        with trace.span("stage-b"):
            raise RuntimeError("boom")
    trace.finish(error=True)
    assert trace.error is True
    tree = trace.tree()
    assert tree["name"] == "request"
    kids = {c["name"]: c for c in tree["children"]}
    assert set(kids) == {"stage-a", "stage-b"}
    assert kids["stage-b"]["error"] is True
    assert kids["stage-a"]["children"][0]["name"] == "child-of-a"
    # child durations can never exceed the root's recorded total
    total = tree["duration_ms"]
    assert sum(c["duration_ms"] for c in tree["children"]) <= total + 1e-6
    # finish() is idempotent and closes abandoned spans
    trace.finish()
    assert all(s.end is not None for s in trace.spans)


def _validate_chrome(doc):
    """Chrome trace-event JSON object format: a traceEvents list whose
    duration events carry ph/name/pid/tid/ts/dur with numeric times."""
    doc = json.loads(json.dumps(doc))  # must be strictly JSON-serializable
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["pid"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["tid"], int)
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
    return doc


def test_chrome_trace_event_export():
    tracer = Tracer(sample=1.0)
    trace = tracer.start_trace("request")
    with trace.span("stage"):
        pass
    trace.finish()
    doc = _validate_chrome(chrome_trace([trace]))
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert names == ["request", "stage"]
    # spans nest by containment on one tid: child inside parent window
    root, stage = (e for e in doc["traceEvents"] if e["ph"] == "X")
    assert root["ts"] <= stage["ts"]
    assert stage["ts"] + stage["dur"] <= root["ts"] + root["dur"] + 1e-3


# ------------------------------------------------------------------ #
# sampling + retention
# ------------------------------------------------------------------ #


def test_disabled_tracer_returns_none():
    tracer = Tracer(sample=0.0)
    assert not tracer.enabled
    assert tracer.start_trace("request") is None


def test_head_sampling_controls_ring_but_forced_always_kept():
    tracer = Tracer(sample=0.01, ring=1000)
    for _ in range(200):
        tracer.start_trace("r").finish()
    # ~2 expected at 1%; catastrophically more means sampling is broken
    assert len(tracer.recent()) < 50
    forced = tracer.start_trace(
        "r", traceparent=format_traceparent("ab" * 16, "cd" * 8, sampled=True)
    )
    forced.finish()
    assert any(t.trace_id == "ab" * 16 for t in tracer.recent())
    assert tracer.inflight == 0


def test_ring_is_bounded():
    tracer = Tracer(sample=1.0, ring=8)
    for _ in range(50):
        tracer.start_trace("r").finish()
    assert len(tracer.recent()) == 8


def _finish_with_duration(trace, seconds):
    """Synthesize a completed trace of a given duration (mixed-latency
    load without sleeping)."""
    trace.root.start = time.monotonic() - seconds
    trace.finish()


def test_slow_reservoir_retains_worst_n_under_mixed_latency_load():
    """The flight-recorder acceptance: at sampling 1.0, a mixed-latency
    stream leaves exactly the worst-N requests in the slow reservoir,
    slowest first — even though the ring has long since evicted them."""
    tracer = Tracer(sample=1.0, ring=4, slow_keep=5)
    rng = np.random.RandomState(0)
    durations = rng.permutation(
        np.concatenate([rng.uniform(0.001, 0.01, 195), [5.0, 4.0, 3.0, 2.0, 1.0]])
    )
    for d in durations:
        _finish_with_duration(tracer.start_trace("r"), float(d))
    slow = tracer.slow()
    got = [round(t.duration_s) for t in slow]
    assert got == [5, 4, 3, 2, 1]
    # the ring only holds the last 4; the reservoir still has the worst
    assert len(tracer.recent()) == 4
    assert tracer.inflight == 0


def test_slow_reservoir_survives_head_sampling():
    """always-sample-slow: a slow trace the head sampler would drop from
    the ring still lands in the reservoir."""
    tracer = Tracer(sample=1e-9, ring=100, slow_keep=3)
    for i in range(50):
        _finish_with_duration(tracer.start_trace(f"r{i}"), 0.001 * (i + 1))
    assert len(tracer.recent()) == 0  # head sampler kept nothing
    assert [t.name for t in tracer.slow()] == ["r49", "r48", "r47"]


def test_current_trace_contextvar():
    assert current_trace() is None
    trace = Trace(None, "build")
    with use_trace(trace):
        assert current_trace() is trace
    assert current_trace() is None


# ------------------------------------------------------------------ #
# stage(): one span and one profiler annotation from the same clock reads
# ------------------------------------------------------------------ #


def test_stage_writes_one_span_per_trace_and_skips_none():
    tracer = Tracer(sample=1.0)
    a, b = tracer.start_trace("a"), tracer.start_trace("b")
    with stage("pad", a, None, b, bucket="f3") as pad:
        pad.attributes["chunks"] = 2
    for trace in (a, b):
        (span,) = [s for s in trace.spans if s.name == "pad"]
        assert span in trace.children()
        assert (span.start, span.end) == (pad.start, pad.end)
        assert span.parent is None  # top level: under each trace's own root
        assert span.attributes == {"bucket": "f3", "chunks": 2}
        assert span.error is False
    assert pad.seconds == pad.end - pad.start >= 0
    with stage("pad"):  # no trace at all: only the annotation and the clock
        pass


def test_stage_parent_and_error_flag():
    """The requests of one coalesced group hold the same span objects:
    a parent opened once (group_span), its children under it in each."""
    tracer = Tracer(sample=1.0)
    a, b = tracer.start_trace("a"), tracer.start_trace("b")
    parent = group_span("device_execute", [a, None, b], time.monotonic())
    with stage("enqueue", a, b, parent=parent):
        pass
    with pytest.raises(RuntimeError):
        with stage("device_wait", a, b, parent=parent) as wait:
            raise RuntimeError("device fault")
    parent.end = wait.end
    with stage("checkpoint", a) as saving:
        saving.error = True  # a failure the block handled itself
    for trace in (a, b):
        assert [s.name for s in trace.children(parent)] == ["enqueue", "device_wait"]
        by_name = {s.name: s for s in trace.spans}
        assert by_name["enqueue"].error is False
        assert by_name["device_wait"].error is True
        trace.finish()
        (execute,) = [
            c for c in trace.tree()["children"] if c["name"] == "device_execute"
        ]
        assert [c["name"] for c in execute["children"]] == ["enqueue", "device_wait"]
    assert {s.name: s for s in a.spans}["checkpoint"].error is True
    # ids are minted on first read, once
    assert parent.span_id == parent.span_id and len(parent.span_id) == 16


def test_covered_seconds_is_the_union():
    trace = Trace(None, "t")
    spans = [
        trace.add_span("x", 1.0, 3.0),
        trace.add_span("x", 2.0, 2.5),  # nested: counts once
        trace.add_span("x", 2.8, 4.0),  # overlapping
        trace.add_span("x", 6.0, 7.0),
        trace.start_span("open"),  # no end yet: ignored
    ]
    assert covered_seconds(spans) == pytest.approx(4.0)
    assert covered_seconds([]) == 0.0


def test_measured_compiles_land_on_the_current_trace():
    """JAX's own compile timings (jax.monitoring) become spans of the
    current trace, under the current parent; outside a trace nothing is
    recorded."""
    import jax
    import jax.numpy as jnp

    with stage("bind"):  # the listener is registered by the first stage
        pass
    trace = Tracer(sample=1.0).start_trace("fit")
    parent = trace.start_span("fit:bucket")
    with use_trace(trace, parent):
        jax.jit(lambda x: jnp.tanh(x * 3.0 + 1.0).sum())(jnp.ones((7, 5)))
    n = len(trace.spans)
    compiled = [s for s in trace.spans if s.name == "backend_compile"]
    assert compiled, [s.name for s in trace.spans]
    assert all(s.parent is parent for s in compiled)
    assert all(s.attributes.get("fun_name") for s in compiled)
    assert all(parent.start <= s.start <= s.end for s in compiled)
    jax.jit(lambda x: jnp.tanh(x * 5.0 - 1.0).sum())(jnp.ones((7, 5)))
    assert len(trace.spans) == n  # no current trace: nothing stamped


_FIT_STAGES = {
    "stack_pad", "to_device", "scaler_fit", "init_state", "epoch",
    "epoch_host", "error_scalers", "unstack", "members",
}
_COMPILE_SPANS = {"trace_lower", "backend_compile", "cache_load"}


def _fit_members():
    rng = np.random.RandomState(5)
    return {f"m{i}": rng.rand(90, 4).astype("float32") for i in range(3)}


def test_fit_without_a_caller_trace_leaves_one_fleet_fit_trace():
    """A fit is a trace: with no caller trace it opens ``fleet_fit`` on
    the process tracer, retained whatever the head sampling says, and the
    bucket's stages are the documented ones, tiling its ``fit:<bucket>``.
    ``epoch_seconds`` is read off the ``epoch`` spans."""
    from gordo_components_tpu.parallel.fleet import FleetTrainer

    before = [t for t in get_tracer().recent() if t.name == "fleet_fit"]
    trainer = FleetTrainer(epochs=4, batch_size=32)
    trainer.fit(_fit_members())
    fits = [t for t in get_tracer().recent() if t.name == "fleet_fit"]
    assert len(fits) == len(before) + 1
    trace = fits[0]
    assert trace.finished and not trace.error
    assert trace.root.attributes["members"] == 3
    (fit_span,) = [s for s in trace.spans if s.name.startswith("fit:")]
    stages = [s for s in trace.children(fit_span) if s.name not in _COMPILE_SPANS]
    assert {s.name for s in stages} == _FIT_STAGES
    assert not [s for s in trace.spans if s.name == "compile"]  # the estimate is gone
    # the stages follow one another inside the fit span and cover it
    for prev, nxt in zip(stages, stages[1:]):
        assert prev.end <= nxt.start
    assert fit_span.start <= stages[0].start and stages[-1].end <= fit_span.end
    assert covered_seconds(stages) >= 0.9 * fit_span.duration_s
    epochs = [s for s in stages if s.name == "epoch"]
    assert [s.attributes["epoch"] for s in epochs] == [0, 1, 2, 3]
    assert trainer.last_stats["buckets"][0]["epoch_seconds"] == [
        s.duration_s for s in epochs
    ]


def test_fit_records_into_the_callers_trace():
    from gordo_components_tpu.parallel.fleet import FleetTrainer

    before = len([t for t in get_tracer().recent() if t.name == "fleet_fit"])
    build = Tracer(sample=1.0).start_trace("fleet_build")
    with use_trace(build):
        FleetTrainer(epochs=2, batch_size=32).fit(_fit_members())
    assert len([t for t in get_tracer().recent() if t.name == "fleet_fit"]) == before
    assert not build.finished  # the caller's to close
    names = {s.name for s in build.spans}
    assert _FIT_STAGES <= names and any(n.startswith("fit:") for n in names)


def test_profiler_session_holds_the_programs_stages(tmp_path, artifact_dir):
    """Under one profiler session the recorded trace's host plane holds
    the ``gordo:<stage>`` regions beside the XLA ops (read as
    benchmarks/tools/record_small_trace.py's trace is read)."""
    import jax
    from jax.profiler import ProfileData

    from gordo_components_tpu.parallel.fleet import FleetTrainer
    from gordo_components_tpu.server.bank import ModelBank
    from gordo_components_tpu.server.model_io import ModelCollection

    bank = ModelBank.from_models(ModelCollection(artifact_dir).models, registry=False)
    requests = [("banked", np.random.RandomState(2).rand(32, 3).astype("float32"), None)]
    bank.score_many(requests)  # compile outside the session
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # TraceMe regions only
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        bank.score_many(requests)
        FleetTrainer(epochs=2, batch_size=32).fit(_fit_members())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    regions = {
        e.name
        for plane in ProfileData.from_file(path).planes
        if not plane.name.startswith("/device:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("gordo:")
    }
    for name in ("coalesce", "pad", "enqueue", "device_wait", "fetch",
                 "reassemble", "stack_pad", "epoch", "epoch_host", "members"):
        assert "gordo:" + name in regions, sorted(regions)


def test_exported_span_lies_on_its_profiler_region(tmp_path):
    """A span's Chrome ``ts`` is on the profiler's clock: the ``gordo:x``
    region that the same ``stage`` opened lies at the trace's
    ``profile_start_time`` plus its ``start_ns``, and the exported span
    starts within 0.5 ms of it and lasts as long."""
    import jax
    from jax.profiler import ProfileData

    trace = Tracer(sample=1.0).start_trace("request")
    stage("x", trace)  # binds JAX's profiler outside the session
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        time.sleep(0.01)
        with stage("x", trace):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    trace.finish()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    data = ProfileData.from_file(path)
    (session_start_ns,) = [
        value
        for plane in data.planes
        for name, value in plane.stats
        if name == "profile_start_time"
    ]
    (region,) = [
        e
        for plane in data.planes
        if not plane.name.startswith("/device:")
        for line in plane.lines
        for e in line.events
        if e.name == "gordo:x"
    ]
    (span,) = [e for e in chrome_trace([trace])["traceEvents"] if e["name"] == "x"]
    assert abs(span["ts"] - (session_start_ns + region.start_ns) * 1e-3) <= 500
    assert abs(span["dur"] - region.duration_ns * 1e-3) <= 500


def test_traces_share_one_clock_anchor():
    """Every trace of the process is exported against one anchor: two
    traces' exported starts lie as far apart as their monotonic starts,
    and ``start_unix`` is the root's start on the same clock."""
    tracer = Tracer(sample=1.0)
    first = tracer.start_trace("request")
    time.sleep(0.02)
    second = tracer.start_trace("request")
    first.finish()
    second.finish()
    roots = [
        e for e in chrome_trace([first, second])["traceEvents"]
        if e["ph"] == "X" and e["name"] == "request"
    ]
    apart_us = (second.root.start - first.root.start) * 1e6
    assert roots[1]["ts"] - roots[0]["ts"] == pytest.approx(apart_us, abs=1.0)
    assert roots[0]["ts"] * 1e-6 == pytest.approx(first.summary()["start_unix"], abs=1e-3)
    assert first.summary()["start_unix"] == pytest.approx(time.time(), abs=5.0)


# ------------------------------------------------------------------ #
# live server: the acceptance round-trip
# ------------------------------------------------------------------ #


@pytest.fixture(scope="module")
def artifact_dir(tmp_path_factory):
    rng = np.random.RandomState(0)
    X = rng.rand(160, 3).astype("float32")
    root = tmp_path_factory.mktemp("trace-collection")
    det = DiffBasedAnomalyDetector(
        base_estimator=AutoEncoder(epochs=1, batch_size=64)
    )
    det.fit(X)
    serializer.dump(det, str(root / "banked"), metadata={"name": "banked"})
    ae = AutoEncoder(epochs=1, batch_size=64)
    ae.fit(X)
    serializer.dump(ae, str(root / "bare"), metadata={"name": "bare"})
    return str(root)


@contextlib.asynccontextmanager
async def _client(artifact_dir, monkeypatch, sample="1.0", **env):
    monkeypatch.setenv("GORDO_TRACE_SAMPLE", sample)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    client = TestClient(TestServer(build_app(artifact_dir)))
    await client.start_server()
    try:
        yield client
    finally:
        await client.close()


def _x_payload(n=24, f=3):
    rng = np.random.RandomState(1)
    return {"X": rng.rand(n, f).tolist()}


# a banked request's top-level spans, in the order it passes through them
_STAGES = (
    "parse", "admit", "queue_wait", "handoff", "coalesce", "pad",
    "device_execute", "postprocess", "resolve", "encode",
)
_CHILDREN = {
    "queue_wait": ("queue_behind", "queue_flush"),
    "device_execute": ("enqueue", "device_wait"),
    "postprocess": ("fetch", "reassemble"),
}


def _flatten(node, out=None):
    out = out if out is not None else []
    out.append(node)
    for child in node.get("children", ()):
        _flatten(child, out)
    return out


async def test_traceparent_request_yields_full_stage_trace(
    artifact_dir, monkeypatch
):
    """The acceptance criterion end to end: a traceparent-carrying request
    is retrievable at GET /traces with all the hot-path stage spans,
    child durations sum to <= the recorded total, the id echoes in the
    X-Request-Id/traceparent response headers, and the Chrome export is
    valid trace-event JSON."""
    tid = "ab" * 16
    async with _client(artifact_dir, monkeypatch) as client:
        resp = await client.post(
            "/gordo/v0/proj/banked/anomaly/prediction",
            json=_x_payload(),
            headers={"traceparent": format_traceparent(tid, "cd" * 8)},
        )
        assert resp.status == 200
        # trace id echoed: X-Request-Id and a continued traceparent
        assert resp.headers["X-Request-Id"] == tid
        echoed = parse_traceparent(resp.headers["traceparent"])
        assert echoed is not None and echoed[0] == tid
        body = await (await client.get(f"/gordo/v0/proj/traces?id={tid}")).json()
        assert body["enabled"] is True
        (trace,) = body["traces"]
        assert trace["trace_id"] == tid
        tree = trace["spans"]
        flat = _flatten(tree)
        names = [n["name"] for n in flat]
        for stage in _STAGES:
            assert stage in names, f"missing stage span {stage!r}"
        # children sum <= recorded total (stages don't overlap)
        total = tree["duration_ms"]
        assert total > 0
        assert sum(c["duration_ms"] for c in tree["children"]) <= total + 1e-6
        # stage spans sit inside the root window
        for node in flat[1:]:
            assert node["start_ms"] >= -1e-6
            assert node["start_ms"] + node["duration_ms"] <= total + 1e-6
        # the exported JSON is valid Chrome trace-event format
        chrome = await (
            await client.get(f"/gordo/v0/proj/traces?id={tid}&format=chrome")
        ).json()
        doc = _validate_chrome(chrome)
        chrome_names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert set(_STAGES) <= chrome_names
        # recent listing + slow reservoir both serve it
        slow = await (await client.get("/gordo/v0/proj/traces/slow")).json()
        assert any(t["trace_id"] == tid for t in slow["traces"])
        # nothing leaked open
        assert client.app["tracer"].inflight == 0


@pytest.mark.parametrize("encoding", ["json", "tensor"])
async def test_top_level_spans_tile_the_request(artifact_dir, monkeypatch, encoding):
    """The top-level stages of a banked anomaly request follow one
    another without overlap and cover its root span (>= 0.9 on the CPU,
    where the stages are short and the middleware's own work is not);
    each child lies inside its parent; ``postprocess`` is the bank's
    alone, once per request per group, and the view's framing is
    ``encode``. A tensor request's ``parse`` holds ``receive``, and its
    answer's ``send`` hangs under the root after the root's end: the
    body's bytes leave once the handler has returned."""
    from gordo_components_tpu.observability.goodput import attribute_trace
    from gordo_components_tpu.utils.wire import TENSOR_CONTENT_TYPE, pack_frames

    X = np.random.RandomState(1).rand(24, 3).astype("float32")
    if encoding == "tensor":
        post = dict(
            data=pack_frames([("X", X)]), headers={"Content-Type": TENSOR_CONTENT_TYPE}
        )
    else:
        post = dict(json={"X": X.tolist()})
    async with _client(artifact_dir, monkeypatch) as client:
        for _ in range(6):
            resp = await client.post("/gordo/v0/proj/banked/anomaly/prediction", **post)
            assert resp.status == 200
            await resp.read()
        traces = [t for t in client.app["tracer"].recent() if t.name == "anomaly"]
    assert len(traces) == 6
    children = dict(_CHILDREN)
    if encoding == "tensor":
        children["parse"] = ("receive",)
    coverages = []
    for trace in traces:
        root = trace.root
        top = trace.children()
        sends = [s for s in top if s.name == "send"]
        assert len(sends) == (encoding == "tensor")
        assert all(root.end <= s.start <= s.end for s in sends)
        top = [s for s in top if s.name != "send"]
        names = [s.name for s in top]
        assert set(names) == set(_STAGES), names
        # once each, but for the two framing steps of the JSON path
        assert all(names.count(n) == 1 for n in _STAGES if n != "encode"), names
        assert root.start <= top[0].start and top[-1].end <= root.end
        for prev, nxt in zip(top, top[1:]):
            assert prev.end <= nxt.start, (prev.name, nxt.name)
        for parent in top:
            kids = trace.children(parent)
            assert [s.name for s in kids] == list(children.get(parent.name, ()))
            for kid in kids:
                assert parent.start <= kid.start and kid.end <= parent.end
        assert len(trace.spans) == (
            1 + len(top) + len(sends) + sum(map(len, children.values()))
        )
        encodes = [s.attributes["stage"] for s in top if s.name == "encode"]
        assert encodes == (["to_wire"] if encoding == "tensor" else ["to_frame", "to_json"])
        coverages.append(covered_seconds(top) / root.duration_s)
        # the goodput attribution reads the same stages: its residual is
        # what the spans leave uncovered
        assert attribute_trace(trace)["coverage"] == pytest.approx(coverages[-1], abs=0.01)
    assert statistics.median(coverages) >= 0.9, coverages


async def test_every_response_carries_request_id(artifact_dir, monkeypatch):
    """Satellite: every response — including generated 500s and 410
    quarantine responses — carries a non-empty X-Request-Id, synthesized
    when the client sent no header at all."""
    async with _client(artifact_dir, monkeypatch) as client:
        # plain 200 with no client headers: synthesized ids
        resp = await client.get("/gordo/v0/proj/models")
        assert resp.headers["X-Request-Id"]
        assert resp.headers["X-Gordo-Request-Id"].startswith("srv-")
        # 404 (HTTPException path)
        resp = await client.get("/gordo/v0/proj/ghost/healthcheck")
        assert resp.status == 404
        assert resp.headers["X-Request-Id"]
        # 400 (bad body)
        resp = await client.post("/gordo/v0/proj/banked/prediction", json={"no": 1})
        assert resp.status == 400
        assert resp.headers["X-Request-Id"]
        # 410 quarantine: trip the breaker directly, then request
        q = client.app["quarantine"]
        for _ in range(10):
            q.record_failure("banked", "poisoned for the header test")
        resp = await client.post(
            "/gordo/v0/proj/banked/prediction", json=_x_payload()
        )
        assert resp.status == 410
        assert resp.headers["X-Request-Id"]
        q.clear(["banked"])
        # generated 500 (handler crash): break the collection under a
        # stats-reading endpoint
        client.app["collection"]._state = None
        resp = await client.get("/gordo/v0/proj/ready")
        assert resp.status == 500
        assert resp.headers["X-Request-Id"]


async def test_exemplar_links_latency_bucket_to_trace(artifact_dir, monkeypatch):
    """Metric spike -> offending trace: /stats carries per-kind exemplars
    keyed by latency-bucket edge, and the exemplar's trace id resolves at
    GET /traces?id=..."""
    async with _client(artifact_dir, monkeypatch) as client:
        resp = await client.post(
            "/gordo/v0/proj/banked/anomaly/prediction", json=_x_payload()
        )
        assert resp.status == 200
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
        exemplars = stats["exemplars"]["anomaly"]
        assert exemplars
        (le, ex), *_ = exemplars.items()
        assert ex["trace_id"] and ex["value_ms"] > 0
        body = await (
            await client.get(f"/gordo/v0/proj/traces?id={ex['trace_id']}")
        ).json()
        assert body["traces"], "exemplar trace must be retrievable"


async def test_per_model_fallback_path_gets_device_execute_span(
    artifact_dir, monkeypatch
):
    async with _client(artifact_dir, monkeypatch) as client:
        tid = "ef" * 16
        resp = await client.post(
            "/gordo/v0/proj/bare/prediction",
            json=_x_payload(),
            headers={"traceparent": format_traceparent(tid, "cd" * 8)},
        )
        assert resp.status == 200
        body = await (await client.get(f"/gordo/v0/proj/traces?id={tid}")).json()
        (trace,) = body["traces"]
        flat = _flatten(trace["spans"])
        execs = [n for n in flat if n["name"] == "device_execute"]
        assert execs and execs[0]["attributes"]["path"] == "per-model"


async def test_tracing_disabled_no_traces_and_no_trace_headers(
    artifact_dir, monkeypatch
):
    async with _client(artifact_dir, monkeypatch, sample="0") as client:
        resp = await client.post(
            "/gordo/v0/proj/banked/prediction",
            json=_x_payload(),
            headers={"traceparent": format_traceparent("ab" * 16, "cd" * 8)},
        )
        assert resp.status == 200
        # request ids still flow; trace machinery stays silent
        assert resp.headers["X-Request-Id"]
        assert "traceparent" not in resp.headers
        body = await (await client.get("/gordo/v0/proj/traces")).json()
        assert body == {"enabled": False, "traces": []}
        slow = await (await client.get("/gordo/v0/proj/traces/slow")).json()
        assert slow == {"enabled": False, "traces": []}


# ------------------------------------------------------------------ #
# hot-loop overhead guard (the PR-1/PR-2 pattern, third instance)
# ------------------------------------------------------------------ #


class _NoStage:
    """What the scoring loop would cost with no stage() in it at all: a
    context manager that reads no clock and opens no profiler region."""

    start = end = seconds = 0.0

    def __init__(self, name, *traces, parent=None, **attributes):
        self.attributes = attributes

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


@pytest.mark.hotloop
def test_tracing_hot_loop_within_5pct(artifact_dir, monkeypatch):
    """The serving hot loop with tracing FULLY ENABLED (a live Trace per
    request: stage() regions, block_until_ready fencing, span appends)
    must stay within 5% of the untraced loop, and the untraced loop —
    where every stage() still opens its profiler annotation and reads the
    clock twice, because stage() has no flag — within 5% of the same loop
    with no stage() at all.

    Measured on a realistically coalesced call (8 requests x 256 rows,
    the shape the engine actually dispatches under load) where the
    tracing layer's small fixed per-call cost must amortize below 5% —
    a per-ROW cost creeping into the span path still fails. Interleaved
    best-of-N timing so machine drift hits all sides."""
    from gordo_components_tpu.server import bank as bank_module
    from gordo_components_tpu.server.model_io import ModelCollection
    from gordo_components_tpu.server.bank import ModelBank

    collection = ModelCollection(artifact_dir)
    bank = ModelBank.from_models(collection.models, registry=False)
    rng = np.random.RandomState(2)
    requests = [
        ("banked", rng.rand(256, 3).astype("float32"), None) for _ in range(8)
    ]
    bank.score_many(requests)  # warm/compile

    tracer = Tracer(sample=1.0, ring=4, slow_keep=4)

    def timed(traced, iters=20):
        t0 = time.perf_counter()
        for _ in range(iters):
            if traced:
                traces = [tracer.start_trace("bench") for _ in requests]
                bank.score_many(requests, traces=traces)
                for trace in traces:
                    trace.finish()
            else:
                bank.score_many(requests)
        return time.perf_counter() - t0

    def timed_bare():
        with monkeypatch.context() as patched:
            patched.setattr(bank_module, "stage", _NoStage)
            return timed(False)

    rounds, ratios, off_ratios = 7, [], []
    for _ in range(rounds):
        bare = timed_bare()
        control = timed(False)
        instrumented = timed(True)
        ratios.append(instrumented / control)
        off_ratios.append(control / bare)
    assert min(ratios) <= 1.05, ratios
    assert min(off_ratios) <= 1.05, off_ratios
    # and the instrumentation actually recorded stage spans
    slow = tracer.slow()
    assert slow and any(s.name == "device_execute" for s in slow[0].spans)
