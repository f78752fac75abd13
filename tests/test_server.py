"""Server tests, in-process via aiohttp's test utilities against small
models trained in a fixture (reference strategy: Flask test_client, SURVEY.md
§4). Async tests are run by the conftest ``pytest_pyfunc_call`` hook."""

import asyncio
import contextlib
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from gordo_components_tpu import serializer
from gordo_components_tpu.models import AutoEncoder, DiffBasedAnomalyDetector
from gordo_components_tpu.observability import Tracer
from gordo_components_tpu.observability.tracing import format_traceparent
from gordo_components_tpu.server import build_app
from gordo_components_tpu.server.model_io import anomaly_frames
from gordo_components_tpu.server.views import TensorBody
from gordo_components_tpu.server.transport import score_tensor_blocking
from gordo_components_tpu.server.utils import dict_to_frame, frame_to_dict
from gordo_components_tpu.utils.wire import (
    TENSOR_CONTENT_TYPE,
    pack_frames,
    unpack_frames,
)


@pytest.fixture(scope="module")
def artifact_dir(tmp_path_factory):
    """Two artifacts under one collection root: an anomaly detector and a
    plain estimator."""
    rng = np.random.RandomState(0)
    Xv = rng.rand(200, 3).astype("float32")
    root = tmp_path_factory.mktemp("collection")

    det = DiffBasedAnomalyDetector(base_estimator=AutoEncoder(epochs=2, batch_size=64))
    det.fit(Xv)
    serializer.dump(det, str(root / "machine-a"), metadata={"name": "machine-a"})

    ae = AutoEncoder(epochs=2, batch_size=64)
    ae.fit(Xv)
    serializer.dump(ae, str(root / "machine-b"), metadata={"name": "machine-b"})
    return str(root)


@contextlib.asynccontextmanager
async def make_client(artifact_dir, on_prepare=(), **kwargs):
    app = build_app(artifact_dir, **kwargs)
    app.on_response_prepare.extend(on_prepare)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        yield client
    finally:
        await client.close()


def _x_payload(n=20, f=3):
    rng = np.random.RandomState(1)
    return {"X": rng.rand(n, f).tolist()}


async def test_readiness_is_count_only(artifact_dir):
    """The K8s probe hits /ready every few seconds; it must be O(1)
    (counts, not the 10k-name + bank-coverage body of /models) and 503
    when the collection holds no models (every artifact removed by a
    refresh — empty-at-startup is rejected earlier by build_app)."""
    async with make_client(artifact_dir) as client:
        resp = await client.get("/gordo/v0/proj/ready")
        assert resp.status == 200
        body = await resp.json()
        assert body == {"ready": True, "models": 2}
        # all models gone (refresh removed them): not ready
        client.app["collection"]._state = ({}, {})
        resp = await client.get("/gordo/v0/proj/ready")
        assert resp.status == 503
        assert (await resp.json())["ready"] is False


async def test_list_models(artifact_dir):
    async with make_client(artifact_dir) as client:
        resp = await client.get("/gordo/v0/proj/models")
        assert resp.status == 200
        body = await resp.json()
        assert body["models"] == ["machine-a", "machine-b"]
        # bank coverage surfaced per model: machine-a (detector) banks,
        # machine-b (bare estimator) falls back with a reason
        assert body["bank"]["banked"] == ["machine-a"]
        assert "machine-b" in body["bank"]["fallback"]
        assert "DiffBasedAnomalyDetector" in body["bank"]["fallback"]["machine-b"]


async def test_metadata_all(artifact_dir):
    """The batched control-plane endpoint: every target's health +
    metadata (+ bank coverage) in one response, so watchman snapshots
    cost O(1) requests instead of O(2N) per-target polls."""
    async with make_client(artifact_dir) as client:
        resp = await client.get("/gordo/v0/proj/metadata-all")
        assert resp.status == 200
        body = await resp.json()
        assert set(body["targets"]) == {"machine-a", "machine-b"}
        for name, entry in body["targets"].items():
            assert entry["healthy"] is True
            assert entry["endpoint-metadata"]["name"] == name
        assert body["bank"]["banked"] == ["machine-a"]
        assert "machine-b" in body["bank"]["fallback"]


async def test_metadata_all_digest(artifact_dir):
    """?digest=1 swaps full per-target metadata for the bounded digest —
    O(small) bytes for watchman polling (full stays the default)."""
    import json as _json

    async with make_client(artifact_dir) as client:
        full = await (await client.get("/gordo/v0/proj/metadata-all")).json()
        dig = await (
            await client.get("/gordo/v0/proj/metadata-all?digest=1")
        ).json()
    assert set(dig["targets"]) == set(full["targets"])
    for name, entry in dig["targets"].items():
        assert "endpoint-metadata" not in entry
        assert entry["healthy"] is True
        d = entry["digest"]
        assert d["name"] == name
        assert len(_json.dumps(d)) < 400
    assert len(_json.dumps(dig)) < len(_json.dumps(full))


async def test_server_stats(artifact_dir):
    """GET /stats reports per-endpoint request counters, errors, uptime,
    and the batching engine's coalescing stats."""
    async with make_client(artifact_dir) as client:
        await client.get("/gordo/v0/proj/models")
        await client.get("/gordo/v0/proj/machine-a/healthcheck")
        await client.get("/gordo/v0/proj/ghost/healthcheck")  # 404 -> errors
        await client.post(
            "/gordo/v0/proj/machine-a/anomaly/prediction", json=_x_payload()
        )
        # scanner probes with unbounded distinct paths must collapse into
        # ONE "other" bucket, not one counter key per probed URL
        await client.get("/admin.php")
        await client.get("/nonsense-123")
        resp = await client.get("/gordo/v0/proj/stats")
        assert resp.status == 200
        body = await resp.json()
    assert body["uptime_seconds"] >= 0
    assert body["requests"]["models"] == 1
    assert body["requests"]["healthcheck"] == 2
    assert body["requests"]["anomaly"] == 1
    assert body["requests"]["other"] == 2
    assert "admin.php" not in body["requests"]
    assert body["errors"] == 3  # ghost 404 + two unmatched probes
    assert body["models"] == 2
    # machine-a banks, so the engine coalescing stats must surface
    assert body["bank_engine"]["requests"] >= 1
    assert body["bank_engine"]["avg_batch"] >= 1
    assert 0 <= body["bank_engine"]["requests_behind"] <= body["bank_engine"]["requests"]
    # latency percentiles per endpoint kind (VERDICT r3 #4): the anomaly
    # request above must have produced a non-empty histogram snapshot
    lat = body["latency"]["anomaly"]
    assert lat["count"] == 1
    assert 0 < lat["p50_ms"] <= lat["p99_ms"] <= lat["max_ms"] * 1.27
    assert lat["mean_ms"] > 0
    # errored requests are measured too (the 404 healthcheck)
    assert body["latency"]["healthcheck"]["count"] == 2
    # and the engine's own queue-wait/service split quantifies flush_ms
    assert body["bank_engine"]["service"]["count"] >= 1
    assert body["bank_engine"]["queue_wait"]["count"] >= 1
    assert (
        body["bank_engine"]["queue_wait"]["p50_ms"]
        <= body["bank_engine"]["service"]["p99_ms"]
    )


async def test_healthcheck_and_404(artifact_dir):
    async with make_client(artifact_dir) as client:
        resp = await client.get("/gordo/v0/proj/machine-a/healthcheck")
        assert resp.status == 200
        assert "gordo-server-version" in await resp.json()
        resp = await client.get("/gordo/v0/proj/ghost/healthcheck")
        assert resp.status == 404


async def test_metadata(artifact_dir):
    async with make_client(artifact_dir) as client:
        resp = await client.get("/gordo/v0/proj/machine-a/metadata")
        body = await resp.json()
        assert body["endpoint-metadata"]["name"] == "machine-a"


async def test_prediction_and_bad_body(artifact_dir):
    async with make_client(artifact_dir) as client:
        resp = await client.post(
            "/gordo/v0/proj/machine-b/prediction", json=_x_payload()
        )
        assert resp.status == 200
        body = await resp.json()
        assert np.asarray(body["data"]).shape == (20, 3)

        resp = await client.post(
            "/gordo/v0/proj/machine-b/prediction", json={"nope": 1}
        )
        assert resp.status == 400


async def test_anomaly_prediction(artifact_dir):
    async with make_client(artifact_dir) as client:
        resp = await client.post(
            "/gordo/v0/proj/machine-a/anomaly/prediction", json=_x_payload()
        )
        assert resp.status == 200
        frame = dict_to_frame(await resp.json())
        assert ("total-anomaly-scaled", "") in frame.columns
        assert len(frame) == 20

        # plain estimator has no .anomaly
        resp = await client.post(
            "/gordo/v0/proj/machine-b/anomaly/prediction", json=_x_payload()
        )
        assert resp.status == 422


async def test_download_model(artifact_dir):
    async with make_client(artifact_dir) as client:
        resp = await client.get("/gordo/v0/proj/machine-b/download-model")
        assert resp.status == 200
        model = serializer.loads(await resp.read())
        assert isinstance(model, AutoEncoder)


def test_frame_dict_roundtrip():
    import pandas as pd

    df = pd.DataFrame(
        {("a", "x"): [1.0, 2.0], ("a", "y"): [3.0, 4.0], ("b", ""): [5.0, 6.0]},
        index=pd.date_range("2020", periods=2, freq="1h", tz="UTC"),
    )
    df.columns = pd.MultiIndex.from_tuples(df.columns)
    rt = dict_to_frame(frame_to_dict(df))
    assert list(rt.columns) == list(df.columns)
    np.testing.assert_allclose(rt.values, df.values)


# --------------------------------------------------------------------- #
# a tensor answer goes to the socket as its arrays (views.TensorBody)
# --------------------------------------------------------------------- #


def _tensor_request(rows, seed):
    X = np.random.RandomState(seed).rand(rows, 3).astype("float32")
    return X, pack_frames([("X", X)])


async def _stored_way(app, target, body, endpoint):
    """The same request through ``score_tensor_blocking``: the joined
    ``encode_*_response`` bytes, as the shm transport puts them in its
    ring's envelope."""
    import asyncio

    status, want = await asyncio.get_running_loop().run_in_executor(
        None, score_tensor_blocking, app, target, body, endpoint
    )
    assert status == 200
    return want


@pytest.mark.parametrize(
    "target, endpoint, use_bank",
    [
        ("machine-a", "anomaly", True),  # the banked path: ScoreResult's arrays
        ("machine-a", "anomaly", False),  # the per-model path: a frame's columns
        ("machine-a", "prediction", True),
        ("machine-b", "prediction", True),  # a bare estimator: never banked
    ],
)
async def test_tensor_answer_is_sent_under_its_length_as_the_encoders_bytes(
    artifact_dir, target, endpoint, use_bank
):
    """30 000 rows: every (rows, 3) array is over the small-payload rule,
    so it leaves by reference; the body is still byte for byte what
    ``encode_*_response`` joins, under ``Content-Length``, not chunked."""
    _, body = _tensor_request(30_000, seed=5)
    path = "anomaly/prediction" if endpoint == "anomaly" else "prediction"
    async with make_client(artifact_dir, use_bank=use_bank) as client:
        resp = await client.post(
            f"/gordo/v0/proj/{target}/{path}", data=body,
            headers={"Content-Type": TENSOR_CONTENT_TYPE},
        )
        assert resp.status == 200
        raw = await resp.read()
        want = await _stored_way(client.app, target, body, endpoint)
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
    assert resp.content_type == TENSOR_CONTENT_TYPE
    assert resp.headers["Content-Length"] == str(len(raw))
    assert "Transfer-Encoding" not in resp.headers
    assert raw == want
    sent = stats["wire"]["response_bytes"]["tensor"]
    by_reference = stats["wire"]["response_bytes_by_reference"]["tensor"]
    assert sent == len(raw)
    large = sum(
        a.nbytes for a in unpack_frames(raw).values() if a.nbytes >= 64 * 1024
    )
    assert large >= 30_000 * 3 * 4
    assert by_reference == large


async def test_two_answers_in_flight_each_come_back_as_their_own(artifact_dir):
    """The first answer's reader stalls after the headers with megabytes
    still on the server's side of the socket, held by reference; a second
    request is scored and answered meanwhile (the bank's staging buffers
    are used again). Each body must be its own request's: a segment that
    referenced anything reused would show the other's rows."""
    import aiohttp

    url = "/gordo/v0/proj/machine-a/anomaly/prediction"
    headers = {"Content-Type": TENSOR_CONTENT_TYPE}
    (X1, body1), (X2, body2) = _tensor_request(150_000, 11), _tensor_request(150_000, 12)
    async with make_client(artifact_dir) as client:
        base = str(client.make_url(""))
        async with aiohttp.ClientSession() as one, aiohttp.ClientSession() as two:
            first = await one.post(base + url, data=body1, headers=headers)
            assert first.status == 200  # headers are in; the body is not read
            # the writer is stalled mid-segment (the rest of that array
            # sits on the transport's queue by reference, the segments
            # after it wait in the payload)
            held = max(
                conn.transport.get_write_buffer_size()
                for conn in client.server.runner.server.connections
            )
            assert held > 2**16
            second = await two.post(base + url, data=body2, headers=headers)
            raw2 = await second.read()
            raw1 = await first.read()
        want1 = await _stored_way(client.app, "machine-a", body1, "anomaly")
        want2 = await _stored_way(client.app, "machine-a", body2, "anomaly")
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
    assert len(raw1) > 4 * X1.nbytes  # more than a socket buffer holds
    np.testing.assert_array_equal(unpack_frames(raw1)["model-input"], X1)
    np.testing.assert_array_equal(unpack_frames(raw2)["model-input"], X2)
    assert raw1 == want1
    assert raw2 == want2
    assert raw1 != raw2
    assert stats["wire"]["response_bytes"]["tensor"] == len(raw1) + len(raw2)


async def test_response_counters_add_up_to_the_bytes_sent(artifact_dir):
    """``/stats`` ``wire.response_bytes`` and ``.response_bytes_by_reference``
    (and their Prometheus series) by the REQUEST's encoding: a JSON answer
    counts whole and nothing by reference; a small tensor answer is all
    header bytes and copied payloads; a large one leaves by reference but
    for its headers, totals and ``__meta__``."""
    url = "/gordo/v0/proj/machine-a/anomaly/prediction"
    headers = {"Content-Type": TENSOR_CONTENT_TYPE}
    async with make_client(artifact_dir) as client:
        resp = await client.post(url, json=_x_payload())
        json_bytes = len(await resp.read())
        small = await (
            await client.post(url, data=_tensor_request(20, 1)[1], headers=headers)
        ).read()
        large = await (
            await client.post(url, data=_tensor_request(30_000, 2)[1], headers=headers)
        ).read()
        refused = await client.post(url, data=b"NOPE", headers=headers)
        assert refused.status == 400
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
        text = await (await client.get("/gordo/v0/proj/metrics")).text()
    wire = stats["wire"]
    assert wire["response_bytes"] == {
        "json": json_bytes, "tensor": len(small) + len(large),
    }
    by_reference = 4 * 30_000 * 3 * 4 + 2 * 30_000 * 4  # four arrays, two totals
    assert wire["response_bytes_by_reference"] == {"json": 0, "tensor": by_reference}
    assert by_reference / len(large) > 0.99
    assert (
        f'gordo_server_response_bytes_total{{encoding="tensor"}} '
        f"{len(small) + len(large)}" in text
    )
    assert (
        f'gordo_server_response_bytes_by_reference_total{{encoding="tensor"}} '
        f"{by_reference}" in text
    )
    assert f'gordo_server_response_bytes_total{{encoding="json"}} {json_bytes}' in text


# --------------------------------------------------------------------- #
# a tensor answer's bytes on the socket, in its trace: `receive` under
# `parse`, `send` after the root, the trace published once the body left
# --------------------------------------------------------------------- #

_ANOMALY = "/gordo/v0/proj/machine-a/anomaly/prediction"


def _traced(tid, **headers):
    """Headers of a request whose trace the server keeps (sampled flag)."""
    return {"traceparent": format_traceparent(tid, "cd" * 8), **headers}


async def _published(tracer, tid, timeout=10.0):
    """``tid``'s retained traces once no trace is in flight: a tensor
    answer's trace is published when the task that wrote it has ended."""
    deadline = time.monotonic() + timeout
    while tracer.inflight and time.monotonic() < deadline:
        await asyncio.sleep(0.01)
    assert tracer.inflight == 0
    return tracer.find(tid)


def _span(trace, name):
    (span,) = [s for s in trace.spans if s.name == name]
    return span


class _Writer:
    """A connection's writer that takes segments, and fails at the
    ``fail_at``-th as a connection whose peer has gone does."""

    def __init__(self, fail_at=None):
        self.got, self.fail_at = [], fail_at

    async def write(self, chunk):
        if len(self.got) == self.fail_at:
            raise ConnectionResetError("Cannot write to closing transport")
        self.got.append(bytes(chunk))


def _answer_frames(rows=30_000):
    rng = np.random.RandomState(3)
    arrays = {
        name: rng.rand(rows, 3).astype("float32")
        for name in ("model-input", "model-output", "tag-anomaly-scaled",
                     "tag-anomaly-unscaled")
    }
    arrays["total-anomaly-scaled"] = rng.rand(rows).astype("float32")
    arrays["total-anomaly-unscaled"] = rng.rand(rows).astype("float32")
    return anomaly_frames(["a", "b", "c"], arrays, 0)


async def test_tensor_body_write_records_send_until_published():
    """``TensorBody.write`` records ``send`` on the trace it was given:
    every segment's bytes when the write completes; those handed before
    the failure, ``error`` set, when the connection fails mid-write (the
    error still propagates); nothing once the trace is published."""
    tracer = Tracer(sample=1.0)
    trace = tracer.start_trace("anomaly")
    trace.finish(publish=False)
    body = TensorBody(_answer_frames(), trace)
    segments = list(body._value)
    assert len(segments) > 2
    writer = _Writer()
    await body.write(writer)
    send = _span(trace, "send")
    assert writer.got == [bytes(seg) for seg in segments]
    assert send.attributes == {"bytes": body.size, "segments": len(segments)}
    assert not send.error and trace.root.end <= send.start <= send.end
    assert tracer.recent() == [] and tracer.inflight == 1
    trace.publish()
    trace.publish()  # once
    assert tracer.recent() == [trace] and tracer.inflight == 0
    await body.write(_Writer())
    assert [s.name for s in trace.spans].count("send") == 1

    failed = tracer.start_trace("anomaly")
    failed.finish(publish=False)
    with pytest.raises(ConnectionResetError):
        await TensorBody(_answer_frames(), failed).write(_Writer(fail_at=1))
    send = _span(failed, "send")
    assert send.error
    assert send.attributes == {"bytes": len(segments[0]), "segments": 1}


async def test_tensor_answer_trace_holds_receive_and_send(artifact_dir, monkeypatch):
    """A banked tensor request's trace: ``receive`` (the body's bytes)
    under ``parse`` and inside it, ``send`` under the root from at or after
    the root's end, carrying the response's ``Content-Length`` and its
    segments; the exemplar, published with the trace, holds the root's
    duration."""
    monkeypatch.setenv("GORDO_TRACE_SAMPLE", "0.1")
    tid = "5e" * 16
    X, body = _tensor_request(30_000, seed=7)
    async with make_client(artifact_dir) as client:
        resp = await client.post(
            _ANOMALY, data=body,
            headers=_traced(tid, **{"Content-Type": TENSOR_CONTENT_TYPE}),
        )
        assert resp.status == 200
        raw = await resp.read()
        (trace,) = await _published(client.app["tracer"], tid)
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
    parse, receive, send = (_span(trace, n) for n in ("parse", "receive", "send"))
    assert receive.parent is parse and send.parent is None
    assert parse.start == receive.start <= receive.end <= parse.end
    assert receive.attributes == {"bytes": len(body)}
    assert trace.root.end <= send.start <= send.end
    assert send.attributes["bytes"] == int(resp.headers["Content-Length"]) == len(raw)
    assert send.attributes["segments"] > 2 and not send.error
    assert not trace.error
    (exemplar,) = [
        e for e in stats["exemplars"]["anomaly"].values() if e["trace_id"] == tid
    ]
    assert exemplar["value_ms"] <= trace.root.duration_s * 1e3 + 1.0


async def test_tensor_answer_trace_is_published_after_its_body(artifact_dir, monkeypatch):
    """While a stalled reader holds the answer's write open (megabytes on
    the server's side of the socket), the request's trace is in flight
    and absent from every read; once the client has read the body it is
    published, ``send`` and all. A JSON answer's trace is published at
    the handler's return, before aiohttp prepares its response."""
    import aiohttp

    monkeypatch.setenv("GORDO_TRACE_SAMPLE", "1")
    tid = "a1" * 16
    _, body = _tensor_request(150_000, 21)
    published_at_prepare = {}

    async def on_prepare(request, response):
        trace = request.get("trace")
        if trace is not None and trace.name == "anomaly":
            published_at_prepare[trace.trace_id] = trace.published

    async with make_client(artifact_dir, on_prepare=[on_prepare]) as client:
        tracer = client.app["tracer"]
        base = str(client.make_url(""))
        async with aiohttp.ClientSession() as http:
            held = await http.post(
                base + _ANOMALY, data=body,
                headers=_traced(tid, **{"Content-Type": TENSOR_CONTENT_TYPE}),
            )
            assert held.status == 200  # headers in, the body not read
            assert max(
                conn.transport.get_write_buffer_size()
                for conn in client.server.runner.server.connections
            ) > 2**16
            assert tracer.find(tid) == [] and tracer.inflight == 1
            assert all(t.trace_id != tid for t in tracer.recent() + tracer.slow())
            raw = await held.read()
        (trace,) = await _published(tracer, tid)
        json_tid = "b2" * 16
        resp = await client.post(_ANOMALY, json=_x_payload(), headers=_traced(json_tid))
        assert resp.status == 200
        assert tracer.find(json_tid)  # before the body is read
        (json_trace,) = await _published(tracer, json_tid)
    assert _span(trace, "send").attributes["bytes"] == len(raw)
    assert published_at_prepare == {tid: False, json_tid: True}
    names = {s.name for s in json_trace.spans}
    assert "parse" in names and not names & {"receive", "send"}


async def test_client_gone_mid_write_leaves_one_trace_with_failed_send(
    artifact_dir, monkeypatch
):
    """The client reads the headers and closes its connection while the
    answer is being written: one trace is published, its ``send`` flagged
    ``error`` and closed at the failure with fewer bytes than the body,
    and nothing is left in flight."""
    import aiohttp

    monkeypatch.setenv("GORDO_TRACE_SAMPLE", "1")
    tid = "c3" * 16
    _, body = _tensor_request(150_000, 22)
    async with make_client(artifact_dir) as client:
        base = str(client.make_url(""))
        async with aiohttp.ClientSession() as http:
            held = await http.post(
                base + _ANOMALY, data=body,
                headers=_traced(tid, **{"Content-Type": TENSOR_CONTENT_TYPE}),
            )
            assert held.status == 200
            length = int(held.headers["Content-Length"])
            held.close()  # the connection goes with it
            (trace,) = await _published(client.app["tracer"], tid)
    send = _span(trace, "send")
    assert send.error and trace.error
    assert send.attributes["bytes"] < length
    assert trace.root.end <= send.start <= send.end
    assert [s.name for s in trace.spans].count("send") == 1


async def test_prepare_failing_leaves_one_trace_without_send(artifact_dir, monkeypatch):
    """The connection is gone when aiohttp prepares the tensor answer
    (the failure surfaces in ``prepare``, before any byte is handed to
    the writer): one trace, published, with no ``send``."""
    monkeypatch.setenv("GORDO_TRACE_SAMPLE", "1")
    tid = "d4" * 16

    async def gone(request, response):
        if request.get("trace") is not None:
            request.transport.abort()
            raise ConnectionResetError("Connection lost")

    async with make_client(artifact_dir, on_prepare=[gone]) as client:
        with pytest.raises(Exception):
            resp = await client.post(
                _ANOMALY, data=_tensor_request(64, 23)[1],
                headers=_traced(tid, **{"Content-Type": TENSOR_CONTENT_TYPE}),
            )
            await resp.read()
        (trace,) = await _published(client.app["tracer"], tid)
    names = [s.name for s in trace.spans]
    assert "receive" in names and "send" not in names
    assert trace.root.attributes["status"] == 200


async def test_tensor_request_the_handler_refuses_publishes_at_return(
    artifact_dir, monkeypatch
):
    """A tensor request the handler raises on (a body that is no tensor
    body: 400) has no answer to write: its trace is published at the
    handler's return, once, with neither new span."""
    monkeypatch.setenv("GORDO_TRACE_SAMPLE", "1")
    tid = "e5" * 16
    async with make_client(artifact_dir) as client:
        resp = await client.post(
            _ANOMALY, data=b"NOPE",
            headers=_traced(tid, **{"Content-Type": TENSOR_CONTENT_TYPE}),
        )
        assert resp.status == 400
        (trace,) = client.app["tracer"].find(tid)
        assert client.app["tracer"].inflight == 0
    assert trace.error
    assert not {s.name for s in trace.spans} & {"receive", "send"}


async def test_trace_is_published_by_the_task_that_writes_the_answer(
    artifact_dir, monkeypatch
):
    """aiohttp runs the middleware and ``finish_response`` (which writes
    the body) in one task, and the trace is published when that task
    ends. An aiohttp that moved the write elsewhere fails here instead of
    dropping ``send``."""
    from aiohttp import web, web_protocol

    monkeypatch.setenv("GORDO_TRACE_SAMPLE", "1")
    tid = "f6" * 16
    seen = {}
    finish_response = web_protocol.RequestHandler.finish_response

    async def watched(self, request, resp, start_time):
        trace = request.get("trace")
        seen["finish"] = asyncio.current_task()
        seen["published_before_write"] = trace.published
        out = await finish_response(self, request, resp, start_time)
        seen["send_at_write_end"] = any(s.name == "send" for s in trace.spans)
        seen["published_at_write_end"] = trace.published
        return out

    @web.middleware
    async def handler_task(request, handler):
        seen["handler"] = asyncio.current_task()
        return await handler(request)

    monkeypatch.setattr(web_protocol.RequestHandler, "finish_response", watched)
    app = build_app(artifact_dir)
    app.middlewares.append(handler_task)  # innermost: the handler's own task
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        resp = await client.post(
            _ANOMALY, data=_tensor_request(64, 24)[1],
            headers=_traced(tid, **{"Content-Type": TENSOR_CONTENT_TYPE}),
        )
        await resp.read()
        (trace,) = await _published(client.app["tracer"], tid)
    finally:
        await client.close()
    assert seen["finish"] is seen["handler"]
    assert seen["published_before_write"] is False
    assert seen["send_at_write_end"] is True
    assert seen["published_at_write_end"] is False  # the task's end does it
    assert trace.published
