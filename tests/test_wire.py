"""Binary tensor wire-format suite (``make wire``; ISSUE 10).

Three layers, matching the data plane's structure:

1. the frame codec itself (utils/wire.py): byte-level round-trips across
   dtypes/shapes/endianness, and every malformed-body class (bad magic,
   unknown version, truncation, trailing bytes, payload-size lies,
   disallowed dtypes) raising :class:`WireFormatError` with a reason;
2. the live HTTP surface: JSON-vs-tensor BITWISE score parity through
   the real app on the banked and per-model paths, malformed bodies as
   400s carrying the reason, and the binary path behaving identically to
   JSON under 410 quarantine, 504 deadline expiry, and chaos
   ``bank.score`` faults;
3. the bulk client: tensor-first auto-negotiation, the foreign-server
   downgrade (JSON-only stub), tensor ingest, and the per-encoding
   metric rows of the stability contract.

The ``perfguard``+``slow`` leg asserts the tensor path never regresses
below the JSON path it bypasses (``make perf-guard``).
"""

import contextlib
import json
import os

import numpy as np
import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from gordo_components_tpu import resilience, serializer
from gordo_components_tpu.models import AutoEncoder, DiffBasedAnomalyDetector
from gordo_components_tpu.resilience import FaultInjected
from gordo_components_tpu.server import build_app
from gordo_components_tpu.utils import wire
from gordo_components_tpu.utils.wire import (
    TENSOR_CONTENT_TYPE,
    WIRE_MAGIC,
    WireFormatError,
    frame_segments,
    pack_frames,
    rows_as_f32,
    unpack_frames,
)

pytestmark = pytest.mark.wire


@pytest.fixture(autouse=True)
def _clean_faults():
    resilience.reset()
    yield
    resilience.reset()


@pytest.fixture(scope="module")
def artifact_dir(tmp_path_factory):
    """An anomaly detector (banks) and a plain estimator (per-model)."""
    rng = np.random.RandomState(0)
    Xv = rng.rand(200, 3).astype("float32")
    root = tmp_path_factory.mktemp("wire-collection")
    det = DiffBasedAnomalyDetector(
        base_estimator=AutoEncoder(epochs=2, batch_size=64)
    )
    det.fit(Xv)
    serializer.dump(det, str(root / "wire-a"), metadata={"name": "wire-a"})
    ae = AutoEncoder(epochs=2, batch_size=64)
    ae.fit(Xv)
    serializer.dump(ae, str(root / "wire-b"), metadata={"name": "wire-b"})
    return str(root)


@contextlib.asynccontextmanager
async def make_client(artifact_dir, **kwargs):
    client = TestClient(TestServer(build_app(artifact_dir, **kwargs)))
    await client.start_server()
    try:
        yield client
    finally:
        await client.close()


def _x(n=20, f=3, seed=1):
    return np.random.RandomState(seed).rand(n, f).astype("float32")


async def _post_tensor(client, url, body):
    return await client.post(
        url, data=body, headers={"Content-Type": TENSOR_CONTENT_TYPE}
    )


# --------------------------------------------------------------------- #
# 1. the frame codec
# --------------------------------------------------------------------- #


class TestFrameCodec:
    @pytest.mark.parametrize(
        "dtype", ["<f4", "<f8", "<i4", "<i8", "|u1", "|b1"]
    )
    @pytest.mark.parametrize("shape", [(3, 4), (0, 5), (7,), (2, 3, 2)])
    def test_roundtrip_dtype_shape(self, dtype, shape):
        rng = np.random.RandomState(0)
        arr = (rng.rand(*shape) * 100).astype(np.dtype(dtype))
        out = unpack_frames(pack_frames([("a", arr)]))["a"]
        assert out.dtype == arr.dtype
        assert out.shape == arr.shape
        np.testing.assert_array_equal(out, arr)
        # zero-copy contract: parsed arrays are read-only views
        assert not out.flags.writeable

    def test_multi_frame_order_and_payloads(self):
        X = _x(5, 3)
        y = _x(5, 2, seed=2)
        meta = np.frombuffer(b'{"k": 1}', np.uint8)
        frames = unpack_frames(
            pack_frames([("__meta__", meta), ("X", X), ("y", y)])
        )
        assert list(frames) == ["__meta__", "X", "y"]
        np.testing.assert_array_equal(frames["X"], X)
        np.testing.assert_array_equal(frames["y"], y)
        assert json.loads(bytes(frames["__meta__"])) == {"k": 1}

    def test_big_endian_roundtrip_and_f32_conversion(self):
        arr = _x(4, 2).astype(">f4")
        out = unpack_frames(pack_frames([("X", arr)]))["X"]
        assert out.dtype == np.dtype(">f4")
        np.testing.assert_array_equal(out.astype("<f4"), arr.astype("<f4"))
        conv = rows_as_f32(out)
        assert conv.dtype == np.dtype("<f4") or conv.dtype.isnative
        np.testing.assert_array_equal(conv, arr.astype("<f4"))

    def test_rows_as_f32_is_zero_copy_for_native_f4(self):
        arr = unpack_frames(pack_frames([("X", _x(6, 2))]))["X"]
        assert rows_as_f32(arr) is arr  # no shadow copy on the fast path

    def test_rows_as_f32_promotes_1d_and_rejects_3d(self):
        assert rows_as_f32(np.ones(4, np.float32)).shape == (4, 1)
        with pytest.raises(WireFormatError, match="rows x features"):
            rows_as_f32(np.ones((2, 2, 2), np.float32))

    def test_bad_magic(self):
        body = pack_frames([("X", _x())])
        with pytest.raises(WireFormatError, match="magic"):
            unpack_frames(b"NOPE" + body[len(WIRE_MAGIC):])

    def test_unknown_version(self):
        body = bytearray(pack_frames([("X", _x())]))
        body[len(WIRE_MAGIC)] = 9
        with pytest.raises(WireFormatError, match="version 9"):
            unpack_frames(bytes(body))

    def test_truncated_payload(self):
        body = pack_frames([("X", _x())])
        with pytest.raises(WireFormatError, match="truncated"):
            unpack_frames(body[:-5])

    def test_truncated_header(self):
        with pytest.raises(WireFormatError, match="shorter than the header"):
            unpack_frames(WIRE_MAGIC + b"\x01")

    def test_oversized_trailing_bytes(self):
        body = pack_frames([("X", _x())])
        with pytest.raises(WireFormatError, match="trailing"):
            unpack_frames(body + b"\x00\x00")

    def test_payload_size_lie(self):
        # tamper the declared payload size of the (last) frame: the
        # redundant NBYTES field must be VERIFIED against shape x dtype
        X = _x(4, 2)
        body = bytearray(pack_frames([("X", X)]))
        size_off = len(body) - X.nbytes - 8
        body[size_off : size_off + 8] = (X.nbytes - 4).to_bytes(8, "little")
        with pytest.raises(WireFormatError, match="does not match"):
            unpack_frames(bytes(body))

    def test_disallowed_dtype(self):
        # hand-craft a frame declaring an object dtype: the whitelist
        # must reject it before any frombuffer attempt
        body = bytearray(pack_frames([("X", _x(2, 2))]))
        dtype_off = body.index(b"<f4")
        body[dtype_off : dtype_off + 3] = b"<m8"  # timedelta: kind "m"
        with pytest.raises(WireFormatError, match="not allowed"):
            unpack_frames(bytes(body))

    def test_empty_and_overlong(self):
        with pytest.raises(WireFormatError):
            pack_frames([])
        with pytest.raises(WireFormatError, match="1..255"):
            pack_frames([("", _x())])


# --------------------------------------------------------------------- #
# 1b. the body as segments: the same bytes, large payloads by reference
# --------------------------------------------------------------------- #

_DTYPES = ["<f4", "<f8", "<i4", "<i8", "|u1", "|b1"]
_SHAPES = [(3, 4), (0, 5), (7,), (2, 3, 2)]
_RULE_AS_SHIPPED = 64 * 1024  # the stored "answer" body stands on both sides of it


def _ramp(n, dtype="<f4"):
    return (np.arange(n) % 251).astype(dtype)


def _stored_cases():
    """Seeded frames whose bodies ``tests/data/wire_bodies.npz`` holds as
    ``pack_frames`` wrote them BEFORE it became a join of segments (one
    preallocated buffer, every array copied in): the codec's existing
    cases, and an answer-like body with payloads on both sides of
    ``INLINE_PAYLOAD_BYTES``."""
    rng = np.random.RandomState(0)
    cases = {}
    for dtype in _DTYPES:
        for shape in _SHAPES:
            arr = (rng.rand(*shape) * 100).astype(np.dtype(dtype))
            name = f"{np.dtype(dtype).name}-{'x'.join(map(str, shape))}"
            cases[name] = [("a", arr)]
    cases["zero-d"] = [("s", np.float32(1.5)), ("t", np.array(7, "<i8"))]
    cases["empty-beside-full"] = [("e", np.zeros((0, 5), "<f4")), ("x", _x(3, 2))]
    cases["non-contiguous"] = [("t", _x(6, 4).T), ("s", _x(8, 6)[::2, ::3])]
    cases["big-endian"] = [("X", _x(4, 2).astype(">f4"))]
    cases["64-frames"] = [(f"f{i:02d}", _ramp(i, "<i4")) for i in range(64)]
    at = _RULE_AS_SHIPPED // 4
    cases["answer"] = [
        ("__meta__", np.frombuffer(b'{"offset": 0, "tags": ["a", "b"]}', np.uint8)),
        ("model-input", _ramp(2 * at).reshape(-1, 2)),  # twice the rule
        ("model-output", _ramp(at)),  # at the rule: by reference
        ("total-anomaly-scaled", _ramp(at - 1)),  # one value under it: copied
        ("expert-selection", _ramp(8 * at, "|u1").reshape(4, -1, 8)),
    ]
    return cases


def _stored_body(case):
    path = os.path.join(os.path.dirname(__file__), "data", "wire_bodies.npz")
    with np.load(path) as stored:
        return stored[case].tobytes()


@pytest.fixture(params=["rule-as-shipped", "every-payload-by-reference"])
def inline_rule(request, monkeypatch):
    if request.param == "every-payload-by-reference":
        monkeypatch.setattr(wire, "INLINE_PAYLOAD_BYTES", 0)
    return request.param


class TestSegments:
    @pytest.mark.parametrize("case", list(_stored_cases()))
    def test_joined_segments_are_the_stored_body(self, case, inline_rule):
        frames = _stored_cases()[case]
        want = _stored_body(case)
        segments = frame_segments(frames)
        assert b"".join(segments) == want
        assert pack_frames(frames) == want
        assert sum(len(seg) for seg in segments) == len(want)
        # a transport takes each as it is: flat bytes
        assert all(
            isinstance(seg, bytes) or (seg.format == "B" and seg.ndim == 1)
            for seg in segments
        )
        got = unpack_frames(b"".join(segments))
        assert list(got) == [name for name, _ in frames]
        for name, arr in frames:
            np.testing.assert_array_equal(got[name], np.ascontiguousarray(arr))

    def test_a_large_payload_is_the_arrays_memory_a_small_one_is_not(self):
        assert wire.INLINE_PAYLOAD_BYTES == _RULE_AS_SHIPPED
        frames = dict(_stored_cases()["answer"])
        segments = frame_segments(list(frames.items()))
        views = [seg for seg in segments if isinstance(seg, memoryview)]
        # header runs and payloads alternate: head, input, head, output,
        # head (with the total copied in), selections
        assert [type(seg) for seg in segments] == [bytes, memoryview] * 3
        for seg, name in zip(views, ("model-input", "model-output", "expert-selection")):
            assert np.shares_memory(np.frombuffer(seg, np.uint8), frames[name])
            assert len(seg) == frames[name].nbytes
        small = frames["total-anomaly-scaled"]
        assert not any(
            np.shares_memory(np.frombuffer(seg, np.uint8), small) for seg in segments
        )
        assert small.tobytes() in segments[4]

    def test_a_segment_keeps_its_array_alive(self):
        arr = _ramp(wire.INLINE_PAYLOAD_BYTES)
        want = arr.tobytes()
        segments = frame_segments([("a", arr)])
        del arr
        assert bytes(segments[1]) == want

    def test_a_non_contiguous_payload_is_referenced_through_its_copy(self):
        arr = _ramp(wire.INLINE_PAYLOAD_BYTES).reshape(-1, 4).T
        segments = frame_segments([("a", arr)])
        assert not np.shares_memory(np.frombuffer(segments[1], np.uint8), arr)
        assert bytes(segments[1]) == np.ascontiguousarray(arr).tobytes()

    def test_the_checks_stand_before_any_segment(self):
        with pytest.raises(WireFormatError, match="at least one frame"):
            frame_segments([])
        with pytest.raises(WireFormatError, match="64-frame bound"):
            frame_segments([(f"f{i}", _x(1, 1)) for i in range(65)])
        with pytest.raises(WireFormatError, match="1..255"):
            frame_segments([("n" * 256, _x())])
        with pytest.raises(WireFormatError, match="not allowed"):
            frame_segments([("c", np.zeros(2, "<c8"))])
        with pytest.raises(WireFormatError, match="9 dims"):
            frame_segments([("d", np.zeros((1,) * 9, "<f4"))])


def test_the_encode_ladder_runs_at_a_tiny_size(monkeypatch, tmp_path, capsys):
    """``tools/encode_ladder.py`` (the chip-host run behind PERF.md's
    table for PR 34), here for its control flow: the three ways serve one
    body, which the tool itself compares byte for byte."""
    import importlib.util
    import pathlib

    monkeypatch.chdir(tmp_path)
    path = pathlib.Path(__file__).parents[1] / "tools" / "encode_ladder.py"
    spec = importlib.util.spec_from_file_location("encode_ladder", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    frames = tool.answer_frames("2048x8s", np.random.default_rng(0))
    assert tool.stored_pack(frames) == pack_frames(frames)
    tool.main(["--shapes", "16x4", "2048x8s", "--reps", "1"])
    out = json.loads((tmp_path / "chiprun_out" / "encode_ladder.json").read_text())
    for shape in ("16x4", "2048x8s"):
        assert set(out[shape]) == {"stored", "join", "segments"}
        assert len({way["body_bytes"] for way in out[shape].values()}) == 1
    assert "client_ms" in capsys.readouterr().out


# --------------------------------------------------------------------- #
# 2. the live HTTP surface
# --------------------------------------------------------------------- #


async def test_malformed_tensor_bodies_400_with_reason(artifact_dir):
    good = pack_frames([("X", _x())])
    cases = [
        (b"NOPE" + good[len(WIRE_MAGIC):], "magic"),
        (good[:-5], "truncated"),
        (good + b"\x00", "trailing"),
        (pack_frames([("Z", _x())]), "must carry an 'X' frame"),
    ]
    async with make_client(artifact_dir) as client:
        for body, needle in cases:
            resp = await _post_tensor(
                client, "/gordo/v0/proj/wire-a/prediction", body
            )
            assert resp.status == 400
            assert needle in (await resp.json())["error"]


async def test_anomaly_parity_banked_bitwise(artifact_dir):
    """The headline contract: the SAME scores from both encodings on the
    banked engine path, bitwise (f32 -> f64 widening is exact)."""
    X = _x(25, 3)
    async with make_client(artifact_dir) as client:
        url = "/gordo/v0/proj/wire-a/anomaly/prediction"
        jresp = await client.post(url, json={"X": X.tolist()})
        assert jresp.status == 200
        j = (await jresp.json())["data"]
        tresp = await _post_tensor(client, url, pack_frames([("X", X)]))
        assert tresp.status == 200
        assert tresp.content_type == TENSOR_CONTENT_TYPE
        frames = unpack_frames(await tresp.read())
        meta = json.loads(bytes(frames.pop("__meta__")))
    assert meta["offset"] == 0
    tags = meta["tags"]
    for top in (
        "model-input", "model-output",
        "tag-anomaly-unscaled", "tag-anomaly-scaled",
    ):
        for i, tag in enumerate(tags):
            json_col = np.asarray(j[top][tag])
            np.testing.assert_array_equal(
                json_col, frames[top][:, i].astype(np.float64), err_msg=top
            )
    for top in ("total-anomaly-unscaled", "total-anomaly-scaled"):
        np.testing.assert_array_equal(
            np.asarray(j[top]), frames[top].astype(np.float64), err_msg=top
        )


async def test_anomaly_parity_with_y(artifact_dir):
    X, y = _x(10, 3), _x(10, 3, seed=7)
    async with make_client(artifact_dir) as client:
        url = "/gordo/v0/proj/wire-a/anomaly/prediction"
        j = await (
            await client.post(url, json={"X": X.tolist(), "y": y.tolist()})
        ).json()
        tresp = await _post_tensor(
            client, url, pack_frames([("X", X), ("y", y)])
        )
        assert tresp.status == 200
        frames = unpack_frames(await tresp.read())
    np.testing.assert_array_equal(
        np.asarray(j["data"]["total-anomaly-scaled"]),
        frames["total-anomaly-scaled"].astype(np.float64),
    )


async def test_prediction_parity_per_model_path(artifact_dir):
    """wire-b is a bare estimator: the tensor fast path through the
    per-model executor route, no engine involved."""
    X = _x(15, 3)
    async with make_client(artifact_dir) as client:
        url = "/gordo/v0/proj/wire-b/prediction"
        j = await (await client.post(url, json={"X": X.tolist()})).json()
        tresp = await _post_tensor(client, url, pack_frames([("X", X)]))
        assert tresp.status == 200
        frames = unpack_frames(await tresp.read())
        meta = json.loads(bytes(frames.pop("__meta__")))
    assert meta["offset"] == len(X) - len(frames["data"])
    np.testing.assert_array_equal(
        np.asarray(j["data"]), frames["data"].astype(np.float64)
    )


async def test_anomaly_parity_bank_disabled(artifact_dir):
    """use_bank=False forces the per-model anomaly route: tensor bodies
    still score, via the one cheap DataFrame wrap that path owns."""
    X = _x(12, 3)
    async with make_client(artifact_dir, use_bank=False) as client:
        url = "/gordo/v0/proj/wire-a/anomaly/prediction"
        j = await (await client.post(url, json={"X": X.tolist()})).json()
        tresp = await _post_tensor(client, url, pack_frames([("X", X)]))
        assert tresp.status == 200
        frames = unpack_frames(await tresp.read())
    np.testing.assert_array_equal(
        np.asarray(j["data"]["total-anomaly-scaled"]),
        frames["total-anomaly-scaled"].astype(np.float64),
    )


@pytest.mark.chaos
async def test_tensor_path_chaos_bank_score_fault_400s(artifact_dir):
    """A bank.score fault on the binary path surfaces exactly like on
    the JSON path (400 with detail), and recovery is immediate."""
    body = pack_frames([("X", _x())])
    async with make_client(artifact_dir, quarantine_threshold=0) as client:
        resilience.arm("bank.score", exc=FaultInjected)
        resp = await _post_tensor(
            client, "/gordo/v0/proj/wire-a/prediction", body
        )
        assert resp.status == 400
        assert "FaultInjected" in (await resp.json())["error"]
        resilience.reset()
        resp = await _post_tensor(
            client, "/gordo/v0/proj/wire-a/prediction", body
        )
        assert resp.status == 200


@pytest.mark.chaos
async def test_tensor_path_quarantine_410(artifact_dir):
    """The failure breaker fires identically for tensor requests: after
    the threshold, the binary path gets the same 410 + reason."""
    body = pack_frames([("X", _x())])
    async with make_client(artifact_dir, quarantine_threshold=2) as client:
        resilience.arm("bank.score", exc=FaultInjected)
        for _ in range(2):
            resp = await _post_tensor(
                client, "/gordo/v0/proj/wire-a/prediction", body
            )
            assert resp.status == 400
        resp = await _post_tensor(
            client, "/gordo/v0/proj/wire-a/prediction", body
        )
        assert resp.status == 410
        assert "quarantined" in (await resp.json())["error"]


@pytest.mark.chaos
async def test_tensor_path_deadline_504(artifact_dir):
    """An expired budget 504s the binary path exactly like JSON — with
    the request id in the body and no scoring attempted."""
    body = pack_frames([("X", _x())])
    async with make_client(artifact_dir) as client:
        resilience.arm("engine.queue", delay_s=0.08, exc=None)
        resp = await _post_tensor(
            client, "/gordo/v0/proj/wire-a/prediction", body
        )
        # arm AFTER warm? engine.queue latency delays admission; budget
        # below expires during it
        assert resp.status == 200  # no deadline -> still served
        resp = await client.post(
            "/gordo/v0/proj/wire-a/prediction",
            data=body,
            headers={
                "Content-Type": TENSOR_CONTENT_TYPE,
                "X-Gordo-Deadline-Ms": "30",
            },
        )
        assert resp.status == 504
        assert (await resp.json())["request_id"]


async def test_accepts_advertises_tensor_before_parquet(artifact_dir):
    async with make_client(artifact_dir) as client:
        body = await (await client.get("/gordo/v0/proj/models")).json()
    accepts = body["accepts"]
    assert TENSOR_CONTENT_TYPE in accepts
    for a in accepts:
        if "parquet" in a:
            # the demotion contract: tensor outranks parquet in the
            # advertised preference order
            assert accepts.index(TENSOR_CONTENT_TYPE) < accepts.index(a)


async def test_per_encoding_metrics_and_stats(artifact_dir):
    """Stability contract: gordo_server_requests_total{encoding} and
    gordo_server_request_bytes_total{encoding} render, and /stats' wire
    block reports the same cells."""
    X = _x()
    body = pack_frames([("X", X)])
    async with make_client(artifact_dir) as client:
        await client.post(
            "/gordo/v0/proj/wire-a/prediction", json={"X": X.tolist()}
        )
        await _post_tensor(client, "/gordo/v0/proj/wire-a/prediction", body)
        await _post_tensor(client, "/gordo/v0/proj/wire-a/prediction", body)
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
        text = await (await client.get("/gordo/v0/proj/metrics")).text()
    wire = stats["wire"]
    assert wire["requests"]["json"] == 1
    assert wire["requests"]["tensor"] == 2
    assert wire["bytes"]["tensor"] == 2 * len(body)
    assert 'gordo_server_requests_total{encoding="tensor"} 2' in text
    assert (
        f'gordo_server_request_bytes_total{{encoding="tensor"}} '
        f"{2 * len(body)}" in text
    )
    assert 'gordo_server_requests_total{encoding="json"} 1' in text


async def test_parse_span_carries_encoding(artifact_dir, monkeypatch):
    monkeypatch.setenv("GORDO_TRACE_SAMPLE", "1")
    body = pack_frames([("X", _x())])
    tid = "cd" * 16
    async with make_client(artifact_dir) as client:
        resp = await client.post(
            "/gordo/v0/proj/wire-a/prediction",
            data=body,
            headers={
                "Content-Type": TENSOR_CONTENT_TYPE,
                "traceparent": f"00-{tid}-{'ab' * 8}-01",
            },
        )
        assert resp.status == 200
        (trace,) = client.app["tracer"].find(tid)
    spans = {s.name: s for s in trace.spans}
    assert spans["parse"].attributes["encoding"] == "tensor"


# --------------------------------------------------------------------- #
# 3. the bulk client
# --------------------------------------------------------------------- #

_FALLBACK = {
    "type": "RandomDataset",
    "tag_list": ["a", "b", "c"],
    "resolution": "10min",
}


async def test_client_tensor_auto_equals_json(artifact_dir, live_server):
    """Auto mode negotiates tensor against our server; scored frames are
    identical (bitwise) to a forced-JSON run. ``parallelism=1`` pins the
    engine's batch composition equal across the two runs — concurrent
    chunks coalesce timing-dependently and XLA programs at different
    batch sizes differ by ~1 ULP (the PR-1 finding), which would mask
    what this test is about: the ENCODING changing nothing."""
    import pandas as pd

    from gordo_components_tpu.client import Client

    start = pd.Timestamp("2020-01-01 00:00:00Z")
    end = pd.Timestamp("2020-01-01 06:00:00Z")
    async with live_server(artifact_dir) as base_url:
        auto = Client(
            "proj", base_url=base_url, batch_size=10, parallelism=1,
            metadata_fallback_dataset=_FALLBACK,
        )
        res_t = await auto.predict_async(start, end, targets=["wire-a"])
        assert auto._tensor_active is True
        assert auto.wire_stats["tensor"]["posts"] > 0
        assert "json" not in auto.wire_stats
        plain = Client(
            "proj", base_url=base_url, batch_size=10, parallelism=1,
            use_tensor=False, use_parquet=False,
            metadata_fallback_dataset=_FALLBACK,
        )
        res_j = await plain.predict_async(start, end, targets=["wire-a"])
    assert res_t[0].ok and res_j[0].ok
    pd.testing.assert_frame_equal(res_t[0].predictions, res_j[0].predictions)
    assert (
        res_t[0].predictions.values == res_j[0].predictions.values
    ).all()  # bitwise, not just allclose


@contextlib.asynccontextmanager
async def _stub_server(accepts, reject_tensor=False):
    """Foreign-server stand-in: advertises ``accepts``; JSON predictions
    echo zeros; tensor bodies 400 when ``reject_tensor``."""
    counts = {"tensor": 0, "json": 0}

    async def models(request):
        return web.json_response({"models": ["m-1"], "accepts": list(accepts)})

    async def metadata(request):
        return web.json_response({"endpoint-metadata": {}})

    async def predict(request):
        if TENSOR_CONTENT_TYPE in (request.content_type or ""):
            counts["tensor"] += 1
            return web.json_response({"error": "no tensors here"}, status=400)
        counts["json"] += 1
        body = await request.json()
        return web.json_response(
            {"data": [[0.0] * 3] * len(body["X"]), "index": body["index"]}
        )

    app = web.Application()
    app.router.add_get("/gordo/v0/proj/models", models)
    app.router.add_get("/gordo/v0/proj/{target}/metadata", metadata)
    app.router.add_post("/gordo/v0/proj/{target}/anomaly/prediction", predict)
    server = TestServer(app)
    await server.start_server()
    try:
        yield f"http://{server.host}:{server.port}", counts
    finally:
        await server.close()


async def test_client_stays_json_against_json_only_server():
    """A server that never advertises tensor keeps auto mode on JSON —
    no tensor body is ever posted at a foreign fleet."""
    import pandas as pd

    from gordo_components_tpu.client import Client

    async with _stub_server(["application/json"]) as (base_url, counts):
        client = Client(
            "proj", base_url=base_url, batch_size=10,
            metadata_fallback_dataset=_FALLBACK,
        )
        results = await client.predict_async(
            pd.Timestamp("2020-01-01 00:00:00Z"),
            pd.Timestamp("2020-01-01 03:00:00Z"),
        )
    assert results[0].ok, results[0].error_messages
    assert client._tensor_active is False
    assert counts["tensor"] == 0 and counts["json"] > 0


async def test_client_downgrades_when_tensor_rejected():
    """A server advertising tensor but rejecting the bodies (foreign
    implementation) must not fail the run: the client re-posts as JSON
    and downgrades the rest of the run."""
    import pandas as pd

    from gordo_components_tpu.client import Client

    async with _stub_server(
        ["application/json", TENSOR_CONTENT_TYPE], reject_tensor=True
    ) as (base_url, counts):
        client = Client(
            "proj", base_url=base_url, batch_size=10,
            metadata_fallback_dataset=_FALLBACK,
        )
        results = await client.predict_async(
            pd.Timestamp("2020-01-01 00:00:00Z"),
            pd.Timestamp("2020-01-01 03:00:00Z"),
        )
    assert results[0].ok, results[0].error_messages
    # in-flight chunks may each probe tensor before the first rejection
    # lands, but every one must re-post as JSON in the same call
    assert 1 <= counts["tensor"] <= counts["json"]
    assert counts["json"] >= 2
    assert client._tensor_active is False


async def test_tensor_ingest_end_to_end(artifact_dir, monkeypatch):
    """The streaming plane accepts the same frame format: float32 rows
    (NaN = dropout) + epoch-seconds timestamps, via the raw endpoint AND
    the client's ``ingest_async(tensor=True)`` forwarder."""
    import time as _time

    monkeypatch.setenv("GORDO_STREAM", "1")
    async with make_client(artifact_dir) as client:
        rows = _x(8, 3).copy()
        rows[2, 1] = np.nan  # sensor dropout rides as a NaN cell
        now = _time.time()
        ts = np.arange(8, dtype=np.float64) + now
        body = pack_frames([("rows", rows), ("timestamps", ts)])
        resp = await client.post(
            "/gordo/v0/proj/wire-a/ingest",
            data=body,
            headers={"Content-Type": TENSOR_CONTENT_TYPE},
        )
        assert resp.status == 200, await resp.text()
        counts = await resp.json()
        assert counts["accepted"] == 8
        # malformed: no rows frame
        resp = await client.post(
            "/gordo/v0/proj/wire-a/ingest",
            data=pack_frames([("X", rows)]),
            headers={"Content-Type": TENSOR_CONTENT_TYPE},
        )
        assert resp.status == 400
        assert "rows" in (await resp.json())["error"]
        # mismatched timestamp count
        resp = await client.post(
            "/gordo/v0/proj/wire-a/ingest",
            data=pack_frames([("rows", rows), ("timestamps", ts[:3])]),
            headers={"Content-Type": TENSOR_CONTENT_TYPE},
        )
        assert resp.status == 400


async def test_client_ingest_tensor_forwarder(artifact_dir, monkeypatch):
    import time as _time

    import pandas as pd

    from gordo_components_tpu.client import Client

    monkeypatch.setenv("GORDO_STREAM", "1")
    server = TestServer(build_app(artifact_dir))
    await server.start_server()
    try:
        base_url = f"http://{server.host}:{server.port}"
        client = Client("proj", base_url=base_url, batch_size=5)
        X = pd.DataFrame(_x(12, 3))
        now = _time.time()
        totals = await client.ingest_async(
            "wire-a", X,
            timestamps=list(np.arange(12, dtype=np.float64) + now),
            tensor=True,
        )
        assert totals["accepted"] == 12
        assert totals["chunks"] == 3
        # ingest traffic lands in its OWN bucket — the scoring cells
        # must never absorb it
        assert client.wire_stats["ingest-tensor"]["posts"] == 3
        assert "tensor" not in client.wire_stats
    finally:
        await server.close()


# --------------------------------------------------------------------- #
# perf guard: the binary path must never regress below the JSON path
# --------------------------------------------------------------------- #


@pytest.mark.perfguard
@pytest.mark.slow
async def test_tensor_path_no_slower_than_json(artifact_dir):
    """ISSUE 10 acceptance guard (``make perf-guard``): same batch, same
    server, N POSTs per encoding — the tensor path's wall time must not
    exceed the JSON path's. Measured at ~4-15x faster in practice, so a
    plain <= holds with huge margin; a failure here means the zero-copy
    path grew a copy."""
    import time as _time

    X = _x(400, 3)
    posts = 15
    body = pack_frames([("X", X)])
    payload = {"X": X.tolist()}
    url = "/gordo/v0/proj/wire-a/anomaly/prediction"
    async with make_client(artifact_dir) as client:
        for _ in range(3):  # warm both paths (compile + allocator)
            assert (await client.post(url, json=payload)).status == 200
            assert (await _post_tensor(client, url, body)).status == 200
        t0 = _time.perf_counter()
        for _ in range(posts):
            resp = await client.post(url, json=payload)
            assert resp.status == 200
            await resp.read()
        t_json = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        for _ in range(posts):
            resp = await _post_tensor(client, url, body)
            assert resp.status == 200
            await resp.read()
        t_tensor = _time.perf_counter() - t0
    assert t_tensor <= t_json, (
        f"tensor path regressed below JSON: {t_tensor:.3f}s vs {t_json:.3f}s "
        f"for {posts} x {len(X)}-row anomaly POSTs"
    )


# --------------------------------------------------------------------- #
# cross-transport parity (ISSUE 13): tcp / uds / shm, identical bytes
# --------------------------------------------------------------------- #


# The same ``GTNS`` body over TCP, UDS, and the shm ring must yield
# IDENTICAL bytes out. Posts are sequential (equal batch composition:
# the repo's bitwise contract is per-composition; concurrent coalescing
# may differ by ~1 ULP of XLA fusion drift), so this is the strict
# byte-for-byte form. The UDS path — the same app behind a
# ``web.UnixSite`` — must also keep the HTTP error surface: malformed
# frames 400 with the reason, quarantined targets 410.


@pytest.mark.saturate
async def test_same_body_same_bytes_all_transports(artifact_dir, tmp_path):
    import asyncio
    import os

    import aiohttp
    from aiohttp import web as aioweb
    from aiohttp.test_utils import TestServer

    from gordo_components_tpu.server.transport import ShmServer
    from gordo_components_tpu.utils.shm_ring import ShmRingClient

    app = build_app(artifact_dir)
    server = TestServer(app)
    await server.start_server()
    uds_path = str(tmp_path / "wire-parity.sock")
    uds_site = aioweb.UnixSite(server.runner, uds_path)
    await uds_site.start()
    shm_name = f"gordo-wire-parity-{os.getpid()}"
    shm_srv = ShmServer.create(app, shm_name, slots=2, slot_mb=1.0)
    ring = ShmRingClient(shm_name)
    loop = asyncio.get_running_loop()
    try:
        body = pack_frames([("X", _x(37, 3))])
        path = "/gordo/v0/proj/wire-a/anomaly/prediction"
        headers = {"Content-Type": TENSOR_CONTENT_TYPE}
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://{server.host}:{server.port}{path}",
                data=body, headers=headers,
            ) as r:
                assert r.status == 200, await r.text()
                tcp_bytes = await r.read()
        async with aiohttp.ClientSession(
            connector=aiohttp.UnixConnector(path=uds_path)
        ) as s:
            async with s.post(
                f"http://localhost{path}", data=body, headers=headers
            ) as r:
                assert r.status == 200, await r.text()
                uds_bytes = await r.read()
        status, shm_bytes = await loop.run_in_executor(
            None, ring.request, "wire-a", body
        )
        assert status == 200
        assert tcp_bytes == uds_bytes == shm_bytes
        # and the parsed scores round-trip identically
        frames = unpack_frames(shm_bytes)
        assert frames["total-anomaly-scaled"].shape == (37,)
    finally:
        ring.close()
        shm_srv.close()
        await server.close()


@pytest.mark.saturate
async def test_uds_malformed_400_and_quarantine_410(artifact_dir, tmp_path):
    import aiohttp
    from aiohttp import web as aioweb
    from aiohttp.test_utils import TestServer

    app = build_app(artifact_dir)
    server = TestServer(app)
    await server.start_server()
    uds_path = str(tmp_path / "wire-errors.sock")
    await aioweb.UnixSite(server.runner, uds_path).start()
    try:
        path = "/gordo/v0/proj/wire-a/anomaly/prediction"
        headers = {"Content-Type": TENSOR_CONTENT_TYPE}
        async with aiohttp.ClientSession(
            connector=aiohttp.UnixConnector(path=uds_path)
        ) as s:
            # truncated body -> 400 with the reason, over the socket
            bad = pack_frames([("X", _x(8, 3))])[:-5]
            async with s.post(
                f"http://localhost{path}", data=bad, headers=headers
            ) as r:
                assert r.status == 400
                assert "truncated" in await r.text()
            # quarantined target -> 410 with the recorded reason
            quarantine = app["quarantine"]
            for _ in range(quarantine.threshold):
                quarantine.record_failure("wire-a", "uds-test-poison")
            body = pack_frames([("X", _x(8, 3))])
            async with s.post(
                f"http://localhost{path}", data=body, headers=headers
            ) as r:
                assert r.status == 410
                assert "uds-test-poison" in await r.text()
            quarantine.clear(["wire-a"])
            async with s.post(
                f"http://localhost{path}", data=body, headers=headers
            ) as r:
                assert r.status == 200
    finally:
        await server.close()
