"""Mesh-sharded model bank: a bank built over the 8-virtual-device CPU
mesh must return results identical to the single-device bank (same math,
same programs, routed instead of gathered), so the generated manifests'
multi-chip server request (``workflow/generator.py`` ``server_devices``)
is backed by code.

The sharded bank places each bucket's stacked params under a
``NamedSharding`` on the model axis — the same layout ``FleetTrainer``
trains under — and routes each request chunk to the shard owning its
model (``server/bank.py`` ``_Bucket.score_batch_sharded``).
"""

import asyncio

import jax
import numpy as np
import pandas as pd
import pytest

from gordo_components_tpu.models import (
    AutoEncoder,
    DiffBasedAnomalyDetector,
    LSTMAutoEncoder,
)
from gordo_components_tpu.parallel.mesh import MODEL_AXIS, fleet_mesh
from gordo_components_tpu.server.bank import BatchingEngine, ModelBank

from bank_parity import assert_slots_equal_their_single_answers

pytestmark = pytest.mark.skipif(
    jax.device_count() < 2, reason="needs the virtual multi-device mesh"
)


def _fit_det(X, base=None, seed=0):
    det = DiffBasedAnomalyDetector(
        base_estimator=base or AutoEncoder(epochs=2, batch_size=64)
    )
    det.fit(X)
    return det


@pytest.fixture(scope="module")
def many_models():
    """12 ff models over one bucket (more models than devices: shard_size
    2 after padding 12 -> 16 over 8 devices) plus one LSTM bucket."""
    rng = np.random.RandomState(7)
    X = rng.rand(120, 3).astype("float32")
    models = {f"m-{i:02d}": _fit_det(X) for i in range(12)}
    lstm = DiffBasedAnomalyDetector(
        base_estimator=LSTMAutoEncoder(lookback_window=4, epochs=1, batch_size=32)
    )
    lstm.fit(X)
    models["lstm"] = lstm
    return models, X


def test_sharded_bank_matches_single_device(many_models):
    models, X = many_models
    single = ModelBank.from_models(models)
    mesh = fleet_mesh()
    sharded = ModelBank.from_models(models, mesh=mesh)
    assert len(sharded) == len(single) == 13
    # every bucket's stacked state actually lives under the mesh sharding
    for bucket in sharded._buckets.values():
        assert bucket.n_shards == mesh.shape[MODEL_AXIS]
        leaf = jax.tree.leaves(bucket.params)[0]
        assert leaf.sharding.mesh.shape[MODEL_AXIS] == mesh.shape[MODEL_AXIS]
    Xq = X[:37]  # odd length exercises row padding
    for name in models:
        a = single.score(name, Xq)
        b = sharded.score(name, Xq)
        np.testing.assert_array_equal(a.model_output, b.model_output)
        np.testing.assert_array_equal(a.total_scaled, b.total_scaled)
        assert a.offset == b.offset


def test_sharded_bank_matches_anomaly_frame(many_models):
    """End-to-end frame parity against the per-model scoring path."""
    models, X = many_models
    sharded = ModelBank.from_models(models, mesh=fleet_mesh())
    for name in ("m-00", "m-11", "lstm"):
        expected = models[name].anomaly(X[:50])
        got = sharded.score(name, X[:50]).to_frame()
        pd.testing.assert_frame_equal(got, expected, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize(
    "names,batch_size",
    [
        (["m-00"], 1), (["m-11"], 1),  # the stack's two ends, on two shards
        (["m-00", "m-01"], 2),  # one shard's two members
        (["m-01", "m-01", "m-00"], 4),  # three on one shard, padded to four
        # six on the first shard (padded to eight), repeats, other shards' too
        (["m-00", "m-01", "m-01", "m-00", "m-11", "m-10", "m-00", "m-01"], 8),
        (["lstm"], 1), (["lstm", "lstm", "lstm"], 4),
    ],
    ids=lambda v: "-".join(n[-2:] for n in v) if isinstance(v, list) else str(v),
)
def test_sharded_batch_slots_match_single_and_per_model_path(
    many_models, monkeypatch, names, batch_size
):
    """Each shard slices ITS slots' members out of its own block of the
    stack: every slot is bitwise the same request's B = 1 answer, and the
    per-model path's to the tolerance of the frame test above."""
    models, X = many_models
    bank = ModelBank.from_models(
        {n: m for n, m in models.items() if (n == "lstm") == (names[0] == "lstm")},
        mesh=fleet_mesh(), registry=False,
    )
    requests = [(name, X[k : k + 40 + k], None) for k, name in enumerate(names)]
    got = assert_slots_equal_their_single_answers(
        bank, requests, monkeypatch, batch_size
    )
    for (name, Xq, _), res in zip(requests, got):
        pd.testing.assert_frame_equal(
            res.to_frame(), models[name].anomaly(Xq), rtol=1e-4, atol=1e-5
        )


def test_sharded_heterogeneous_batch(many_models):
    """One score_many over models owned by different shards."""
    models, X = many_models
    single = ModelBank.from_models(models)
    sharded = ModelBank.from_models(models, mesh=fleet_mesh())
    reqs = [(f"m-{i:02d}", X[: 20 + i], None) for i in range(12)]
    got = sharded.score_many(reqs)
    want = single.score_many(reqs)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.model_output, w.model_output)
        np.testing.assert_array_equal(g.total_scaled, w.total_scaled)


def test_sharded_fewer_models_than_devices():
    """3 models over 8 devices: padding must not change results."""
    rng = np.random.RandomState(3)
    X = rng.rand(80, 2).astype("float32")
    models = {f"s-{i}": _fit_det(X) for i in range(3)}
    single = ModelBank.from_models(models)
    sharded = ModelBank.from_models(models, mesh=fleet_mesh())
    for name in models:
        np.testing.assert_array_equal(
            single.score(name, X[:25]).total_scaled,
            sharded.score(name, X[:25]).total_scaled,
        )


def test_sharded_long_request_chunking(many_models):
    """Requests longer than max_rows chunk identically on both paths."""
    models, X = many_models
    big = np.tile(X, (3, 1))  # 360 rows
    single = ModelBank.from_models(models, max_rows_per_call=128)
    sharded = ModelBank.from_models(models, max_rows_per_call=128, mesh=fleet_mesh())
    for name in ("m-05", "lstm"):
        a = single.score(name, big)
        b = sharded.score(name, big)
        assert len(b.model_output) == len(big) - b.offset
        np.testing.assert_array_equal(a.model_output, b.model_output)


def test_sharded_warmup(many_models):
    models, _ = many_models
    sharded = ModelBank.from_models(models, mesh=fleet_mesh())
    assert sharded.warmup(rows=64) == sharded.n_buckets


async def test_build_app_devices_serves_sharded(tmp_path, many_models):
    """build_app(devices=8): the served bank is mesh-sharded end-to-end —
    an HTTP anomaly request returns the same frame a single-device app
    produces, and /models reports full bank coverage."""
    from aiohttp.test_utils import TestClient, TestServer

    from gordo_components_tpu import serializer
    from gordo_components_tpu.server import build_app

    models, X = many_models
    for name in ("m-00", "m-07"):
        serializer.dump(models[name], str(tmp_path / name), metadata={"name": name})
    payload = {"X": X[:30].tolist()}
    frames = []
    for devices in (1, 8):
        client = TestClient(
            TestServer(build_app(str(tmp_path), devices=devices))
        )
        await client.start_server()
        try:
            app = client.app
            assert (app["bank"].mesh is not None) == (devices == 8)
            resp = await client.post(
                "/gordo/v0/proj/m-07/anomaly/prediction", json=payload
            )
            assert resp.status == 200
            frames.append(await resp.json())
            mresp = await client.get("/gordo/v0/proj/models")
            mbody = await mresp.json()
            assert set(mbody["bank"]["banked"]) == {"m-00", "m-07"}
            assert mbody["bank"]["devices"] == devices
        finally:
            await client.close()
    assert frames[0] == frames[1]


async def test_reload_rebuilds_under_same_mesh(tmp_path, many_models):
    """POST /reload must rebuild the bank under the app's original mesh —
    a reload on an 8-chip server that silently fell back to one device
    would strand 7 chips until the next restart."""
    from aiohttp.test_utils import TestClient, TestServer

    from gordo_components_tpu import serializer
    from gordo_components_tpu.server import build_app

    models, X = many_models
    serializer.dump(models["m-01"], str(tmp_path / "m-01"), metadata={"name": "m-01"})
    client = TestClient(TestServer(build_app(str(tmp_path), devices=8)))
    await client.start_server()
    try:
        app = client.app
        assert app["bank"].mesh is not None
        # a new artifact appears on disk; reload picks it up
        serializer.dump(
            models["m-02"], str(tmp_path / "m-02"), metadata={"name": "m-02"}
        )
        resp = await client.post("/gordo/v0/p/reload")
        assert resp.status == 200
        body = await resp.json()
        assert body["bank_models"] == 2
        assert app["bank"].mesh is not None  # still sharded
        assert app["bank"].mesh.devices.size == 8
        resp = await client.post(
            "/gordo/v0/p/m-02/anomaly/prediction", json={"X": X[:20].tolist()}
        )
        assert resp.status == 200
    finally:
        await client.close()


def test_devices_beyond_available_clamp(tmp_path, many_models):
    """devices > jax.device_count() warns and clamps instead of crashing
    (a manifest requesting 8 chips must still boot on a smaller slice)."""
    from gordo_components_tpu import serializer
    from gordo_components_tpu.server import build_app

    models, _ = many_models
    serializer.dump(models["m-03"], str(tmp_path / "m-03"), metadata={"name": "m-03"})
    app = build_app(str(tmp_path), devices=999)
    bank = app["bank"]
    assert bank.mesh is not None
    assert bank.mesh.devices.size == jax.device_count()


async def test_batching_engine_over_sharded_bank(many_models):
    """Concurrent requests coalesce through the engine and still match."""
    models, X = many_models
    single = ModelBank.from_models(models)
    engine = BatchingEngine(
        ModelBank.from_models(models, mesh=fleet_mesh()), flush_ms=5.0
    )
    names = [f"m-{i:02d}" for i in range(12)] + ["lstm"]
    try:
        results = await asyncio.gather(
            *[engine.score(n, X[:40]) for n in names]
        )
    finally:
        await engine.stop()
    assert engine.stats["max_batch_seen"] > 1  # they really coalesced
    for n, r in zip(names, results):
        # allclose, not array_equal: the engine coalesces these into one
        # padded batch (B=16), and XLA fuses a B=16 program differently
        # from the B=1 reference — ~1 ULP float32 reassociation on CPU.
        # Bitwise sharded-vs-single parity at the SAME batch composition
        # is asserted by test_sharded_heterogeneous_batch above.
        np.testing.assert_allclose(
            r.total_scaled,
            single.score(n, X[:40]).total_scaled,
            rtol=1e-5,
            atol=1e-6,
        )
