"""Model-bank tests: stacked HBM-resident scoring must be frame-identical
to the per-model ``DiffBasedAnomalyDetector.anomaly`` path (the two share
``assemble_anomaly_frame``), and the continuous-batching engine must
coalesce concurrent requests without changing results."""

import asyncio

import numpy as np
import pandas as pd
import pytest
from sklearn.pipeline import Pipeline
from sklearn.preprocessing import MaxAbsScaler, MinMaxScaler, RobustScaler

from gordo_components_tpu.models import (
    AutoEncoder,
    DiffBasedAnomalyDetector,
    LSTMAutoEncoder,
)
from gordo_components_tpu.models.transformers import JaxMinMaxScaler
from gordo_components_tpu.server.bank import BatchingEngine, ModelBank

from bank_parity import assert_slots_equal_their_single_answers


def _make_det(Xv, scaler=None, base=None, **ae_kwargs):
    if base is None:
        kwargs = dict(epochs=2, batch_size=64)
        kwargs.update(ae_kwargs)
        base = AutoEncoder(**kwargs)
    est = (
        Pipeline([("scale", scaler), ("model", base)]) if scaler is not None else base
    )
    det = DiffBasedAnomalyDetector(base_estimator=est)
    det.fit(Xv)
    return det


@pytest.fixture(scope="module")
def fleet_models():
    rng = np.random.RandomState(0)
    X3 = rng.rand(150, 3).astype("float32")
    X5 = rng.rand(150, 5).astype("float32")
    return {
        "plain": _make_det(X3),
        "jax-scaled": _make_det(X3, scaler=JaxMinMaxScaler()),
        "sk-scaled": _make_det(X3, scaler=MinMaxScaler()),
        "wide": _make_det(X5),
    }, {"plain": X3, "jax-scaled": X3, "sk-scaled": X3, "wide": X5}


def test_bank_membership_and_buckets(fleet_models):
    models, _ = fleet_models
    lstm = DiffBasedAnomalyDetector(
        base_estimator=LSTMAutoEncoder(lookback_window=5, epochs=1, batch_size=32)
    )
    lstm.fit(np.random.RandomState(1).rand(60, 3).astype("float32"))
    bank = ModelBank.from_models({**models, "lstm": lstm})
    assert len(bank) == 5  # sequence models bank too
    assert "lstm" in bank
    assert all(name in bank for name in models)
    # 3-feature ff models share a bucket; 5-feature ff and the lstm each
    # get their own
    assert bank.n_buckets == 3


def test_coverage_device_block_reads_the_arrays_devices(fleet_models):
    """``coverage()["device"]`` is where the stacked weights actually sit
    (platform/kind/count from the arrays' own devices), single-device and
    sharded — and None for a bank that holds nothing."""
    import jax

    from gordo_components_tpu.parallel.mesh import fleet_mesh

    models, _ = fleet_models
    dev = jax.devices()[0]
    block = {"platform": dev.platform, "kind": dev.device_kind}
    assert ModelBank.from_models(models, registry=False).coverage()[
        "device"
    ] == {**block, "count": 1}
    sharded = ModelBank.from_models(models, registry=False, mesh=fleet_mesh(4))
    assert sharded.coverage()["device"] == {**block, "count": 4}
    assert ModelBank.from_models({}, registry=False).coverage()["device"] is None


@pytest.mark.parametrize("name", ["plain", "jax-scaled", "sk-scaled", "wide"])
def test_bank_scoring_matches_per_model_path(fleet_models, name):
    models, data = fleet_models
    bank = ModelBank.from_models(models)
    X = data[name][:37]  # odd length -> exercises padding
    expected = models[name].anomaly(X)
    got = bank.score(name, X).to_frame()
    pd.testing.assert_frame_equal(got, expected, rtol=1e-4, atol=1e-5)


@pytest.fixture(scope="module")
def one_bucket_fleets():
    """Nine dense members of ONE bucket and five LSTM members of another,
    each fitted on its own data (so no two members' answers agree), in
    stack order: the first and the last name are the stack's ends."""
    rng = np.random.RandomState(3)
    data = rng.rand(14, 120, 3).astype("float32") + np.arange(14)[:, None, None] / 14
    dense = {
        f"d{i}": _make_det(data[i], epochs=1) for i in range(9)
    }
    lstm = {
        f"l{i}": _make_det(
            data[9 + i],
            base=LSTMAutoEncoder(lookback_window=4, epochs=1, batch_size=32),
        )
        for i in range(5)
    }
    return {"dense": dense, "lstm": lstm}, rng.rand(8, 64, 3).astype("float32")


@pytest.mark.parametrize(
    "slots",
    [
        [0], [-1],  # B = 1: the stack's two ends
        [0, -1], [1, 1],  # B = 2; the same member twice in one batch
        [-1, 0, 2],  # three requests in a batch padded to four
        [0, 4, 4, -1, 2, 0, 1, 3],  # B = 8, repeats, both ends
    ],
    ids=lambda slots: "-".join(map(str, slots)),
)
@pytest.mark.parametrize("kind", ["dense", "lstm"])
def test_batch_slots_match_single_and_per_model_path(
    one_bucket_fleets, monkeypatch, kind, slots
):
    """Select-then-compute (ISSUE 27): each slot of a coalesced batch is
    bitwise its member's own B = 1 answer, and the per-model path's to the
    tolerance of ``test_bank_scoring_matches_per_model_path``."""
    fleets, bodies = one_bucket_fleets
    models = fleets[kind]
    bank = ModelBank.from_models(models, registry=False)
    assert bank.n_buckets == 1
    names = list(models)
    # distinct bodies and lengths per slot, all padded to 64 rows
    requests = [
        (names[member], bodies[slot][: 40 + 3 * slot], None)
        for slot, member in enumerate(slots)
    ]
    batch = assert_slots_equal_their_single_answers(
        bank, requests, monkeypatch, batch_size={1: 1, 2: 2, 3: 4, 8: 8}[len(slots)]
    )
    for (name, X, _), got in zip(requests, batch):
        pd.testing.assert_frame_equal(
            got.to_frame(), models[name].anomaly(X), rtol=1e-4, atol=1e-5
        )


def test_bank_scoring_with_y(fleet_models):
    models, data = fleet_models
    bank = ModelBank.from_models(models)
    X = data["jax-scaled"][:20]
    y = X + 0.1
    expected = models["jax-scaled"].anomaly(X, y)
    got = bank.score("jax-scaled", X, y).to_frame()
    pd.testing.assert_frame_equal(got, expected, rtol=1e-4, atol=1e-5)


def test_score_many_mixed_buckets_and_chunking(fleet_models):
    models, data = fleet_models
    bank = ModelBank.from_models(models, max_rows_per_call=16)
    requests = [
        ("plain", data["plain"][:50], None),  # chunked: 50 rows > 16
        ("wide", data["wide"][:7], None),
        ("sk-scaled", data["sk-scaled"][:16], None),
    ]
    results = bank.score_many(requests)
    for (name, X, _), res in zip(requests, results):
        assert res.model_output.shape == X.shape
        expected = models[name].anomaly(X)
        pd.testing.assert_frame_equal(
            res.to_frame(), expected, rtol=1e-4, atol=1e-5
        )


def test_bank_rejects_wrong_shape_and_unknown(fleet_models):
    models, data = fleet_models
    bank = ModelBank.from_models(models)
    with pytest.raises(KeyError):
        bank.score("ghost", data["plain"][:5])
    with pytest.raises(ValueError):
        bank.score("plain", data["wide"][:5])  # 5 features into 3-feature model
    with pytest.raises(ValueError):
        bank.score("plain", data["plain"][:0])  # empty input
    with pytest.raises(ValueError):
        bank.score("plain", data["plain"][:10], y=data["plain"][:4])  # short y


def test_bank_respects_compute_dtype(fleet_models):
    """bf16 and f32 models with identical kwargs must not share a bucket,
    and bf16 bank scoring must match the bf16 per-model path."""
    _, data = fleet_models
    X = data["plain"]
    det16 = _make_det(X, compute_dtype="bfloat16")
    det32 = _make_det(X)
    bank = ModelBank.from_models({"bf16": det16, "f32": det32})
    assert bank.n_buckets == 2
    expected = det16.anomaly(X[:21])
    got = bank.score("bf16", X[:21]).to_frame()
    pd.testing.assert_frame_equal(got, expected, rtol=1e-4, atol=1e-5)


def test_bank_max_rows_cap_not_pow2():
    from gordo_components_tpu.server.bank import _prev_pow2

    assert _prev_pow2(5000) == 4096
    assert _prev_pow2(4096) == 4096
    assert _prev_pow2(1) == 1


@pytest.mark.parametrize("flush_ms", [0.0, 20.0])
async def test_batching_engine_coalesces(fleet_models, flush_ms):
    """Requests queued together ride one call, with or without a window."""
    models, data = fleet_models
    bank = ModelBank.from_models(models)
    engine = BatchingEngine(bank, max_batch=8, flush_ms=flush_ms)
    try:
        names = ["plain", "jax-scaled", "sk-scaled", "wide"] * 3
        results = await asyncio.gather(
            *(engine.score(n, data[n][:10]) for n in names)
        )
        for n, res in zip(names, results):
            expected = models[n].anomaly(data[n][:10])
            pd.testing.assert_frame_equal(
                res.to_frame(), expected, rtol=1e-4, atol=1e-5
            )
        assert engine.stats["requests"] == len(names)
        # coalescing happened: fewer XLA dispatch rounds than requests
        assert engine.stats["batches"] < len(names)
        assert engine.stats["max_batch_seen"] > 1
    finally:
        await engine.stop()


async def test_batching_engine_propagates_errors(fleet_models):
    models, data = fleet_models
    bank = ModelBank.from_models(models)
    engine = BatchingEngine(bank, max_batch=4, flush_ms=5.0)
    try:
        good, bad = await asyncio.gather(
            engine.score("plain", data["plain"][:5]),
            engine.score("plain", data["wide"][:5]),  # wrong width
            return_exceptions=True,
        )
        # one request's bad shape must not poison the good one
        assert not isinstance(good, Exception)
        assert isinstance(bad, ValueError)
    finally:
        await engine.stop()


@pytest.mark.parametrize(
    "make_scaler",
    [
        lambda: RobustScaler(),
        lambda: RobustScaler(with_centering=False),
        lambda: RobustScaler(with_scaling=False),
        lambda: MaxAbsScaler(),
    ],
    ids=["robust", "robust-no-center", "robust-no-scale", "maxabs"],
)
def test_bank_affine_scaler_family(make_scaler):
    """RobustScaler/MaxAbsScaler are affine: the bank must reproduce the
    per-model scoring exactly, not fall back."""
    rng = np.random.RandomState(7)
    X = (rng.rand(150, 4).astype("float32") - 0.3) * 5.0
    det = _make_det(X, scaler=make_scaler())
    bank = ModelBank.from_models({"m": det})
    cov = bank.coverage()
    assert cov["banked"] == 1 and "m" not in cov["fallback"], cov
    expected = det.anomaly(X[:41])
    got = bank.score("m", X[:41]).to_frame()
    pd.testing.assert_frame_equal(got, expected, rtol=1e-4, atol=1e-5)


def test_bank_standard_scaler_without_std(fleet_models):
    """StandardScaler(with_std=False) leaves scale_=None: the bank must
    treat it as a pure-centering affine (ADVICE r1), not crash."""
    from sklearn.preprocessing import StandardScaler

    _, data = fleet_models
    X = data["plain"]
    det = _make_det(X, scaler=StandardScaler(with_std=False))
    bank = ModelBank.from_models({"centered": det})
    assert "centered" in bank
    got = bank.score("centered", X[:20]).to_frame()
    expected = det.anomaly(X[:20])
    pd.testing.assert_frame_equal(got, expected, rtol=1e-4, atol=1e-5)


def test_bank_extraction_failure_isolated(fleet_models):
    """One model whose extraction raises must not abort bank construction
    for the whole collection (runs at server startup and /reload)."""
    models, data = fleet_models

    class _Boom:
        @property
        def scaler_params_(self):
            raise RuntimeError("boom")

    broken = _make_det(data["plain"])
    broken.base_estimator = Pipeline(
        [("scale", _Boom()), ("model", broken.base_estimator)]
    )
    bank = ModelBank.from_models({**models, "broken": broken})
    assert "broken" not in bank
    assert len(bank) == len(models)  # everything else still banked


async def test_batching_engine_stop_resolves_pending(fleet_models):
    """A request awaiting engine.score() at shutdown must be cancelled,
    not hang forever (ADVICE r1)."""
    models, data = fleet_models
    bank = ModelBank.from_models(models)
    # huge flush window: the request sits collected-but-unscored at stop()
    engine = BatchingEngine(bank, max_batch=64, flush_ms=60_000.0)
    task = asyncio.ensure_future(engine.score("plain", data["plain"][:8]))
    await asyncio.sleep(0.05)
    await engine.stop()
    with pytest.raises(asyncio.CancelledError):
        await task


class TestSequenceBank:
    """LSTM/conv/forecast detectors bank too (BASELINE.md config 5 over
    the full zoo): banked scoring must be frame-identical to the per-model
    ``.anomaly()`` path, including the warm-up offset alignment."""

    @pytest.fixture(scope="class")
    def seq_models(self):
        from gordo_components_tpu.models import ConvAutoEncoder, LSTMForecast

        rng = np.random.RandomState(2)
        X = rng.rand(120, 3).astype("float32")
        out = {}
        out["lstm"] = _make_det(
            X, base=LSTMAutoEncoder(lookback_window=6, epochs=2, batch_size=64)
        )
        out["lstm-scaled"] = _make_det(
            X,
            scaler=MinMaxScaler(),
            base=LSTMAutoEncoder(lookback_window=6, epochs=2, batch_size=64),
        )
        out["forecast"] = _make_det(
            X, base=LSTMForecast(lookback_window=6, epochs=2, batch_size=64)
        )
        out["conv"] = _make_det(
            X, base=ConvAutoEncoder(lookback_window=16, epochs=2, batch_size=64)
        )
        return out, X

    @pytest.mark.parametrize("name", ["lstm", "lstm-scaled", "forecast", "conv"])
    def test_sequence_bank_matches_anomaly(self, seq_models, name):
        models, X = seq_models
        bank = ModelBank.from_models(models)
        assert name in bank
        idx = pd.date_range("2020-01-01", periods=40, freq="10min")
        Xdf = pd.DataFrame(X[:40], columns=["t1", "t2", "t3"], index=idx)
        got = bank.score(name, X[:40]).to_frame(index=idx)
        expected = models[name].anomaly(Xdf)
        pd.testing.assert_frame_equal(got, expected, rtol=1e-4, atol=1e-5)

    def test_sequence_chunk_overlap_loses_no_rows(self, seq_models):
        """Chunked long requests overlap by the warm-up: output length and
        values match the unchunked per-model path."""
        models, X = seq_models
        bank = ModelBank.from_models(models, max_rows_per_call=32)
        res = bank.score("lstm", X)  # 120 rows -> several 32-row chunks
        assert len(res.model_output) == len(X) - 5  # offset = lookback-1
        expected = models["lstm"].anomaly(X)
        got = res.to_frame()
        pd.testing.assert_frame_equal(got, expected, rtol=1e-4, atol=1e-5)

    def test_sequence_too_short_request_raises(self, seq_models):
        models, X = seq_models
        bank = ModelBank.from_models(models)
        with pytest.raises(ValueError, match="warm-up"):
            bank.score("lstm", X[:5])  # 5 rows <= offset

    def test_coverage_reports_fallback_reasons(self, seq_models):
        models, X = seq_models
        from sklearn.decomposition import PCA
        from sklearn.pipeline import Pipeline as SkPipeline

        from gordo_components_tpu.models import AutoEncoder

        pca_det = DiffBasedAnomalyDetector(
            base_estimator=SkPipeline(
                [("pca", PCA(n_components=3)), ("model", AutoEncoder(epochs=1))]
            )
        )
        pca_det.fit(X)
        bank = ModelBank.from_models({**models, "pca": pca_det})
        cov = bank.coverage()
        assert cov["banked"] == len(models)
        assert "pca" in cov["fallback"]
        assert "non-affine" in cov["fallback"]["pca"]


def test_bank_warmup_precompiles_buckets(fleet_models):
    """warmup() compiles each bucket's scoring program so the first real
    request is served from the jit cache, and never raises."""
    models, data = fleet_models
    bank = ModelBank.from_models(models)
    assert bank.warmup(rows=64) == bank.n_buckets
    sizes_after_warmup = {
        k: b._score._cache_size() for k, b in bank._buckets.items()
    }
    assert all(n == 1 for n in sizes_after_warmup.values())
    # a request at the warmed row shape REUSES the compiled program (the
    # warmup shape must keep matching score_many's shape computation)
    X = data["plain"][:64]
    pd.testing.assert_frame_equal(
        bank.score("plain", X).to_frame(),
        models["plain"].anomaly(X),
        rtol=1e-4,
        atol=1e-5,
    )
    key = bank._index["plain"][0]
    assert bank._buckets[key]._score._cache_size() == 1  # no new compile


def test_bank_warmup_covers_sequence_buckets():
    """Sequence buckets warm with a T that covers their lookback even if
    the requested warmup rows are smaller."""
    rng = np.random.RandomState(3)
    X = rng.rand(120, 3).astype("float32")
    det = _make_det(
        X, base=LSTMAutoEncoder(lookback_window=48, epochs=1, batch_size=64)
    )
    bank = ModelBank.from_models({"long-lb": det})
    assert bank.warmup(rows=8) == 1  # 8 < lookback: clamped internally
