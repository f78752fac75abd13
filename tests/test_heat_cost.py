"""Fleet heat & device-cost observatory (ISSUE 18): the decayed
per-member access-heat accountant (observability/heat.py), the
per-bucket FLOPs/MFU attribution (observability/cost.py), the metrics
registry's cardinality guard, and their serving/watchman surfaces.

The acceptance story this file proves: on a synthetic skewed load (4
hot members at 8x), ``GET /heat`` ranks exactly those members hottest
and watchman's fleet rollup agrees byte-for-byte with the per-replica
bodies; ``GET /costs`` reports a per-bucket MFU for every live bucket
(mixed architectures included); the heat history survives two
``/reload`` bank swaps; analytic FLOPs agree with XLA's own
``cost_analysis`` within a documented band; and the accountant stays
within the 5% hot-loop overhead budget both disabled and enabled.
"""

import asyncio
import json
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from gordo_components_tpu import serializer
from gordo_components_tpu.models import (
    AutoEncoder,
    DiffBasedAnomalyDetector,
    LSTMAutoEncoder,
)
from gordo_components_tpu.observability import MetricsRegistry
from gordo_components_tpu.observability.cost import (
    CostModel,
    bucket_cost_row,
    conv1d_autoencoder_flops,
    dense_chain_flops,
    estimate_flops_per_row,
    lstm_stack_flops,
    merge_cost_snapshots,
    resolve_peak_flops,
)
from gordo_components_tpu.observability.goodput import GoodputLedger
from gordo_components_tpu.observability.heat import (
    HeatAccountant,
    merge_heat_snapshots,
)
from gordo_components_tpu.server import build_app
from gordo_components_tpu.server.bank import ModelBank

pytestmark = pytest.mark.heat

LN2 = float(np.log(2.0))


@pytest.fixture(scope="module")
def hot_cold_models():
    """Eight identically-shaped members (one bucket) — the skewed-load
    acceptance fleet: requests make m0..m3 hot, m4..m7 cold."""
    rng = np.random.RandomState(0)
    X = rng.rand(160, 3).astype("float32")
    models = {}
    for i in range(8):
        det = DiffBasedAnomalyDetector(
            base_estimator=AutoEncoder(epochs=1, batch_size=64)
        )
        det.fit(X + 0.01 * i)
        models[f"m{i}"] = det
    return models


@pytest.fixture(scope="module")
def mixed_arch_models():
    """Two buckets (dense f3, LSTM f3) — the mixed-architecture /costs
    fleet, small enough that compiles stay cheap."""
    rng = np.random.RandomState(1)
    X = rng.rand(160, 3).astype("float32")
    dense = DiffBasedAnomalyDetector(
        base_estimator=AutoEncoder(epochs=1, batch_size=64)
    )
    dense.fit(X)
    lstm = DiffBasedAnomalyDetector(
        base_estimator=LSTMAutoEncoder(lookback_window=6, epochs=1, batch_size=64)
    )
    lstm.fit(X)
    return {"dense-a": dense, "lstm-a": lstm}


@pytest.fixture(scope="module")
def hot_cold_dir(tmp_path_factory, hot_cold_models):
    root = tmp_path_factory.mktemp("heat-collection")
    for name, det in hot_cold_models.items():
        serializer.dump(det, str(root / name), metadata={"name": name})
    return str(root)


@pytest.fixture(scope="module")
def mixed_arch_dir(tmp_path_factory, mixed_arch_models):
    root = tmp_path_factory.mktemp("cost-collection")
    for name, det in mixed_arch_models.items():
        serializer.dump(det, str(root / name), metadata={"name": name})
    return str(root)


def _x_payload(rows=24, cols=3, seed=7):
    rng = np.random.RandomState(seed)
    return {"X": rng.rand(rows, cols).tolist()}


async def _serve(artifact_dir, **kwargs):
    kwargs.setdefault("devices", 1)
    client = TestClient(TestServer(build_app(artifact_dir, **kwargs)))
    await client.start_server()
    return client


# ------------------------------------------------------------------ #
# accountant units: decay math, tiers, eviction
# ------------------------------------------------------------------ #


def test_heat_decay_and_rate_identity():
    """One half-life halves every cell, and rate = heat * ln2 / halflife
    converts decayed rows into a rows/second estimate."""
    t = [0.0]
    h = HeatAccountant(
        halflife_s=10.0, hot_rate=5.0, warm_rate=1.0,
        sample_interval_s=0.0, clock=lambda: t[0],
    )
    h.pending["a"] = 100.0
    t[0] = 1.0
    h.sample(force=True)
    rate0 = h.rates()["a"]
    assert rate0 == pytest.approx(100.0 * LN2 / 10.0)
    t[0] = 11.0  # exactly one half-life later
    h.sample(force=True)
    assert h.rates()["a"] == pytest.approx(rate0 / 2.0)


def test_heat_tiers_and_histogram():
    t = [0.0]
    h = HeatAccountant(
        halflife_s=LN2,  # rate_of = ln2/halflife = 1: rate == heat
        hot_rate=50.0, warm_rate=5.0,
        sample_interval_s=0.0, clock=lambda: t[0],
    )
    h.pending.update({"hot1": 100.0, "hot2": 80.0, "warm1": 10.0, "cold1": 1.0})
    t[0] = 0.5
    h.sample(force=True)
    snap = h.snapshot()
    assert snap["tiers"] == {"hot": 2, "warm": 1, "cold": 1}
    assert snap["members_tracked"] == 4
    # the histogram is log-binned counts, never per-member series
    assert sum(n for _edge, n in snap["histogram"]) == 4
    ranked = h.ranked(2)
    assert [e["member"] for e in ranked["hottest"]] == ["hot1", "hot2"]
    assert ranked["coldest"][0]["member"] == "cold1"


def test_heat_steady_state_converges_to_rate():
    """Feeding r rows/sec for many half-lives converges the estimated
    rate to r (the steady-state identity the thresholds classify)."""
    t = [0.0]
    h = HeatAccountant(
        halflife_s=5.0, hot_rate=1e9, warm_rate=1e9,
        sample_interval_s=0.0, clock=lambda: t[0],
    )
    # fine ticks: discrete feeding overshoots the continuous-limit
    # identity by ~dt*ln2/(2*halflife), so dt=0.1s keeps it sub-1%
    for step in range(1, 801):  # 80s = 16 half-lives at 20 rows/s
        h.pending["m"] = h.pending.get("m", 0.0) + 2.0
        t[0] = 0.1 * step
        h.sample(force=True)
    assert h.rates()["m"] == pytest.approx(20.0, rel=0.02)


def test_heat_evicts_dead_cells():
    t = [0.0]
    h = HeatAccountant(
        halflife_s=1.0, sample_interval_s=0.0, clock=lambda: t[0]
    )
    h.pending["gone"] = 4.0
    t[0] = 1.0
    h.sample(force=True)
    assert "gone" in h.rates()
    t[0] = 30.0  # 29 half-lives: 4 * 2^-29 << the eviction floor
    h.sample(force=True)
    assert h.rates() == {}
    assert h.snapshot()["members_tracked"] == 0


def test_heat_bound_bank_counts_cold_members():
    """Members the live bank holds but nobody scores are COLD members
    (rate 0), not invisible — the capacity advisor's cold tier."""
    t = [0.0]
    h = HeatAccountant(
        halflife_s=LN2, hot_rate=5.0, warm_rate=1.0,
        sample_interval_s=0.0, clock=lambda: t[0],
    )

    class FakeBank:
        def placement(self):
            return {
                "buckets": [
                    {"bucket": "bkt", "members": ["seen", "never-scored"]}
                ]
            }

    bank = FakeBank()  # bind_bank holds only a weakref; keep it alive
    h.bind_bank(bank)
    h.pending["seen"] = 100.0
    t[0] = 1.0
    h.sample(force=True)
    snap = h.snapshot()
    assert snap["members_total"] == 2
    assert snap["tiers"]["cold"] == 1
    assert snap["per_bucket"]["bkt"]["hot"] == 1
    cold = [e for e in h.ranked(2)["coldest"] if e["member"] == "never-scored"]
    assert cold and cold[0]["rate"] == 0.0 and cold[0]["bucket"] == "bkt"


# ------------------------------------------------------------------ #
# cardinality guard (metrics registry)
# ------------------------------------------------------------------ #


def test_metric_series_cap_drops_and_counts():
    reg = MetricsRegistry(max_series_per_metric=4)
    fam = reg.counter("guard_total", "t", ("l",))
    for i in range(10):
        fam.labels(str(i)).inc(2)
    assert fam.dropped == 6
    snap = reg.snapshot()
    assert len(snap["guard_total"]["values"]) == 4
    drops = snap["gordo_metrics_dropped_series_total"]["values"]
    assert drops == [{"labels": {"metric": "guard_total"}, "value": 6}]
    # a dropped label set writes into a detached cell: no error, no growth
    fam.labels("9").inc()
    assert len(reg.snapshot()["guard_total"]["values"]) == 4


def test_metric_series_cap_env(monkeypatch):
    monkeypatch.setenv("GORDO_METRIC_MAX_SERIES", "2")
    reg = MetricsRegistry()
    fam = reg.gauge("g", "t", ("l",))
    for i in range(5):
        fam.labels(str(i)).set(i)
    assert fam.dropped == 3
    assert "gordo_metrics_dropped_series_total" in reg.render()


def test_heat_exposition_is_bounded(monkeypatch):
    """The heat plane NEVER emits a per-member series no matter how
    many members it tracks — tier gauges + one histogram only."""
    reg = MetricsRegistry()
    t = [0.0]
    h = HeatAccountant(
        halflife_s=10.0, sample_interval_s=0.0, registry=reg,
        clock=lambda: t[0],
    )
    for i in range(5000):
        h.pending[f"member-{i}"] = float(i + 1)
    t[0] = 1.0
    h.sample(force=True)
    text = reg.render()
    assert "member-" not in text
    assert "gordo_heat_tier_members" in text
    assert "gordo_heat_member_rate_bucket" in text
    assert "gordo_metrics_dropped_series_total" not in text


# ------------------------------------------------------------------ #
# analytic FLOPs model
# ------------------------------------------------------------------ #


def test_flops_closed_forms():
    # dense 3 -> 8 -> 4 -> 8 -> 3: 2*(24+32+32+24)
    assert dense_chain_flops(3, (8,), (4, 8)) == 2 * (24 + 32 + 32 + 24)
    # lstm: T * 8h(in+h) per layer + final dense
    assert lstm_stack_flops(3, (16,), 6) == 6 * 8 * 16 * 19 + 2 * 16 * 3
    # conv: stride-2 SAME encoder halves (ceil), decoder doubles,
    # final full-length conv back to n_features
    expect = (
        2 * 8 * 3 * 3 * 8      # enc1: L16->8, 3ch->8ch, K3
        + 2 * 4 * 3 * 8 * 4    # enc2: L8->4, 8->4
        + 2 * 8 * 3 * 4 * 4    # dec1: L4->8, 4->4 (reversed channels)
        + 2 * 16 * 3 * 4 * 8   # dec2: L8->16, 4->8
        + 2 * 16 * 3 * 8 * 3   # final: L16, 8->3
    )
    assert conv1d_autoencoder_flops(3, (8, 4), 3, 16) == expect


def test_lstm_flops_trip_count_explicit():
    """The LSTM closed form is exactly lookback scan trips of the
    per-step unit plus the Dense head — the decomposition the
    time-major layout (ops/seq_scan.py) makes literal, and the reason
    the closed form is layout-invariant: both layouts run the same
    per-step math, differing only in the batched axis."""
    from gordo_components_tpu.observability.cost import lstm_step_flops

    for f, dims, T in [(3, (16,), 6), (5, (8, 4), 12)]:
        assert lstm_stack_flops(f, dims, T) == (
            T * lstm_step_flops(f, dims) + 2 * dims[-1] * f
        )
    # per-step unit: 4 gates = 8h(in+h) per layer, layers chained
    assert lstm_step_flops(3, (16,)) == 8 * 16 * (3 + 16)
    assert lstm_step_flops(3, (16, 4)) == 8 * 16 * 19 + 8 * 4 * 20


def test_estimate_flops_duck_typing_and_fallback():
    from gordo_components_tpu.models.register import lookup_factory

    dense = lookup_factory("AutoEncoder", "feedforward_model")(3)
    f, method = estimate_flops_per_row(dense, 3, 1)
    assert method == "analytic" and f > 0
    lstm = lookup_factory("LSTMAutoEncoder", "lstm_symmetric")(3)
    f, method = estimate_flops_per_row(lstm, 3, 6)
    assert method == "analytic" and f > 0
    conv = lookup_factory("LSTMAutoEncoder", "conv1d_autoencoder")(3)
    f, method = estimate_flops_per_row(conv, 3, 16)
    assert method == "analytic" and f > 0
    # unknown architecture: the classic 2*params*steps bound, tagged
    f, method = estimate_flops_per_row(object(), 3, 4, params_per_member=100)
    assert (f, method) == (800.0, "params")
    assert estimate_flops_per_row(object(), 3, 4)[1] == "unknown"


@pytest.mark.slow
@pytest.mark.parametrize(
    "registry_type,kind,lookback,x_shape",
    [
        ("AutoEncoder", "feedforward_model", 1, (1, 3)),
        ("LSTMAutoEncoder", "lstm_symmetric", 8, (1, 8, 3)),
        ("LSTMAutoEncoder", "conv1d_autoencoder", 16, (1, 16, 3)),
    ],
)
def test_flops_vs_xla_cost_analysis(registry_type, kind, lookback, x_shape):
    """The analytic FLOPs cross-checked against XLA's own
    ``cost_analysis()`` where that API reports flops.

    Tolerance band, documented: the analytic model counts matmul MACs
    as 2 FLOPs and omits bias adds / activations / elementwise glue,
    while XLA counts post-fusion HLO flops (and on some backends folds
    or re-associates work), so agreement within a factor of 2 — not
    percent-level equality — is the contract. The band is asymmetric
    on purpose: the analytic number must never be more than 2x ABOVE
    XLA's (we never overclaim MFU by more than 2x) and never below
    40% of it (the model must actually count the dominant matmuls)."""
    import jax

    from gordo_components_tpu.models.register import lookup_factory

    module = lookup_factory(registry_type, kind)(3)
    x = np.zeros(x_shape, np.float32)
    params = module.init(jax.random.PRNGKey(0), x)
    try:
        compiled = jax.jit(module.apply).lower(params, x).compile()
        cost = compiled.cost_analysis()
    except Exception as exc:  # pragma: no cover - backend-dependent API
        pytest.skip(f"cost_analysis unavailable on this backend: {exc}")
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    xla_flops = float((cost or {}).get("flops") or 0.0)
    if xla_flops <= 0:
        pytest.skip("backend reports no flops in cost_analysis")
    analytic, method = estimate_flops_per_row(module, 3, lookback)
    assert method == "analytic"

    def in_band(a):
        # never claim more than 2x what XLA counted, and count at
        # least 40% of it (the dominant matmuls must be in the model)
        return 0.4 * xla_flops <= a <= 2.0 * xla_flops

    # HLO cost analysis is trip-count-blind: a scan/while-lowered LSTM
    # reports ONE loop-body iteration, so the analytic number may match
    # either the full window or a single timestep — accept whichever
    # the backend counted, reject everything outside both bands
    assert in_band(analytic) or in_band(analytic / max(1, lookback)), (
        analytic, xla_flops, lookback,
    )


@pytest.mark.slow
@pytest.mark.seqperf
def test_flops_vs_xla_cost_analysis_time_major():
    """The SAME analytic closed form must stay in band against XLA's
    count of the TIME-MAJOR gang program (ops/seq_scan.py): the layout
    re-batches the matmuls but runs identical per-step math, so
    ``gordo_bucket_mfu`` keeps one FLOPs provenance across layouts.
    Same asymmetric 0.4x..2x band and scan-trip-count-blindness
    allowance as the legacy-layout leg above."""
    import jax
    import jax.numpy as jnp

    from gordo_components_tpu.models.register import lookup_factory
    from gordo_components_tpu.ops.seq_scan import lstm_time_major_forward

    M, B, T, f = 2, 4, 8, 3
    module = lookup_factory("LSTMAutoEncoder", "lstm_symmetric")(f)
    xb = jnp.zeros((M, B, T, f), jnp.float32)
    params = jax.vmap(
        lambda k: module.init(k, xb[0])
    )(jax.random.split(jax.random.PRNGKey(0), M))

    def fwd(p, x):
        return lstm_time_major_forward(module, p, x, kernel="jnp")

    try:
        compiled = jax.jit(fwd).lower(params, xb).compile()
        cost = compiled.cost_analysis()
    except Exception as exc:  # pragma: no cover - backend-dependent API
        pytest.skip(f"cost_analysis unavailable on this backend: {exc}")
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    xla_flops = float((cost or {}).get("flops") or 0.0)
    if xla_flops <= 0:
        pytest.skip("backend reports no flops in cost_analysis")
    per_row = xla_flops / (M * B)
    analytic, method = estimate_flops_per_row(module, f, T)
    assert method == "analytic"
    # the time-major program HOISTS the input projections out of the
    # scan (one wide einsum per layer, counted at full trip count by
    # XLA) while the in-loop hidden matmuls hit the trip-count-blind
    # while-body count (once) — so the third candidate is the hoisted
    # decomposition of the same closed form
    inp = hid = 0.0
    prev = f
    for h in (int(d) for d in module.dims):
        inp += 8.0 * h * prev
        hid += 8.0 * h * h
        prev = h
    head = 2.0 * int(module.dims[-1]) * f
    hoisted = T * inp + hid + head
    assert abs(T * (inp + hid) + head - analytic) < 1e-6  # same closed form

    def in_band(a):
        return 0.4 * per_row <= a <= 2.0 * per_row

    assert in_band(analytic) or in_band(analytic / T) or in_band(hoisted), (
        analytic, hoisted, per_row, T,
    )


@pytest.mark.slow
def test_bank_buckets_carry_flops(mixed_arch_models):
    bank = ModelBank.from_models(mixed_arch_models, registry=False)
    stats = bank.flops_stats()
    assert len(stats) == 2
    for label, row in stats.items():
        assert row["flops_per_row"] > 0, label
        assert row["flops_method"] == "analytic", label
        assert row["params_per_member"] > 0
    lstm_label = next(l for l in stats if l.startswith("LSTMAutoEncoder"))
    dense_label = next(l for l in stats if l.startswith("AutoEncoder"))
    # the LSTM runs its cell over the whole window; it must cost more
    # per row than the small dense chain
    assert stats[lstm_label]["flops_per_row"] > stats[dense_label]["flops_per_row"]


# ------------------------------------------------------------------ #
# cost model: ledger join, no-drift, fleet merge
# ------------------------------------------------------------------ #


class _StaticBank:
    def __init__(self, stats):
        self._stats = stats

    def flops_stats(self):
        return self._stats


def test_cost_model_joins_ledger_and_ranks():
    led = GoodputLedger()
    # busy bucket: 30ms useful + 10ms padded over 300 real / 100 pad rows
    led.account_group(
        "busy", 0.040, 0.030, 0.010, ok=True, shard_rows=[("0", 300, 100)]
    )
    # wasteful bucket: same window, 90% padding
    led.account_group(
        "wasteful", 0.040, 0.004, 0.036, ok=True, shard_rows=[("0", 40, 360)]
    )
    bank = _StaticBank({
        "busy": {"flops_per_row": 1000.0, "flops_method": "analytic",
                 "members": 4, "kind": "feedforward_model"},
        "wasteful": {"flops_per_row": 1000.0, "flops_method": "analytic",
                     "members": 4, "kind": "feedforward_model"},
        "idle": {"flops_per_row": 500.0, "flops_method": "analytic",
                 "members": 1, "kind": "feedforward_model"},
    })
    cm = CostModel(
        led, lambda: bank, sample_interval_s=0.0, peak_flops=1e9
    )
    snap = cm.snapshot()
    buckets = snap["buckets"]
    # EVERY live bucket gets an MFU row, including the never-scored one
    assert set(buckets) == {"busy", "wasteful", "idle"}
    assert all(b["mfu"] is not None for b in buckets.values())
    busy = buckets["busy"]
    assert busy["mfu"] == pytest.approx(1000.0 * 300 / 0.040 / 1e9, rel=1e-3)
    assert busy["device_s_per_1k_rows"] == pytest.approx(
        1000.0 * 0.040 / 300, rel=1e-3
    )
    assert busy["pad_waste_score"] == pytest.approx(0.25, abs=1e-6)
    assert buckets["idle"]["mfu"] == 0.0 and buckets["idle"]["live"]
    # ranking: pad waste x device share puts "wasteful" first
    assert snap["ranking"][0]["bucket"] == "wasteful"
    assert snap["ranking"][0]["wasted_device_score"] > snap["ranking"][1][
        "wasted_device_score"
    ]


def test_cost_snapshot_cached_until_sample():
    """No-drift: between samples the snapshot is byte-identical even as
    the ledger keeps accumulating; a forced sample sees the new state."""
    led = GoodputLedger()
    led.account_group("b", 0.040, 0.030, 0.010, ok=True,
                      shard_rows=[("0", 300, 100)])
    cm = CostModel(
        led, lambda: _StaticBank({"b": {"flops_per_row": 10.0,
                                        "flops_method": "analytic"}}),
        sample_interval_s=3600.0, peak_flops=1e12,
    )
    s1 = cm.snapshot()
    led.account_group("b", 0.040, 0.030, 0.010, ok=True,
                      shard_rows=[("0", 300, 100)])
    assert cm.snapshot() is s1  # the SAME cached object
    cm.sample(force=True)
    s2 = cm.snapshot()
    assert s2["buckets"]["b"]["routed_rows"] == 600


def test_cost_fleet_merge_single_replica_identity():
    led = GoodputLedger()
    led.account_group("b", 0.040, 0.0312345678, 0.0087654321, ok=True,
                      shard_rows=[("0", 299, 101)])
    cm = CostModel(
        led, lambda: _StaticBank({"b": {"flops_per_row": 123.456789,
                                        "flops_method": "analytic",
                                        "members": 3, "kind": "k"}}),
        sample_interval_s=0.0, peak_flops=7e11,
    )
    body = json.loads(json.dumps({"enabled": True, **cm.snapshot()}))
    merged = merge_cost_snapshots([body])
    assert merged["buckets"] == body["buckets"]
    assert merged["ranking"] == body["ranking"]
    assert merged["peak_flops"] == body["peak_flops"]


def test_cost_fleet_merge_sums_two_replicas():
    led1, led2 = GoodputLedger(), GoodputLedger()
    led1.account_group("b", 0.04, 0.03, 0.01, ok=True,
                       shard_rows=[("0", 300, 100)])
    led2.account_group("b", 0.04, 0.02, 0.02, ok=True,
                       shard_rows=[("0", 200, 200)])
    stats = {"b": {"flops_per_row": 100.0, "flops_method": "analytic"}}
    bodies = [
        json.loads(json.dumps({"enabled": True, **CostModel(
            led, lambda: _StaticBank(stats),
            sample_interval_s=0.0, peak_flops=1e12,
        ).snapshot()}))
        for led in (led1, led2)
    ]
    merged = merge_cost_snapshots(bodies)
    b = merged["buckets"]["b"]
    assert b["routed_rows"] == 500
    assert b["padded_rows"] == 300
    assert b["device_s"] == pytest.approx(0.08)
    assert merged["replicas_scraped"] == 2


def test_resolve_peak_flops_env(monkeypatch):
    monkeypatch.setenv("GORDO_DEVICE_PEAK_FLOPS", "2.5e14")
    assert resolve_peak_flops() == (2.5e14, "env")
    monkeypatch.delenv("GORDO_DEVICE_PEAK_FLOPS")
    peak, source = resolve_peak_flops()
    # CPU dev loop: a device the spec table does not know has NO peak —
    # never an assumed one — and the MFU fields derived from it are null
    assert (peak, source) == (None, "unknown")
    row = bucket_cost_row(100.0, "analytic", 10, 0, 0.5, 0.0, 0.0, peak)
    assert row["mfu"] is None and row["mfu_dispatched"] is None


# ------------------------------------------------------------------ #
# serving acceptance: skewed load, /costs MFU, no-drift, reload
# (slow: each trains real artifacts + boots the live server stack —
#  tier-1 keeps the pure-math/unit half of this module; these legs run
#  in `make heat` and the CI heat lane, which select on the heat
#  marker and so include slow-marked tests)
# ------------------------------------------------------------------ #


@pytest.mark.slow
async def test_skewed_load_heat_ranking_and_watchman_rollup(
    hot_cold_dir, monkeypatch
):
    """THE acceptance criterion: 4 hot members at 8x rank exactly
    hottest on ``GET /heat``, and watchman's fleet rollup agrees
    byte-for-byte with the per-replica body (no-drift contract)."""
    from gordo_components_tpu.watchman.server import build_watchman_app

    monkeypatch.setenv("GORDO_HEAT_SAMPLE_S", "3600")  # folds only on refresh
    client = await _serve(hot_cold_dir)
    try:
        hot = ["m0", "m1", "m2", "m3"]
        for name in hot:
            for _ in range(8):
                resp = await client.post(
                    f"/gordo/v0/proj/{name}/prediction", json=_x_payload()
                )
                assert resp.status == 200
        for name in ("m4", "m5", "m6", "m7"):
            resp = await client.post(
                f"/gordo/v0/proj/{name}/prediction", json=_x_payload()
            )
            assert resp.status == 200
        body = await (
            await client.get("/gordo/v0/proj/heat?refresh=1&top=4")
        ).json()
        assert body["enabled"]
        assert sorted(e["member"] for e in body["hottest"]) == hot
        assert body["tiers"]["hot"] + body["tiers"]["warm"] + body[
            "tiers"
        ]["cold"] == 8
        # every ranked entry attributes its bucket
        assert all(e["bucket"] for e in body["hottest"])
        # the cold four rank coldest
        assert sorted(e["member"] for e in body["coldest"]) == [
            "m4", "m5", "m6", "m7"
        ]

        base = f"http://{client.server.host}:{client.server.port}"
        wapp = build_watchman_app(
            "proj", base, metrics_urls=[f"{base}/gordo/v0/proj/metrics"]
        )
        wclient = TestClient(TestServer(wapp))
        await wclient.start_server()
        try:
            rollup = await (await wclient.get("/heat?top=4")).json()
            # byte-for-byte: one replica's rollup IS that replica's body
            replica = await (
                await client.get("/gordo/v0/proj/heat?top=4")
            ).json()
            for key in ("hottest", "coldest", "tiers", "per_bucket",
                        "rate_total", "members_total"):
                assert rollup[key] == replica[key], key
            assert rollup["replicas_scraped"] == 1
        finally:
            await wclient.close()
    finally:
        await client.close()


@pytest.mark.slow
async def test_costs_mfu_per_bucket_and_watchman_rollup(
    mixed_arch_dir, monkeypatch
):
    """`GET /costs` reports a per-bucket MFU for EVERY live bucket
    (mixed dense + LSTM architectures), and watchman's fleet rollup
    reproduces the single replica's body byte-for-byte."""
    from gordo_components_tpu.watchman.server import build_watchman_app

    # the CPU rig's device has no spec-table peak (MFU would be null):
    # the operator knob supplies one so the MFU plumbing is exercised
    monkeypatch.setenv("GORDO_DEVICE_PEAK_FLOPS", "1e12")

    client = await _serve(mixed_arch_dir)
    try:
        for name in ("dense-a", "lstm-a"):
            for _ in range(3):
                resp = await client.post(
                    f"/gordo/v0/proj/{name}/prediction", json=_x_payload(rows=32)
                )
                assert resp.status == 200
        body = await (await client.get("/gordo/v0/proj/costs?refresh=1")).json()
        assert body["enabled"]
        live = {l: b for l, b in body["buckets"].items() if b["live"]}
        assert len(live) == 2  # dense bucket + LSTM bucket
        for label, b in live.items():
            assert b["mfu"] is not None, label
            assert b["flops_per_row"] > 0 and b["flops_method"] == "analytic"
            assert b["routed_rows"] > 0 and b["device_s"] > 0
            assert b["mfu"] > 0
        assert body["peak_source"] == "env"
        assert [r["bucket"] for r in body["ranking"]]

        base = f"http://{client.server.host}:{client.server.port}"
        wapp = build_watchman_app(
            "proj", base, metrics_urls=[f"{base}/gordo/v0/proj/metrics"]
        )
        wclient = TestClient(TestServer(wapp))
        await wclient.start_server()
        try:
            rollup = await (await wclient.get("/costs")).json()
            replica = await (await client.get("/gordo/v0/proj/costs")).json()
            assert rollup["buckets"] == replica["buckets"]
            assert rollup["ranking"] == replica["ranking"]
            assert rollup["replicas_scraped"] == 1
        finally:
            await wclient.close()
    finally:
        await client.close()


@pytest.mark.slow
async def test_heat_cost_no_drift_endpoint_stats_registry(
    hot_cold_dir, monkeypatch
):
    """The no-drift contract: between samples, /heat and /costs bodies,
    the /stats embeds, and the registry's gauge values all read the
    SAME cached snapshot."""
    monkeypatch.setenv("GORDO_DEVICE_PEAK_FLOPS", "1e12")  # MFU gauges live
    client = await _serve(hot_cold_dir)
    try:
        for _ in range(4):
            resp = await client.post(
                "/gordo/v0/proj/m0/prediction", json=_x_payload()
            )
            assert resp.status == 200
        await client.get("/gordo/v0/proj/heat?refresh=1")
        await client.get("/gordo/v0/proj/costs?refresh=1")
        heat_body = await (await client.get("/gordo/v0/proj/heat")).json()
        cost_body = await (await client.get("/gordo/v0/proj/costs")).json()
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
        for key in ("tiers", "rate_total", "members_tracked", "histogram"):
            assert stats["heat"][key] == heat_body[key], key
        assert stats["costs"]["buckets"] == cost_body["buckets"]
        assert stats["costs"]["ranking"] == cost_body["ranking"]
        metrics = stats["metrics"]
        tier_samples = {
            s["labels"]["tier"]: s["value"]
            for s in metrics["gordo_heat_tier_members"]["values"]
        }
        assert tier_samples == heat_body["tiers"]
        mfu_samples = {
            s["labels"]["bucket"]: s["value"]
            for s in metrics["gordo_bucket_mfu"]["values"]
        }
        for label, b in cost_body["buckets"].items():
            assert mfu_samples[label] == b["mfu"], label
    finally:
        await client.close()


@pytest.mark.slow
async def test_heat_survives_two_reloads(hot_cold_dir, monkeypatch):
    """The model_rows regression fix: `/reload` swaps the bank but the
    app-level heat accountant keeps its decayed history — scoring
    across TWO reload generations accumulates, never resets."""
    monkeypatch.setenv("GORDO_HEAT_HALFLIFE_S", "100000")  # decay ~ none
    monkeypatch.setenv("GORDO_HEAT_SAMPLE_S", "3600")
    client = await _serve(hot_cold_dir)
    try:
        heat = client.app["heat"]
        assert heat is not None

        async def score_and_rate():
            for _ in range(3):
                resp = await client.post(
                    "/gordo/v0/proj/m0/prediction", json=_x_payload()
                )
                assert resp.status == 200
            body = await (
                await client.get("/gordo/v0/proj/heat?refresh=1&top=1")
            ).json()
            assert body["hottest"][0]["member"] == "m0"
            return body["hottest"][0]["rate"]

        r1 = await score_and_rate()
        assert (await client.post("/gordo/v0/proj/reload")).status == 200
        assert client.app["heat"] is heat  # same accountant, new bank
        r2 = await score_and_rate()
        assert (await client.post("/gordo/v0/proj/reload")).status == 200
        r3 = await score_and_rate()
        assert client.app["bank"].generation == 2
        # cumulative across generations: each phase adds the same rows,
        # so the rate keeps climbing instead of resetting per swap
        assert r2 > r1 and r3 > r2, (r1, r2, r3)
        # model_rows carried across the swap too (the planner's signal)
        assert client.app["bank"].model_rows.get("m0", 0) > 0
    finally:
        await client.close()


@pytest.mark.slow
async def test_heat_disabled_by_env(hot_cold_dir, monkeypatch):
    """GORDO_HEAT=0: no accountant exists, /heat reports disabled, no
    gordo_heat series render, scoring untouched."""
    monkeypatch.setenv("GORDO_HEAT", "0")
    client = await _serve(hot_cold_dir)
    try:
        assert client.app["heat"] is None
        resp = await client.post(
            "/gordo/v0/proj/m0/prediction", json=_x_payload()
        )
        assert resp.status == 200
        body = await (await client.get("/gordo/v0/proj/heat")).json()
        assert body == {"enabled": False}
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
        assert "heat" not in stats
        text = await (await client.get("/gordo/v0/proj/metrics")).text()
        assert "gordo_heat_" not in text
    finally:
        await client.close()


@pytest.mark.slow
async def test_cost_disabled_by_env(hot_cold_dir, monkeypatch):
    monkeypatch.setenv("GORDO_COST", "0")
    client = await _serve(hot_cold_dir)
    try:
        assert client.app["cost"] is None
        body = await (await client.get("/gordo/v0/proj/costs")).json()
        assert body == {"enabled": False}
        text = await (await client.get("/gordo/v0/proj/metrics")).text()
        assert "gordo_bucket_mfu" not in text
    finally:
        await client.close()


# ------------------------------------------------------------------ #
# hot-loop overhead guard (CI lanes: make heat / make hotloop)
# ------------------------------------------------------------------ #


@pytest.mark.slow
@pytest.mark.hotloop
def test_heat_overhead_within_5pct(hot_cold_models):
    """The accountant on the scoring path must stay within 5% of the
    heat-free configuration, BOTH ways: disabled (bank.heat None — one
    None check) and enabled (one dict get+set per request; decay math
    amortized into sample(), never per request). Interleaved best-of-N
    so machine drift hits both sides."""
    rng = np.random.RandomState(6)
    bank = ModelBank.from_models(hot_cold_models, registry=False)
    heat = HeatAccountant(sample_interval_s=3600.0)
    requests = [
        (name, rng.rand(64, 3).astype("float32"), None)
        for name in hot_cold_models
    ]
    bank.score_many(requests)  # warm/compile

    def timed(h, iters=40):
        bank.heat = h
        t0 = time.perf_counter()
        for _ in range(iters):
            bank.score_many(requests)
        bank.heat = None
        return time.perf_counter() - t0

    rounds, ratios = 7, []
    for _ in range(rounds):
        control = timed(None)
        instrumented = timed(heat)
        ratios.append(instrumented / control)
    assert min(ratios) <= 1.05, ratios
    # and the mailbox actually filled (the instrumented arm measured
    # real accounting, not a silently-disabled path)
    heat.sample(force=True)
    assert len(heat.rates()) == len(hot_cold_models)
