"""Sequence-model fast path (ops/seq_scan.py + friends): time-major
scan-over-time with the member axis innermost, the fused recurrent-step
kernel, fleet width autotuning, and cross-arch gang scheduling.

Parity contract (ISSUE 20): the time-major layout re-associates the gate
matmuls, so it matches the legacy vmap-over-members layout to fp32
rounding (documented band, NOT bitwise) — while the jnp-step forward
matches ``vmap(module.apply)`` exactly and the interpret-mode fused
kernel matches the jnp step within ULP-level bands like
tests/test_banked_kernel.py. On this CPU rig ``auto`` resolves the
layout to ``legacy`` (the layout is a lane-utilization bet for the TPU
with no rate on the chip yet — PERF.md), so every test that exercises
the fast path opts in explicitly via ``GORDO_SEQ_LAYOUT``.

The ``seqperf`` marker forms the `make seqperf` lane; the heavier
end-to-end legs also carry ``slow`` so tier-1 stays inside its budget.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gordo_components_tpu.models import train_core
from gordo_components_tpu.models.factories import lstm_symmetric
from gordo_components_tpu.ops import seq_scan
from gordo_components_tpu.ops.seq_scan import (
    extract_lstm_weights,
    fused_lstm_step,
    lstm_step_jnp,
    lstm_time_major_forward,
    pad_gate_lanes,
    resolve_seq_kernel_mode,
    resolve_seq_layout,
    supports_time_major,
)
from gordo_components_tpu.parallel import FleetTrainer, autotune
from gordo_components_tpu.parallel.autotune import resolve_fleet_width

LOOKBACK = 8


def _seq_members(n, rows=64, f=3, seed=0):
    rng = np.random.RandomState(seed)
    t = np.arange(rows)
    out = {}
    for i in range(n):
        freqs = 0.05 + 0.01 * rng.rand(f)
        X = np.sin(np.outer(t, freqs)) + rng.normal(scale=0.03, size=(rows, f))
        out[f"m{i}"] = X.astype("float32")
    return out


def _stacked_module(M=3, f=3, dims=(5,), B=4, T=6, seed=0):
    """A tiny LSTMStack + M independently-initialized members stacked on
    a leading axis + a member-major (M, B, T, F) batch."""
    module = lstm_symmetric(f, dims=dims)
    sample = jnp.zeros((B, T, f), jnp.float32)
    params = jax.vmap(
        lambda k: module.init(k, sample), in_axes=0
    )(jax.random.split(jax.random.PRNGKey(seed), M))
    xb = jnp.asarray(
        np.random.RandomState(seed + 1).randn(M, B, T, f).astype("float32")
    )
    return module, params, xb


# ------------------------------------------------------------------ #
# Env-knob resolution
# ------------------------------------------------------------------ #


def test_resolve_seq_layout(monkeypatch):
    monkeypatch.delenv(seq_scan.SEQ_LAYOUT_ENV, raising=False)
    # auto on this CPU rig keeps the legacy layout: the CPU suite pins
    # byte-for-byte fleet-vs-single guarantees the scan re-association
    # would break (tests opt in explicitly)
    assert resolve_seq_layout() == "legacy"
    assert resolve_seq_layout("time_major") == "time_major"
    assert resolve_seq_layout("legacy") == "legacy"
    monkeypatch.setenv(seq_scan.SEQ_LAYOUT_ENV, "time_major")
    assert resolve_seq_layout() == "time_major"
    # explicit argument wins over the env
    assert resolve_seq_layout("legacy") == "legacy"
    with pytest.raises(ValueError, match="GORDO_SEQ_LAYOUT"):
        resolve_seq_layout("columnar")


def test_resolve_seq_kernel_mode(monkeypatch):
    monkeypatch.delenv(seq_scan.SEQ_KERNEL_ENV, raising=False)
    # auto off-TPU is the jnp step (a pure function of the backend)
    assert resolve_seq_kernel_mode() == "jnp"
    assert resolve_seq_kernel_mode("interpret") == "interpret"
    assert resolve_seq_kernel_mode("pallas") == "pallas"
    monkeypatch.setenv(seq_scan.SEQ_KERNEL_ENV, "interpret")
    assert resolve_seq_kernel_mode() == "interpret"
    assert resolve_seq_kernel_mode("jnp") == "jnp"
    with pytest.raises(ValueError, match="GORDO_SEQ_KERNEL"):
        resolve_seq_kernel_mode("fused")


def test_supports_time_major():
    from gordo_components_tpu.models.factories.conv import conv1d_autoencoder

    assert supports_time_major(lstm_symmetric(3, dims=(4,)))
    # conv has no recurrence — its fast path is the matmul impl, and the
    # time-major branch must never claim it
    assert not supports_time_major(conv1d_autoencoder(3, channels=(4,)))


# ------------------------------------------------------------------ #
# Forward parity: time-major vs vmap(module.apply)
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("dims", [(5,), (6, 4)])
def test_time_major_forward_matches_vmap_apply(dims):
    module, params, xb = _stacked_module(M=3, dims=dims)
    want = jax.vmap(lambda p, x: module.apply(p, x))(params, xb)
    got = lstm_time_major_forward(module, params, xb, kernel="jnp")
    # the same dot products as the flax cell's. dims=(5,) (two layers) comes
    # out bitwise; in the four layers of (6, 4) the input projections,
    # hoisted out of the scan as one wide einsum a layer, accumulate in
    # another order than the cell's matmul a step: 4e-9 absolute here
    if dims == (5,):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7
        )


def test_extracted_weights_have_gate_order_shapes():
    module, params, _ = _stacked_module(M=2, f=3, dims=(5,))
    # lstm_symmetric mirrors dims: (5,) -> layers of width 5, 5
    layers, (Wd, bd) = extract_lstm_weights(module, params)
    assert len(layers) == 2
    (Wi0, Wh0, b0), (Wi1, Wh1, b1) = layers
    assert Wi0.shape == (2, 3, 20) and Wh0.shape == (2, 5, 20)
    assert Wi1.shape == (2, 5, 20) and Wh1.shape == (2, 5, 20)
    assert b0.shape == b1.shape == (2, 20)
    assert Wd.shape == (2, 5, 3) and bd.shape == (2, 3)


# ------------------------------------------------------------------ #
# Fused recurrent-step kernel: interpret mode vs jnp (CI parity vehicle)
# ------------------------------------------------------------------ #


def test_fused_step_interpret_matches_jnp_aligned():
    # lane-aligned shapes: the kernel runs without padding
    B, M, H = 8, 2, seq_scan.LANE
    rng = np.random.RandomState(3)
    xz = jnp.asarray(rng.randn(B, M, 4 * H).astype("float32"))
    h = jnp.asarray(rng.randn(B, M, H).astype("float32"))
    c = jnp.asarray(rng.randn(B, M, H).astype("float32"))
    Wh = jnp.asarray(rng.randn(M, H, 4 * H).astype("float32") * 0.1)
    b = jnp.asarray(rng.randn(M, 4 * H).astype("float32"))
    want_c, want_h = lstm_step_jnp(xz, h, c, Wh, b)
    # the kernel is member-major (M, B, ·); the jnp step keeps members
    # second-last — same math, swapped axes
    mm = lambda a: jnp.swapaxes(a, 0, 1)
    got_c, got_h = fused_lstm_step(mm(xz), mm(h), mm(c), Wh, b, interpret=True)
    np.testing.assert_allclose(
        np.asarray(mm(got_c)), np.asarray(want_c), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(mm(got_h)), np.asarray(want_h), rtol=1e-6, atol=1e-6
    )


def test_gate_lane_padding_is_self_contained():
    """Padded lanes must contribute exactly zero to real lanes across
    steps: pad_gate_lanes zeroes the padded Wh ROWS, so the 0.5-sigmoid
    garbage a padded lane carries never reaches a real gate."""
    B, M, H = 2, 2, 5
    Hp = seq_scan.LANE
    rng = np.random.RandomState(4)
    Wh = jnp.asarray(rng.randn(M, H, 4 * H).astype("float32") * 0.2)
    b = jnp.asarray(rng.randn(M, 4 * H).astype("float32"))
    Whp, bp = pad_gate_lanes(Wh, b, H, Hp)
    assert Whp.shape == (M, Hp, 4 * Hp) and bp.shape == (M, 4 * Hp)
    xz = rng.randn(B, M, 4 * H).astype("float32")
    xzp = np.concatenate(
        [
            np.pad(p, ((0, 0), (0, 0), (0, Hp - H)))
            for p in np.split(xz, 4, axis=-1)
        ],
        axis=-1,
    )
    h = jnp.asarray(rng.randn(B, M, H).astype("float32"))
    c = jnp.asarray(rng.randn(B, M, H).astype("float32"))
    hp = jnp.pad(h, ((0, 0), (0, 0), (0, Hp - H)))
    cp = jnp.pad(c, ((0, 0), (0, 0), (0, Hp - H)))
    # two chained steps so first-step padded-lane garbage would surface
    c1, h1 = lstm_step_jnp(jnp.asarray(xz), h, c, Wh, b)
    c2, h2 = lstm_step_jnp(jnp.asarray(xz), h1, c1, Wh, b)
    c1p, h1p = lstm_step_jnp(jnp.asarray(xzp), hp, cp, Whp, bp)
    c2p, h2p = lstm_step_jnp(jnp.asarray(xzp), h1p, c1p, Whp, bp)
    np.testing.assert_allclose(
        np.asarray(h2p)[..., :H], np.asarray(h2), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(c2p)[..., :H], np.asarray(c2), rtol=1e-6, atol=1e-6
    )


def test_time_major_forward_interpret_kernel_band():
    """Full forward through the interpret-mode kernel (unaligned H and B
    exercise gate-aligned lane padding + sublane padding) stays within
    the documented fp32 band of the jnp path."""
    module, params, xb = _stacked_module(M=2, f=3, dims=(5,), B=3, T=6)
    want = lstm_time_major_forward(module, params, xb, kernel="jnp")
    got = lstm_time_major_forward(module, params, xb, kernel="interpret")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )


# ------------------------------------------------------------------ #
# Gang epoch parity: time-major vs vmapped legacy program
# ------------------------------------------------------------------ #


def test_gang_epoch_matches_vmapped_legacy_epoch():
    """make_seq_gang_epoch must replay the legacy per-member epoch
    byte-for-byte on the rng/shuffle plan and within fp32 rounding on
    the numerics — including members whose masks are partly padding."""
    rows, f, lb, bs, M = 41, 3, 6, 8, 3
    module = lstm_symmetric(f, dims=(5,))
    optimizer = train_core.make_optimizer("adam", 1e-3)

    n_pad = 48  # 6 batches
    rows_pad = n_pad + lb - 1
    rng = np.random.RandomState(0)
    X = np.zeros((M, rows_pad, f), np.float32)
    mask = np.zeros((M, n_pad), np.float32)
    for m in range(M):
        r = rows - 4 * m  # heterogeneous real lengths
        X[m, :r] = rng.rand(r, f)
        mask[m, : r - lb + 1] = 1.0
    X, mask = jnp.asarray(X), jnp.asarray(mask)

    s_init, s_epoch = train_core.make_seq_train_fns(module, optimizer, bs, lb, 0)
    w0 = jnp.zeros((lb, f), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(11), M)
    states = jax.vmap(lambda k: s_init(k, w0))(keys)

    legacy_states, legacy_loss = jax.jit(
        jax.vmap(lambda st, x, mk: s_epoch(st, x, x, mk))
    )(states, X, mask)

    gang = train_core.make_seq_gang_epoch(module, optimizer, bs, lb, 0)
    gang_states, gang_loss = jax.jit(gang)(states, X, mask)

    # identical rng streams: the next epoch's plan starts from the same key
    np.testing.assert_array_equal(
        np.asarray(legacy_states.rng), np.asarray(gang_states.rng)
    )
    np.testing.assert_allclose(
        np.asarray(gang_loss), np.asarray(legacy_loss), rtol=1e-5, atol=1e-7
    )
    for a, b in zip(
        jax.tree.leaves(legacy_states.params),
        jax.tree.leaves(gang_states.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )


# ------------------------------------------------------------------ #
# Fleet end-to-end: legacy vs time-major layout
# ------------------------------------------------------------------ #


def _fit(members, monkeypatch, layout, **kw):
    monkeypatch.setenv(seq_scan.SEQ_LAYOUT_ENV, layout)
    config = dict(
        model_type="LSTMAutoEncoder", kind="lstm_symmetric", dims=(6,),
        lookback_window=LOOKBACK, epochs=1, batch_size=32, seed=0,
    )
    config.update(kw)
    trainer = FleetTrainer(**config)
    return trainer.fit(members), trainer


@pytest.mark.seqperf
def test_fleet_time_major_matches_legacy(monkeypatch):
    members = _seq_members(3)
    legacy, t_leg = _fit(members, monkeypatch, "legacy")
    tm, t_tm = _fit(members, monkeypatch, "time_major")
    assert all(
        b["layout"] == "legacy" for b in t_leg.last_stats["buckets"]
    )
    assert all(
        b["layout"] == "time_major" for b in t_tm.last_stats["buckets"]
    )
    for name in members:
        np.testing.assert_allclose(
            legacy[name].history["loss"], tm[name].history["loss"],
            rtol=1e-5, atol=1e-7,
        )
        for a, b in zip(
            jax.tree.leaves(legacy[name].params),
            jax.tree.leaves(tm[name].params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )
        np.testing.assert_allclose(
            legacy[name].feature_thresholds, tm[name].feature_thresholds,
            rtol=1e-4, atol=1e-6,
        )


@pytest.mark.seqperf
@pytest.mark.slow
def test_fleet_time_major_heterogeneous_multibucket_8shard(monkeypatch):
    """Two feature widths (two buckets) and 8 members on the 8-device
    test mesh: the time-major program trains sharded over the models
    axis and still matches the legacy layout within the documented
    band."""
    wide = {
        f"w{i}": v
        for i, v in enumerate(_seq_members(3, f=5, seed=9).values())
    }
    members = dict(_seq_members(8, rows=64, f=3), **wide)
    legacy, t_leg = _fit(members, monkeypatch, "legacy")
    tm, t_tm = _fit(members, monkeypatch, "time_major")
    assert len(t_tm.last_stats["buckets"]) >= 2
    assert all(b["layout"] == "time_major" for b in t_tm.last_stats["buckets"])
    for name in members:
        for a, b in zip(
            jax.tree.leaves(legacy[name].params),
            jax.tree.leaves(tm[name].params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )
        assert legacy[name].total_threshold == pytest.approx(
            tm[name].total_threshold, rel=1e-4, abs=1e-6
        )


@pytest.mark.seqperf
@pytest.mark.slow
@pytest.mark.perfguard
def test_perfguard_time_major_no_slower_than_legacy():
    """No-slower guard: one compiled
    epoch, min-of-3 walltime. On this CPU container the honest claim is
    structural (time-major must not be a pessimization here while it
    wins on TPU — the >=2x assertion is TPU/multi-core-gated per the
    PR 13/14 rules), so the band is generous."""
    import time

    rows_pad, f, lb, bs, M = 135, 4, 8, 32, 16
    module = lstm_symmetric(f, dims=(8,))
    optimizer = train_core.make_optimizer("adam", 1e-3)
    rng = np.random.RandomState(2)
    X = jnp.asarray(rng.rand(M, rows_pad, f).astype("float32"))
    mask = jnp.ones((M, rows_pad - lb + 1), jnp.float32)
    w0 = jnp.zeros((lb, f), jnp.float32)
    s_init, s_epoch = train_core.make_seq_train_fns(module, optimizer, bs, lb, 0)
    states = jax.vmap(lambda k: s_init(k, w0))(
        jax.random.split(jax.random.PRNGKey(0), M)
    )
    legacy = jax.jit(jax.vmap(lambda st, x, mk: s_epoch(st, x, x, mk)))
    gang = jax.jit(train_core.make_seq_gang_epoch(module, optimizer, bs, lb, 0))

    def min_of_3(fn, *a):
        jax.block_until_ready(fn(*a))  # compile outside the clock
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            best = min(best, time.perf_counter() - t0)
        return best

    t_legacy = min_of_3(legacy, states, X, mask)
    t_tm = min_of_3(gang, states, X, mask)
    assert t_tm <= max(t_legacy * 3.0, t_legacy + 0.05), (
        f"time-major epoch {t_tm:.4f}s vs legacy {t_legacy:.4f}s"
    )


# ------------------------------------------------------------------ #
# Bank scoring: time-major path parity + provenance
# ------------------------------------------------------------------ #


@pytest.fixture(scope="module")
def lstm_detectors():
    members = _seq_members(2)
    models = FleetTrainer(
        model_type="LSTMAutoEncoder", kind="lstm_symmetric", dims=(6,),
        lookback_window=LOOKBACK, epochs=1, batch_size=32, seed=0,
    ).fit(members)
    return {n: m.to_estimator() for n, m in models.items()}, members


def _bank_scores(dets, X, monkeypatch, layout, kernel=None):
    from gordo_components_tpu.server.bank import ModelBank

    monkeypatch.setenv(seq_scan.SEQ_LAYOUT_ENV, layout)
    if kernel is None:
        monkeypatch.delenv(seq_scan.SEQ_KERNEL_ENV, raising=False)
    else:
        monkeypatch.setenv(seq_scan.SEQ_KERNEL_ENV, kernel)
    bank = ModelBank.from_models(dets)
    return {n: bank.score(n, X) for n in dets}, bank


@pytest.mark.seqperf
@pytest.mark.parametrize("kernel", [None, "interpret"])
def test_bank_time_major_scoring_parity(lstm_detectors, monkeypatch, kernel):
    dets, members = lstm_detectors
    X = members["m0"]
    legacy, bank_leg = _bank_scores(dets, X, monkeypatch, "legacy")
    tm, bank_tm = _bank_scores(dets, X, monkeypatch, "time_major", kernel)
    for row in bank_tm.flops_stats().values():
        assert row["seq_layout"] == "time_major"
        assert row["seq_kernel"] == (kernel or "jnp")
        assert f":time_major(T={LOOKBACK})" in row["flops_method"]
    for row in bank_leg.flops_stats().values():
        assert row["seq_layout"] == "legacy"
    for name in dets:
        for field, a in vars(legacy[name]).items():
            b = getattr(tm[name], field)
            if isinstance(a, np.ndarray):
                np.testing.assert_allclose(
                    b, a, rtol=1e-4, atol=1e-5, err_msg=f"{name}.{field}"
                )


# ------------------------------------------------------------------ #
# Width autotuning (GORDO_FLEET_WIDTH)
# ------------------------------------------------------------------ #


def test_resolve_fleet_width_parsing(monkeypatch):
    monkeypatch.delenv(autotune.FLEET_WIDTH_ENV, raising=False)
    assert resolve_fleet_width("LSTMAutoEncoder:lstm_symmetric") is None
    monkeypatch.setenv(autotune.FLEET_WIDTH_ENV, "off")
    assert resolve_fleet_width("x") is None
    monkeypatch.setenv(autotune.FLEET_WIDTH_ENV, "4096")
    assert resolve_fleet_width("x") == 4096
    monkeypatch.setenv(autotune.FLEET_WIDTH_ENV, "0")
    with pytest.raises(ValueError, match=">= 1"):
        resolve_fleet_width("x")
    monkeypatch.setenv(autotune.FLEET_WIDTH_ENV, "wide")
    with pytest.raises(ValueError, match="GORDO_FLEET_WIDTH"):
        resolve_fleet_width("x")


def test_autotune_sweep_runs_once_and_persists(monkeypatch, tmp_path):
    """auto mode: the calibration sweep runs ONCE per (arch, device),
    persists to the JSON table, and later resolutions — in-process and
    from a fresh process-cache — read the stored width instead of
    re-sweeping."""
    cache = tmp_path / "fleet_width.json"
    monkeypatch.setenv(autotune.FLEET_WIDTH_ENV, "auto")
    monkeypatch.setenv(autotune.FLEET_WIDTH_CACHE_ENV, str(cache))
    calls = []

    def sweep(arch):
        calls.append(arch)
        return 2048, {"2048": 1.0}

    arch = "TestArch:seqperf_round_trip"
    assert resolve_fleet_width(arch, sweep=sweep) == 2048
    assert calls == [arch]
    tab = json.loads(cache.read_text())
    (key,) = [k for k in tab if k.startswith(f"{arch}|")]
    assert tab[key]["width"] == 2048 and tab[key]["measured"] == {"2048": 1.0}
    # in-process cache: no re-sweep
    assert resolve_fleet_width(arch, sweep=sweep) == 2048
    assert calls == [arch]
    # fresh process (cleared process cache): the persisted table answers,
    # a sweep that would fail is never invoked
    autotune._process_cache.pop(key, None)

    def explode(arch):  # pragma: no cover - must not run
        raise AssertionError("sweep re-ran despite persisted width")

    assert resolve_fleet_width(arch, sweep=explode) == 2048


def test_autotune_flat_curve_defaults_to_knee():
    """calibrate_width's tiebreak: a flat efficiency curve is no
    evidence against the measured TPU knee, so it returns 4096."""
    eff = {w: 1.0 for w in autotune.SWEEP_WIDTHS}
    good = [w for w in autotune.SWEEP_WIDTHS if eff[w] >= 0.9 * max(eff.values())]
    width = (
        autotune.KNEE_DEFAULT
        if set(good) >= set(autotune.SWEEP_WIDTHS)
        else min(good)
    )
    assert width == autotune.KNEE_DEFAULT


@pytest.mark.seqperf
def test_width_cap_splits_training_dispatches(monkeypatch):
    """GORDO_FLEET_WIDTH=4 over 9 same-shape members: three near-equal
    dispatches of <=4 members each, every member still trained and
    servable. The cap re-buckets, so members get fresh init rng per
    chunk — the knob trades bitwise reproducibility vs uncapped for
    dispatch width (see docs/operations.md)."""
    monkeypatch.setenv(autotune.FLEET_WIDTH_ENV, "4")
    rng = np.random.RandomState(5)
    members = {f"d{i}": rng.rand(48, 3).astype("float32") for i in range(9)}
    trainer = FleetTrainer(epochs=1, batch_size=16, seed=0)
    models = trainer.fit(members)
    assert set(models) == set(members)
    for m in models.values():
        assert np.isfinite(m.history["loss"]).all()
    assert trainer.last_stats["width_cap"] == 4
    buckets = trainer.last_stats["buckets"]
    assert len(buckets) == 3
    # ceil(9/4)=3 chunks, balanced to near-equal widths (never over cap)
    assert sorted(b["n_members"] for b in buckets) == [3, 3, 3]
    assert all(b["n_members"] <= 4 for b in buckets)


# ------------------------------------------------------------------ #
# Cross-arch gang scheduling (builder/fleet_build.py)
# ------------------------------------------------------------------ #


def test_resolve_gang_width(monkeypatch):
    from gordo_components_tpu.builder.fleet_build import (
        GANG_WIDTH_ENV,
        resolve_gang_width,
    )

    monkeypatch.delenv(GANG_WIDTH_ENV, raising=False)
    # the test mesh has 8 virtual devices, so auto schedules up to 4
    # small groups concurrently (clamped to the group count)
    assert resolve_gang_width(1) == 1
    assert resolve_gang_width(3) == 3
    assert resolve_gang_width(10) == 4
    monkeypatch.setenv(GANG_WIDTH_ENV, "2")
    assert resolve_gang_width(5) == 2
    assert resolve_gang_width(1) == 1  # clamped to the group count
    monkeypatch.setenv(GANG_WIDTH_ENV, "0")
    with pytest.raises(ValueError, match="GORDO_GANG_WIDTH"):
        resolve_gang_width(2)


@pytest.mark.seqperf
@pytest.mark.slow
def test_gang_scheduled_build_matches_serial(monkeypatch, tmp_path):
    """Two small heterogeneous groups (dense + LSTM) built with the gang
    scheduler (width 2) must produce the SAME artifacts as a serial
    build: scheduling changes dispatch overlap, never numerics."""
    from gordo_components_tpu import serializer
    from gordo_components_tpu.builder.fleet_build import GANG_WIDTH_ENV, build_fleet
    from gordo_components_tpu.workflow.config import Machine

    def machines():
        dataset = {
            "type": "RandomDataset",
            "train_start_date": "2020-01-01T00:00:00Z",
            "train_end_date": "2020-01-02T00:00:00Z",
            "tag_list": ["x", "y", "z"],
        }

        def pipeline(path, kw):
            return {
                "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
                    "base_estimator": {
                        "sklearn.pipeline.Pipeline": {
                            "steps": [
                                "sklearn.preprocessing.MinMaxScaler",
                                {path: kw},
                            ]
                        }
                    }
                }
            }

        return [
            Machine(name="dense", dataset=dict(dataset), model=pipeline(
                "gordo_components_tpu.models.AutoEncoder",
                {"epochs": 1, "batch_size": 64},
            )),
            Machine(name="lstm", dataset=dict(dataset), model=pipeline(
                "gordo_components_tpu.models.LSTMAutoEncoder",
                {"lookback_window": 8, "epochs": 1, "batch_size": 32,
                 "kind": "lstm_symmetric", "dims": [6]},
            )),
        ]

    monkeypatch.setenv(GANG_WIDTH_ENV, "1")
    serial = build_fleet(machines(), str(tmp_path / "serial"))
    monkeypatch.setenv(GANG_WIDTH_ENV, "2")
    ganged = build_fleet(machines(), str(tmp_path / "ganged"))
    assert set(serial) == set(ganged) == {"dense", "lstm"}
    for name in serial:
        a = serializer.load(serial[name])
        b = serializer.load(ganged[name])
        for la, lb in zip(
            jax.tree.leaves(a.base_estimator.steps[-1][1].params_),
            jax.tree.leaves(b.base_estimator.steps[-1][1].params_),
        ):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        md = serializer.load_metadata(ganged[name])
        assert md["model"].get("fleet_trained"), name


# ------------------------------------------------------------------ #
# Conv impl env knob (satellite 1)
# ------------------------------------------------------------------ #


def test_conv_impl_env_flips_default(monkeypatch):
    from gordo_components_tpu.models.factories.conv import (
        CONV_IMPL_ENV,
        conv1d_autoencoder,
    )

    monkeypatch.delenv(CONV_IMPL_ENV, raising=False)
    assert conv1d_autoencoder(3).conv_impl == "matmul"
    monkeypatch.setenv(CONV_IMPL_ENV, "lax")
    assert conv1d_autoencoder(3).conv_impl == "lax"
    # an explicit kwarg (or a pickled estimator's pinned value) wins
    assert conv1d_autoencoder(3, conv_impl="matmul").conv_impl == "matmul"
    # a typo'd env value must fail loudly at first trace, not silently
    # pick a perf profile (numerics are identical between impls)
    monkeypatch.setenv(CONV_IMPL_ENV, "im2col")
    bad = conv1d_autoencoder(3, channels=(4,), kernel_size=3)
    with pytest.raises(ValueError, match="conv_impl"):
        bad.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 3), jnp.float32))
