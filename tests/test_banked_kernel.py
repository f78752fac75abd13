"""Banked fused-scoring kernel parity harness (ISSUE 6 tentpole).

CI runs on CPU, so the batched (member, row-tile) Pallas kernel is
exercised in interpreter mode against the batched jnp reference — the
same kernel logic, scalar-prefetch scaler gathers, lane masking, and
tile padding as the compiled TPU path, like the seed per-model kernel's
suite (tests/test_pallas.py).

Error budget (documented in docs/operations.md "Precision & capacity
tuning"): at fp32 the elementwise outputs (``diff``, ``scaled``) are
BITWISE equal to the jnp path — they never cross a reduction — while
the two row norms reduce over the 128-lane padded feature axis and may
differ from the unpadded jnp sum's tree order by a few ULP (observed
≤2 ULP; asserted here ≤4 ULP via rtol=1e-6).
"""

import numpy as np
import pytest

from gordo_components_tpu.ops.pallas_score import (
    ROW_TILE,
    _jnp_banked_score,
    banked_anomaly_score,
    resolve_bank_kernel_mode,
)

# 4-ULP-at-fp32 band for the reduction outputs (see module docstring)
NORM_RTOL = 1e-6
NORM_ATOL = 1e-6


def _case(B, T, F, M, seed=0):
    rng = np.random.RandomState(seed)
    target = rng.randn(B, T, F).astype("float32")
    output = (target + 0.1 * rng.randn(B, T, F)).astype("float32")
    shift_bank = (rng.randn(M, F) * 0.01).astype("float32")
    scale_bank = (1.0 + rng.rand(M, F)).astype("float32")
    idx = rng.randint(0, M, size=B).astype("int32")
    return target, output, shift_bank, scale_bank, idx


def _assert_banked_parity(got, want):
    for g, w, name in zip(got[:2], want[:2], ["diff", "scaled"]):
        assert g.shape == w.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)
    for g, w, name in zip(got[2:], want[2:], ["tot_u", "tot_s"]):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=NORM_RTOL, atol=NORM_ATOL,
            err_msg=name,
        )


@pytest.mark.parametrize(
    "B,T,F,M",
    [
        (4, 33, 10, 7),  # the default sensor width, odd rows
        (1, 7, 3, 1),  # tiny everything, heavy padding
        (2, ROW_TILE, 128, 3),  # exactly one tile, no padding
        (3, ROW_TILE + 5, 130, 5),  # spills into second row tile + lane tile
        (8, 16, 257, 16),  # three lane tiles, every member distinct
    ],
)
def test_banked_kernel_matches_reference(B, T, F, M):
    args = _case(B, T, F, M)
    want = _jnp_banked_score(*args)
    got = banked_anomaly_score(*args, mode="interpret")
    _assert_banked_parity(got, want)


@pytest.mark.perfguard
def test_banked_kernel_parity_sweep():
    """The perf-guard lane's parity leg: a denser shape sweep than the
    fast tier-1 cases above, still interpreter-mode on CPU."""
    for seed, (B, T, F, M) in enumerate(
        [(2, 12, 5, 4), (5, 64, 24, 9), (1, 130, 10, 2), (7, 40, 50, 7),
         (4, 256, 12, 33)]
    ):
        args = _case(B, T, F, M, seed=seed)
        _assert_banked_parity(
            banked_anomaly_score(*args, mode="interpret"),
            _jnp_banked_score(*args),
        )


def test_banked_gather_selects_the_right_member():
    """Wildly different per-member scalers: a wrong scalar-prefetch
    gather would be off by orders of magnitude, not ULPs."""
    B, T, F, M = 6, 9, 4, 6
    rng = np.random.RandomState(42)
    target = rng.randn(B, T, F).astype("float32")
    output = (target + rng.randn(B, T, F)).astype("float32")
    # member m scales by 10^m: any index mixup is unmissable
    scale_bank = np.stack(
        [np.full(F, 10.0**m, np.float32) for m in range(M)]
    )
    shift_bank = np.zeros((M, F), np.float32)
    idx = np.asarray([5, 0, 3, 1, 4, 2], np.int32)  # a permutation
    got = banked_anomaly_score(
        target, output, shift_bank, scale_bank, idx, mode="interpret"
    )
    want = _jnp_banked_score(target, output, shift_bank, scale_bank, idx)
    _assert_banked_parity(got, want)
    # and each batch slot really saw ITS member's scale
    diff = np.abs(target - output)
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(got[1][b]), diff[b] * 10.0 ** idx[b], rtol=1e-5
        )


def test_banked_padded_lanes_do_not_leak_into_norms():
    """Nonzero shift on padded feature lanes must not perturb totals
    (the in-kernel mask is what keeps the affine shift out of padding)."""
    target, output, shift_bank, scale_bank, idx = _case(3, 16, 5, 4, seed=3)
    shift_bank = shift_bank + 100.0
    want = _jnp_banked_score(target, output, shift_bank, scale_bank, idx)
    got = banked_anomaly_score(
        target, output, shift_bank, scale_bank, idx, mode="interpret"
    )
    np.testing.assert_allclose(
        np.asarray(got[3]), np.asarray(want[3]), rtol=1e-5
    )


def test_resolve_bank_kernel_mode(monkeypatch):
    monkeypatch.delenv("GORDO_BANK_KERNEL", raising=False)
    # auto on this CPU rig resolves to the jnp path
    assert resolve_bank_kernel_mode() == "jnp"
    assert resolve_bank_kernel_mode("jnp") == "jnp"
    assert resolve_bank_kernel_mode("interpret") == "interpret"
    assert resolve_bank_kernel_mode("pallas") == "pallas"
    monkeypatch.setenv("GORDO_BANK_KERNEL", "interpret")
    assert resolve_bank_kernel_mode() == "interpret"
    # explicit argument wins over the env
    assert resolve_bank_kernel_mode("jnp") == "jnp"
    with pytest.raises(ValueError, match="GORDO_BANK_KERNEL"):
        resolve_bank_kernel_mode("fused")
    # an unresolved mode must not silently fall through inside a traced
    # program either
    args = _case(1, 4, 2, 1)
    with pytest.raises(ValueError, match="resolved"):
        banked_anomaly_score(*args, mode="auto")


@pytest.fixture(scope="module")
def fitted():
    from gordo_components_tpu.models import AutoEncoder, DiffBasedAnomalyDetector

    X = np.random.RandomState(0).rand(120, 4).astype("float32")
    det = DiffBasedAnomalyDetector(
        base_estimator=AutoEncoder(epochs=1, batch_size=64)
    )
    det.fit(X)
    return det, X


def test_bank_dispatches_kernel_end_to_end(fitted):
    """The bank's compiled bucket program with the kernel in interpreter
    mode vs the default jnp program: same fp32 parity contract as the
    raw kernel, through the real ``score_many`` path (chunking, arena,
    reassembly and all)."""
    from gordo_components_tpu.server.bank import ModelBank

    det, X = fitted
    models = {"m": det}
    requests = [("m", X[:37], None), ("m", X[:21], None)]
    jnp_bank = ModelBank.from_models(models, registry=False, bank_kernel="jnp")
    kern_bank = ModelBank.from_models(
        models, registry=False, bank_kernel="interpret"
    )
    assert jnp_bank.kernel_mode == "jnp"
    assert kern_bank.kernel_mode == "interpret"
    want = jnp_bank.score_many(requests)
    got = kern_bank.score_many(requests)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.diff, w.diff)
        np.testing.assert_array_equal(g.scaled, w.scaled)
        np.testing.assert_array_equal(g.model_output, w.model_output)
        np.testing.assert_allclose(
            g.total_scaled, w.total_scaled, rtol=NORM_RTOL, atol=NORM_ATOL
        )
        np.testing.assert_allclose(
            g.total_unscaled, w.total_unscaled, rtol=NORM_RTOL, atol=NORM_ATOL
        )


# ------------------------------------------------------------------ #
# no silent degrade: `auto` is a pure function of the backend, and a
# kernel that cannot compile is an error that reaches the caller
# ------------------------------------------------------------------ #


def test_auto_on_tpu_is_pallas_without_compiling(monkeypatch):
    """With the backend reporting ``tpu``, ``auto`` resolves to the
    compiled kernel by looking at the backend alone: nothing is
    probe-compiled, so nothing can quietly decide otherwise."""
    import jax

    from gordo_components_tpu.ops import pallas_score, seq_scan

    def no_compile(*a, **k):
        raise AssertionError("auto resolution must not run a kernel")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_score, "_pallas_banked_score", no_compile)
    monkeypatch.setattr(seq_scan, "fused_lstm_step", no_compile)
    monkeypatch.delenv("GORDO_BANK_KERNEL", raising=False)
    monkeypatch.delenv(seq_scan.SEQ_KERNEL_ENV, raising=False)
    assert resolve_bank_kernel_mode() == "pallas"
    assert seq_scan.resolve_seq_kernel_mode() == "pallas"
    assert seq_scan.resolve_seq_layout() == "time_major"


def test_auto_kernel_failure_reaches_the_caller(monkeypatch):
    """``fused_anomaly_score`` in auto mode on a TPU backend runs the
    compiled kernel and lets its failure out — no fall-back to jnp."""
    import jax

    from gordo_components_tpu.ops import pallas_score

    def refused(*a, **k):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_score, "_pallas_score", refused)
    x = np.ones((8, 4), "float32")
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        pallas_score.fused_anomaly_score(
            x, x, np.zeros(4, "float32"), np.ones(4, "float32")
        )


def test_bank_warmup_compile_failure_propagates(fitted):
    """A bucket program that cannot compile fails ``warmup`` (here: the
    compiled kernel has no lowering on the CPU backend) — it is not
    logged and swallowed."""
    from gordo_components_tpu.server.bank import ModelBank

    det, _ = fitted
    bank = ModelBank.from_models({"m": det}, registry=False, bank_kernel="pallas")
    assert bank.finalize_failures == {}  # finalize builds, warm-up compiles
    with pytest.raises(Exception):
        bank.warmup(rows=8)
