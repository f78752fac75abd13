"""The control of ``correct``: the reference computed in bfloat16, the
nearest precision below what the configurations state, put in the
program's place, has to fail the cell's limits (read on the chip at full
size by ``control.py``; here at a size a test can hold)."""

import jax.numpy as jnp
import numpy as np
import pytest

import families
from harness import check, refit, reference, serve, weights
from queued import cell as make_cell


@pytest.mark.parametrize("name", ["dense300.live", "lstm300.backfill"])
def test_bf16_control_fails_a_serve_cell(name):
    cell = make_cell(name, overrides={"config": {"tags_per_machine": 64}})
    rows = 64
    samples = [{"member": m, "body": m} for m in range(3)]

    def answers(**how):
        return [
            reference.anomaly(
                cell.config, weights.member_weights(cell.config, 9, s["member"]),
                weights.request_body(cell.config, 9, s["body"], rows), **how,
            )
            for s in samples
        ]

    sound = serve.compare_answers(cell.config, 9, rows, samples, answers())
    control = serve.compare_answers(cell.config, 9, rows, samples, answers(dtype="bfloat16"))
    assert check.is_correct(check.verdict(sound, cell.limits))
    assert not check.is_correct(check.verdict(control, cell.limits))


def _refit_readings():
    cell = make_cell("dense300.refit", overrides={
        "config": {"tags_per_machine": 32, "epochs": 4, "batch_size": 20}})
    config = cell.config
    X = np.stack([weights.member_train_data(config, 3, i, 90) for i in range(3)])
    run = lambda **how: families.load("dense", "refit").refit_sample(config, 3, 8, [0, 3, 5], X, 120, **how)
    want = run()
    read = lambda **how: check.verdict(
        refit._compare(config, refit.reference_as_program(run(**how)), want, X), cell.limits
    )
    return read


def test_planted_faults_fail_the_refit_cell():
    read = _refit_readings()
    assert check.is_correct(read())
    for fault in ("half_batch", "no_update"):
        assert not check.is_correct(read(fault=fault)), fault


def test_bf16_control_fails_the_refit_cell():
    """The whole step in bfloat16: matmuls, parameters and Adam's moments."""
    verdict = _refit_readings()(dtype="bfloat16", state_dtype="bfloat16")
    assert not check.is_correct(verdict)
    assert not verdict["weight_change_gap"]["ok"]


def test_bf16_matmuls_alone_read_like_the_program():
    """Why the control keeps its state in bfloat16 too: the program's float32
    matmuls already round their operands to bfloat16 on a TPU, so a reference
    that only computes in bfloat16 is within the limits (PERF.md section 2)."""
    assert check.is_correct(_refit_readings()(dtype="bfloat16"))


# ---- a sound program that tiles its matmuls differently has to pass

def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32))


def _tiled_matmul(a, b, chunk):
    """What a TPU's default float32 matmul computes (operands rounded to
    bfloat16, float32 accumulation), accumulated ``chunk`` columns at a time."""
    a, b = _bf16(a), _bf16(b)
    out = np.zeros((a.shape[0], b.shape[1]), np.float32)
    for s in range(0, a.shape[1], chunk):
        out = (out + a[:, s : s + chunk] @ b[s : s + chunk]).astype(np.float32)
    return out


def _retiled_forward(config, w, xs, chunk):
    mm = lambda a, b: _tiled_matmul(a, b, chunk)
    n_layers = 2 * int(config["encoding_layers"])
    if config["family"] == "dense":
        h = xs
        for k in range(n_layers + 1):
            h = mm(h, w[f"w{k}"]) + w[f"b{k}"]
            h = np.tanh(h).astype(np.float32) if k < n_layers else h
        return h, xs
    L = int(config["lookback_window"])
    sig = lambda z: (1 / (1 + np.exp(-z))).astype(np.float32)
    seq = xs[np.arange(xs.shape[0] - L + 1)[:, None] + np.arange(L)[None, :]]
    for k in range(n_layers):
        H = w[f"wh{k}"].shape[0]
        h, c, hs = np.zeros((seq.shape[0], H), np.float32), np.zeros((seq.shape[0], H), np.float32), []
        for t in range(L):
            z = mm(seq[:, t], w[f"wi{k}"]) + mm(h, w[f"wh{k}"]) + w[f"b{k}"]
            i, f, o = sig(z[:, :H]), sig(z[:, H : 2 * H]), sig(z[:, 3 * H :])
            c = f * c + i * np.tanh(z[:, 2 * H : 3 * H])
            h = (o * np.tanh(c)).astype(np.float32)
            hs.append(h)
        seq = np.tanh(np.stack(hs, 1)).astype(np.float32)
    return mm(seq[:, -1], w["wd"]) + w["bd"], xs[L - 1 :]


@pytest.mark.parametrize("name, rows", [("dense300.live", 256), ("lstm300.backfill", 76)])
def test_a_retiled_program_stays_within_the_serve_limits(name, rows):
    """The limits sit between two readings at the cells' real widths: the
    same arithmetic accumulated in another order (a sound later PR) passes,
    and PERF.md section 2 has the bfloat16 control's readings above them."""
    cell = make_cell(name)
    w = weights.member_weights(cell.config, 12345, 7)
    X = weights.request_body(cell.config, 12345, 0, rows)
    xs = ((X - w["in_shift"]) * w["in_scale"]).astype(np.float32)
    (a, target), (b, _) = (_retiled_forward(cell.config, w, xs, chunk) for chunk in (128, 256))
    gaps = {
        "input_echo_gap": 0.0,
        "output_gap": check.rel_l2_gap(a, b),
        "score_gap": check.rel_l2_gap(np.abs(target - a), np.abs(target - b)),
    }
    assert 0.0 < gaps["output_gap"], "the two tilings must differ for this to test anything"
    assert check.is_correct(check.verdict(gaps, cell.limits)), gaps
