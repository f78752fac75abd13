"""Each cell end to end on the CPU at a tiny size: the driver, the child
load generator, the reference, the result line. And the faults ``correct``
has to catch, planted in the program underneath the same drive."""

import json
import time

import numpy as np
import pytest

from harness import refit, serve, spec
from queued import LISTED, QUEUED

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
DEVICE_SOURCED = {
    m["name"] for m in spec.load_benchmark()["per_layer"] if m["source"] == "device_trace"
}


def _drive(cell, traced, capsys, seed=2**31 + 17):
    driver = refit if cell.traffic["driver"] == "refit" else serve
    result = driver.run(cell, seed, 1.0, traced, time.time(), on_tpu=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(result))
    return result


CELLS = LISTED + sorted(QUEUED)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_cell_rehearsal(tiny_cell, capsys, name, traced):
    cell = tiny_cell(name)
    result = _drive(cell, traced, capsys)
    assert set(result) >= KEYS and list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    names = set(result["metrics"])
    if traced:
        # off the chip no device metric is printed, and no busy time
        assert not names & DEVICE_SOURCED
        assert "busy_s" not in result["device"] and "breakdown" not in result
        assert names <= {m["name"] for m in cell.per_layer} and names
    else:
        assert names == {m["name"] for m in cell.end_to_end}
    assert all(np.isfinite(m["value"]) for m in result["metrics"].values())


def _fresh_programs(monkeypatch):
    """The trainer caches compiled programs process-wide; a fault patched
    into what they trace needs them rebuilt."""
    from gordo_components_tpu.parallel import fleet

    for attr in ("_PROGRAM_CACHE", "_PROGRAMS"):
        cache = getattr(fleet, attr, None)
        if cache is not None:
            monkeypatch.setattr(fleet, attr, type(cache)())


def test_refit_fault_state_returned_unchanged(tiny_cell, capsys, monkeypatch):
    """A step that hands back the weights it was given."""
    from gordo_components_tpu.models import train_core

    _fresh_programs(monkeypatch)
    monkeypatch.setattr(train_core.optax, "apply_updates", lambda params, updates: params)
    result = _drive(tiny_cell("dense300.refit"), False, capsys)
    assert result["correct"] is False
    assert result["checks"]["weight_change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_refit_fault_half_of_every_batch_left_out(tiny_cell, capsys, monkeypatch):
    """Half of each batch dropped from the loss, the mean taken over the rest."""
    import jax.numpy as jnp

    from gordo_components_tpu.models import train_core

    _fresh_programs(monkeypatch)
    real = train_core.mse_loss

    def half(pred, target, mask=None):
        keep = (jnp.arange(pred.shape[0]) < pred.shape[0] // 2).astype(pred.dtype)
        return real(pred, target, keep if mask is None else mask * keep)

    monkeypatch.setattr(train_core, "mse_loss", half)
    result = _drive(tiny_cell("dense300.refit"), False, capsys)
    assert result["correct"] is False


@pytest.mark.parametrize("name", ["dense300.live", "lstm300.backfill"])
def test_serve_fault_answer_altered_where_it_is_produced(tiny_cell, capsys, monkeypatch, name):
    """One tag's scaled anomaly off by 2% in every answer the bank returns."""
    from gordo_components_tpu.server import bank

    real = bank.ScoreResult.to_arrays

    def altered(self):
        arrays = dict(real(self))
        scaled = np.array(arrays["tag-anomaly-scaled"])
        scaled[:, 0] *= 1.02
        arrays["tag-anomaly-scaled"] = scaled
        return arrays

    monkeypatch.setattr(bank.ScoreResult, "to_arrays", altered)
    result = _drive(tiny_cell(name), False, capsys)
    assert result["correct"] is False
    assert not result["checks"]["score_gap"]["value"] <= result["checks"]["score_gap"]["limit"]
