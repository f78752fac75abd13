"""A gang's rows staged in pieces (``parallel/fleet.py::stage_gang``): the
block and mask on the device are ``native.fleet_stack_pad``'s bit for bit
however many pieces carried them, the rule that picks the way reads bytes
alone, and a fit staged in pieces returns the one-piece fit's members.

The piece size is a module constant, patched small here (no option
exists): the CPU's test gangs are far under one piece."""

import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

from gordo_components_tpu.native import fleet_stack_pad
from gordo_components_tpu.observability.tracing import Tracer, use_trace
from gordo_components_tpu.parallel import FleetTrainer, fleet, fleet_mesh
from gordo_components_tpu.parallel.mesh import shard_model_axis

F = 5
BLOCK_ROWS = 48
SLOT_BYTES = 4 * BLOCK_ROWS * F


def _members(n, lo, hi, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.rand(rng.randint(lo, hi + 1), F).astype("float32") for _ in range(n)]


@pytest.fixture
def pieces_of(monkeypatch):
    """Staging in pieces of ``slots`` slots, whatever a member weighs."""

    def patch(slots, rows=BLOCK_ROWS):
        monkeypatch.setattr(fleet, "STAGING_PIECE_BYTES", slots * 4 * rows * F)
        monkeypatch.setattr(fleet, "STAGING_MEMBER_BYTES", 0)

    return patch


# name: (members, M, padded rows, devices, slots a piece, pieces expected)
WARMUP = 8 - 1 + fleet._target_offset_for("LSTMForecast")  # lookback 8, as _fit_bucket counts
GANGS = {
    "ragged": (_members(12, 20, BLOCK_ROWS), 12, BLOCK_ROWS, 1, 5, 3),
    "equal_rows": (_members(12, BLOCK_ROWS, BLOCK_ROWS), 12, BLOCK_ROWS, 1, 4, 3),
    "cyclic_dummies": (_members(5, 20, 40), 12, BLOCK_ROWS, 1, 5, 3),
    "sequence_warmup_rows": (_members(6, 24 + WARMUP, 40 + WARMUP), 6, 40 + WARMUP, 1, 2, 3),
    "mesh_of_4": (_members(13, 20, BLOCK_ROWS), 16, BLOCK_ROWS, 4, 3, 8),
    "mesh_of_4_a_piece_a_shard": (_members(13, 20, BLOCK_ROWS), 16, BLOCK_ROWS, 4, 9, 4),
    "one_piece": (_members(12, 20, BLOCK_ROWS), 12, BLOCK_ROWS, 1, None, 1),
    "one_piece_on_a_mesh": (_members(13, 20, BLOCK_ROWS), 16, BLOCK_ROWS, 4, None, 1),
}


@pytest.mark.parametrize("gang", sorted(GANGS))
def test_staged_block_and_mask_are_fleet_stack_pad_bitwise(gang, pieces_of):
    members, M, rows, n_devices, slots, n_pieces = GANGS[gang]
    if slots is not None:
        pieces_of(slots, rows)
    sharding = shard_model_axis(fleet_mesh(n_devices))
    want_x, want_mask = fleet_stack_pad(members, M, rows, F)
    X, mask, staging = fleet.stage_gang(members, M, rows, F, sharding)
    assert staging == {"pieces": n_pieces, "bytes": want_x.nbytes}
    assert X.sharding == sharding and X.dtype == np.float32
    assert mask.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(X), want_x)
    np.testing.assert_array_equal(mask, want_mask)
    # each shard's slots lie on the device the sharding gives them
    for shard in X.addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data), want_x[shard.index])


@pytest.mark.parametrize("gang", ["ragged", "equal_rows"])
def test_no_piece_shares_memory_with_anything_that_changes_later(gang, pieces_of):
    """The aliasing hazard: on the CPU backend a device array made from a
    host array can BE that array. Every piece has to survive the staging
    of all later pieces (a short member's padded copy is made per piece)
    and whatever the caller does to its arrays once the block is staged."""
    members, M, rows, _, slots, _ = GANGS[gang]
    pieces_of(slots)
    want_x, _ = fleet_stack_pad(members, M, rows, F)
    X, _, staging = fleet.stage_gang(members, M, rows, F, shard_model_axis(fleet_mesh(1)))
    assert staging["pieces"] >= 3
    first = np.array(X[:slots])  # the first piece, after every later one
    for a in members:
        a.fill(-1.0)
    np.testing.assert_array_equal(first, want_x[:slots])
    np.testing.assert_array_equal(np.asarray(X), want_x)


@pytest.mark.parametrize(
    "piece_bytes,member_bytes,pieces",
    [
        (4 * SLOT_BYTES, 0, 3),  # over a piece: pieces
        (12 * SLOT_BYTES, 0, 1),  # a block of just one piece's bytes
        (4 * SLOT_BYTES, SLOT_BYTES, 3),  # members just wide enough
        (4 * SLOT_BYTES, SLOT_BYTES + 1, 1),  # too narrow: stacked on the host
    ],
)
def test_the_way_follows_from_bytes_alone(monkeypatch, piece_bytes, member_bytes, pieces):
    monkeypatch.setattr(fleet, "STAGING_PIECE_BYTES", piece_bytes)
    monkeypatch.setattr(fleet, "STAGING_MEMBER_BYTES", member_bytes)
    members = _members(12, 20, BLOCK_ROWS)
    sharding = shard_model_axis(fleet_mesh(1))
    assert fleet.stage_gang(members, 12, BLOCK_ROWS, F, sharding)[2]["pieces"] == pieces


@pytest.mark.parametrize("slots", [4, 12], ids=["in_pieces", "one_piece"])
@pytest.mark.parametrize(
    "bad", [np.zeros((20, F + 1), np.float32), np.zeros((BLOCK_ROWS + 1, F), np.float32),
            np.zeros(F, np.float32)],
    ids=["too_wide", "too_long", "one_dimension"],
)
def test_both_ways_refuse_the_same_members(pieces_of, slots, bad):
    pieces_of(slots)
    members = _members(11, 20, BLOCK_ROWS) + [bad]
    with pytest.raises(ValueError, match="Bad member shape"):
        fleet.stage_gang(members, 12, BLOCK_ROWS, F, shard_model_axis(fleet_mesh(1)))


def test_the_shipped_rule_keeps_test_sized_and_narrow_gangs_whole():
    """No CPU test gang, few-member refit or narrow gang leaves the
    one-piece path: 256 MiB a block and 512 KiB a member before it does."""
    assert fleet.STAGING_PIECE_BYTES == 256 << 20 and fleet.STAGING_MEMBER_BYTES == 512 << 10
    members = _members(12, 20, BLOCK_ROWS)
    sharding = shard_model_axis(fleet_mesh(1))
    assert fleet.stage_gang(members, 12, BLOCK_ROWS, F, sharding)[2]["pieces"] == 1


def _fit(model_type, members, **kwargs):
    family = (
        dict(kind="feedforward_symmetric", dims=(8, 4))
        if model_type == "AutoEncoder"
        else dict(model_type=model_type, kind="lstm_symmetric", dims=(6,), lookback_window=8)
    )
    trainer = FleetTrainer(epochs=2, batch_size=16, seed=3, **family, **kwargs)
    tracer = Tracer(sample=1.0)
    trace = tracer.start_trace("test_fit", force=True)
    with use_trace(trace):
        models = trainer.fit(members)
    return models, trainer.last_stats, trace


@pytest.mark.parametrize(
    "model_type,mesh_devices",
    [("AutoEncoder", None), ("AutoEncoder", 4), ("LSTMAutoEncoder", None), ("LSTMForecast", 1)],
)
def test_fit_staged_in_pieces_returns_the_one_piece_fit(model_type, mesh_devices, monkeypatch):
    """Ragged members (one bucket: 4 batches of 16 items in every family),
    mesh-padding dummies, sequence warm-up rows: the
    whole fit, bit for bit, and the pieces reported where a caller and an
    operator look for them."""
    rng = np.random.RandomState(7)
    members = {
        f"m{i}": rng.rand(rng.randint(57, 65), F).astype("float32") for i in range(11)
    }
    kwargs = {} if mesh_devices is None else {"mesh": fleet_mesh(mesh_devices)}
    whole, whole_stats, _ = _fit(model_type, members, **kwargs)
    (bucket,) = whole_stats["buckets"]
    assert bucket["staging"] == {
        "pieces": 1, "bytes": 4 * bucket["padded_members"] * bucket["padded_rows"] * F
    }

    monkeypatch.setattr(fleet, "STAGING_PIECE_BYTES", 3 * 4 * bucket["padded_rows"] * F)
    monkeypatch.setattr(fleet, "STAGING_MEMBER_BYTES", 0)
    staged, staged_stats, trace = _fit(model_type, members, **kwargs)
    staging = staged_stats["buckets"][0]["staging"]
    assert staging["pieces"] > 1 and staging["bytes"] == bucket["staging"]["bytes"]
    (fit_span,) = [s for s in trace.spans if s.name.startswith("fit:")]
    assert fit_span.attributes["staging"] == staging
    head = ("stack_pad", "to_device", "scaler_fit", "init_state")
    assert tuple(s.name for s in trace.children(fit_span) if s.name in head) == head

    assert set(staged) == set(whole)
    for name, a in whole.items():
        b = staged[name]
        jax.tree.map(np.testing.assert_array_equal, a.params, b.params)
        for got, want in ((b.scaler, a.scaler), (b.error_scaler, a.error_scaler)):
            for g, w in zip(got, want):
                np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(b.feature_thresholds, a.feature_thresholds)
        assert b.total_threshold == a.total_threshold
        assert b.history == a.history


def test_the_ladder_tool_runs_at_a_tiny_size(monkeypatch, tmp_path, capsys):
    """``tools/staging_ladder.py`` (the chip run behind the rule's two
    constants): both ways over an equal and a ragged gang, here for its
    control flow alone. It sets the constants; the patch puts them back."""
    for name in ("STAGING_PIECE_BYTES", "STAGING_MEMBER_BYTES"):
        monkeypatch.setattr(fleet, name, getattr(fleet, name))
    monkeypatch.chdir(tmp_path)
    path = pathlib.Path(__file__).parents[1] / "tools" / "staging_ladder.py"
    spec = importlib.util.spec_from_file_location("staging_ladder", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    tool.main(["--shapes", "24x200x256x64", "24x200x256x64r", "--reps", "1", "--pieces-mb", "1"])
    out = json.loads((tmp_path / "chiprun_out" / "staging_ladder.json").read_text())
    for shape in ("24x200x256x64", "24x200x256x64r"):
        assert out[shape]["block_bytes"] == 4 * 24 * 256 * 64
        assert {"whole block", "stage_gang, 1 MB a piece (2 pieces)"} <= set(out[shape])
    assert "ready_ms" in capsys.readouterr().out
