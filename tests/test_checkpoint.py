"""Fleet checkpoint/resume tests: an interrupted fleet training run must
resume from its last checkpoint and converge to the same result as an
uninterrupted run (the saved TrainState carries the PRNG stream, so the
on-device shuffles replay identically)."""

import os

import numpy as np
import pytest

from gordo_components_tpu.parallel.checkpoint import (
    FleetBucketCheckpoint,
    bucket_checkpoint_key,
)
from gordo_components_tpu.parallel.fleet import FleetTrainer
from gordo_components_tpu.parallel.mesh import fleet_mesh


def _members(n=6, rows=64, f=3, seed=0):
    rng = np.random.RandomState(seed)
    return {f"m-{i}": rng.rand(rows, f).astype("float32") for i in range(n)}


class _Preempt(Exception):
    pass


def _kill_after(n_epochs):
    calls = {"count": 0}

    def cb(info):
        calls["count"] += 1
        if calls["count"] >= n_epochs:
            raise _Preempt(f"simulated preemption after epoch {info['epoch']}")

    return cb


def test_resume_matches_uninterrupted_run(tmp_path):
    members = _members()
    common = dict(kind="feedforward_hourglass", epochs=6, batch_size=32, seed=3)

    reference = FleetTrainer(**common).fit(members)

    ckdir = str(tmp_path / "ck")
    t1 = FleetTrainer(
        **common, checkpoint_dir=ckdir, checkpoint_every=1,
        epoch_callback=_kill_after(3),
    )
    with pytest.raises(_Preempt):
        t1.fit(members)
    assert os.listdir(ckdir), "checkpoint must exist after preemption"

    t2 = FleetTrainer(**common, checkpoint_dir=ckdir, checkpoint_every=1)
    resumed = t2.fit(members)

    for name in members:
        ref, got = reference[name], resumed[name]
        # full 6-epoch history: 3 before the kill + 3 after resume
        assert len(got.history["loss"]) == 6
        np.testing.assert_allclose(
            got.history["loss"], ref.history["loss"], rtol=1e-5
        )
        ref_leaves = [np.asarray(x) for x in _leaves(ref.params)]
        got_leaves = [np.asarray(x) for x in _leaves(got.params)]
        for a, b in zip(ref_leaves, got_leaves):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # finished run cleans its checkpoint up
    assert not any(os.scandir(ckdir)) or all(
        not any(os.scandir(e.path)) for e in os.scandir(ckdir)
    )


def _leaves(tree):
    import jax

    return jax.tree.leaves(tree)


def _counting_cb():
    """Records which epoch indices the trainer actually RAN — the proof a
    resume genuinely skipped completed epochs (a silent restore failure
    retrains from 0 with identical outputs on a same-seed run, so output
    equality alone cannot detect it)."""
    epochs: list = []

    def cb(info):
        epochs.append(int(info["epoch"]))

    return epochs, cb


def test_seq_fleet_resume_matches_uninterrupted_run(tmp_path):
    """Preemption recovery must be family-agnostic: a gather-windowed LSTM
    fleet resumed from its checkpoint ends bit-close to the uninterrupted
    run (checkpoint keys carry model_type/lookback) — and genuinely
    resumes rather than retraining from scratch."""
    members = _members(n=4, rows=80)
    common = dict(
        model_type="LSTMAutoEncoder", kind="lstm_symmetric", dims=(6,),
        lookback_window=8, epochs=4, batch_size=32, seed=3,
    )
    reference = FleetTrainer(**common).fit(members)

    ckdir = str(tmp_path / "ck")
    t1 = FleetTrainer(
        **common, checkpoint_dir=ckdir, checkpoint_every=1,
        epoch_callback=_kill_after(2),
    )
    with pytest.raises(_Preempt):
        t1.fit(members)
    assert os.listdir(ckdir)

    ran, cb = _counting_cb()
    resumed = FleetTrainer(
        **common, checkpoint_dir=ckdir, checkpoint_every=1, epoch_callback=cb
    ).fit(members)
    # killed during epoch 1's callback -> epoch 0's save committed ->
    # the resume must run ONLY epochs 1..3
    assert ran == [1, 2, 3], ran
    for name in members:
        assert len(resumed[name].history["loss"]) == 4
        np.testing.assert_allclose(
            resumed[name].history["loss"], reference[name].history["loss"],
            rtol=1e-4,
        )
        for a, b in zip(_leaves(reference[name].params), _leaves(resumed[name].params)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            )


def test_seq_lookback_change_invalidates_checkpoint(tmp_path):
    """A different lookback must never resume another lookback's state."""
    members = _members(n=2, rows=80)
    base = dict(
        model_type="LSTMAutoEncoder", kind="lstm_symmetric", dims=(6,),
        epochs=2, batch_size=32, seed=0,
    )
    ckdir = str(tmp_path / "ck")
    t1 = FleetTrainer(
        # kill during epoch 1's callback so epoch 0's save COMMITS (the
        # callback precedes the save, so killing at epoch 0 would leave
        # no checkpoint at all and make this test vacuous)
        **base, lookback_window=8, checkpoint_dir=ckdir, checkpoint_every=1,
        epoch_callback=_kill_after(2),
    )
    with pytest.raises(_Preempt):
        t1.fit(members)
    assert os.listdir(ckdir)
    # different lookback: a FRESH run executing every epoch (a wrong resume
    # of the lookback-8 state would skip epoch 0 and be caught here)
    ran, cb = _counting_cb()
    out = FleetTrainer(
        **base, lookback_window=12, checkpoint_dir=ckdir, checkpoint_every=1,
        epoch_callback=cb,
    ).fit(members)
    assert ran == [0, 1], ran
    for m in out.values():
        assert len(m.history["loss"]) == 2


def test_resume_with_early_stopping_state(tmp_path):
    members = _members(n=4)
    common = dict(
        kind="feedforward_hourglass", epochs=8, batch_size=32, seed=1,
        early_stopping_patience=2,
    )
    reference = FleetTrainer(**common).fit(members)

    ckdir = str(tmp_path / "ck")
    t1 = FleetTrainer(
        **common, checkpoint_dir=ckdir, epoch_callback=_kill_after(4)
    )
    with pytest.raises(_Preempt):
        t1.fit(members)
    resumed = FleetTrainer(**common, checkpoint_dir=ckdir).fit(members)
    for name in members:
        assert resumed[name].history["loss"] == pytest.approx(
            reference[name].history["loss"], rel=1e-5
        )


def test_bucket_checkpoint_key_is_pinned(tmp_path):
    """The key names the directory an interrupted fit resumes from: an
    edit that moves it orphans every checkpoint in flight. The digest was
    computed on PR 30's tree (d8147d0) with the product's defaults."""
    ckdir = str(tmp_path / "ck")
    trainer = FleetTrainer(
        mesh=fleet_mesh(1), checkpoint_dir=ckdir, epoch_callback=_kill_after(2)
    )
    with pytest.raises(_Preempt):
        trainer.fit(_members())
    assert os.listdir(ckdir) == ["3d9d90d954d2e65304ea58dd"]


def test_config_change_invalidates_checkpoint(tmp_path):
    members = _members(n=2)
    ckdir = str(tmp_path / "ck")
    t1 = FleetTrainer(
        kind="feedforward_hourglass", epochs=4, batch_size=32,
        checkpoint_dir=ckdir, epoch_callback=_kill_after(2),
    )
    with pytest.raises(_Preempt):
        t1.fit(members)
    # different lr -> different bucket key -> fresh run, full history
    t2 = FleetTrainer(
        kind="feedforward_hourglass", epochs=4, batch_size=32,
        learning_rate=5e-4, checkpoint_dir=ckdir,
    )
    out = t2.fit(members)
    assert all(len(m.history["loss"]) == 4 for m in out.values())


def test_torn_checkpoint_ignored(tmp_path):
    key = bucket_checkpoint_key(["anything"])
    ck = FleetBucketCheckpoint(str(tmp_path), key)
    # epoch dir with state but no host.json commit marker == torn save
    os.makedirs(os.path.join(ck.root, "3", "state"))
    assert ck.restore() is None


def test_previous_checkpoint_survives_torn_save(tmp_path):
    """A preemption mid-save must not destroy the last good checkpoint."""
    key = bucket_checkpoint_key(["x"])
    ck = FleetBucketCheckpoint(str(tmp_path), key)
    ck.save(2, {"a": np.ones((2, 3), np.float32)}, {"active": [1.0]})
    # torn save of epoch 3: state written, host.json never committed
    os.makedirs(os.path.join(ck.root, "3", "state"))
    restored = ck.restore()
    assert restored is not None and restored["epoch"] == 2
    np.testing.assert_array_equal(restored["state"]["a"], np.ones((2, 3)))
    # a later complete save prunes both the old epoch and the torn one
    ck.save(3, {"a": np.zeros((2, 3), np.float32)}, {"active": [1.0]})
    assert sorted(os.listdir(ck.root)) == ["3"]
    assert ck.restore()["epoch"] == 3


def test_data_change_invalidates_key():
    payload = ["same", "config"]
    a = bucket_checkpoint_key(payload, data=np.ones((4, 8), np.float32))
    b = bucket_checkpoint_key(payload, data=np.ones((4, 8), np.float32))
    c = bucket_checkpoint_key(payload, data=np.full((4, 8), 2.0, np.float32))
    assert a == b != c


def test_key_stability():
    a = bucket_checkpoint_key(["x", 1, ["m1", "m2"]])
    b = bucket_checkpoint_key(["x", 1, ["m1", "m2"]])
    c = bucket_checkpoint_key(["x", 1, ["m1", "m3"]])
    assert a == b != c


def _fake_bucket_dir(parent, key, age_days=0.0):
    import time

    path = os.path.join(str(parent), key)
    os.makedirs(os.path.join(path, "0"))
    if age_days:
        old = time.time() - age_days * 86400
        os.utime(path, (old, old))
    return path


def test_clear_does_not_prune_siblings_by_default(tmp_path):
    """clear() removing OTHER buckets' state as a side effect would destroy
    a paused gang's resumable state (ADVICE r1): pruning is opt-in."""
    stale = _fake_bucket_dir(tmp_path, "a" * 24, age_days=30)
    ckpt = FleetBucketCheckpoint(str(tmp_path), "b" * 24)
    os.makedirs(os.path.join(ckpt.root, "0"))
    ckpt.clear()
    assert not os.path.isdir(ckpt.root)
    assert os.path.isdir(stale)  # sibling untouched


def test_prune_stale_checkpoints_janitor(tmp_path):
    from gordo_components_tpu.parallel.checkpoint import prune_stale_checkpoints

    stale = _fake_bucket_dir(tmp_path, "a" * 24, age_days=30)
    fresh = _fake_bucket_dir(tmp_path, "b" * 24, age_days=0)
    not_ours = os.path.join(str(tmp_path), "user-data")
    os.makedirs(not_ours)
    old = __import__("time").time() - 60 * 86400
    os.utime(not_ours, (old, old))
    assert prune_stale_checkpoints(str(tmp_path), older_than_days=7) == 1
    assert not os.path.isdir(stale)
    assert os.path.isdir(fresh)
    assert os.path.isdir(not_ours)  # non-checkpoint dirs never touched


class TestAsyncProtocol:
    """Direct tests of the deferred-commit async checkpoint protocol —
    the path FleetTrainer actually runs (use_async=True)."""

    def _state(self, seed=0):
        rng = np.random.RandomState(seed)
        return {"state": {"0": rng.rand(4, 8).astype("float32")}}

    def test_commit_is_deferred_to_next_save(self, tmp_path):
        ck = FleetBucketCheckpoint(str(tmp_path), "a" * 24, use_async=True)
        ck.save(0, self._state(0), {"histories": [[0.5]]})
        # no commit marker yet: an immediate crash leaves a torn epoch 0
        assert ck.restore() is None
        ck.save(1, self._state(1), {"histories": [[0.5, 0.4]]})
        # the NEXT save committed epoch 0
        resumed = ck.restore()
        assert resumed is not None and resumed["epoch"] == 0
        ck.flush()
        resumed = ck.restore()
        assert resumed["epoch"] == 1
        assert resumed["histories"] == [[0.5, 0.4]]
        ck.close()

    def test_deferred_host_state_is_snapshotted(self, tmp_path):
        """Live lists mutated after save() must not leak into the
        deferred commit."""
        ck = FleetBucketCheckpoint(str(tmp_path), "b" * 24, use_async=True)
        histories = [[0.5]]
        ck.save(0, self._state(), {"histories": histories})
        histories[0].append(0.4)  # training continues past the save
        ck.flush()
        assert ck.restore()["histories"] == [[0.5]]
        ck.close()

    def test_commit_prunes_older_epochs_only_after_wait(self, tmp_path):
        ck = FleetBucketCheckpoint(str(tmp_path), "c" * 24, use_async=True)
        for e in range(3):
            ck.save(e, self._state(e), {"histories": []})
        ck.flush()
        # only the newest committed epoch dir remains
        assert ck._committed_epochs() == [2]
        ck.close()

    def test_torn_async_save_ignored_and_previous_survives(self, tmp_path):
        ck = FleetBucketCheckpoint(str(tmp_path), "d" * 24, use_async=True)
        ck.save(0, self._state(0), {"histories": []})
        ck.flush()  # epoch 0 committed
        ck.save(1, self._state(1), {"histories": []})
        ck.close()  # waits but does NOT commit -> epoch 1 stays torn
        resumed = FleetBucketCheckpoint(str(tmp_path), "d" * 24).restore()
        assert resumed is not None and resumed["epoch"] == 0

    def test_clear_discards_pending(self, tmp_path):
        ck = FleetBucketCheckpoint(str(tmp_path), "e" * 24, use_async=True)
        ck.save(0, self._state(), {"histories": []})
        ck.clear()
        assert not os.path.isdir(ck.root)
        assert ck.restore() is None


class TestReadValidation:
    """Digest validation on RESTORE (the write side was always atomic;
    the read side used to trust the payload): a checkpoint whose state
    bytes changed on disk must be rejected and the most recent VALID
    checkpoint (or a fresh start) used instead."""

    def _state(self, k=0.0):
        return {
            "w": np.arange(100, dtype=np.float32) + k,
            "b": np.ones((4,), np.float32) * k,
        }

    def test_digest_written_and_round_trips(self, tmp_path):
        import json

        ck = FleetBucketCheckpoint(str(tmp_path), "f" * 24)
        ck.save(3, self._state(1.0), {"histories": [[0.5]]})
        with open(os.path.join(ck.root, "3", "host.json")) as f:
            host = json.load(f)
        assert len(host["state_digest"]) == 64  # sha256 hex
        resumed = ck.restore()
        assert resumed is not None and resumed["epoch"] == 3
        np.testing.assert_array_equal(resumed["state"]["w"], self._state(1.0)["w"])
        # the digest is consumed by validation, not leaked to the trainer
        assert "state_digest" not in resumed

    def test_tampered_digest_falls_back_to_older_valid_epoch(self, tmp_path):
        import json
        import shutil

        ck = FleetBucketCheckpoint(str(tmp_path), "a" * 24)
        ck.save(1, self._state(1.0), {"histories": []})
        # forge a NEWER committed epoch whose recorded digest does not
        # match its (otherwise perfectly readable) state payload
        shutil.copytree(
            os.path.join(ck.root, "1"), os.path.join(ck.root, "2")
        )
        host_path = os.path.join(ck.root, "2", "host.json")
        with open(host_path) as f:
            host = json.load(f)
        host["state_digest"] = "0" * 64
        with open(host_path, "w") as f:
            json.dump(host, f)
        resumed = ck.restore()
        # the corrupt newest epoch is skipped; the older valid one resumes
        assert resumed is not None and resumed["epoch"] == 1
        np.testing.assert_array_equal(resumed["state"]["w"], self._state(1.0)["w"])

    def test_corrupted_state_bytes_rejected(self, tmp_path):
        ck = FleetBucketCheckpoint(str(tmp_path), "b" * 24)
        ck.save(0, self._state(2.0), {"histories": []})
        # flip bytes in the largest state payload file (where the array
        # data lives); whether orbax's own integrity checks or our digest
        # catches it, restore must fall back to a fresh start, not crash
        # and not resume into garbage
        state_dir = os.path.join(ck.root, "0", "state")
        paths = [
            os.path.join(root, f)
            for root, _dirs, files in os.walk(state_dir)
            for f in files
        ]
        victim = max(paths, key=os.path.getsize)
        data = bytearray(open(victim, "rb").read())
        mid = len(data) // 2
        for i in range(mid, min(mid + 16, len(data))):
            data[i] ^= 0xFF
        with open(victim, "wb") as f:
            f.write(bytes(data))
        assert ck.restore() is None

    def test_legacy_checkpoint_without_digest_still_restores(self, tmp_path):
        import json

        ck = FleetBucketCheckpoint(str(tmp_path), "c" * 24)
        ck.save(0, self._state(), {"histories": []})
        host_path = os.path.join(ck.root, "0", "host.json")
        with open(host_path) as f:
            host = json.load(f)
        host.pop("state_digest")
        with open(host_path, "w") as f:
            json.dump(host, f)
        resumed = ck.restore()
        assert resumed is not None and resumed["epoch"] == 0
