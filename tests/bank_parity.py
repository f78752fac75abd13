"""Shared by the bank test files (single-device, quantized, sharded): a
coalesced batch of B requests must give every slot the answer the same
request gets alone — bitwise — whichever members the batch names. The
bucket program slices its B members out of the stacked bank before it
computes (``server/bank.py::_select_members``); a wrong slice, a slot
reading its neighbour's member, or a padded slot leaking into a real one
shows here as a slot that differs from its own B = 1 answer."""

import numpy as np

FIELDS = ("model_output", "diff", "scaled", "total_unscaled", "total_scaled")


def watch_batches(bank, monkeypatch):
    """Record the slot count B (per shard, under a mesh) of every bucket
    program dispatch of ``bank`` from here on."""
    seen = []
    for bucket in bank._buckets.values():
        for method in ("score_batch", "score_batch_sharded"):
            inner = getattr(bucket, method)

            def spy(indices, X, Y, _inner=inner):
                seen.append(int(np.shape(indices)[-1]))
                return _inner(indices, X, Y)

            monkeypatch.setattr(bucket, method, spy)
    return seen


def assert_slots_equal_their_single_answers(bank, requests, monkeypatch, batch_size):
    """Score ``requests`` (one bucket, rows within one power of two) as
    one batch of ``batch_size`` slots, then each alone; return the batch's
    results."""
    seen = watch_batches(bank, monkeypatch)
    batch = bank.score_many(requests)
    assert seen == [batch_size], seen
    for slot, ((name, X, y), got) in enumerate(zip(requests, batch)):
        alone = bank.score(name, X, y)
        assert seen[-1] == 1
        assert got.offset == alone.offset
        for field in FIELDS:
            np.testing.assert_array_equal(
                getattr(got, field), getattr(alone, field),
                err_msg=f"slot {slot} ({name}) {field}",
            )
    return batch
