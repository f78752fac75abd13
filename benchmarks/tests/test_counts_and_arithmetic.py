"""FLOP/byte counts against hand-worked numbers, the published peaks, and
the generator's schedule arithmetic."""

import numpy as np
import pytest

from harness import counts, refit, spec, weights
from queued import cell as make_cell
from harness.loadgen import open_schedule


def _config(name):
    return make_cell(name).config


def test_dense300_counts_by_hand():
    c = _config("dense300.refit")
    assert weights.hourglass_dims(300, 3, 0.5) == (250, 200, 150, 150, 200, 250)
    # 2 x (300*250 + 250*200 + 200*150 + 150*150 + 150*200 + 200*250 + 250*300)
    assert counts.forward_flops_per_row(c) == 2 * 332_500 == 665_000
    assert weights.n_params(c) == 332_500 + 1_500 == c["parameters"]
    # one epoch of a 640-member gang over 1440 rows: 3 x forward per row
    assert counts.train_epoch_flops(c, 640, 1440) == 3 * 665_000 * 1440 * 640
    # data once (1600 padded rows) + 15 steps x 6 x P floats, per member
    assert counts.train_epoch_bytes(c, 640, 1440, 1600) == 640 * 4 * (1600 * 300 + 15 * 6 * 334_000)


def test_lstm300_counts_by_hand():
    c = _config("lstm300.backfill")
    per_step = 8 * (250 * 550 + 200 * 450 + 150 * 350 + 150 * 300 + 200 * 350 + 250 * 450)
    assert per_step == 4_060_000
    assert counts.forward_flops_per_row(c) == 12 * per_step + 2 * 250 * 300 == 48_870_000
    assert weights.n_params(c) == 2_110_100 == c["parameters"]
    assert counts.windows_per_request(c, 1000) == 989
    assert counts.score_request_bytes(c, 1000) == 4 * (2_110_100 + 1200 + 300_000 + 989 * 902)


def test_roofline_names_the_bound_and_peaks_are_published():
    peaks = spec.peaks_for("TPU v5 lite")
    assert (peaks["flops_bf16"], peaks["hbm_bytes_per_s"]) == (197e12, 819e9)
    share, bound = counts.roofline(197e12, 1.0, 2.0, peaks)
    assert (round(share, 6), bound) == (50.0, "compute")
    share, bound = counts.roofline(1.0, 819e9, 4.0, peaks)
    assert (round(share, 6), bound) == (25.0, "memory")
    with pytest.raises(SystemExit):
        spec.peaks_for("TPU v99")


def test_open_schedule_replays_one_draw_in_another_order():
    traffic = {"rate_rps": 200.0, "schedule_seed": 7}
    a = open_schedule(traffic, weights.rng_for(1, weights.ARRIVALS), 10.0)
    b = open_schedule(traffic, weights.rng_for(2**31 + 5, weights.ARRIVALS), 10.0)
    # every seed offers exactly rate x seconds requests inside the window
    assert len(a) == len(b) == 2000
    assert (np.diff(a) > 0).all() and 0 < a[0] and a[-1] < 10.0 and b[-1] < 10.0
    # the same gaps in another order: same load, bursts elsewhere
    gaps = lambda due: np.sort(np.diff(due, prepend=0.0))
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=0, atol=1e-9)
    assert not np.allclose(a[:50], b[:50])
    # exponential gaps: coefficient of variation near 1, not a metronome
    assert 0.9 < np.std(gaps(a)) / np.mean(gaps(a)) < 1.1


def test_latency_percentiles_and_rate_arithmetic():
    """p50/p95/p99 are numpy's linear-interpolation percentiles over all
    completed requests; a rate is work over the whole window."""
    latency = np.arange(1, 1001, dtype=np.float64)  # 1..1000 ms
    assert np.percentile(latency, 50) == 500.5
    assert np.percentile(latency, 95) == pytest.approx(950.05)
    assert np.percentile(latency, 99) == pytest.approx(990.01)
    assert 751 * 1000 / 10.0 == 75100.0  # rows of completed requests / window


def test_seed_streams_are_reproducible_and_distinct():
    c = _config("dense300.live")
    big = 2**31 + 12345  # the driver's seeds exceed 32 signed bits
    w1, w2 = weights.member_weights(c, big, 3), weights.member_weights(c, big, 3)
    assert all(np.array_equal(w1[k], w2[k]) for k in w1)
    assert not np.array_equal(w1["w0"], weights.member_weights(c, big, 4)["w0"])
    assert not np.array_equal(w1["w0"], weights.member_weights(c, big + 1, 3)["w0"])
    assert weights.request_body(c, big, 0, 256).shape == (256, 300)


def test_padding_rule_of_the_mix_is_the_trainers_ladder():
    """``traffic/refit.json`` states the padding as data; the harness never
    asks the program. Here, and only here, the two are held side by side."""
    from gordo_components_tpu.parallel.fleet import quantize_batch_count, quantize_member_count

    pad = make_cell("dense300.refit").traffic["padding"]
    for n in range(1, 3000):
        assert refit.ladder_up(n, **pad["members"]) == quantize_member_count(n), n
    for n in range(1, 400):
        assert refit.ladder_up(n, **pad["batches"]) == quantize_batch_count(n), n
    gang = refit.Gang(make_cell("dense300.refit", overrides={
        "config": {"tags_per_machine": 4, "gang_members": 9}, "traffic": {"rows": 130}}), seed=1)
    assert (gang.padded_members, gang.padded_rows) == (10, 200)
    gang.check_padding({"padded_members": 10, "padded_items": 200})
    with pytest.raises(RuntimeError, match="padded the gang"):
        gang.check_padding({"padded_members": 12, "padded_items": 200})


@pytest.mark.parametrize("name", ["dense300.live", "lstm300.backfill"])
def test_family_layout_round_trips_through_the_programs_names(name):
    import families

    c = make_cell(name, overrides={"config": {"tags_per_machine": 8}}).config
    layout = families.load(c["family"], "layout")
    w = weights.member_weights(c, 3, 0)
    back = layout.from_program(layout.to_program(c, w))
    assert set(back) == {n for n, _, _ in layout.layer_shapes(c)}
    assert all(np.array_equal(back[k], w[k]) for k in back)


def test_a_family_part_that_is_missing_names_the_file_to_add():
    import families

    assert families.load("dense", "refit").refit_sample
    with pytest.raises(SystemExit, match="benchmarks/families/lstm/refit.py"):
        families.load("lstm", "refit")
    with pytest.raises(SystemExit, match="benchmarks/families/conv/layout.py"):
        families.load("conv", "layout")
