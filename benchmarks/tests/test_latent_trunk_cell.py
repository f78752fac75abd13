"""``axk1_trunk300.week`` on the CPU at a tiny size (hidden 64, 1 dense + 2
routed layers, 4 heads over ranks 32 and 16, 4 of 16 experts held, top 4 of
2 of 4 groups, 96 rows, 3 machines): the cell driven end to end by its own
driver (``harness/latent_trunk_serve.py``), the control and each planted
fault failing ``correct``, and the four readers this cell brings on a
recorded observation."""

import json
import time

import numpy as np
import pytest

import families
from harness import adapter, check, latent_trunk_serve, spec, weights

CELL = "axk1_trunk300.week"
YARN = dict(type="yarn", factor=4, original_max_position_embeddings=32, beta_fast=32,
            beta_slow=1, mscale=1, mscale_all_dim=1)
TINY = dict(
    tags_per_machine=5, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=4, num_experts_per_tok=4,
    n_group=4, topk_group=2, rope_scaling=YARN,
    published=dict(num_hidden_layers=5, n_routed_experts=16),
    expert_shard=dict(chips_sharing_a_layer=4, index=1, held=[4, 8]),
    nominal_request_rows=96, bank_members=3,
)
PROGRAM_SIZES = dict(
    hidden_size=64, num_hidden_layers=3, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
    rope_scaling=YARN, expert_offset=4, experts_held=4, chunk_size=16,
)
# at this size a near-tie flips more of 96 x 4 choices than of 10 080 x 8, and one flipped row in
# 96, its expert held or not for it, moves the output by several per cent
TINY_LIMITS = dict(output_gap=0.07, score_gap=0.06, expert_selection_gap=0.015)
SEED = 2**31 + 33


@pytest.fixture
def tiny(monkeypatch):
    cell = spec.Cell(CELL, overrides={
        "config": TINY, "limits": TINY_LIMITS,
        "traffic": dict(request_rows=96, rate_rps=10.0, warm_seconds=0.3, trace_seconds=0.5,
                        check_requests=3),
    })
    adapter._estimator_kwargs(cell.config["model"]).update(PROGRAM_SIZES)
    monkeypatch.setenv("GORDO_BANK_KERNEL", "interpret")
    return cell


@pytest.mark.parametrize("traced", [False, True])
def test_cell_rehearsal(tiny, capsys, traced):
    result = latent_trunk_serve.run(tiny, SEED, 1.5, traced, time.time(), on_tpu=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(result))
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["checks"]) >= set(TINY_LIMITS) | {"input_echo_gap"}
    names = set(result["metrics"])
    if traced:
        # off the chip no device metric is printed
        assert not names & {"latent_attention_roofline.serve", "held_experts_roofline.serve",
                            "trunk_device_ms.serve", "mfu.serve", "idle_share.serve"}
        assert {"held_pair_share.serve", "span_coverage.serve", "server_ms.serve"} <= names
        assert 0.0 <= result["metrics"]["held_pair_share.serve"]["value"] <= 100.0
        assert result["metrics"]["span_coverage.serve"]["value"] >= 95.0
    else:
        assert names == {"score_p50_ms", "setup_s"}


@pytest.fixture(scope="module")
def readings():
    cell = spec.Cell(CELL, overrides={
        "config": TINY, "limits": TINY_LIMITS, "traffic": dict(request_rows=96, check_requests=3),
    })
    (row,) = latent_trunk_serve.control_readings(cell, [SEED])
    return cell, row


def test_the_stated_arithmetic_passes(readings):
    cell, row = readings
    assert check.is_correct(check.verdict(row["stated_bf16"], cell.limits)), row["stated_bf16"]


@pytest.mark.parametrize("variant", ["control_e4m3"] + [
    "fault_" + f for f in families.load("axk1_trunk", "forward").FAULTS])
def test_control_and_planted_faults_fail_correct(readings, variant):
    cell, row = readings
    verdict = check.verdict(row[variant], cell.limits)
    assert not check.is_correct(verdict), row[variant]


def test_the_control_fails_by_one_limit_not_by_each(readings):
    """One precision below is caught, and not because everything is off:
    the echo of the input stays exact."""
    _, row = readings
    assert row["control_e4m3"]["input_echo_gap"] == 0.0


def test_a_missing_selection_frame_fails():
    want = {"experts": np.ones((2, 4, 16), bool)}
    assert latent_trunk_serve.expert_selection_gap({}, want) == float("inf")
    short = {"expert-selection": np.zeros((2, 3, 4), np.uint8)}
    assert latent_trunk_serve.expert_selection_gap(short, want) == float("inf")


def test_the_selection_gap_by_hand():
    experts = np.zeros((1, 8, 16), bool)
    experts[0, :, [3, 9]] = True
    chosen = np.tile(np.array([3, 9], np.uint8), (1, 8, 1))
    chosen[0, 3] = [2, 3]  # one of sixteen choices wrong
    got = {"expert-selection": chosen}
    assert latent_trunk_serve.expert_selection_gap(got, {"experts": experts}) == 1 / 16
    chosen[0, 3] = [3, 3]  # an expert named twice agrees once
    assert latent_trunk_serve.expert_selection_gap(got, {"experts": experts}) == 1 / 16


def test_weights_are_remade_from_the_seed_and_a_share_is_a_slice_of_the_layer():
    import jax.numpy as jnp

    layout = families.load("axk1_trunk", "layout")
    config = dict(TINY, family="axk1_trunk", first_k_dense_replace=1, n_shared_experts=1)
    a, b = layout.trunk_layer(config, SEED, 1), layout.trunk_layer(config, SEED, 1)
    for name, leaf in a.items():
        np.testing.assert_array_equal(leaf, b[name])
        if leaf.ndim > 1:
            np.testing.assert_array_equal(leaf, leaf.astype(jnp.bfloat16).astype(jnp.float32))
        else:  # a norm's scale does work: a norm left out shows
            assert 0.5 <= float(leaf.min()) < float(leaf.max()) < 1.5
    assert "router" not in layout.trunk_layer(config, SEED, 0) and a["router"].shape == (64, 16)
    assert not np.array_equal(a["q_a"], layout.trunk_layer(config, SEED + 1, 1)["q_a"])
    whole = layout.trunk_layer(
        dict(config, expert_shard=dict(config["expert_shard"], held=[0, 16])), SEED, 1)
    for name in layout.EXPERT_LEAVES:
        np.testing.assert_array_equal(a[name], whole[name][4:8])
    np.testing.assert_array_equal(a["router"], whole["router"])
    w = weights.member_weights(config, SEED, 2)
    assert w["in_w"].shape == (5, 64) and w["out_w"].shape == (64, 5)


# ------------------------------------------------------------- the readers

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def _recorded(**changes):
    """What a traced run of the cell observes, at the published sizes."""
    obs = {
        "config": spec.Cell(CELL).config, "request_rows": 10080, "peaks": PEAKS,
        "engine": {"batches": 30, "requests": 30},
        "trace": {"module_calls": {"jit_score": 6}},
        "scopes": {"trunk/attend": 6 * 0.2, "trunk/route": 0.03, "trunk/experts": 0.05,
                   "trunk/combine": 0.04},
        "shared": {"dispatches": 32, "routed_pairs": 32 * 10080 * 8 * 5,
                   "held_pairs": 32 * 10080 * 8 * 5 // 16,
                   "held_tokens_busiest": 32 * 10080 * 8 * 5 // 16 // 4},
    }
    obs.update(changes)
    return obs


def test_the_latent_attention_roofline_by_hand():
    read = spec.load_reader("latent_attention_roofline.serve")
    flops = 6 * 6 * 2 * 64 * (128 + 64 + 128) * 10080 * 10081 / 2  # requests x layers
    assert read(_recorded()) == pytest.approx(100 * flops / 197e12 / 1.2)
    assert 30 < read(_recorded()) < 40  # compute-bound at these sizes
    assert read(_recorded(scopes={"trunk/experts": 1.0})) is None  # no such scope: the parent's program
    assert read(_recorded(trace=None)) is None


def test_the_held_experts_roofline_by_hand():
    read = spec.load_reader("held_experts_roofline.serve")
    pairs = 6 * 10080 * 8 * 5 / 16
    flops = pairs * 6 * 7168 * 2048
    nbytes = 6 * 5 * 12 * 3 * 7168 * 2048 * 2 + 6 * 10080 * 5 * 2 * 7168 * 4
    least = max(flops / 197e12, nbytes / 819e9)
    assert read(_recorded()) == pytest.approx(100 * least / 0.12)
    # no pair held in the window: nothing to read, never 0
    assert read(_recorded(shared={"dispatches": 32, "routed_pairs": 100, "held_pairs": 0})) is None
    assert read(_recorded(shared={"dispatches": 32})) is None  # the parent keeps no such counter


def test_the_held_pair_share_by_hand():
    read = spec.load_reader("held_pair_share.serve")
    assert read(_recorded()) == pytest.approx(6.25)
    assert read(_recorded(shared={"dispatches": 3, "routed_pairs": 80, "held_pairs": 0})) == 0.0
    assert read(_recorded(shared={"dispatches": 3, "expert_tokens": 7})) is None
    assert read({}) is None


def test_the_held_expert_imbalance_by_hand():
    read = spec.load_reader("held_expert_imbalance.serve")
    assert read(_recorded()) == pytest.approx(12 / 4)  # a quarter of the held pairs on one of 12
    even = {"dispatches": 1, "routed_pairs": 1920, "held_pairs": 120, "held_tokens_busiest": 10}
    assert read(_recorded(shared=even)) == pytest.approx(1.0)
    # no pair held in the window, or the parent's program: nothing to read, never 0
    assert read(_recorded(shared={"dispatches": 3, "routed_pairs": 80, "held_pairs": 0})) is None
    assert read(_recorded(shared={"dispatches": 3, "expert_tokens": 7})) is None
    assert read({}) is None


# ------------------------------------------- tools/latent_trunk_requests.py


def test_the_replay_and_the_window_summary_by_hand():
    import importlib.util
    import os

    path = os.path.join(spec.BENCH_DIR, "tools", "latent_trunk_requests.py")
    module_spec = importlib.util.spec_from_file_location("latent_trunk_requests", path)
    tool = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(tool)
    # one server, 0.5 s a request: the second waits 0.3 s, the third 0.6 s, the fourth none
    assert tool.replay([0.0, 0.2, 0.4, 5.0], 0.5, 700.0) == pytest.approx([700, 1000, 1300, 700])
    rows = [
        {"due_s": 0.0, "client_ms": 700.0, "queue_wait": 0.1, "device_execute": 440.0, "dispatched_s": 0.02},
        {"due_s": 0.2, "client_ms": 1000.0, "queue_wait": 300.0, "device_execute": 480.0, "dispatched_s": 0.52},
        {"due_s": 0.4, "client_ms": 1300.0, "queue_wait": 600.0, "device_execute": 480.0, "dispatched_s": 1.02},
        {"due_s": 5.0, "client_ms": 700.0, "queue_wait": 0.1, "device_execute": 440.0, "dispatched_s": 5.02},
    ]
    line = tool.summarise(rows, seed=1, rate=0.8)
    assert (line["waited"], line["p50_alone_ms"], line["p50_waited_ms"]) == (2, 700.0, 1150.0)
    assert line["back_to_back_s"] == pytest.approx(0.5)
    assert line["p50_ms"] == line["replayed_p50_ms"] == pytest.approx(850.0)
    assert "waited" not in tool.summarise([{"due_s": 0.0, "client_ms": 700.0}], 1, 0.8)  # no trace matched
