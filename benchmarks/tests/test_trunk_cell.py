"""``keye_trunk300.week`` on the CPU at a tiny size (hidden 64, 2 layers,
8 experts top-2, top-k 16 keys, 96 rows, 3 machines): the cell driven end
to end by its own driver (``harness/trunk_serve.py``; ``test_rehearsal.py``
drives every listed cell through ``serve.py``, which cannot stage a shared
trunk: PERF.md section 7), the control and each planted fault failing
``correct``, and the reduction of a trace to device seconds by scope."""

import json
import os
import time

import numpy as np
import pytest

import families
from harness import adapter, check, scope_trace, spec, trace, trunk_serve, weights

CELL = "keye_trunk300.week"
TINY = dict(
    tags_per_machine=5, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, rope_scaling={"mrope_section": [2, 3, 3]},
    sa_config=dict(indexer_num_heads=4, indexer_head_dim=16, indexer_num_kv_heads=1,
                   topk=16, q_chunk_size=16, kv_chunk_size=16),
    nominal_request_rows=96, bank_members=3,
)
PROGRAM_SIZES = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    indexer_num_heads=4, indexer_head_dim=16, indexer_rope_dim=8, indexer_topk=16, chunk_size=16,
)
# at this size a near-tie flips more of 96 x 2 choices than of 10 080 x 8
TINY_LIMITS = dict(output_gap=0.08, score_gap=0.08, expert_selection_gap=0.015, key_selection_gap=0.06)
SEED = 2**31 + 17


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
    result = trunk_serve.run(tiny, SEED, 1.5, traced, time.time(), on_tpu=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(result))
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["checks"]) >= set(TINY_LIMITS) | {"input_echo_gap"}
    names = set(result["metrics"])
    if traced:
        # off the chip no device metric is printed
        assert not names & {"experts_roofline.serve", "sparse_attention_roofline.serve",
                            "trunk_device_ms.serve", "mfu.serve", "idle_share.serve"}
        assert {"expert_imbalance.serve", "span_coverage.serve", "server_ms.serve"} <= names
        assert result["metrics"]["expert_imbalance.serve"]["value"] >= 1.0
        assert result["metrics"]["span_coverage.serve"]["value"] >= 95.0
    else:
        assert names == {"score_p50_ms", "setup_s"}


@pytest.fixture(scope="module")
def readings():
    cell = spec.Cell(CELL, overrides={
        "config": TINY, "limits": TINY_LIMITS, "traffic": dict(request_rows=96, check_requests=3),
    })
    (row,) = trunk_serve.control_readings(cell, [SEED])
    return cell, row


def test_the_stated_arithmetic_passes(readings):
    cell, row = readings
    assert check.is_correct(check.verdict(row["stated_bf16"], cell.limits)), row["stated_bf16"]


@pytest.mark.parametrize("variant", ["control_e4m3"] + [
    "fault_" + f for f in families.load("keye_trunk", "forward").FAULTS])
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
    want = {"experts": np.ones((2, 4, 8), bool), "keys": np.ones((2, 1, 4), bool)}
    got = {"expert-selection": np.zeros((2, 3, 2), np.uint8),
           "key-selection": np.zeros((2, 1, 1), np.uint8)}
    assert trunk_serve.selection_gaps(got, want)["expert_selection_gap"] == float("inf")


def test_selection_gaps_by_hand():
    experts = np.zeros((1, 8, 4), bool)
    experts[0, :, [0, 1]] = True
    keys = np.zeros((1, 1, 8), bool)
    keys[0, 0, :4] = True
    want = {"experts": experts, "keys": keys}
    chosen = np.tile(np.array([0, 1], np.uint8), (1, 8, 1))
    chosen[0, 3] = [2, 0]  # one of sixteen choices wrong
    theirs = np.zeros((1, 1, 8), bool)
    theirs[0, 0, 1:5] = True  # three of four in common
    got = {"expert-selection": chosen, "key-selection": np.packbits(theirs, axis=-1, bitorder="little")}
    gaps = trunk_serve.selection_gaps(got, want)
    assert gaps == {"expert_selection_gap": 1 / 16, "key_selection_gap": 0.25}
    # an expert named twice agrees once
    chosen[0, 3] = [0, 0]
    assert trunk_serve.selection_gaps(got, want)["expert_selection_gap"] == 1 / 16


def test_weights_are_remade_from_the_seed_and_fit_bfloat16():
    import jax.numpy as jnp

    layout = families.load("keye_trunk", "layout")
    config = dict(TINY, family="keye_trunk")
    a, b = layout.trunk_layer(config, SEED, 1), layout.trunk_layer(config, SEED, 1)
    other = layout.trunk_layer(config, SEED + 1, 1)
    for name, leaf in a.items():
        np.testing.assert_array_equal(leaf, b[name])
        np.testing.assert_array_equal(leaf, leaf.astype(jnp.bfloat16).astype(jnp.float32))
    assert not np.array_equal(a["wq"], other["wq"])
    assert not np.array_equal(a["wq"], layout.trunk_layer(config, SEED, 0)["wq"])
    w = weights.member_weights(config, SEED, 2)
    assert w["in_w"].shape == (5, 64) and w["out_w"].shape == (64, 5)


def test_device_seconds_by_scope_from_a_recorded_trace():
    """The raw reader finds each op's framework name: the recorded trace's
    op time is all ``jit(small_chain)/...``, and adds up to the busy time
    ``trace.py`` reads from the same file."""
    path = os.path.join(os.path.dirname(__file__), "data", "small_chain.xplane.pb")
    with open(path, "rb") as fh:
        by_name = scope_trace.op_seconds_by_framework_name(fh.read())
    assert by_name and all(name.startswith("jit(small_chain)/") for name in by_name)
    busy = trace.reduce_file(path)["busy_s"]
    assert sum(by_name.values()) == pytest.approx(busy, rel=0.01)  # copies carry no framework name
    scopes = scope_trace.seconds_by_scope(by_name, ["jit(small_chain)", "trunk/experts"])
    assert scopes["jit(small_chain)"] == pytest.approx(sum(by_name.values()))
    assert scopes["trunk/experts"] == 0.0


def test_the_innermost_scope_keeps_its_time():
    by_name = {"jit(score_layer)/trunk/project/dot_general:": 1.0,
               "jit(score_layer)/trunk/route/trunk/experts/pallas_call:": 2.0,
               "jit(score)/member/head/dot_general:": 0.5, "jit(score)/add:": 9.0}
    got = scope_trace.seconds_by_scope(by_name, trunk_serve.SCOPES)
    assert got["trunk/project"] == 1.0 and got["trunk/experts"] == 2.0
    assert got["trunk/route"] == 0.0 and got["member/head"] == 0.5
    assert sum(got.values()) == 3.5
