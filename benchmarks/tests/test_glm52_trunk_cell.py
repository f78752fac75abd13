"""``glm52_trunk300.week`` on the CPU at a tiny size (hidden 64, 1 dense + 3
routed layers that are full, shared, shared, full; 4 heads of 16 + 8 | 24
over ranks 32 and 16; an indexer of 4 x 16 that keeps 24 keys; 4 of 16
experts held, top 4 under a correction bias; 96 rows, 3 machines): the cell
driven end to end by its own driver
(``harness/selected_latent_trunk_serve.py``), the control and the planted
faults failing ``correct``, and the three readers this cell brings on a
recorded observation."""

import json
import time

import numpy as np
import pytest

import families
from harness import adapter, check, selected_latent_trunk_serve, spec, weights

CELL = "glm52_trunk300.week"
KINDS = ["full", "shared", "shared", "full"]
TINY = dict(
    tags_per_machine=5, hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=4, num_experts_per_tok=4,
    index_n_heads=4, index_head_dim=16, index_topk=24, chunk_size=16,
    held_layers=dict(indexer_types=KINDS, mlp_layer_types=["dense", "sparse", "sparse", "sparse"]),
    published=dict(num_hidden_layers=8, first_k_dense_replace=3, n_routed_experts=16),
    expert_shard=dict(chips_sharing_a_layer=4, index=1, held=[4, 8]),
    nominal_request_rows=96, bank_members=3,
)
PROGRAM_SIZES = dict(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4, index_n_heads=4,
    index_head_dim=16, index_topk=24, indexer_types=KINDS, expert_offset=4, experts_held=4,
    chunk_size=16,
)
# at this size a near-tie flips more of 96 x 4 expert choices and of 24 keys a row than of
# 10 080 x 8 and 2048, and one flipped row in 96 moves the output by several per cent
TINY_LIMITS = dict(output_gap=0.08, score_gap=0.07, expert_selection_gap=0.02, key_selection_gap=0.03)
# the faults this size shows whatever the seed: a layer that attends under another selection than
# it should by ``key-selection`` itself, which holds what EVERY layer attended under.
# ``bias_in_weights`` reads inside every limit at any size (a hundredth of a kept weight: the one
# named exception, PERF.md section 2); ``scale_192`` shows at the cell's own size (2048 keys a
# row), not among 24 keys a row of 96
CAUGHT = ("shared_attends_all", "stale_selection", "half_topk", "no_correction_bias", "top_k_minus_one",
          "capacity_drop")
SEED = 2**31 + 35


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
    result = selected_latent_trunk_serve.run(tiny, SEED, 1.5, traced, time.time(), on_tpu=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(result))
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["checks"]) >= set(TINY_LIMITS) | {"input_echo_gap"}
    names = set(result["metrics"])
    if traced:
        # off the chip no device metric is printed
        assert not names & {"selected_latent_attention_roofline.serve", "selection_device_ms.serve",
                            "held_experts_roofline.serve", "trunk_device_ms.serve", "mfu.serve",
                            "idle_share.serve"}
        assert {"selection_reuse.serve", "held_pair_share.serve", "held_expert_imbalance.serve",
                "span_coverage.serve", "server_ms.serve"} <= names
        assert result["metrics"]["selection_reuse.serve"]["value"] == 2.0  # 4 uses of 2 selections
        assert result["metrics"]["span_coverage.serve"]["value"] >= 95.0
    else:
        assert names == {"score_p50_ms", "setup_s"}


@pytest.fixture(scope="module")
def readings():
    cell = spec.Cell(CELL, overrides={
        "config": TINY, "limits": TINY_LIMITS, "traffic": dict(request_rows=96, check_requests=3),
    })
    (row,) = selected_latent_trunk_serve.control_readings(cell, [SEED])
    return cell, row


def test_the_stated_arithmetic_passes(readings):
    cell, row = readings
    assert check.is_correct(check.verdict(row["stated_bf16"], cell.limits)), row["stated_bf16"]


@pytest.mark.parametrize("variant", ["control_e4m3"] + ["fault_" + f for f in CAUGHT])
def test_control_and_planted_faults_fail_correct(readings, variant):
    cell, row = readings
    assert set(CAUGHT) <= set(families.load("glm52_trunk", "forward").FAULTS)
    verdict = check.verdict(row[variant], cell.limits)
    assert not check.is_correct(verdict), row[variant]


def test_the_control_fails_by_one_limit_not_by_each(readings):
    """One precision below is caught, and not because everything is off:
    the echo of the input stays exact."""
    _, row = readings
    assert row["control_e4m3"]["input_echo_gap"] == 0.0


def test_a_layer_under_the_wrong_selection_shows_in_the_key_selections(readings):
    """``key-selection`` holds what every layer attended under. A last full
    layer that hands on the selection it made but attended under the old
    one is one layer of four wrong; shared layers that attend over every
    causal key are two of four, with more keys than the reference's."""
    cell, row = readings
    stated = row["stated_bf16"]["key_selection_gap"]
    limit = cell.limits["key_selection_gap"]
    assert stated < limit
    assert row["fault_stale_selection"]["key_selection_gap"] > max(3 * limit, 0.08)
    assert row["fault_shared_attends_all"]["key_selection_gap"] > 0.25


def test_weights_are_remade_from_the_seed_and_a_share_is_a_slice_of_the_layer():
    import jax.numpy as jnp

    layout = families.load("glm52_trunk", "layout")
    config = dict(TINY, family="glm52_trunk", n_shared_experts=1)
    a, b = layout.trunk_layer(config, SEED, 3), layout.trunk_layer(config, SEED, 3)
    for name, leaf in a.items():
        np.testing.assert_array_equal(leaf, b[name])
        if leaf.ndim > 1:
            np.testing.assert_array_equal(leaf, leaf.astype(jnp.bfloat16).astype(jnp.float32))
        elif name == "router_bias":  # away from zero: a bias left out shows
            assert -0.02 <= float(leaf.min()) < -0.01 and 0.01 < float(leaf.max()) < 0.02
        elif name.endswith("_bias"):
            assert -0.1 <= float(leaf.min()) < float(leaf.max()) < 0.1
        elif name == "q_a_norm":  # the logits three times as wide: the softmax is not flat
            assert 2.0 <= float(leaf.min()) < float(leaf.max()) < 4.0
        else:  # a norm's scale does work: a norm left out shows
            assert 0.5 <= float(leaf.min()) < float(leaf.max()) < 1.5
    # what a layer holds follows held_layers: full layers an indexer, sparse layers a router and its bias
    names = [set(layout.trunk_layer(config, SEED, l)) for l in range(4)]
    assert ["idx_wq" in n for n in names] == [True, False, False, True]
    assert ["router_bias" in n for n in names] == ["router" in n for n in names] == [False, True, True, True]
    assert a["router"].shape == (64, 16) and a["idx_wq"].shape == (32, 64)
    # a leaf is the same leaf whatever else its layer holds (keyed by name), another seed's is not
    assert not np.array_equal(a["q_a"], layout.trunk_layer(config, SEED + 1, 3)["q_a"])
    all_full = dict(config, held_layers=dict(config["held_layers"], indexer_types=["full"] * 4))
    np.testing.assert_array_equal(layout.trunk_layer(all_full, SEED, 1)["router"], layout.trunk_layer(config, SEED, 1)["router"])
    whole = layout.trunk_layer(
        dict(config, expert_shard=dict(config["expert_shard"], held=[0, 16])), SEED, 3)
    for name in layout.EXPERT_LEAVES:
        np.testing.assert_array_equal(a[name], whole[name][4:8])
    np.testing.assert_array_equal(a["router"], whole["router"])
    np.testing.assert_array_equal(a["router_bias"], whole["router_bias"])
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
        "scopes": {"trunk/attend": 6 * 0.16, "trunk/indexer": 6 * 0.012, "trunk/select": 6 * 0.008,
                   "trunk/experts": 0.05},
        "shared": {"dispatches": 32, "selection_layers": 32 * 2, "selection_uses": 32 * 5,
                   "key_selections": 32 * 2 * 18_548_736},
    }
    obs.update(changes)
    return obs


def test_the_selected_latent_attention_roofline_by_hand():
    read = spec.load_reader("selected_latent_attention_roofline.serve")
    selected = 2048 * 2049 // 2 + (10080 - 2048) * 2048
    flops = 6 * 5 * 2 * 64 * (192 + 64 + 256) * selected  # requests x layers under a selection
    assert read(_recorded()) == pytest.approx(100 * flops / 197e12 / 0.96)
    assert 15 < read(_recorded()) < 25  # a third of the causal pairs: it reads low
    nbytes = 5 * 6 * 10080 * (2 * (64 * 256 + 576 + 64 * 256) + 10081 / 16)
    assert nbytes / 819e9 < flops / 197e12  # compute-bound at these sizes
    # never over 100 for a kernel that skipped nothing; None, never 0, where there is nothing to read
    assert read(_recorded(scopes={"trunk/experts": 1.0})) is None  # no such scope
    assert read(_recorded(trace=None)) is None  # an untraced run
    assert read(_recorded(shared={"dispatches": 32, "held_pairs": 7})) is None  # the parent's counters
    assert read(_recorded(shared={"dispatches": 32, "selection_uses": 0})) is None


def test_the_selection_device_ms_by_hand():
    read = spec.load_reader("selection_device_ms.serve")
    assert read(_recorded()) == pytest.approx(1e3 * (0.012 + 0.008))
    assert read(_recorded(scopes={"trunk/attend": 1.0})) is None  # a program that selects nothing
    assert read(_recorded(trace=None)) is None
    assert read({}) is None


def test_the_selection_reuse_by_hand():
    read = spec.load_reader("selection_reuse.serve")
    assert read(_recorded()) == pytest.approx(2.5)
    every = {"dispatches": 4, "selection_layers": 24, "selection_uses": 24}
    assert read(_recorded(shared=every)) == 1.0  # every layer selects for itself
    assert read(_recorded(shared={"dispatches": 3, "held_pairs": 7})) is None
    assert read(_recorded(shared={"dispatches": 3, "selection_layers": 0, "selection_uses": 0})) is None
    assert read({}) is None


def test_the_accepted_readers_take_this_familys_sizes():
    """``held_*`` and ``mfu.serve`` read the family's ``layout``: 16 held,
    4 routed layers, the selected pairs."""
    shared = {"dispatches": 32, "routed_pairs": 32 * 10080 * 8 * 4, "held_pairs": 32 * 10080 * 8 * 4 // 16,
              "held_tokens_busiest": 32 * 10080 * 8 * 4 // 16 // 4}
    obs = _recorded(shared=shared, scopes={"trunk/route": 0.03, "trunk/experts": 0.05, "trunk/combine": 0.04})
    assert spec.load_reader("held_pair_share.serve")(obs) == pytest.approx(6.25)
    assert spec.load_reader("held_expert_imbalance.serve")(obs) == pytest.approx(16 / 4)
    pairs = 6 * 10080 * 8 * 4 / 16
    least = max(pairs * 6 * 6144 * 2048 / 197e12,
                (6 * 4 * 16 * 3 * 6144 * 2048 * 2 + 6 * 10080 * 4 * 2 * 6144 * 4) / 819e9)
    assert spec.load_reader("held_experts_roofline.serve")(obs) == pytest.approx(100 * least / 0.12)
    obs.update(requests_completed=30, window_s=50.0)
    layout = families.load("glm52_trunk", "layout")
    want = 100 * layout.forward_flops_per_row(obs["config"]) * 10080 * 30 / 50.0 / 197e12
    assert spec.load_reader("mfu.serve")(obs) == pytest.approx(want)
    assert layout.forward_flops_per_row(obs["config"]) * 10080 / 1e12 == pytest.approx(33.3, abs=0.1)
