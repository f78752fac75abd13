"""``nemotron3_trunk300.week`` on the CPU at a tiny size (hidden 64, the
held layers ``MEM*E`` of a pattern ``MEM*EM``: mixers of 4 heads of 8 over
2 groups of 16, chunks of 16; 4 of 16 squared-ReLU experts held, top 4
under a correction bias, beside a shared one; attention 4/2 heads of 16;
96 rows, 3 machines): the cell driven end to end by its own driver
(``harness/hybrid_trunk_serve.py``), the control and the planted faults
failing ``correct``, and the three readers this cell brings on a recorded
observation."""

import json
import time

import numpy as np
import pytest

import families
from harness import adapter, check, hybrid_trunk_serve, spec, weights

CELL = "nemotron3_trunk300.week"
TINY = dict(
    tags_per_machine=5, hidden_size=64, num_hidden_layers=5, hybrid_override_pattern="MEM*EM",
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=16,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, n_routed_experts=4, num_experts_per_tok=4,
    held_layers=dict(published_index=[0, 1, 2, 3, 4]),
    published=dict(num_hidden_layers=6, n_routed_experts=16),
    expert_shard=dict(chips_sharing_a_layer=4, index=1, held=[4, 8]),
    nominal_request_rows=96, bank_members=3,
)
PROGRAM_SIZES = dict(
    hidden_size=64, num_hidden_layers=5, hybrid_override_pattern="MEM*EM", held_layers=[0, 1, 2, 3, 4],
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    n_routed_experts=16, num_experts_per_tok=4, expert_offset=4, experts_held=4, chunk_size=16,
)
# at this size one row in 96 is a tenth of the output's norm, and a near-tie that flips one of
# its 4 of 16 experts (a rounding of bfloat16 in a layer below is enough) moves the output by
# 4.5%: one flip in the first routed layer, five in the second, read 6.2% after the fifth layer
TINY_LIMITS = dict(output_gap=0.12, score_gap=0.1, expert_selection_gap=0.02)
# every planted fault but the correction bias counted into the weights (a hundredth of a kept
# weight at a bias of +-0.02: the named exception, as glm52_trunk300's)
CAUGHT = tuple(f for f in families.load("nemotron3_trunk", "forward").FAULTS if f != "bias_in_weights")
SEED = 2**31 + 42


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
    result = hybrid_trunk_serve.run(tiny, SEED, 1.5, traced, time.time(), on_tpu=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(result))
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["checks"]) >= set(TINY_LIMITS) | {"input_echo_gap"}
    names = set(result["metrics"])
    if traced:
        # off the chip no device metric is printed
        assert not names & {"ssm_scan_roofline.serve", "ssm_mixer_ms.serve",
                            "causal_attention_roofline.serve", "held_experts_roofline.serve",
                            "trunk_device_ms.serve", "mfu.serve", "idle_share.serve"}
        assert {"held_pair_share.serve", "held_expert_imbalance.serve", "span_coverage.serve",
                "server_ms.serve"} <= names
        assert result["metrics"]["span_coverage.serve"]["value"] >= 95.0
    else:
        assert names == {"score_p50_ms", "setup_s"}


@pytest.fixture(scope="module")
def readings():
    cell = spec.Cell(CELL, overrides={
        "config": TINY, "limits": TINY_LIMITS, "traffic": dict(request_rows=96, check_requests=3),
    })
    (row,) = hybrid_trunk_serve.control_readings(cell, [SEED])
    return cell, row


def test_the_stated_arithmetic_passes(readings):
    cell, row = readings
    assert check.is_correct(check.verdict(row["stated_bf16"], cell.limits)), row["stated_bf16"]


@pytest.mark.parametrize("variant", ["control_e4m3"] + ["fault_" + f for f in CAUGHT])
def test_control_and_planted_faults_fail_correct(readings, variant):
    cell, row = readings
    verdict = check.verdict(row[variant], cell.limits)
    assert not check.is_correct(verdict), row[variant]


def test_the_control_fails_by_one_limit_not_by_each(readings):
    """One precision below is caught, and not because everything is off:
    the echo of the input stays exact."""
    _, row = readings
    assert row["control_e4m3"]["input_echo_gap"] == 0.0


def test_weights_are_remade_from_the_seed_and_a_share_is_a_slice_of_the_layer():
    import jax.numpy as jnp

    layout = families.load("nemotron3_trunk", "layout")
    config = dict(spec.Cell(CELL).config, **TINY)
    assert layout.kinds(config) == ["M", "E", "M", "*", "E"]
    a, b = layout.trunk_layer(config, SEED, 1), layout.trunk_layer(config, SEED, 1)
    mixer = layout.trunk_layer(config, SEED, 0)
    for name, leaf in a.items():
        np.testing.assert_array_equal(leaf, b[name])
    for name, leaf in [*a.items(), *mixer.items()]:
        if leaf.ndim > 1:
            np.testing.assert_array_equal(leaf, leaf.astype(jnp.bfloat16).astype(jnp.float32))
        elif name == "router_bias":  # away from zero: a bias left out shows
            assert -0.02 <= float(leaf.min()) < -0.005 and 0.005 < float(leaf.max()) < 0.02
        elif name == "A_log":  # A on [1, 16)
            assert 0.0 <= float(leaf.min()) < float(leaf.max()) < np.log(16.0)
        elif name == "dt_bias":  # softplus(dt_bias) on [0.001, 0.1]
            dt = np.log1p(np.exp(np.asarray(leaf)))
            assert 0.001 * 0.999 <= dt.min() < dt.max() <= 0.1 * 1.001
        elif name == "D":
            np.testing.assert_array_equal(leaf, 1.0)
        elif name == "conv_bias":
            assert -0.1 <= float(leaf.min()) < float(leaf.max()) < 0.1
        else:  # a norm's scale does work: a norm left out shows
            assert 0.5 <= float(leaf.min()) < float(leaf.max()) < 1.5
    assert mixer["in_proj"].shape == (64, 2 * 32 + 2 * 2 * 16 + 4) and mixer["conv"].shape == (4, 32 + 64)
    assert a["router"].shape == (64, 16) and a["up"].shape == (4, 64, 32) and "gate" not in a
    assert set(layout.trunk_layer(config, SEED, 3)) == {"input_norm", "wq", "wk", "wv", "wo"}
    whole = layout.trunk_layer(
        dict(config, expert_shard=dict(config["expert_shard"], held=[0, 16])), SEED, 1)
    for name in layout.EXPERT_LEAVES:
        np.testing.assert_array_equal(a[name], whole[name][4:8])
    np.testing.assert_array_equal(a["router"], whole["router"])
    np.testing.assert_array_equal(a["router_bias"], whole["router_bias"])
    assert not np.array_equal(a["router"], layout.trunk_layer(config, SEED + 1, 1)["router"])
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
        "scopes": {"trunk/mamba/scan": 6 * 0.012, "trunk/mamba/in_proj": 6 * 0.01,
                   "trunk/mamba/conv": 6 * 0.002, "trunk/mamba/norm": 6 * 0.001,
                   "trunk/mamba/out_proj": 6 * 0.004, "trunk/attention": 6 * 0.01, "trunk/experts": 0.05},
        "shared": {"dispatches": 32, "ssm_layers": 32 * 4, "ssm_chunks": 32 * 4 * 79},
    }
    obs.update(changes)
    return obs


def test_the_ssm_scan_roofline_by_hand():
    read = spec.load_reader("ssm_scan_roofline.serve")
    rows = 6 * 4 * 79 * 128  # dispatches x layers x valid chunks x chunk rows
    flops = rows * (2 * 128 * 8 * 128 + 2 * 128 * 4096 + 4 * 4096 * 128)
    nbytes = rows * (2 * (4096 + 2 * 8 * 128) + 4 * 2 * 64 + 4 * 4096)
    assert flops / 197e12 < nbytes / 819e9  # memory-bound by the count
    assert read(_recorded()) == pytest.approx(100 * nbytes / 819e9 / 0.072)
    assert read(_recorded(scopes={"trunk/experts": 1.0})) is None  # no such scope
    assert read(_recorded(trace=None)) is None
    assert read(_recorded(shared={"dispatches": 32, "held_pairs": 7})) is None  # another kind's counters
    assert read(_recorded(shared={"dispatches": 32, "ssm_layers": 0, "ssm_chunks": 0})) is None


def test_the_ssm_mixer_ms_by_hand():
    read = spec.load_reader("ssm_mixer_ms.serve")
    assert read(_recorded()) == pytest.approx(1e3 * (0.012 + 0.01 + 0.002 + 0.001 + 0.004))
    assert read(_recorded(scopes={"trunk/attention": 1.0})) is None
    assert read(_recorded(trace=None)) is None
    assert read({}) is None


def test_the_causal_attention_roofline_by_hand():
    read = spec.load_reader("causal_attention_roofline.serve")
    flops = 6 * 1 * 4 * 32 * 128 * 10080 * 10081 / 2  # requests x layers x scores and values
    nbytes = 6 * 10080 * (2 * (32 + 4) * 128 + 4 * 32 * 128)
    assert nbytes / 819e9 < flops / 197e12  # compute-bound
    assert read(_recorded()) == pytest.approx(100 * flops / 197e12 / 0.06)
    assert read(_recorded(scopes={"trunk/experts": 1.0})) is None
    assert read(_recorded(trace=None)) is None
    assert read({}) is None


def test_the_accepted_readers_take_this_familys_sizes():
    """``held_*`` and ``mfu.serve`` read the family's ``layout``: 64 of 128
    held, 4 routed layers, two matrices an expert."""
    shared = {"dispatches": 32, "routed_pairs": 32 * 10080 * 6 * 4, "held_pairs": 32 * 10080 * 6 * 4 // 2,
              "held_tokens_busiest": 32 * 10080 * 6 * 4 // 2 // 16}
    obs = _recorded(shared=shared, scopes={"trunk/route": 0.03, "trunk/experts": 0.05, "trunk/combine": 0.04})
    assert spec.load_reader("held_pair_share.serve")(obs) == pytest.approx(50.0)
    assert spec.load_reader("held_expert_imbalance.serve")(obs) == pytest.approx(64 / 16)
    pairs = 6 * 10080 * 6 * 4 / 2
    least = max(pairs * 4 * 2688 * 1856 / 197e12,
                (6 * 4 * 64 * 2 * 2688 * 1856 * 2 + 6 * 10080 * 4 * 2 * 2688 * 4) / 819e9)
    assert spec.load_reader("held_experts_roofline.serve")(obs) == pytest.approx(100 * least / 0.12)
    obs.update(requests_completed=30, window_s=50.0)
    layout = families.load("nemotron3_trunk", "layout")
    want = 100 * layout.forward_flops_per_row(obs["config"]) * 10080 * 30 / 50.0 / 197e12
    assert spec.load_reader("mfu.serve")(obs) == pytest.approx(want)
    assert layout.forward_flops_per_row(obs["config"]) * 10080 / 1e12 == pytest.approx(8.6, abs=0.1)
