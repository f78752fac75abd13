"""The latent-attention trunk (``LatentMoEDecoder``: kind
``latent_moe_decoder`` of ``models/factories/trunk.py``,
``ops/latent_attention.py``, the routing and the held range of
``ops/moe.py``, the two kinds of layer in ``server/bank.py``), at a size the
CPU holds: hidden 64, 4 heads over ranks 32 and 16, 16 experts in 4 groups,
top 4 of 2 groups, 4 chips sharing a layer (this one holds experts 4-7), 1
dense + 2 routed layers, 96 rows, 3 machines. The plain reference is the
benchmark's own (``benchmarks/families/axk1_trunk/forward.py``), which
imports nothing of the program."""

import contextlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import families  # noqa: E402

from gordo_components_tpu import serializer  # noqa: E402
from gordo_components_tpu.models.factories.trunk import LatentMoEDecoder  # noqa: E402
from gordo_components_tpu.ops import latent_attention, moe, sparse_attention  # noqa: E402
from gordo_components_tpu.server import build_app  # noqa: E402
from gordo_components_tpu.server.bank import ModelBank  # noqa: E402
from gordo_components_tpu.utils.wire import TENSOR_CONTENT_TYPE  # noqa: E402
from gordo_components_tpu.utils.wire import pack_frames, unpack_frames  # noqa: E402

F, ROWS, MACHINES = 5, 96, 3
YARN = dict(type="yarn", factor=4, original_max_position_embeddings=32, beta_fast=32,
            beta_slow=1, mscale=1, mscale_all_dim=1)
SIZES = dict(
    hidden_size=64, num_hidden_layers=3, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, first_k_dense_replace=1, n_routed_experts=16, n_shared_experts=1,
    num_experts_per_tok=4, n_group=4, topk_group=2, routed_scaling_factor=2.5,
    rope_theta=10000.0, rope_scaling=YARN, expert_offset=4, experts_held=4, chunk_size=16,
)  # a 96-row request goes through the routed experts in 2 runs of 48 rows
# the same sizes under the published config's key names, as the reference reads them
CONFIG = dict(
    family="axk1_trunk", tags_per_machine=F, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
    first_k_dense_replace=1, n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=4,
    n_group=4, topk_group=2, routed_scaling_factor=2.5, rope_theta=10000, rope_scaling=YARN,
    rms_norm_eps=1e-6, published=dict(n_routed_experts=16, num_hidden_layers=5),
    expert_shard=dict(chips_sharing_a_layer=4, index=1, held=[4, 8]),
    nominal_request_rows=ROWS, bank_members=MACHINES,
)
MODULE = LatentMoEDecoder(n_features=F, **SIZES)
FORWARD = families.load("axk1_trunk", "forward")
LAYOUT = families.load("axk1_trunk", "layout")


def definition(trunk: str, seed: int = 0) -> dict:
    return {"gordo_components_tpu.models.DiffBasedAnomalyDetector": {"base_estimator": {
        "sklearn.pipeline.Pipeline": {"steps": [
            "sklearn.preprocessing.MinMaxScaler",
            {"gordo_components_tpu.models.TrunkForecast": dict(
                kind="latent_moe_decoder", trunk=trunk, sequence_rows=64, seed=seed, **SIZES)},
        ]}}}}


def machine_rows(i: int, n: int = 200) -> np.ndarray:
    t = np.arange(n)[:, None]
    noise = np.random.default_rng(i).normal(size=(n, F))
    return (np.sin(t * np.linspace(0.05, 0.3, F)[None] * (1 + i)) + 0.05 * noise).astype(np.float32)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """Three fitted machines that name one trunk artifact by a relative
    path, and the models as ``serializer.load`` returns them."""
    root = tmp_path_factory.mktemp("latent-trunk-collection")
    for i in range(MACHINES):
        det = serializer.from_definition(definition(str(root / "trunk-a"), seed=i))
        det.fit(machine_rows(i))
        det.base_estimator.steps[-1][1].trunk = "trunk-a"  # beside the member's artifact
        serializer.dump(det, str(root / f"m{i}"), metadata={"name": f"m{i}"})
    models = {f"m{i}": serializer.load(str(root / f"m{i}")) for i in range(MACHINES)}
    return str(root), models


@pytest.fixture(scope="module")
def bank(tree):
    return ModelBank.from_models(tree[1], registry=False)


def _reference(models, name: str, X: np.ndarray, **how):
    """The plain reference's forecast for one machine's request, from the
    machine's fitted leaves and the trunk artifact's weights, given the
    same share of the experts (4-7 of 16)."""
    det = models[name]
    scaler, est = det.base_estimator.steps[0][1], det.base_estimator.steps[-1][1]
    trunk = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), est.trunk_params)
    w = {k: jnp.asarray(v) for k, v in LAYOUT.from_program(est.params_["params"]).items()}
    xs = np.asarray(scaler.transform(X), np.float32)
    got = FORWARD.forecast(CONFIG, lambda l: trunk["layers"][l], w, xs, **how)
    return xs, {k: np.asarray(v) for k, v in got.items()}


def _rel(got, want) -> float:
    return float(np.linalg.norm(np.asarray(got) - np.asarray(want)) / np.linalg.norm(np.asarray(want)))


# ------------------------------------------------------- through build_app


@contextlib.asynccontextmanager
async def _client(root):
    # one device: a bucket with shared leaves does not shard over a mesh
    client = TestClient(TestServer(build_app(root, devices=1)))
    await client.start_server()
    try:
        if client.app.get("warmup_future") is not None:
            await client.app["warmup_future"]
        yield client
    finally:
        await client.close()


@pytest.mark.parametrize("machine", range(MACHINES))
async def test_served_answer_matches_the_plain_reference(tree, machine):
    """``run-server``'s normal path: build_app -> ModelCollection ->
    ModelBank -> BatchingEngine -> POST, against the reference.

    Tolerances. The program rounds every trunk matmul's operands to
    bfloat16 (8 bits of mantissa: relative 2^-9 an operand) and so does the
    reference's ``operands="bfloat16"``; over 3 layers at hidden 64 that
    reading lies 0.2-0.5% from float32, and the program's as far, by
    another order of accumulation and an online softmax. float8 e4m3
    operands (3 bits: 2^-4) lie 8-15% off. So the program must be within
    1.5 times the stated arithmetic's distance plus 0.5%, which the
    control misses tenfold; and the router's choices, made in float32 in
    both, may differ in the few rows where two scores tie to 2^-9."""
    root, models = tree
    X = machine_rows(machine, ROWS) * 0.9
    async with _client(root) as client:
        assert client.app["bank"].coverage()["fallback"] == {}
        resp = await client.post(
            f"/gordo/v0/proj/m{machine}/anomaly/prediction", data=pack_frames([("X", X)]),
            headers={"Content-Type": TENSOR_CONTENT_TYPE},
        )
        assert resp.status == 200, await resp.text()
        got = unpack_frames(await resp.read())
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
    np.testing.assert_array_equal(got["model-input"], X[1:])
    assert got["model-output"].shape == (ROWS - 1, F)
    xs, exact = _reference(models, f"m{machine}", X)
    _, stated = _reference(models, f"m{machine}", X, operands="bfloat16")
    _, control = _reference(models, f"m{machine}", X, operands="float8_e4m3fn")
    bound = 1.5 * _rel(stated["out"], exact["out"]) + 0.005
    assert _rel(got["model-output"], exact["out"][:-1]) < bound
    assert _rel(control["out"], exact["out"]) > 3 * bound  # one precision lower fails
    diff = np.abs(xs[1:] - got["model-output"])
    np.testing.assert_allclose(got["tag-anomaly-unscaled"], diff, rtol=1e-5, atol=1e-6)
    # the frames of the answer: the routed layers' experts, of all 16; no key selection
    assert "key-selection" not in got
    experts = got["expert-selection"].astype(np.int64)
    assert experts.shape == (2, ROWS, 4) and experts.max() < 16
    assert np.take_along_axis(exact["experts"], experts, axis=-1).mean() > 0.97
    # the trunk is held once, and the counters are served
    cap = stats["bank_capacity"]
    assert cap["shared_bytes"] > 0 and cap["weight_bytes"] < 2 * cap["shared_bytes"]
    shared = stats["bank_shared"]
    assert shared["dispatches"] >= 1 and shared["routed_pairs"] == ROWS * 4 * 2
    held_by_reference = int(exact["experts"][:, :, 4:8].sum())
    assert abs(shared["held_pairs"] - held_by_reference) <= 0.03 * ROWS * 4 * 2
    assert 0 < shared["held_tokens_busiest"] <= shared["held_pairs"]
    # two routed layers, two runs of 48 rows a request: a trip for each run that held a pair
    assert 0 < shared["held_pair_blocks"] <= 4 * shared["dispatches"]
    assert "expert_tokens" not in shared and "key_selections" not in shared


def test_the_counters_are_scraped(bank):
    from gordo_components_tpu.server.bank import _SHARED_COUNTERS

    assert {"routed_pairs", "held_pairs", "held_tokens_busiest", "held_pair_blocks"} <= set(_SHARED_COUNTERS)


# ------------------------------------------------------------------- bank


@pytest.mark.parametrize("pair", [(0, 1), (1, 2), (2, 0)])
def test_two_machines_batched_get_the_answers_they_get_alone(bank, pair):
    Xs = [machine_rows(i, ROWS) for i in pair]
    together = bank.score_many([(f"m{i}", X, None) for i, X in zip(pair, Xs)])
    for i, X, both in zip(pair, Xs, together):
        alone = bank.score(f"m{i}", X)
        np.testing.assert_array_equal(both.model_output, alone.model_output)
        np.testing.assert_array_equal(both.total_scaled, alone.total_scaled)
        np.testing.assert_array_equal(
            both.selections["expert-selection"], alone.selections["expert-selection"])
        assert set(both.selections) == {"expert-selection"}


@pytest.mark.parametrize("rows", [17, 50, 90])
def test_padding_is_left_out_of_the_counters(bank, tree, rows):
    """Any length goes as one call; the padding changes nothing and is
    counted nowhere but in ``tokens`` and, as the pairs it is routed as, in
    the blocks of held pairs (a trip for each run of 48 rows that held a
    pair, padding's among them: 2 routed layers x 2 runs)."""
    X = machine_rows(1, rows)
    before = dict(bank.shared_stats)
    got = bank.score("m1", X)
    after = bank.shared_stats
    grew = lambda name: after[name] - before.get(name, 0)
    assert grew("dispatches") == 1 and grew("rows") == rows
    assert grew("tokens") == MODULE.padded_rows(rows)
    assert grew("routed_pairs") == rows * 4 * 2
    _, exact = _reference(tree[1], "m1", X)
    assert abs(grew("held_pairs") - int(exact["experts"][:, :, 4:8].sum())) <= 0.03 * rows * 8 + 1
    assert (grew("held_pairs") > 0) <= (grew("held_pair_blocks") > 0) and grew("held_pair_blocks") <= 4
    assert got.model_output.shape == (rows - 1, F)
    assert _rel(got.model_output, exact["out"][:-1]) < 0.02


def test_both_kinds_of_layer_compile_once_each_whatever_the_depth(tree, tmp_path):
    """Five layers (1 dense + 4 routed) behind the same bucket program:
    ``score_layer`` is traced for the two kinds and no more."""
    deep = dict(SIZES, num_hidden_layers=5)
    det = serializer.from_definition({"gordo_components_tpu.models.DiffBasedAnomalyDetector": {
        "base_estimator": {"sklearn.pipeline.Pipeline": {"steps": [
            "sklearn.preprocessing.MinMaxScaler",
            {"gordo_components_tpu.models.TrunkForecast": dict(
                kind="latent_moe_decoder", trunk=str(tmp_path / "deep"), sequence_rows=64, **deep)},
        ]}}}})
    det.fit(machine_rows(0))
    deep_bank = ModelBank.from_models({"deep": det}, registry=False)
    (bucket,) = deep_bank._buckets.values()
    assert len(bucket.shared["layers"]) == 5
    assert ["router" in w for w in bucket.shared["layers"]] == [False, True, True, True, True]
    got = deep_bank.score("deep", machine_rows(0, ROWS))
    assert bucket._layer._cache_size() == 2
    assert got.selections["expert-selection"].shape == (4, ROWS, 4)
    deep_bank.score("deep", machine_rows(1, ROWS))
    assert bucket._layer._cache_size() == 2


@pytest.mark.parametrize("free_gb,expect", [(None, None), (0.0, 1), (1e3, 64)])
def test_the_batch_is_bounded_by_the_programs_bytes(bank, free_gb, expect):
    (bucket,) = bank._buckets.values()
    old = bucket._free_bytes
    try:
        bucket._free_bytes = None if free_gb is None else int(free_gb * 1e9)
        limit = bank.batch_limit("m0", ROWS)
        assert limit == expect or (expect == 64 and limit >= 64)
    finally:
        bucket._free_bytes = old


def test_one_week_long_request_a_call_at_the_published_sizes():
    """Beside the 7.75 GB trunk and the bank a v5e's 16.9e9 bytes leave
    5.82e9 where the bank reads them (the benchmark's server, on the chip):
    the count allows one request of 10 240 rows a call, not two (the bank
    doubles)."""
    module = LatentMoEDecoder(n_features=300, num_hidden_layers=6, experts_held=12)
    assert module.program_bytes(1, 10240) < 5.82e9 < module.program_bytes(2, 10240)
    assert module._rows_a_run(10240) == 2560 == module._rows_a_run(2 * 10240)
    assert module._rows_a_run(10240 + 512) == 3 * 512  # 21 chunks: runs of 3


def test_flops_count_the_share_a_row_meets(bank):
    from gordo_components_tpu.observability.cost import estimate_flops_per_row

    (row,) = bank.flops_stats().values()
    flops, method = estimate_flops_per_row(MODULE, F, 1, 123)
    assert row["flops_per_row"] == flops and method == "analytic:context=320"
    attention = 64 * 32 + 32 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 4 * 16 * 64
    routed = 64 * 16 + 3 * 64 * 32 + 3 * 64 * 32 * 4 * 4 / 16  # router, shared, 4 of 16 held of a row's 4
    by_hand = 2.0 * (3 * attention + 3 * 64 * 96 + 2 * routed + 2 * F * 64)
    by_hand += 3 * 2.0 * 4 * (16 + 8 + 16) * (320 + 1) / 2
    assert flops == pytest.approx(by_hand)


# ------------------------------------------------- the share and the whole


def _uncut():
    """The whole layer's configuration and weights, and each chip's."""
    whole = dict(CONFIG, n_routed_experts=16, expert_shard=dict(chips_sharing_a_layer=1, index=0, held=[0, 16]))
    shares = [dict(CONFIG, expert_shard=dict(chips_sharing_a_layer=4, index=i, held=[4 * i, 4 * i + 4]))
              for i in range(4)]
    return whole, shares


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four chips' shares, with the shared expert
    (which every chip computes alike) counted once, are what the uncut
    reference gives for the whole layer: in the reference, and in the
    program's ``expert_layer`` handed each range in turn."""
    whole, shares = _uncut()
    seed = 33
    w_whole = LAYOUT.trunk_layer(whole, seed, 1)
    h = jax.random.normal(jax.random.PRNGKey(5), (ROWS, 64))
    with jax.default_matmul_precision("highest"):
        routed, shared, kept = FORWARD.ffn_parts(whole, w_whole, h)
        parts = [FORWARD.ffn_parts(c, LAYOUT.trunk_layer(c, seed, 1), h) for c in shares]
    np.testing.assert_allclose(sum(p[0] for p in parts), routed, rtol=1e-5, atol=1e-6)
    for part in parts:
        np.testing.assert_array_equal(part[1], shared)  # every chip's shared expert is the same
        np.testing.assert_array_equal(part[2], kept)  # and so is the routing, over all 16
    assert int(kept.sum()) == ROWS * 4
    # the program, share by share, against the whole layer it computes when it holds all 16
    routing = dict(scoring="sigmoid", n_group=4, topk_group=2, scale=2.5)
    as_program = lambda w: {k: (v if v.ndim == 1 else v.astype(jnp.bfloat16)) for k, v in w.items()}
    valid = jnp.ones((ROWS,), bool)
    full, experts, counts, _ = moe.expert_layer(h, as_program(w_whole), 4, valid, True, **routing)
    total, held = jnp.zeros_like(full), 0
    for i, c in enumerate(shares):
        out, theirs, tokens, _ = moe.expert_layer(
            h, as_program(LAYOUT.trunk_layer(c, seed, 1)), 4, valid, True, expert_offset=4 * i, **routing)
        np.testing.assert_array_equal(theirs, experts)
        np.testing.assert_array_equal(tokens, counts[4 * i: 4 * i + 4])
        total, held = total + out, held + int(tokens.sum())
    assert held == ROWS * 4
    np.testing.assert_allclose(total, full, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(full, routed, rtol=3e-2, atol=3e-2)  # bfloat16 operands against float32


def test_the_references_latent_and_expanded_forms_agree():
    w = {k: jnp.asarray(v) for k, v in LAYOUT.trunk_layer(CONFIG, 7, 1).items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (ROWS, 64))
    with jax.default_matmul_precision("highest"):
        expanded = FORWARD.attention(CONFIG, w, x, "float32", None, "expanded")
        absorbed = FORWARD.attention(CONFIG, w, x, "float32", None, "absorbed")
    np.testing.assert_allclose(absorbed, expanded, rtol=1e-4, atol=1e-5)


def test_the_kernel_is_causal_latent_attention():
    """``latent_attention`` against the sum it is, written out: two-part
    scores, the rope key shared by the heads, other widths for scores and
    values, no key beyond the query's own row."""
    H, T, nope, dr, dv = 4, 64, 16, 8, 24
    keys = jax.random.split(jax.random.PRNGKey(8), 5)
    bf = lambda k, shape: jax.random.normal(k, shape).astype(jnp.bfloat16)
    qn, qr, kn, kr, v = (bf(keys[0], (H, T, nope)), bf(keys[1], (H, T, dr)), bf(keys[2], (H, T, nope)),
                         bf(keys[3], (T, dr)), bf(keys[4], (H, T, dv)))
    got = latent_attention.latent_attention(qn, qr, kn, kr, v, granule=16, interpret=True)
    f = lambda a: a.astype(jnp.float32)
    logits = jnp.einsum("htn,hsn->hts", f(qn), f(kn)) + jnp.einsum("htr,sr->hts", f(qr), f(kr))
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((T, T), bool)), logits, -jnp.inf), axis=-1)
    want = jnp.einsum("hts,hsv->htv", f(p.astype(jnp.bfloat16)), f(v))
    assert got.shape == (H, T, dv) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(f(got), want, rtol=3e-2, atol=3e-2)
    # a later key changes no earlier row
    again = latent_attention.latent_attention(
        qn, qr, kn.at[:, 40:].set(9.0), kr, v.at[:, 40:].set(-9.0), granule=16, interpret=True)
    np.testing.assert_array_equal(again[:, :40], got[:, :40])
    # a week of minutes padded to 512s takes the largest tile; a short request its granule
    assert latent_attention.tiling(10240, 512) == (1024, 2)
    assert latent_attention.tiling(10240 + 512, 512) == (512, 4)
    assert latent_attention.tiling(64, 16) == (16, 4)


# ------------------------------------------------------------------- YaRN


def test_yarn_at_factor_one_is_plain_rope():
    inv_freq, multiplier = latent_attention.yarn(8, 10000.0, None)
    again, one = latent_attention.yarn(8, 10000.0, dict(YARN, factor=1))
    np.testing.assert_array_equal(inv_freq, again)
    assert multiplier == one == 1.0
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 3, 8))
    positions = jnp.arange(24)
    np.testing.assert_allclose(
        sparse_attention.rope(x, positions, 10000.0, inv_freq=inv_freq),
        sparse_attention.rope(x, positions, 10000.0), rtol=1e-6, atol=1e-6)
    ref_freq, ref_scale = FORWARD.yarn(dict(CONFIG, rope_scaling=None))
    np.testing.assert_allclose(ref_freq, inv_freq, rtol=1e-6)
    assert ref_scale == pytest.approx(24 ** -0.5)


def test_yarn_against_a_count_by_hand_at_the_published_keys():
    published = dict(type="yarn", factor=32, original_max_position_embeddings=4096, beta_fast=32,
                     beta_slow=1, mscale=1, mscale_all_dim=1)
    inv_freq, multiplier = latent_attention.yarn(64, 10000.0, published)
    f = lambda i: 10000.0 ** (-2 * i / 64)
    dim = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(10000.0))
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (10, 23)
    assert inv_freq[0] == pytest.approx(1.0) and inv_freq[10] == pytest.approx(f(10))  # turn fast: kept
    assert inv_freq[23] == pytest.approx(f(23) / 32) and inv_freq[31] == pytest.approx(f(31) / 32)
    assert inv_freq[15] == pytest.approx(f(15) / 32 * 5 / 13 + f(15) * 8 / 13)  # on the ramp
    assert multiplier == pytest.approx((0.1 * math.log(32) + 1) ** 2) == pytest.approx(1.8133, abs=1e-4)
    assert 192 ** -0.5 * multiplier == pytest.approx(0.07217 * 1.8133, rel=1e-3)
    ref_freq, ref_scale = FORWARD.yarn(dict(CONFIG, qk_nope_head_dim=128, qk_rope_head_dim=64,
                                             rope_scaling=published))
    np.testing.assert_allclose(ref_freq, inv_freq, rtol=1e-5)
    assert ref_scale == pytest.approx(192 ** -0.5 * multiplier)


def test_yarn_that_would_scale_cos_and_sin_is_refused():
    with pytest.raises(ValueError, match="cos and sin"):
        latent_attention.yarn(8, 10000.0, dict(YARN, mscale_all_dim=0))
    with pytest.raises(ValueError, match="not implemented"):
        latent_attention.yarn(8, 10000.0, dict(YARN, type="linear"))


# ---------------------------------------------------------------- routing


def test_one_group_is_plain_top_k():
    h = jax.random.normal(jax.random.PRNGKey(2), (40, 16))
    w = jax.random.normal(jax.random.PRNGKey(3), (16, 12))
    weights, experts = moe.route(h, w, 3, scoring="sigmoid", n_group=1, scale=2.5)
    scores = jax.nn.sigmoid(jnp.dot(h, w, precision="highest"))
    np.testing.assert_array_equal(experts, jax.lax.top_k(scores, 3)[1])
    # softmax, one group, scale 1: the renormalised-probability router, bit for bit
    plain_w, plain_e = moe.route(h, w, 3)
    p, e = jax.lax.top_k(jax.nn.softmax(jnp.dot(h, w, precision="highest"), axis=-1), 3)
    np.testing.assert_array_equal(plain_e, e)
    np.testing.assert_array_equal(plain_w, p / jnp.sum(p, axis=-1, keepdims=True))


def test_a_high_score_in_a_group_that_is_not_kept_is_not_chosen():
    # 8 experts in 4 groups of 2; keep 1 group, then top 2. Expert 6 has the
    # single highest score, but its group (6, 7) sums lower than group (0, 1).
    h = jnp.ones((1, 1))  # one row whose logits are the router's one row
    router = jnp.asarray([[3.0, 2.9, -9, -9, -9, -9, 3.5, -9.0]])
    weights, experts = moe.route(h, router, 2, scoring="sigmoid", n_group=4, topk_group=1, scale=2.5)
    assert sorted(np.asarray(experts[0]).tolist()) == [0, 1]
    assert float(weights.sum()) == pytest.approx(2.5)
    # without the limit the high score is taken
    _, free = moe.route(h, router, 2, scoring="sigmoid")
    assert 6 in np.asarray(free[0]).tolist()


def test_the_kept_weights_sum_to_the_scale():
    h = jax.random.normal(jax.random.PRNGKey(4), (50, 16))
    w = jax.random.normal(jax.random.PRNGKey(5), (16, 16))
    weights, experts = moe.route(h, w, 4, scoring="sigmoid", n_group=4, topk_group=2, scale=2.5)
    np.testing.assert_allclose(weights.sum(axis=-1), 2.5, rtol=1e-6)
    groups = np.asarray(experts) // 4
    assert all(len(set(row)) <= 2 for row in groups.tolist())  # the 4 kept lie in 2 groups
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        moe.route(h, w, 4, scoring="tanh")


def _share(router, held=4, D=16, I=8, E=16):
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    return {"router": router,
            "gate": jax.random.normal(keys[0], (held, D, I), jnp.bfloat16),
            "up": jax.random.normal(keys[1], (held, D, I), jnp.bfloat16),
            "down": jax.random.normal(keys[2], (held, I, D), jnp.bfloat16)}


def test_every_row_on_held_experts_drops_no_pair():
    """All 64 rows choose experts 4 and 5, both held (range 4-7): 128 pairs,
    two long groups, every one computed."""
    D, N = 16, 64
    router = jnp.zeros((D, 16)).at[:, 4].set(50.0).at[:, 5].set(40.0)
    params = _share(router)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(10), (N, D))) + 0.1
    out, experts, counts, _ = moe.expert_layer(
        h, params, 2, jnp.ones((N,), bool), True, expert_offset=4, scoring="sigmoid", scale=2.5)
    assert np.asarray(counts).tolist() == [N, N, 0, 0]
    weights, chosen = moe.route(h, router, 2, scoring="sigmoid", scale=2.5)
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    want = jnp.zeros((N, D))
    for slot in range(2):
        g, u, dn = (params[n].astype(jnp.float32)[chosen[:, slot] - 4] for n in ("gate", "up", "down"))
        act = jax.nn.silu(jnp.einsum("nd,ndi->ni", bf(h), g)) * jnp.einsum("nd,ndi->ni", bf(h), u)
        want += weights[:, slot, None] * jnp.einsum("ni,nid->nd", bf(act), dn)
    np.testing.assert_allclose(out, want, rtol=2e-2, atol=2e-2)


def test_no_row_on_held_experts_leaves_the_shared_experts_output_alone():
    """Every row chooses experts 0 and 1, neither held: the routed part is
    exactly zero, and the layer's feed-forward is the shared expert's."""
    D, N = 16, 48
    router = jnp.zeros((D, 16)).at[:, 0].set(50.0).at[:, 1].set(40.0)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(11), (N, D))) + 0.1
    out, experts, counts, _ = moe.expert_layer(
        h, _share(router), 2, jnp.ones((N,), bool), True, expert_offset=4, scoring="sigmoid")
    assert int(counts.sum()) == 0 and sorted(np.unique(np.asarray(experts)).tolist()) == [0, 1]
    np.testing.assert_array_equal(out, jnp.zeros((N, D)))
    # and in the layer: x + attention + shared expert, nothing else
    trunk = MODULE.init_trunk(jax.random.PRNGKey(12))
    w = dict(trunk["layers"][1])
    w["router"] = jnp.zeros_like(w["router"]).at[:, 0].set(1.0).at[:, 1].set(0.9)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(13), (1, 32, 64))) + 0.1
    got, _, seen = MODULE.layer(w, x, jnp.asarray([32]), interpret=True)
    assert int(seen["held_tokens"].sum()) == 0
    from gordo_components_tpu.models.factories.trunk import _rmsnorm, _swiglu

    x2 = x + MODULE._attention(w, x, jnp.asarray([32]), None, True)[0]
    h2 = _rmsnorm(x2, w["mlp_norm"], 1e-6)
    np.testing.assert_allclose(
        got, x2 + _swiglu(h2, w["shared_gate"], w["shared_up"], w["shared_down"]), rtol=1e-6, atol=1e-6)


def test_padding_is_left_out_of_the_held_counts():
    D, N = 16, 32
    params = _share(jax.random.normal(jax.random.PRNGKey(14), (D, 16)))
    h = jax.random.normal(jax.random.PRNGKey(15), (N, D))
    routing = dict(expert_offset=4, scoring="sigmoid", n_group=4, topk_group=2, scale=2.5)
    _, experts, every, _ = moe.expert_layer(h, params, 4, jnp.ones((N,), bool), True, **routing)
    _, _, counts, _ = moe.expert_layer(h, params, 4, jnp.arange(N) < 20, True, **routing)
    local = np.asarray(experts)[:20] - 4
    assert int(counts.sum()) == int(((local >= 0) & (local < 4)).sum()) < int(every.sum())


def test_the_runs_of_the_routed_experts_change_nothing(monkeypatch):
    """A request's rows go through the routed experts in runs of at most
    ``_CHUNKS_A_RUN`` chunks: two runs or one, the same answer and counts."""
    from gordo_components_tpu.models.factories import trunk as trunk_mod

    trunk = MODULE.init_trunk(jax.random.PRNGKey(16))
    x = jax.random.normal(jax.random.PRNGKey(17), (2, 48, 64))
    n_valid = jnp.asarray([48, 30])
    assert MODULE._rows_a_run(96) == 48
    a, _, seen_a = MODULE.layer(trunk["layers"][2], x, n_valid, interpret=True)
    monkeypatch.setattr(trunk_mod, "_CHUNKS_A_RUN", 6)
    assert MODULE._rows_a_run(96) == 96
    b, _, seen_b = MODULE.layer(trunk["layers"][2], x, n_valid, interpret=True)
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(seen_a["experts"], seen_b["experts"])
    np.testing.assert_array_equal(seen_a["held_tokens"], seen_b["held_tokens"])


# a block of 16 sorted pairs in the cases below (two 8-row tiles of the grouped matmul)
_BLOCK = 16


def _planted(per_expert, N, top_k=2, D=32, E=16, first_held=4, seed=21):
    """``N`` rows whose routing is planted: ``per_expert[i]`` rows choose
    held expert ``first_held + i``, every other slot goes to absent experts
    0 and 1. The rows' first E features carry the choice, an identity
    router reads them; the rest is noise. Returns ``(h, router, chosen)``."""
    rng = np.random.default_rng(seed)
    picks = np.repeat(first_held + np.arange(len(per_expert)), per_expert)
    assert max(per_expert, default=0) <= N and picks.size <= N * top_k
    chosen = np.tile(np.arange(top_k), (N, 1))  # absent experts 0, 1: slot by slot
    chosen[np.arange(picks.size) % N, np.arange(picks.size) // N] = picks
    h = rng.normal(size=(N, D)).astype(np.float32)
    h[:, :E] = -4.0
    np.put_along_axis(h, chosen, 4.0 - 0.1 * np.arange(top_k)[None, :], axis=1)  # slot order is score order
    router = np.zeros((D, E), np.float32)
    router[np.arange(E), np.arange(E)] = 1.0
    return jnp.asarray(h), jnp.asarray(router), chosen


def _dense_sum(h, params, weights, chosen, first_held=4):
    """The held experts' part written out slot by slot, every row through
    its own expert's matrices: bfloat16 operands, float32 sums."""
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    held = params["gate"].shape[0]
    want = jnp.zeros(h.shape)
    for slot in range(chosen.shape[1]):
        local = chosen[:, slot] - first_held
        mine = (local >= 0) & (local < held)
        g, u, dn = (params[n].astype(jnp.float32)[np.clip(local, 0, held - 1)] for n in ("gate", "up", "down"))
        act = jax.nn.silu(jnp.einsum("nd,ndi->ni", bf(h), g)) * jnp.einsum("nd,ndi->ni", bf(h), u)
        want += jnp.where(mine[:, None], weights[:, slot, None] * jnp.einsum("ni,nid->nd", bf(act), dn), 0.0)
    return want


@pytest.mark.parametrize("per_expert,N", [
    pytest.param([0, 0, 0, 0], 24, id="no-pair-held"),
    pytest.param([9, 6, 0, 0], 24, id="one-under-a-block"),
    pytest.param([9, 7, 0, 0], 24, id="a-block-exactly"),
    pytest.param([9, 7, 1, 0], 24, id="one-over-a-block"),
    pytest.param([10, 12, 3, 0], 24, id="a-group-straddles-two-blocks"),
    pytest.param([0, 0, 0, 40], 40, id="one-group-over-three-blocks"),
    pytest.param([16, 16, 16, 16], 32, id="every-pair-held"),
])
def test_the_held_pairs_go_through_in_blocks_whose_count_follows_them(monkeypatch, per_expert, N):
    """Whatever the load, every pair on a held expert is computed, in
    ``ceil(held pairs / block)`` trips: none held is no trip and exact
    zeros, every pair held is ``pairs / block`` trips."""
    monkeypatch.setattr(moe, "_BLOCK_PAIRS", _BLOCK)
    h, router, chosen = _planted(per_expert, N)
    params = _share(router, D=32)
    routing = dict(expert_offset=4, scoring="sigmoid", scale=2.5)
    out, experts, counts, blocks = moe.expert_layer(h, params, 2, jnp.ones((N,), bool), True, **routing)
    np.testing.assert_array_equal(experts, chosen)
    assert np.asarray(counts).tolist() == per_expert
    held = sum(per_expert)
    assert int(blocks) == -(-held // _BLOCK)
    weights, _ = moe.route(h, router, 2, scoring="sigmoid", scale=2.5)
    want = _dense_sum(h, params, weights, chosen)
    if held == 0:
        np.testing.assert_array_equal(out, jnp.zeros_like(out))
    assert float(jnp.abs(want).max()) > 1.0 or held == 0
    np.testing.assert_allclose(out, want, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("per_expert", [[9, 7, 1, 0], [3, 0, 0, 2]], ids=["a-block-and-one", "five-pairs"])
def test_rows_no_matmul_wrote_are_masked_not_multiplied(monkeypatch, per_expert):
    """The grouped matmul leaves rows past its last group unwritten, and
    they may hold anything: here NaN is planted there. The output is
    finite and what it is without the NaN: the rows are selected away
    (``where``), not multiplied by zero."""
    real = moe.gmm

    def with_nan_past_the_groups(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        return jnp.where(jnp.arange(lhs.shape[0])[:, None] < jnp.sum(group_sizes), out, jnp.nan)

    monkeypatch.setattr(moe, "_BLOCK_PAIRS", _BLOCK)
    N = 24
    h, router, chosen = _planted(per_expert, N)
    params = _share(router, D=32)
    call = lambda: moe.expert_layer(
        h, params, 2, jnp.ones((N,), bool), True, expert_offset=4, scoring="sigmoid", scale=2.5)
    clean = call()
    monkeypatch.setattr(moe, "gmm", with_nan_past_the_groups)
    planted = call()
    assert bool(jnp.all(jnp.isfinite(planted[0])))
    for got, want in zip(planted, clean):
        np.testing.assert_array_equal(got, want)


def test_a_layer_observes_the_blocks_of_all_its_runs(monkeypatch):
    """``held_blocks`` is summed over a request's runs of rows as
    ``held_tokens`` is: each run's ``ceil(its held pairs / block)``,
    padding's pairs among them."""
    monkeypatch.setattr(moe, "_BLOCK_PAIRS", _BLOCK)
    trunk = MODULE.init_trunk(jax.random.PRNGKey(18))
    x = jax.random.normal(jax.random.PRNGKey(19), (2, 48, 64))
    _, _, seen = MODULE.layer(trunk["layers"][1], x, jnp.asarray([48, 30]), interpret=True)
    local = np.asarray(seen["experts"]).astype(np.int64).reshape(2, 48 * 4) - 4  # a run is a request here
    held_a_run = ((local >= 0) & (local < 4)).sum(axis=1)
    assert held_a_run.min() > _BLOCK  # more than a trip each
    assert int(seen["held_blocks"]) == int(np.ceil(held_a_run / _BLOCK).sum())
    assert int(seen["held_tokens"].sum()) < held_a_run.sum()  # padding is in the blocks, not in the counts


# -------------------------------------------------- the benchmark's counts


def test_the_benchmarks_counts_against_a_count_by_hand():
    full = json.load(open(os.path.join(BENCH, "configs", "axk1_trunk300.json")))
    D, H = 7168, 64
    attention = D * 1536 + 1536 * H * 192 + D * 576 + 512 * H * 256 + H * 128 * D
    assert LAYOUT.attention_matrices(full) == attention == 101_122_048
    assert full["parameters"]["dense_layer"] == attention + 3 * D * 18432
    assert full["parameters"]["routed_layer_held_here"] == attention + D * 192 + 3 * D * 2048 * 13
    assert full["parameters"]["trunk"] == (
        full["parameters"]["dense_layer"] + 5 * full["parameters"]["routed_layer_held_here"])
    assert full["parameters"]["per_machine"] == 2 * 300 * D + D + 300
    rows = 10080
    assert LAYOUT.latent_attention_flops(full, rows) == 2 * H * 320 * rows * (rows + 1) / 2
    assert LAYOUT.latent_attention_bytes(full, rows) == rows * 2 * (H * 192 + 576 + H * 128)
    assert LAYOUT.held_experts_flops(full, 1000) == 1000 * 6 * D * 2048
    assert LAYOUT.held_experts_bytes(full, 2, 3 * rows) == (
        2 * 5 * 12 * 3 * D * 2048 * 2 + 3 * rows * 5 * 2 * D * 4)
    # a request's work, in TFLOP (ISSUE 33's arithmetic): 12.1 + 5 x 5.48 = 39.5
    per_row = LAYOUT.forward_flops_per_row(full)
    assert per_row * rows / 1e12 == pytest.approx(39.5, abs=0.2)
    dense_layer = 2 * (attention + 3 * D * 18432) * rows + LAYOUT.latent_attention_flops(full, rows)
    assert dense_layer / 1e12 == pytest.approx(12.1, abs=0.1)
    # and the program's own count of the same row agrees
    sizes = full["model"]["gordo_components_tpu.models.DiffBasedAnomalyDetector"]["base_estimator"][
        "sklearn.pipeline.Pipeline"]["steps"][-1]["gordo_components_tpu.models.TrunkForecast"]
    sizes = {k: v for k, v in sizes.items() if k not in ("kind", "trunk")}
    module = LatentMoEDecoder(n_features=300, **sizes)
    assert module.forward_flops_per_row(rows) == pytest.approx(per_row, rel=1e-9)
    assert module.layer_shapes(0)["gate"] == (D, 18432) and module.layer_shapes(1)["gate"] == (12, D, 2048)
    program = sum(math.prod(s) for s in module.layer_shapes(1).values() if len(s) > 1)
    assert program == full["parameters"]["routed_layer_held_here"]
    # every published key of the catalog entry stands, but for the two reduced
    assert full["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert (full["published"], full["expert_shard"]["held"]) == (
        {"num_hidden_layers": 61, "n_routed_experts": 192}, [0, 12])
