"""The latent-attention trunk under an indexer's selection that one layer
makes and the next ones reuse (``LatentMoEDecoder`` with ``indexer_types``:
``models/factories/trunk.py``; the selection read by
``ops/latent_attention.py``'s kernel and made by ``ops/sparse_attention.py``'s
``select_keys``; the correction bias of ``ops/moe.py``'s router; the
selection carried by ``server/bank.py``), at a size the CPU holds: hidden
64, 4 heads of 16 + 8 | 24 over ranks 32 and 16, an indexer of 4 x 16 that
keeps 24 keys, 16 experts top 4 of which this chip holds 4-7, 1 dense + 3
routed layers (full, shared, shared, full), 96 rows, 3 machines. The plain
reference is the benchmark's own
(``benchmarks/families/glm52_trunk/forward.py``), which imports nothing of
the program."""

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
from gordo_components_tpu.server.bank import _SHARED_COUNTERS, ModelBank  # noqa: E402
from gordo_components_tpu.utils.wire import TENSOR_CONTENT_TYPE  # noqa: E402
from gordo_components_tpu.utils.wire import pack_frames, unpack_frames  # noqa: E402

F, ROWS, MACHINES, TRUNK_SEED = 5, 96, 3, 35
KINDS = ("full", "shared", "shared", "full")
SIZES = dict(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24, intermediate_size=96,
    moe_intermediate_size=32, first_k_dense_replace=1, n_routed_experts=16, n_shared_experts=1,
    num_experts_per_tok=4, n_group=1, topk_group=1, routed_scaling_factor=2.5,
    topk_method="noaux_tc", rope_theta=8e6, rms_norm_eps=1e-5, index_n_heads=4, index_head_dim=16,
    index_topk=24, indexer_types=list(KINDS), expert_offset=4, experts_held=4, chunk_size=16,
)
# the same sizes under the published config's key names, as the reference reads them
CONFIG = dict(
    family="glm52_trunk", tags_per_machine=F, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=24, intermediate_size=96, moe_intermediate_size=32,
    first_k_dense_replace=1, n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=4,
    n_group=1, topk_group=1, routed_scaling_factor=2.5, rms_norm_eps=1e-5,
    rope_parameters=dict(rope_theta=8e6, rope_type="default"),
    index_n_heads=4, index_head_dim=16, index_topk=24, chunk_size=16,
    held_layers=dict(indexer_types=list(KINDS), mlp_layer_types=["dense", "sparse", "sparse", "sparse"]),
    published=dict(n_routed_experts=16, num_hidden_layers=8, first_k_dense_replace=3),
    expert_shard=dict(chips_sharing_a_layer=4, index=1, held=[4, 8]),
    nominal_request_rows=ROWS, bank_members=MACHINES,
)
MODULE = LatentMoEDecoder(n_features=F, **dict(SIZES, indexer_types=KINDS))
FORWARD = families.load("glm52_trunk", "forward")
LAYOUT = families.load("glm52_trunk", "layout")
STRIDE = 16  # every chunk's last query where a chunk is shorter than 64
SAMPLED = np.arange(STRIDE - 1, ROWS, STRIDE)


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
def trunk():
    """The benchmark's seeded trunk as the program holds it: norm scales,
    the LayerNorm's bias and the router's correction bias away from their
    neutral values."""
    return LAYOUT.trunk_to_program(CONFIG, TRUNK_SEED)


@pytest.fixture(scope="module")
def tree(tmp_path_factory, trunk):
    """Three fitted machines that name one trunk artifact (the seeded
    trunk, written before the first fit) by a relative path, and the models
    as ``serializer.load`` returns them."""
    root = tmp_path_factory.mktemp("selected-latent-trunk-collection")
    serializer.dump_trunk(jax.tree.map(np.asarray, trunk), str(root / "trunk-a"))
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
    held = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), est.trunk_params)
    w = {k: jnp.asarray(v) for k, v in LAYOUT.from_program(est.params_["params"]).items()}
    xs = np.asarray(scaler.transform(X), np.float32)
    sampled = np.arange(STRIDE - 1, len(xs), STRIDE)
    got = FORWARD.forecast(CONFIG, lambda l: held["layers"][l], w, xs, sampled, **how)
    return xs, {k: np.asarray(v) for k, v in got.items()}


def _rel(got, want) -> float:
    return float(np.linalg.norm(np.asarray(got) - np.asarray(want)) / np.linalg.norm(np.asarray(want)))


def _keys(witness, rows: int = ROWS) -> np.ndarray:
    """A ``key-selection`` frame's bits: (layers, sampled, rows) bool."""
    bits = np.unpackbits(np.asarray(witness), axis=-1, bitorder="little")
    return bits[:, : len(np.arange(STRIDE - 1, rows, STRIDE)), :rows].astype(bool)


# ----------------------------------------------------------- the kernel


def _heads(T=64, H=4, nope=16, dr=8, dv=24, seed=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    bf = lambda k, shape: jax.random.normal(k, shape).astype(jnp.bfloat16)
    return (bf(keys[0], (H, T, nope)), bf(keys[1], (H, T, dr)), bf(keys[2], (H, T, nope)),
            bf(keys[3], (T, dr)), bf(keys[4], (H, T, dv)))


def _written_out(qn, qr, kn, kr, v, keep):
    f = lambda a: a.astype(jnp.float32)
    logits = jnp.einsum("htn,hsn->hts", f(qn), f(kn)) + jnp.einsum("htr,sr->hts", f(qr), f(kr))
    p = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
    return jnp.einsum("hts,hsv->htv", f(p.astype(jnp.bfloat16)), f(v))


@pytest.mark.parametrize("kept", [3, 12, 40])
def test_the_kernel_attends_under_a_selection(kept):
    """``latent_attention`` under a (T, T) int8 selection against the sum
    it is, written out: every head under the one mask, a row's keys
    anywhere at or before it. With 3 keys a row of 64 most rows keep no key
    in their first tiles of 16, and what they summed there is gone when
    their first kept key comes."""
    T = 64
    heads = _heads(T)
    rng = np.random.default_rng(kept)
    keep = np.zeros((T, T), bool)
    for t in range(T):
        keep[t, rng.choice(t + 1, size=min(kept, t + 1), replace=False)] = True
    if kept == 3:
        assert (~keep[:, :16].any(axis=1)).sum() > 10
    got = latent_attention.latent_attention(
        *heads, granule=16, interpret=True, selection=jnp.asarray(keep, jnp.int8))
    assert got.shape == (4, T, 24) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), _written_out(*heads, keep), rtol=3e-2, atol=3e-2)
    # a key that is not selected changes nothing: nor its value
    t, s = 50, int(np.flatnonzero(~keep[50, :51])[0])
    qn, qr, kn, kr, v = heads
    again = latent_attention.latent_attention(
        qn, qr, kn.at[:, s].set(9.0), kr, v.at[:, s].set(-9.0), granule=16, interpret=True,
        selection=jnp.asarray(keep, jnp.int8))
    np.testing.assert_array_equal(again[:, t], got[:, t])


def test_without_a_selection_the_kernel_is_what_it_was():
    """No selection: the causal program, and a selection that keeps every
    causal key gives its output bit for bit (it masks what the diagonal's
    rule masks and nothing else)."""
    T = 64
    heads = _heads(T, seed=9)
    causal = jnp.tril(jnp.ones((T, T), jnp.int8))
    plain = latent_attention.latent_attention(*heads, granule=16, interpret=True)
    under_all = latent_attention.latent_attention(*heads, granule=16, interpret=True, selection=causal)
    np.testing.assert_array_equal(plain, under_all)
    np.testing.assert_allclose(
        plain.astype(jnp.float32), _written_out(*heads, np.tril(np.ones((T, T), bool))), rtol=3e-2, atol=3e-2)
    # one tile size at both trunks' widths (the ladder, ops/latent_attention.py)
    assert latent_attention.tiling(10240, 512) == (1024, 2) and latent_attention.tiling(64, 16) == (16, 4)


# --------------------------------------------------------- the selection


def test_the_selection_is_a_function_of_its_own():
    """``select_keys`` alone gives the mask ``select_and_attend`` attends
    under, its count and its witness; a chunk that sees no more keys than
    ``topk`` keeps every causal key."""
    T, J, dI, G, R, d = 64, 4, 16, 2, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    qi, ki, wi = (jax.random.normal(keys[0], (T, J, dI)), jax.random.normal(keys[1], (T, dI)),
                  jax.random.normal(keys[2], (T, J)))
    n_valid = jnp.asarray(57)
    mask, count, witness = sparse_attention.select_keys(qi, ki, wi, n_valid, topk=20, chunk=16)
    assert mask.shape == (T, T) and mask.dtype == jnp.bool_
    mask = np.asarray(mask)
    assert not np.triu(mask, 1).any() and mask.any(axis=1).all()
    np.testing.assert_array_equal(mask[:16], np.tril(np.ones((T, T), bool))[:16])  # 16 keys <= topk
    per_row = mask[:57].sum(axis=1)
    least = np.minimum(np.arange(57) + 1, 20)
    assert (per_row >= least).all() and (per_row <= least + 2).all()  # ties with the 20th are kept
    assert int(count) == int(per_row.sum())
    assert not mask[:57, 57:].any()  # padded keys hide from valid queries
    np.testing.assert_array_equal(
        np.unpackbits(np.asarray(witness), axis=-1, bitorder="little").astype(bool), mask[15::16])
    q, k, v = (jax.random.normal(keys[3], (T, G * R, d)), jax.random.normal(keys[4], (T, G, d)),
               jax.random.normal(keys[5], (T, G, d)))
    _, again_count, again_witness = sparse_attention.select_and_attend(
        q, k, v, qi, ki, wi, n_valid, topk=20, chunk=16, interpret=True)
    assert int(again_count) == int(count)
    np.testing.assert_array_equal(again_witness, witness)
    # the divisor scales the scores and moves no selection (a power of two: exact)
    scaled, _, _ = sparse_attention.select_keys(qi, ki, wi, n_valid, topk=20, chunk=16, divisor=8.0)
    np.testing.assert_array_equal(scaled, mask)


# ------------------------------------------------------------- the router


def test_the_bias_moves_the_choice_and_not_the_weights():
    h = jax.random.normal(jax.random.PRNGKey(2), (200, 16))
    w = jax.random.normal(jax.random.PRNGKey(3), (16, 12))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (12,))
    scores = jax.nn.sigmoid(jnp.dot(h, w, precision="highest"))
    plain_w, plain_e = moe.route(h, w, 3, scoring="sigmoid", scale=2.5)
    weights, experts = moe.route(h, w, 3, scoring="sigmoid", scale=2.5, bias=bias)
    np.testing.assert_array_equal(experts, jax.lax.top_k(scores + bias, 3)[1])
    moved = float((np.sort(experts, -1) != np.sort(plain_e, -1)).any(-1).mean())
    assert 0.2 < moved < 1.0
    kept = jnp.take_along_axis(scores, experts, axis=-1)  # the UNBIASED scores of the kept
    np.testing.assert_allclose(weights, 2.5 * kept / kept.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    # zero bias is today's router, choice and weights
    zero_w, zero_e = moe.route(h, w, 3, scoring="sigmoid", scale=2.5, bias=jnp.zeros((12,)))
    np.testing.assert_array_equal(zero_e, plain_e)
    np.testing.assert_array_equal(zero_w, plain_w)


def test_the_bias_enters_the_groups_choice_too():
    # 8 experts in 4 groups of 2, keep 1 group then top 2: unbiased, group (0, 1) wins;
    # a bias on group (6, 7) moves the choice there, and the weights stay the scores'
    h = jnp.ones((1, 1))
    router = jnp.asarray([[3.0, 2.9, -9, -9, -9, -9, 2.0, 1.0]])
    how = dict(scoring="sigmoid", n_group=4, topk_group=1, scale=2.5)
    _, free = moe.route(h, router, 2, **how)
    assert sorted(np.asarray(free[0]).tolist()) == [0, 1]
    bias = jnp.zeros((8,)).at[6].set(0.3).at[7].set(0.3)
    weights, experts = moe.route(h, router, 2, bias=bias, **how)
    assert sorted(np.asarray(experts[0]).tolist()) == [6, 7]
    s = jax.nn.sigmoid(jnp.asarray([2.0, 1.0]))
    np.testing.assert_allclose(np.sort(np.asarray(weights[0])), np.sort(2.5 * s / s.sum()), rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer_with_the_bias():
    """The share tied to the model: the routed parts of all four chips'
    shares, with the shared expert (which every chip computes alike)
    counted once, are what the uncut reference gives for the whole layer,
    the correction bias in the choice: in the reference, and in the
    program's ``expert_layer`` handed each range in turn."""
    whole = dict(CONFIG, n_routed_experts=16, expert_shard=dict(chips_sharing_a_layer=1, index=0, held=[0, 16]))
    shares = [dict(CONFIG, expert_shard=dict(chips_sharing_a_layer=4, index=i, held=[4 * i, 4 * i + 4]))
              for i in range(4)]
    seed = 35
    w_whole = LAYOUT.trunk_layer(whole, seed, 1)
    assert float(jnp.abs(w_whole["router_bias"]).max()) > 0.015  # drawn away from zero
    h = jax.random.normal(jax.random.PRNGKey(5), (ROWS, 64))
    with jax.default_matmul_precision("highest"):
        routed, shared, kept = FORWARD.ffn_parts(whole, w_whole, h)
        parts = [FORWARD.ffn_parts(c, LAYOUT.trunk_layer(c, seed, 1), h) for c in shares]
        _, _, unbiased = FORWARD.ffn_parts(whole, w_whole, h, fault="no_correction_bias")
    np.testing.assert_allclose(sum(p[0] for p in parts), routed, rtol=1e-5, atol=1e-6)
    for part in parts:
        np.testing.assert_array_equal(part[1], shared)  # every chip's shared expert is the same
        np.testing.assert_array_equal(part[2], kept)  # and so is the routing, over all 16
    assert int(kept.sum()) == ROWS * 4
    assert int((kept != unbiased).sum()) > 0  # the bias does move choices of these rows
    # the program, share by share, against the whole layer it computes when it holds all 16
    routing = dict(scoring="sigmoid", n_group=1, topk_group=1, scale=2.5)
    as_program = lambda w: {k: (v if v.ndim == 1 else v.astype(jnp.bfloat16)) for k, v in w.items()}
    valid = jnp.ones((ROWS,), bool)
    full, experts, counts, _ = moe.expert_layer(h, as_program(w_whole), 4, valid, True, **routing)
    np.testing.assert_array_equal(
        np.sort(experts, -1), np.sort(np.argsort(~np.asarray(kept), -1, kind="stable")[:, :4], -1))
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


# ----------------------------------------------------- the trunk's layers


def _member(seed: int = 0):
    rng = np.random.default_rng(seed)
    w = {n: (rng.random(s, dtype=np.float32) * 2 - 1) * lim for n, s, lim in LAYOUT.layer_shapes(CONFIG)}
    return w, jax.tree.map(lambda a: jnp.asarray(a)[None], LAYOUT.to_program(CONFIG, w))


def test_the_layers_hold_what_the_published_keys_say(trunk):
    assert [sorted(k for k in w if k.startswith("idx_")) != [] for w in trunk["layers"]] == [
        True, False, False, True]
    assert ["router" in w for w in trunk["layers"]] == [False, True, True, True]
    assert all(("router_bias" in w) == ("router" in w) for w in trunk["layers"])
    for index, w in enumerate(trunk["layers"]):
        assert {k: v.shape for k, v in w.items()} == MODULE.layer_shapes(index)
        assert w["idx_wq"].shape == (32, 4 * 16) if "idx_wq" in w else True  # from the query latent
    assert MODULE.layer_shapes() == MODULE.layer_shapes(-1) == MODULE.layer_shapes(3)
    # without the keys: no indexer, no bias, today's leaves
    plain = LatentMoEDecoder(n_features=F, **{
        k: v for k, v in SIZES.items() if k not in ("indexer_types", "topk_method")})
    assert not any(k.startswith("idx_") or k == "router_bias" for i in range(4) for k in plain.layer_shapes(i))
    two_dense = LatentMoEDecoder(n_features=F, **dict(SIZES, first_k_dense_replace=2, indexer_types=KINDS))
    assert "router" not in two_dense.layer_shapes(1) and "router" in two_dense.layer_shapes(2)
    with pytest.raises(ValueError, match="indexer_types"):
        LatentMoEDecoder(n_features=F, **dict(SIZES, indexer_types=("shared", "full", "full", "full")))
    with pytest.raises(ValueError, match="indexer_types"):
        LatentMoEDecoder(n_features=F, **dict(SIZES, indexer_types=("full", "shared")))


def test_the_trunk_matches_the_plain_reference_on_seeded_weights(trunk):
    """``module.apply`` over full and shared layers against
    ``families/glm52_trunk/forward.py``: the forecast, the experts of every
    routed layer, the keys every layer attended under. The program is as
    near the float32 reference as the reference is with bfloat16 operands
    (what the model states); float8 operands are far off."""
    w, member = _member()
    xs = np.random.default_rng(1).random((ROWS, F), dtype=np.float32)
    out, seen = MODULE.apply(trunk, member, jnp.asarray(xs)[None], jnp.asarray([ROWS]), interpret=True)
    how = lambda **kw: FORWARD.forecast(
        CONFIG, lambda l: LAYOUT.trunk_layer(CONFIG, TRUNK_SEED, l), {k: jnp.asarray(v) for k, v in w.items()},
        xs, SAMPLED, **kw)
    exact, stated, control = how(), how(operands="bfloat16"), how(operands="float8_e4m3fn")
    bound = 1.5 * _rel(stated["out"], exact["out"]) + 0.005
    assert _rel(out[0], exact["out"]) < bound
    assert _rel(control["out"], exact["out"]) > 2 * bound  # (3 x before q_a_norm was drawn on [2, 4))
    # what the layers observed: 3 routed layers' experts, 2 full layers' selections, 4 uses and
    # the witness of each: what every layer attended under, made there or handed on
    assert seen["experts"].shape == (3, 1, ROWS, 4) and seen["held_tokens"].shape == (3, 4)
    assert seen["witness"].shape == (4, 1, ROWS // STRIDE, ROWS // 8) and seen["selections"].shape == (2, 1)
    for shared_layer in (1, 2):
        np.testing.assert_array_equal(seen["witness"][shared_layer], seen["witness"][0])
    assert (np.asarray(seen["witness"][3]) != np.asarray(seen["witness"][0])).any()
    assert seen["selection_uses"].shape == (4,) and int(seen["selection_uses"].sum()) == 4
    experts = np.asarray(seen["experts"][:, 0]).astype(np.int64)
    assert np.take_along_axis(np.asarray(exact["experts"]), experts, axis=-1).mean() > 0.97
    keys, want = _keys(seen["witness"][:, 0]), np.asarray(exact["keys"])
    assert keys.shape == want.shape == (4, len(SAMPLED), ROWS)
    assert (keys & want).sum() / max(keys.sum(), want.sum()) > 0.97
    by_hand = 24 * 25 // 2 + (ROWS - 24) * 24  # min(t + 1, 24) a query; ties with the 24th add a few
    assert all(by_hand <= int(n) <= 1.05 * by_hand for n in seen["selections"][:, 0])


def test_a_shared_layer_attends_under_the_selection_it_is_handed(trunk):
    x = jax.random.normal(jax.random.PRNGKey(21), (1, ROWS, 64))
    n_valid = jnp.asarray([ROWS])
    full, shared = trunk["layers"][0], trunk["layers"][1]
    x1, made, seen = MODULE.layer(full, x, n_valid, None, True)
    assert made.shape == (1, ROWS, ROWS) and made.dtype == jnp.int8
    assert set(seen) == {"witness", "selections", "selection_uses"}  # a dense layer routes nothing
    x2, handed_on, seen2 = MODULE.layer(shared, x1, n_valid, made, True)
    assert handed_on is made and int(seen2["selection_uses"]) == 1
    # its witness is cut from what its kernel read: the selection it was handed
    np.testing.assert_array_equal(seen2["witness"], seen["witness"])
    # another selection, another answer; every causal key (none handed), another again
    rng = np.random.default_rng(3)
    other = np.tril(rng.random((ROWS, ROWS)) < 0.3) | np.eye(ROWS, dtype=bool)
    x2_other, _, seen_other = MODULE.layer(shared, x1, n_valid, jnp.asarray(other, jnp.int8)[None], True)
    np.testing.assert_array_equal(_keys(seen_other["witness"])[0], other[STRIDE - 1 :: STRIDE])
    x2_all, none, seen_all = MODULE.layer(shared, x1, n_valid, None, True)
    assert none is None and not set(seen_all) & {"selection_uses", "witness"}
    assert _rel(x2_other, x2) > 1e-3 and _rel(x2_all, x2) > 1e-3
    # the last layer is full: it attends under its own selection, not the one it is handed
    x3, own, seen3 = MODULE.layer(trunk["layers"][3], x2, n_valid, made, True)
    x3_again, own_again, _ = MODULE.layer(trunk["layers"][3], x2, n_valid, jnp.asarray(other, jnp.int8)[None], True)
    np.testing.assert_array_equal(own, own_again)
    np.testing.assert_array_equal(x3, x3_again)
    assert (np.asarray(own) != np.asarray(made)).mean() > 0.01
    np.testing.assert_array_equal(_keys(seen3["witness"])[0], np.asarray(own[0, STRIDE - 1 :: STRIDE]) != 0)


def test_all_full_gives_another_answer(trunk):
    """``indexer_types`` all ``full``: every layer selects for itself, so
    the layers that shared layer 0's selection answer otherwise."""
    every = LatentMoEDecoder(n_features=F, **dict(SIZES, indexer_types=("full",) * 4))
    layers = []
    for index, w in enumerate(trunk["layers"]):
        extra = {k: trunk["layers"][0][k] for k in ("idx_wq", "idx_wk", "idx_k_scale", "idx_k_bias", "idx_ww")}
        layers.append({**extra, **w})  # a full layer keeps its own indexer
        assert {k: v.shape for k, v in layers[-1].items()} == every.layer_shapes(index)
    _, member = _member()
    xs = jnp.asarray(np.random.default_rng(1).random((1, ROWS, F), dtype=np.float32))
    n_valid = jnp.asarray([ROWS])
    shared_out, seen = MODULE.apply(trunk, member, xs, n_valid, interpret=True)
    full_out, seen_full = every.apply({**trunk, "layers": layers}, member, xs, n_valid, interpret=True)
    assert seen_full["witness"].shape[0] == seen["witness"].shape[0] == 4
    assert seen_full["selections"].shape[0] == 4 and seen["selections"].shape[0] == 2
    np.testing.assert_array_equal(seen_full["witness"][0], seen["witness"][0])  # layer 0 is the same layer
    assert (np.asarray(seen_full["witness"][1]) != np.asarray(seen["witness"][1])).any()  # layer 1 selects anew
    assert _rel(full_out, shared_out) > 1e-3


def test_a_trunk_without_the_keys_is_the_trunk_it_was():
    """No ``indexer_types``: no selection is made, handed on or observed,
    and no counter of them appears (``axk1_trunk300``'s program)."""
    plain = LatentMoEDecoder(n_features=F, **{
        k: v for k, v in SIZES.items() if k not in ("indexer_types", "topk_method")})
    trunk = plain.init_trunk(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 64))
    for w in trunk["layers"]:
        x, selection, seen = plain.layer(w, x, jnp.asarray([32]), None, True)
        assert selection is None and not set(seen) & {"witness", "selections", "selection_uses"}


# ------------------------------------------------------------------- bank


def test_the_bank_carries_the_selection_as_apply_does(bank, tree):
    """``_Bucket.score_batch`` (three programs, the selection handed from
    one ``score_layer`` call to the next on the device) against
    ``module.apply`` (one program): the same forecast and observations."""
    (bucket,) = bank._buckets.values()
    det = tree[1]["m1"]
    scaler, est = det.base_estimator.steps[0][1], det.base_estimator.steps[-1][1]
    X = machine_rows(1, ROWS)
    got = bank.score("m1", X)
    xs = np.asarray(scaler.transform(X), np.float32)
    member = jax.tree.map(lambda a: jnp.asarray(a)[None], est.params_["params"])
    out, seen = jax.jit(lambda *a: MODULE.apply(*a, interpret=True))(
        est.trunk_params, member, jnp.asarray(xs)[None], jnp.asarray([ROWS], jnp.int32))
    # two compilations of one arithmetic: a near-tie among 24 keys or 16 experts may flip
    # (read here: 0.37% of the output's norm, 0.17% of either kind of selection)
    assert _rel(got.model_output, out[0, :-1]) < 1e-2
    assert (got.selections["expert-selection"] == np.asarray(seen["experts"][:, 0])).mean() > 0.99
    assert (_keys(got.selections["key-selection"]) == _keys(seen["witness"][:, 0])).mean() > 0.995
    # three kinds of layer, three programs, whatever the depth
    assert bucket._layer._cache_size() == 3
    bank.score("m2", machine_rows(2, ROWS))
    assert bucket._layer._cache_size() == 3


@pytest.mark.parametrize("pair", [(0, 1), (1, 2), (2, 0)])
def test_two_machines_batched_get_the_answers_they_get_alone(bank, pair):
    Xs = [machine_rows(i, ROWS) for i in pair]
    together = bank.score_many([(f"m{i}", X, None) for i, X in zip(pair, Xs)])
    for i, X, both in zip(pair, Xs, together):
        alone = bank.score(f"m{i}", X)
        np.testing.assert_array_equal(both.model_output, alone.model_output)
        assert set(both.selections) == {"expert-selection", "key-selection"}
        for name in both.selections:
            np.testing.assert_array_equal(both.selections[name], alone.selections[name])


@pytest.mark.parametrize("rows", [17, 50, 90])
def test_padding_is_left_out_of_the_counters(bank, tree, rows):
    """Any length goes as one call; the padding changes nothing and is
    counted nowhere but in ``tokens``."""
    X = machine_rows(1, rows)
    before = dict(bank.shared_stats)
    got = bank.score("m1", X)
    after = bank.shared_stats
    grew = lambda name: after[name] - before.get(name, 0)
    assert grew("dispatches") == 1 and grew("rows") == rows
    assert grew("tokens") == MODULE.padded_rows(rows)
    assert grew("routed_pairs") == rows * 4 * 3
    assert (grew("selection_layers"), grew("selection_uses")) == (2, 4)
    _, exact = _reference(tree[1], "m1", X)
    assert abs(grew("held_pairs") - int(exact["experts"][:, :, 4:8].sum())) <= 0.03 * rows * 12 + 1
    by_hand = 2 * sum(min(t + 1, 24) for t in range(rows))  # valid queries only, two full layers
    assert by_hand <= grew("key_selections") <= 1.25 * by_hand  # ties with the 24th (exact zeros) are kept
    assert got.model_output.shape == (rows - 1, F)
    assert _rel(got.model_output, exact["out"][:-1]) < 0.08  # 24 keys a row under a sharp softmax
    keys = _keys(got.selections["key-selection"], rows)
    assert not keys[:, :, rows:].any() if keys.shape[-1] > rows else True


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


def test_the_programs_bytes_count_the_selection():
    """At the published sizes the selection and the indexer's dots are in
    the count (the mask as handed in, as handed on and as built; a chunk's
    32 heads of dots): without ``indexer_types`` the same sizes count less
    by exactly that, and ``axk1_trunk300``'s count stands."""
    sizes = json.load(open(os.path.join(BENCH, "configs", "glm52_trunk300.json")))
    sizes = sizes["model"]["gordo_components_tpu.models.DiffBasedAnomalyDetector"]["base_estimator"][
        "sklearn.pipeline.Pipeline"]["steps"][-1]["gordo_components_tpu.models.TrunkForecast"]
    sizes = {k: v for k, v in sizes.items() if k not in ("kind", "trunk")}
    module = LatentMoEDecoder(n_features=300, **dict(sizes, indexer_types=tuple(sizes["indexer_types"])))
    plain = LatentMoEDecoder(n_features=300, **{k: v for k, v in sizes.items() if k != "indexer_types"})
    T = module.padded_rows(10080)
    assert T == 10240
    selection = 3 * T * T + 4 * 512 * T * 32
    assert module.program_bytes(1, T) - plain.program_bytes(1, T) == selection
    assert module.program_bytes(2, T) - plain.program_bytes(2, T) == selection + 2 * T * T
    # beside the 7.29 GB trunk and the bank of 128 a v5e leaves room for one request a call
    assert module.program_bytes(1, T) < 5.5e9 < module.program_bytes(2, T)
    axk1 = LatentMoEDecoder(n_features=300, num_hidden_layers=6, experts_held=12)
    assert axk1.program_bytes(1, 10240) == 3_103_784_960


def test_flops_count_the_selected_pairs_and_the_indexer(bank):
    from gordo_components_tpu.observability.cost import estimate_flops_per_row

    (row,) = bank.flops_stats().values()
    flops, method = estimate_flops_per_row(MODULE, F, 1, 123)
    assert row["flops_per_row"] == flops and method == "analytic:context=320"
    attention = 64 * 32 + 32 * 4 * 24 + 64 * 24 + 16 * 4 * 40 + 4 * 24 * 64
    indexer = 32 * 4 * 16 + 64 * 16 + 64 * 4
    routed = 64 * 16 + 3 * 64 * 32 + 3 * 64 * 32 * 4 * 4 / 16  # router, shared, 4 of 16 held of a row's 4
    by_hand = 2.0 * (4 * attention + 2 * indexer + 3 * 64 * 96 + 3 * routed + 2 * F * 64)
    selected = 24 * 25 // 2 + (320 - 24) * 24
    assert MODULE.selected_pairs(320) == selected and MODULE.selected_pairs(20) == 20 * 21 // 2
    by_hand += 4 * 2.0 * 4 * (16 + 8 + 24) * selected / 320  # every layer attends under a selection
    by_hand += 2 * 2.0 * 4 * 16 * (320 + 1) / 2  # two full layers' indexer scores, every causal pair
    assert flops == pytest.approx(by_hand)
    two_dense = LatentMoEDecoder(n_features=F, **dict(SIZES, first_k_dense_replace=2, indexer_types=KINDS))
    assert two_dense.forward_flops_per_row(320) - flops == pytest.approx(2.0 * (3 * 64 * 96 - routed))


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
    ModelBank -> BatchingEngine -> POST, against the reference: the
    forecast, both frames of selections, the counters on ``/stats`` and in
    the scrape.

    Tolerance (``tests/test_latent_trunk.py`` has the reasoning for 1.5
    times the stated arithmetic's distance plus 0.5%): here a second kind
    of choice is discontinuous, and coarser at this size: one of a row's 24
    keys flipped on rounding is 4% of what that row attends to, in this
    layer and in those that share the selection (one of 2048 at the
    published size). So twice the stated arithmetic's distance plus 1%,
    which float8 operands still miss by half again (twofold before the
    query latent's scale was drawn on [2, 4): among 24 keys a row the
    sharper softmax makes a flipped key count for more)."""
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
        scrape = await (await client.get("/gordo/v0/proj/metrics")).text()
    np.testing.assert_array_equal(got["model-input"], X[1:])
    assert got["model-output"].shape == (ROWS - 1, F)
    xs, exact = _reference(models, f"m{machine}", X)
    _, stated = _reference(models, f"m{machine}", X, operands="bfloat16")
    _, control = _reference(models, f"m{machine}", X, operands="float8_e4m3fn")
    bound = 2 * _rel(stated["out"], exact["out"]) + 0.01
    assert _rel(got["model-output"], exact["out"][:-1]) < bound
    assert _rel(control["out"], exact["out"]) > 1.5 * bound  # one precision lower fails
    diff = np.abs(xs[1:] - got["model-output"])
    np.testing.assert_allclose(got["tag-anomaly-unscaled"], diff, rtol=1e-5, atol=1e-6)
    # the frames: the routed layers' experts, of all 16; every layer's keys (what it attended under), every 16th query's
    experts = got["expert-selection"].astype(np.int64)
    assert experts.shape == (3, ROWS, 4) and experts.max() < 16
    assert np.take_along_axis(exact["experts"], experts, axis=-1).mean() > 0.97
    assert got["key-selection"].shape == (4, ROWS // STRIDE, ROWS // 8)  # every layer attended under one
    keys = _keys(got["key-selection"])
    assert (keys & exact["keys"]).sum() / max(keys.sum(), exact["keys"].sum()) > 0.97
    # the counters: both kinds' where a layer observed them, the new two, by hand
    shared = stats["bank_shared"]
    assert shared["dispatches"] >= 1 and shared["routed_pairs"] == ROWS * 4 * 3
    assert abs(shared["held_pairs"] - int(exact["experts"][:, :, 4:8].sum())) <= 0.03 * ROWS * 12
    assert (shared["selection_layers"], shared["selection_uses"]) == (2, 4)
    by_hand = 2 * (24 * 25 // 2 + (ROWS - 24) * 24)
    # keys that tie with the 24th are kept: under the indexer's ReLU many scores are exactly 0
    assert by_hand <= shared["key_selections"] <= 1.25 * by_hand
    assert "expert_tokens" not in shared  # that is the kind that holds every expert
    for name in ("key_selections", "selection_layers", "selection_uses", "held_pairs", "held_pair_blocks"):
        assert f"gordo_bank_shared_{name}_total {shared[name]}" in scrape.replace(".0\n", "\n")


async def test_a_server_that_is_cleaned_up_holds_no_device_memory(tree):
    """aiohttp keeps a cleaned-up application alive (its middleware cache
    holds the last 1024), and the application holds the bank: clean-up
    releases the bank's buckets, so the stacked weights and the trunk do
    not stay on the device with it (on the chip, 9.3 GB that the
    benchmark's reference needs: PERF.md section 6, PR 35)."""
    import gc
    import weakref

    async with _client(tree[0]) as client:
        bank = client.app["bank"]
        (bucket,) = bank._buckets.values()
        leaves = [weakref.ref(leaf) for leaf in jax.tree.leaves((bucket.params, bucket.scalers))]
        assert len(bank) == MACHINES and bucket.shared is not None
        del bucket
    assert len(bank) == 0 and bank._buckets == {}
    gc.collect()
    assert all(ref() is None for ref in leaves)  # nothing else held the stacks
    with pytest.raises(KeyError):
        bank.score("m0", machine_rows(0, ROWS))


def test_the_counters_are_scraped():
    assert {"key_selections", "selection_layers", "selection_uses"} <= set(_SHARED_COUNTERS)


# -------------------------------------------------- the benchmark's counts


def test_the_benchmarks_counts_against_a_count_by_hand():
    full = json.load(open(os.path.join(BENCH, "configs", "glm52_trunk300.json")))
    D, H = 6144, 64
    attention = D * 2048 + 2048 * H * 256 + D * 576 + 512 * H * 448 + H * 256 * D
    indexer = 2048 * 32 * 128 + D * 128 + D * 32
    assert LAYOUT.attention_matrices(full) == attention == 165_019_648
    assert LAYOUT.indexer_matrices(full) == indexer == 9_371_648
    p = full["parameters"]
    assert p["dense_layer_full"] == attention + indexer + 3 * D * 12288 == 400_883_712
    assert p["routed_layer_shared_held_here"] == attention + D * 256 + 3 * D * 2048 * 17 == 808_321_024
    assert p["routed_layer_full_held_here"] == p["routed_layer_shared_held_here"] + indexer
    assert p["trunk"] == (p["dense_layer_full"] + 3 * p["routed_layer_shared_held_here"]
                          + p["routed_layer_full_held_here"]) == 3_643_539_456
    assert p["per_machine"] == 2 * 300 * D + D + 300
    rows = 10080
    selected = 2048 * 2049 // 2 + (rows - 2048) * 2048
    assert LAYOUT.selected_pairs(full, rows) == selected
    assert 0.36 < selected / LAYOUT.causal_pairs(rows) < 0.37  # about a third of the causal pairs
    assert LAYOUT.selected_attention_flops(full, rows) == 2 * H * 512 * selected
    assert LAYOUT.selected_attention_bytes(full, 3 * rows, rows) == 3 * rows * (
        2 * (H * 256 + 576 + H * 256) + (rows + 1) / 16)
    assert LAYOUT.indexer_flops(full, rows) == 2 * 32 * 128 * rows * (rows + 1) / 2
    assert LAYOUT.held_experts_flops(full, 1000) == 1000 * 6 * D * 2048
    assert LAYOUT.held_experts_bytes(full, 2, 3 * rows) == (
        2 * 4 * 16 * 3 * D * 2048 * 2 + 3 * rows * 4 * 2 * D * 4)
    assert (LAYOUT.routed_layers(full), LAYOUT.full_layers(full)) == (4, 2)
    assert LAYOUT.witness_stride(full) == 64
    # and the program's own count of the same row agrees
    sizes = full["model"]["gordo_components_tpu.models.DiffBasedAnomalyDetector"]["base_estimator"][
        "sklearn.pipeline.Pipeline"]["steps"][-1]["gordo_components_tpu.models.TrunkForecast"]
    sizes = {k: v for k, v in sizes.items() if k not in ("kind", "trunk")}
    module = LatentMoEDecoder(n_features=300, **dict(sizes, indexer_types=tuple(sizes["indexer_types"])))
    per_row = LAYOUT.forward_flops_per_row(full)
    assert module.forward_flops_per_row(rows) == pytest.approx(per_row, rel=1e-9)
    assert module.selected_pairs(rows) == selected
    assert module.layer_shapes(0)["gate"] == (D, 12288) and module.layer_shapes(1)["gate"] == (16, D, 2048)
    assert module.layer_shapes(0)["idx_wq"] == (2048, 32 * 128) and "idx_wq" not in module.layer_shapes(2)
    for index, name in ((0, "dense_layer_full"), (1, "routed_layer_shared_held_here"),
                        (4, "routed_layer_full_held_here")):
        assert sum(math.prod(s) for s in module.layer_shapes(index).values() if len(s) > 1) == p[name]
    # every published key of the catalog entry stands, but for the three reduced
    assert full["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts"]
    assert (full["published"], full["expert_shard"]["held"]) == (
        {"num_hidden_layers": 78, "first_k_dense_replace": 3, "n_routed_experts": 256}, [0, 16])
    assert len(full["indexer_types"]) == len(full["mlp_layer_types"]) == 78  # as published
    held = full["held_layers"]
    assert held["indexer_types"] == [full["indexer_types"][i] for i in held["published_index"]] == [
        "full", "shared", "shared", "shared", "full"] == sizes["indexer_types"]
    assert held["mlp_layer_types"] == [full["mlp_layer_types"][i] for i in held["published_index"]] == [
        "dense", "sparse", "sparse", "sparse", "sparse"]
    assert (full["hidden_size"], full["q_lora_rank"], full["kv_lora_rank"], full["intermediate_size"],
            full["moe_intermediate_size"], full["index_topk"], full["routed_scaling_factor"]) == (
        6144, 2048, 512, 12288, 2048, 2048, 2.5)


# ------------------------------------------------- the benchmark's readers

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
SELECTED = 2048 * 2049 // 2 + (10080 - 2048) * 2048
READERS = {
    # 6 requests x 5 layers under a selection x 2 . 64 . 512 . the SELECTED pairs, at the peak, over 0.96 s
    "selected_latent_attention_roofline.serve": 100 * 6 * 5 * 2 * 64 * 512 * SELECTED / 197e12 / 0.96,
    "selection_device_ms.serve": 1e3 * (0.012 + 0.008),  # indexer + select, a dispatch
    "selection_reuse.serve": 2.5,  # 5 uses of 2 selections
}


def _recorded(**changes):
    """What a traced run of ``glm52_trunk300.week`` observes, at the published sizes."""
    obs = {
        "config": json.load(open(os.path.join(BENCH, "configs", "glm52_trunk300.json"))),
        "request_rows": 10080, "peaks": PEAKS, "engine": {"batches": 30, "requests": 30},
        "trace": {"module_calls": {"jit_score": 6}},
        "scopes": {"trunk/attend": 6 * 0.16, "trunk/indexer": 6 * 0.012, "trunk/select": 6 * 0.008},
        "shared": {"dispatches": 32, "selection_layers": 32 * 2, "selection_uses": 32 * 5},
    }
    obs.update(changes)
    return obs


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_new_reader_by_hand_and_none_where_there_is_nothing_to_read(metric):
    """``benchmarks/layer_metrics/<metric>.py`` as the benchmark's run finds
    it: the value by hand on a recorded observation; ``None``, never 0, on
    a program without this PR's scopes and counters (the parent's), on an
    untraced run (the two device metrics) and on nothing at all."""
    from harness import spec

    read = spec.load_reader(metric)
    assert read(_recorded()) == pytest.approx(READERS[metric])
    parent = _recorded(scopes={"trunk/attend": 1.0, "trunk/experts": 0.2},
                       shared={"dispatches": 32, "routed_pairs": 9, "held_pairs": 1})
    assert read(parent) is None and read({}) is None
    if metric != "selection_device_ms.serve":  # the two that read the counters: no layer selected
        assert read(_recorded(shared={"dispatches": 32, "selection_layers": 0, "selection_uses": 0})) is None
    if metric != "selection_reuse.serve":  # a device metric: nothing without a trace
        assert read(_recorded(trace=None)) is None and read(_recorded(scopes={})) is None
    else:
        assert read(_recorded(trace=None, scopes=None)) == 2.5  # a counter: read untraced too
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        entry = {m["name"]: m for m in json.load(fh)["per_layer"]}[metric]
    assert entry["workloads"] == ["glm52_trunk300.week"] and entry["moves"] == "score_p50_ms"
    assert entry["layer"] == ("bank" if metric == "selection_reuse.serve" else "kernels")
    if metric == "selected_latent_attention_roofline.serve":
        assert 15 < READERS[metric] < 25 and entry["unit"] == "%"  # a third of the causal pairs: it reads low
