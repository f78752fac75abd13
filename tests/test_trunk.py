"""A decoder trunk shared by a bank's machines, with per-machine
projections (``models/factories/trunk.py``, ``ops/moe.py``,
``ops/sparse_attention.py``, the shared leaves of ``server/bank.py``), at a
size the CPU holds: hidden 64, 2 layers, 8 experts top-2, top-k 16 keys,
96 rows, 3 machines. The plain reference is the benchmark's own
(``benchmarks/families/keye_trunk/forward.py``), which imports nothing of
the program."""

import contextlib
import json
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

from gordo_components_tpu import serializer  # noqa: E402
from gordo_components_tpu.models.factories.trunk import SparseMoEDecoder  # noqa: E402
from gordo_components_tpu.ops import moe, sparse_attention  # noqa: E402
from gordo_components_tpu.server import build_app  # noqa: E402
from gordo_components_tpu.server.bank import BatchingEngine, ModelBank  # noqa: E402
from gordo_components_tpu.utils.wire import TENSOR_CONTENT_TYPE  # noqa: E402
from gordo_components_tpu.utils.wire import pack_frames, unpack_frames  # noqa: E402

F, ROWS, MACHINES = 5, 96, 3
SIZES = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    indexer_num_heads=4, indexer_head_dim=16, indexer_rope_dim=8, indexer_topk=16,
    chunk_size=16,
)
# the same sizes under the published config's key names, as the reference reads them
CONFIG = dict(
    family="keye_trunk", tags_per_machine=F, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32, rms_norm_eps=1e-6, rope_theta=1e7,
    rope_scaling={"mrope_section": [2, 3, 3]},
    sa_config=dict(indexer_num_heads=4, indexer_head_dim=16, indexer_num_kv_heads=1,
                   topk=16, q_chunk_size=16, kv_chunk_size=16),
    nominal_request_rows=ROWS, bank_members=MACHINES,
)
MODULE = SparseMoEDecoder(n_features=F, **SIZES)


def definition(trunk: str, seed: int = 0) -> dict:
    return {"gordo_components_tpu.models.DiffBasedAnomalyDetector": {"base_estimator": {
        "sklearn.pipeline.Pipeline": {"steps": [
            "sklearn.preprocessing.MinMaxScaler",
            {"gordo_components_tpu.models.TrunkForecast": dict(
                kind="sparse_moe_decoder", trunk=trunk, sequence_rows=64, seed=seed, **SIZES)},
        ]}}}}


def machine_rows(i: int, n: int = 200) -> np.ndarray:
    t = np.arange(n)[:, None]
    noise = np.random.default_rng(i).normal(size=(n, F))
    return (np.sin(t * np.linspace(0.05, 0.3, F)[None] * (1 + i)) + 0.05 * noise).astype(np.float32)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """Three fitted machines that name one trunk artifact by a relative
    path, and the models as ``serializer.load`` returns them."""
    root = tmp_path_factory.mktemp("trunk-collection")
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
    machine's fitted leaves and the trunk artifact's weights."""
    import families

    forward = families.load("keye_trunk", "forward")
    det = models[name]
    scaler, est = det.base_estimator.steps[0][1], det.base_estimator.steps[-1][1]
    trunk = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), est.trunk_params)
    member = est.params_["params"]
    w = {"in_w": member["in_proj"]["kernel"], "in_b": member["in_proj"]["bias"],
         "out_w": member["head"]["kernel"], "out_b": member["head"]["bias"]}
    xs = np.asarray(scaler.transform(X), np.float32)
    sampled = np.arange(MODULE.witness_stride() - 1, len(xs), MODULE.witness_stride())
    got = forward.forecast(
        CONFIG, lambda l: trunk["layers"][l], {k: jnp.asarray(v) for k, v in w.items()},
        xs, sampled, **how,
    )
    return xs, {k: np.asarray(v) for k, v in got.items()}


def _rel(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _assert_close_as_the_stated_arithmetic(models, name, X, got_output):
    """The program's forecast is as near the float32 reference as the
    reference itself is when its matmul operands are rounded to bfloat16
    (what the model states). At this size a near-tie in a router of 8 or
    among 16 keys flips on rounding, so the two are compared by their
    distance from float32, not with each other."""
    xs, exact = _reference(models, name, X)
    _, stated = _reference(models, name, X, operands="bfloat16")
    program = _rel(got_output, exact["out"][:-1])
    assert program < 1.5 * _rel(stated["out"], exact["out"]) + 0.02, program
    return xs, exact


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


async def _post(client, name: str, X: np.ndarray):
    resp = await client.post(
        f"/gordo/v0/proj/{name}/anomaly/prediction", data=pack_frames([("X", X)]),
        headers={"Content-Type": TENSOR_CONTENT_TYPE},
    )
    assert resp.status == 200, await resp.text()
    return unpack_frames(await resp.read())


@pytest.mark.parametrize("machine", range(MACHINES))
async def test_served_answer_matches_the_plain_reference(tree, machine):
    """``run-server``'s normal path: build_app -> ModelCollection ->
    ModelBank -> BatchingEngine -> POST, the six arrays, against the
    reference at the operand precision the model states (bfloat16)."""
    root, models = tree
    X = machine_rows(machine, ROWS) * 0.9
    async with _client(root) as client:
        assert client.app["bank"].coverage()["fallback"] == {}
        got = await _post(client, f"m{machine}", X)
        stats = await (await client.get("/gordo/v0/proj/stats")).json()
    np.testing.assert_array_equal(got["model-input"], X[1:])
    assert got["model-output"].shape == (ROWS - 1, F)
    xs, want = _assert_close_as_the_stated_arithmetic(
        models, f"m{machine}", X, got["model-output"])
    diff = np.abs(xs[1:] - got["model-output"])
    np.testing.assert_allclose(got["tag-anomaly-unscaled"], diff, rtol=1e-5, atol=1e-6)
    err = models[f"m{machine}"].error_scaler_
    scaled = (diff - err.shift) * err.scale
    np.testing.assert_allclose(got["tag-anomaly-scaled"], scaled, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        got["total-anomaly-scaled"], np.linalg.norm(scaled, axis=-1), rtol=1e-4, atol=1e-5)
    # the selections ride the answer, and agree with the reference's
    experts = got["expert-selection"].astype(np.int64)
    assert experts.shape == (2, ROWS, 2)
    assert np.take_along_axis(want["experts"], experts, axis=-1).mean() > 0.97
    keys = np.unpackbits(got["key-selection"], axis=-1, bitorder="little")[:, :6, :ROWS]
    assert (keys.astype(bool) & want["keys"]).sum() / want["keys"].sum() > 0.93
    # the trunk is held once, and the counters are served
    cap = stats["bank_capacity"]
    assert cap["shared_bytes"] > 0 and cap["weight_bytes"] < 2 * cap["shared_bytes"]
    assert stats["bank_shared"]["dispatches"] >= 1 and stats["bank_shared"]["rows"] >= ROWS


async def test_build_model_then_run_server_round_trip(tmp_path):
    """``build`` (build-model) writes the member and, the first time, the
    trunk; ``build_app`` on the tree answers for it like ``anomaly()``."""
    from gordo_components_tpu.builder import provide_saved_model

    root = tmp_path / "models"
    data = {"type": "RandomDataset", "train_start_date": "2020-01-01T00:00:00Z",
            "train_end_date": "2020-01-02T00:00:00Z", "tag_list": [f"t{j}" for j in range(F)]}
    path = provide_saved_model(
        "pump-1", definition(str(root / "trunk-a")), data, {}, output_dir=str(root / "pump-1"),
        evaluation_config={"cv_mode": "build_only"},
    )
    assert os.path.exists(root / "trunk-a" / "trunk.pkl")
    det = serializer.load(path)
    X = machine_rows(7, 40)
    frame = det.anomaly(X)
    async with _client(str(root)) as client:
        assert "pump-1" in client.app["bank"]
        got = await _post(client, "pump-1", X)
    assert _rel(got["model-output"], frame["model-output"].to_numpy()) < 5e-3
    assert _rel(got["total-anomaly-scaled"], frame[("total-anomaly-scaled", "")].to_numpy()) < 5e-3


def test_fleet_build_refuses_a_trunk_member(tmp_path):
    from gordo_components_tpu.builder.fleet_build import build_fleet
    from gordo_components_tpu.workflow.config import Machine

    machine = Machine(
        name="m", model=definition("trunk-a"),
        dataset={"type": "RandomDataset", "train_start_date": "2020-01-01T00:00:00Z",
                 "train_end_date": "2020-01-02T00:00:00Z", "tag_list": ["a", "b"]},
    )
    with pytest.raises(ValueError, match="cannot be fleet-built"):
        build_fleet([machine], str(tmp_path))


def test_extract_fleetable_is_none():
    from gordo_components_tpu.builder.fleet_build import extract_fleetable

    assert extract_fleetable(definition("trunk-a")) is None


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


@pytest.mark.parametrize("rows", [17, 50, 90])
def test_a_request_is_padded_to_whole_chunks_never_cut(bank, tree, rows):
    """Any length goes as one call; the padding changes nothing."""
    X = machine_rows(1, rows)
    before = dict(bank.shared_stats)
    got = bank.score("m1", X)
    assert bank.shared_stats["dispatches"] == before.get("dispatches", 0) + 1
    assert bank.shared_stats["tokens"] - before.get("tokens", 0) == MODULE.padded_rows(rows)
    assert bank.shared_stats["rows"] - before.get("rows", 0) == rows
    assert got.model_output.shape == (rows - 1, F)
    _assert_close_as_the_stated_arithmetic(tree[1], "m1", X, got.model_output)


def test_a_shared_leaf_is_placed_and_counted_once(bank, tree):
    (bucket,) = bank._buckets.values()
    trunk_bytes = sum(np.asarray(a).nbytes for a in jax.tree.leaves(
        tree[1]["m0"].base_estimator.steps[-1][1].trunk_params))
    assert bucket.shared_bytes == trunk_bytes
    cap = bank.capacity_stats()
    assert cap["shared_bytes"] == trunk_bytes
    assert cap["weight_bytes"] == trunk_bytes + bucket.weight_bytes
    # the members' own stack holds the projections alone
    assert bucket.params_per_member == 2 * F * 64 + 64 + F
    # every member holds the SAME trunk object
    trunks = {id(m.base_estimator.steps[-1][1].trunk_params) for m in tree[1].values()}
    assert len(trunks) == 1


@pytest.mark.parametrize("free_gb,expect", [(None, None), (0.0, 1), (1e3, 64)])
def test_the_batch_is_bounded_by_the_programs_bytes(bank, free_gb, expect):
    (bucket,) = bank._buckets.values()
    old = bucket._free_bytes
    try:
        bucket._free_bytes = None if free_gb is None else int(free_gb * 1e9)
        limit = bank.batch_limit("m0", ROWS)
        assert limit == expect or (expect == 64 and limit >= 64)
        # a longer group than the limit goes as several calls, one at a time
        before = bank.shared_stats.get("dispatches", 0)
        bank.score_many([(f"m{i % MACHINES}", machine_rows(i, 20), None) for i in range(3)])
        calls = bank.shared_stats["dispatches"] - before
        assert calls == (3 if expect == 1 else 1)
    finally:
        bucket._free_bytes = old


async def test_the_engine_stops_a_batch_at_the_buckets_limit(bank):
    (bucket,) = bank._buckets.values()
    old = bucket._free_bytes
    engine = BatchingEngine(bank, max_batch=8, flush_ms=200.0, registry=False)
    try:
        bucket._free_bytes = 0  # one request a call
        import asyncio

        results = await asyncio.gather(*[
            engine.score(f"m{i % MACHINES}", machine_rows(i, 20)) for i in range(3)
        ])
        assert len(results) == 3 and engine.stats["max_batch_seen"] == 1
        assert engine.stats["batches"] == 3
    finally:
        bucket._free_bytes = old
        await engine.stop()


def test_flops_count_the_experts_and_keys_a_row_meets(bank):
    from gordo_components_tpu.observability.cost import estimate_flops_per_row

    (row,) = bank.flops_stats().values()
    flops, method = estimate_flops_per_row(MODULE, F, 1, 123)
    assert row["flops_per_row"] == flops and method.startswith("analytic:context=")
    every_expert = 2.0 * 2 * (64 * 8 + 3 * 8 * 64 * 32)  # router + all 8 experts, 2 layers
    assert flops < every_expert  # top-2 of 8: far under the 2 x params fallback
    by_hand = 2.0 * MODULE.active_params_per_row()
    context = 5 * 16
    selected = sum(min(t + 1, 16) for t in range(context)) / context
    by_hand += 2 * (4.0 * 4 * 16 * selected + 2.0 * 4 * 16 * (context + 1) / 2)
    assert flops == pytest.approx(by_hand)


# -------------------------------------------------------------------- ops


def test_topk_at_least_the_rows_is_dense_causal_attention():
    T, H, G, d, J, dI = 32, 4, 2, 16, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v = (jax.random.normal(keys[i], (T, n, d)) for i, n in ((0, H), (1, G), (2, G)))
    qi, ki, wi = (jax.random.normal(keys[3], (T, J, dI)), jax.random.normal(keys[4], (T, dI)),
                  jax.random.normal(keys[5], (T, J)))
    out, n_sel, _ = sparse_attention.select_and_attend(
        q, k, v, qi, ki, wi, jnp.int32(T), topk=T, chunk=8, interpret=True)
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    logits = jnp.einsum("tgrd,sgd->grts", bf(q).reshape(T, G, H // G, d), bf(k)) / d ** 0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    dense = jnp.einsum("grts,sgd->tgrd", bf(p), bf(v)).reshape(T, H, d)
    np.testing.assert_allclose(out, dense, rtol=2e-2, atol=5e-3)
    assert int(n_sel) == T * (T + 1) // 2


@pytest.mark.parametrize("k", [1, 5, 16, 40])
def test_kth_largest_is_exact(k):
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(7, 40)).astype(np.float32)
    scores[0, :10] = 0.0  # ties
    scores[1, 20:] = -np.inf  # a short row
    got = np.asarray(sparse_attention.kth_largest(jnp.asarray(scores), k))
    want = np.sort(scores, axis=-1)[:, ::-1][:, k - 1]
    np.testing.assert_array_equal(got, want)


def test_mrope_with_equal_streams_is_rope():
    import families

    forward = families.load("keye_trunk", "forward")
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 3, 16))
    positions = jnp.arange(24)
    for rotary in (None, 8):
        plain = sparse_attention.rope(x, positions, 1e7, rotary)
        three = forward.mrope(x, jnp.broadcast_to(positions, (3, 24)), 1e7, [2, 3, 3], rotary)
        np.testing.assert_allclose(plain, three, rtol=1e-6, atol=1e-6)
    # and it is not, where the streams differ
    skew = jnp.stack([positions, positions * 2, positions * 3])
    assert not np.allclose(forward.mrope(x, skew, 1e7, [2, 3, 3]), sparse_attention.rope(x, positions, 1e7))


@pytest.mark.parametrize("hog", [0, 5])
def test_no_token_is_dropped_when_one_expert_takes_every_token(hog):
    D, E, I, N, k = 16, 8, 8, 64, 2
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    params = {
        "router": jnp.zeros((D, E)).at[:, hog].set(50.0),  # every row's first choice
        "gate": jax.random.normal(keys[1], (E, D, I), jnp.bfloat16),
        "up": jax.random.normal(keys[2], (E, D, I), jnp.bfloat16),
        "down": jax.random.normal(keys[3], (E, I, D), jnp.bfloat16),
    }
    h = jnp.abs(jax.random.normal(keys[4], (N, D))) + 0.1
    out, experts, counts, _ = moe.expert_layer(h, params, k, jnp.ones((N,), bool), interpret=True)
    assert int(counts[hog]) == N and int(counts.sum()) == N * k
    assert bool((experts[:, 0] == hog).all())
    weights, chosen = moe.route(h, params["router"], k)
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    want = jnp.zeros((N, D))
    for slot in range(k):
        g, u, dn = (params[n].astype(jnp.float32)[chosen[:, slot]] for n in ("gate", "up", "down"))
        act = jax.nn.silu(jnp.einsum("nd,ndi->ni", bf(h), g)) * jnp.einsum("nd,ndi->ni", bf(h), u)
        want += weights[:, slot, None] * jnp.einsum("ni,nid->nd", bf(act), dn)
    np.testing.assert_allclose(out, want, rtol=2e-2, atol=2e-2)


def test_padding_is_left_out_of_the_routers_counts():
    D, E, I, N, k = 16, 8, 8, 32, 2
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    params = {"router": jax.random.normal(keys[0], (D, E)),
              "gate": jax.random.normal(keys[1], (E, D, I), jnp.bfloat16),
              "up": jax.random.normal(keys[2], (E, D, I), jnp.bfloat16),
              "down": jax.random.normal(keys[3], (E, I, D), jnp.bfloat16)}
    h = jax.random.normal(keys[4], (N, D))
    valid = jnp.arange(N) < 20
    _, _, counts, _ = moe.expert_layer(h, params, k, valid, interpret=True)
    assert int(counts.sum()) == 20 * k


def test_the_softmax_router_with_every_expert_held_is_bitwise_what_it_was():
    """``expert_layer`` as this kind calls it (softmax, one group, every
    expert held, no offset) against what it returned before it could hold a
    range or route by sigmoid (``tests/data``: the parent commit's output
    for these seeded inputs, interpret mode on the CPU)."""
    rng = np.random.default_rng(20261003)
    T, D, E, I, k = 64, 32, 8, 16, 2
    h = rng.normal(size=(T, D)).astype(np.float32)
    params = {"router": rng.normal(size=(D, E)).astype(np.float32) * 0.3,
              "gate": jnp.asarray(rng.normal(size=(E, D, I)) * D ** -0.5, jnp.bfloat16),
              "up": jnp.asarray(rng.normal(size=(E, D, I)) * D ** -0.5, jnp.bfloat16),
              "down": jnp.asarray(rng.normal(size=(E, I, D)) * I ** -0.5, jnp.bfloat16)}
    valid = np.arange(T) < 50
    out, experts, counts, _ = moe.expert_layer(jnp.asarray(h), params, k, jnp.asarray(valid), True)
    was = np.load(os.path.join(os.path.dirname(__file__), "data", "expert_layer_softmax_case.npz"))
    np.testing.assert_array_equal(np.asarray(out), was["out"])
    np.testing.assert_array_equal(np.asarray(experts), was["experts"])
    np.testing.assert_array_equal(np.asarray(counts), was["counts"])


# -------------------------------------------------- the benchmark's counts


def test_the_benchmarks_counts_against_a_count_by_hand():
    import families

    layout = families.load("keye_trunk", "layout")
    full = json.load(open(os.path.join(BENCH, "configs", "keye_trunk300.json")))
    # one row and layer at the published sizes, in MFLOP (ISSUE 28's arithmetic)
    assert layout.dense_flops_per_row(full) / 1e6 == pytest.approx(37.7 + 4.5 + 0.5, abs=0.1)
    assert layout.experts_flops_per_row(full) / 1e6 == pytest.approx(75.5, abs=0.1)
    rows = 10080
    assert layout.selected_keys(rows, 2048) == 2048 * 2049 / 2 + (rows - 2048) * 2048
    attend = 4 * 32 * 128 * layout.selected_keys(rows, 2048) / rows / 1e6
    index = 2 * 16 * 64 * (rows + 1) / 2 / 1e6
    assert layout.sparse_attention_flops(full, rows) / rows / 1e6 == pytest.approx(attend + index)
    per_row = layout.forward_flops_per_row(full)
    assert per_row / 6 / 1e6 == pytest.approx(159.0, abs=1.0)  # 8 experts and selected keys, not 128 and all
    # and the program's own count of the same row agrees
    from gordo_components_tpu.models.factories.trunk import SparseMoEDecoder as Decoder

    program = Decoder(n_features=300, num_hidden_layers=6).forward_flops_per_row(rows)
    assert program == pytest.approx(per_row, rel=1e-3)
    # bytes: six layers' experts once a dispatch, rows in and out
    experts = 6 * 128 * 3 * 2048 * 768 * 2
    assert layout.experts_bytes(full, 2, 100) == 2 * experts + 100 * 6 * 2 * 2048 * 4
