"""The hybrid trunk of one-mixer layers (``HybridMoEDecoder``: kind
``hybrid_moe_decoder`` of ``models/factories/trunk.py``; the chunked scan of
``ops/ssd.py``; the squared-ReLU experts and the held range of
``ops/moe.py``; the mask-free causal path of
``ops/sparse_attention.py``'s kernel; the mixer's counters in
``server/bank.py``), at a size the CPU holds: hidden 64, the held layers
``MEM*E`` of a pattern ``MEM*EM``, mixers of 4 heads of 8 over 2 groups of
16 in chunks of 16, 16 experts top 4 of which this chip holds 4-7 beside a
shared one, attention 4/2 heads of 16, 96 rows, 3 machines. The plain
reference is the benchmark's own
(``benchmarks/families/nemotron3_trunk/forward.py``: the mixer as the
sequential recurrence), which imports nothing of the program."""

import contextlib
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
from gordo_components_tpu.models.factories.trunk import HybridMoEDecoder  # noqa: E402
from gordo_components_tpu.ops import moe, sparse_attention  # noqa: E402
from gordo_components_tpu.ops.ssd import ssd_scan  # noqa: E402
from gordo_components_tpu.server import build_app  # noqa: E402
from gordo_components_tpu.server.bank import _SHARED_COUNTERS, ModelBank  # noqa: E402
from gordo_components_tpu.utils.wire import TENSOR_CONTENT_TYPE  # noqa: E402
from gordo_components_tpu.utils.wire import pack_frames, unpack_frames  # noqa: E402

F, ROWS, MACHINES, TRUNK_SEED = 5, 96, 3, 42
PATTERN, HELD = "MEM*EM", (0, 1, 2, 3, 4)
SIZES = dict(
    hidden_size=64, num_hidden_layers=5, hybrid_override_pattern=PATTERN, held_layers=list(HELD),
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16, conv_kernel=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, n_routed_experts=16, num_experts_per_tok=4,
    routed_scaling_factor=2.5, rms_norm_eps=1e-5, expert_offset=4, experts_held=4, chunk_size=16,
)
# the same sizes under the published config's key names, as the reference reads them
CONFIG = dict(
    family="nemotron3_trunk", tags_per_machine=F, hidden_size=64, num_hidden_layers=5,
    hybrid_override_pattern=PATTERN, held_layers=dict(published_index=list(HELD)),
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=16,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4, layer_norm_epsilon=1e-5,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, n_routed_experts=4, num_experts_per_tok=4, n_group=1,
    topk_group=1, routed_scaling_factor=2.5, published=dict(n_routed_experts=16, num_hidden_layers=6),
    expert_shard=dict(chips_sharing_a_layer=4, index=1, held=[4, 8]),
    nominal_request_rows=ROWS, bank_members=MACHINES,
)
MODULE = HybridMoEDecoder(n_features=F, **dict(SIZES, held_layers=HELD))
FORWARD = families.load("nemotron3_trunk", "forward")
LAYOUT = families.load("nemotron3_trunk", "layout")
ROUTING = dict(scoring="sigmoid", n_group=1, topk_group=1, scale=2.5)


def definition(trunk: str, seed: int = 0) -> dict:
    return {"gordo_components_tpu.models.DiffBasedAnomalyDetector": {"base_estimator": {
        "sklearn.pipeline.Pipeline": {"steps": [
            "sklearn.preprocessing.MinMaxScaler",
            {"gordo_components_tpu.models.TrunkForecast": dict(
                kind="hybrid_moe_decoder", trunk=trunk, sequence_rows=64, seed=seed, **SIZES)},
        ]}}}}


def machine_rows(i: int, n: int = 200) -> np.ndarray:
    t = np.arange(n)[:, None]
    noise = np.random.default_rng(i).normal(size=(n, F))
    return (np.sin(t * np.linspace(0.05, 0.3, F)[None] * (1 + i)) + 0.05 * noise).astype(np.float32)


def _rel(got, want) -> float:
    return float(np.linalg.norm(np.asarray(got) - np.asarray(want)) / np.linalg.norm(np.asarray(want)))


# ------------------------------------------------------------ the scan


def _recurrence(x, dt, A, Bm, Cm, D):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t,
    one row after another, head h reading group h // (H / G)."""
    x, dt, Bm, Cm = (np.asarray(a, np.float64) for a in (x, dt, Bm, Cm))
    A, D = np.asarray(A, np.float64), np.asarray(D, np.float64)
    batch, T, H, P = x.shape
    group = np.arange(H) // (H // Bm.shape[2])
    y = np.zeros_like(x)
    for b in range(batch):
        S = np.zeros((H, P, Bm.shape[3]))
        for t in range(T):
            S = np.exp(dt[b, t] * A)[:, None, None] * S + (dt[b, t][:, None] * x[b, t])[:, :, None] * Bm[b, t][group][:, None, :]
            y[b, t] = np.einsum("hpn,hn->hp", S, Cm[b, t][group]) + D[:, None] * x[b, t]
    return y


def _scan_inputs(T, H=4, G=2, P=8, N=16, batch=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    exact = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # the kernel's operands, unrounded
    x = exact(jax.random.normal(k[0], (batch, T, H, P)))
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, T, H)) - 1.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.0))
    Bm = exact(jax.random.normal(k[3], (batch, T, G, N)))
    Cm = exact(jax.random.normal(k[4], (batch, T, G, N)))
    D = jax.random.normal(k[5], (H,))
    return x, dt, A, Bm, Cm, D


@pytest.mark.parametrize("T", [8, 9, 40])
def test_the_chunked_scan_is_the_sequential_recurrence(T):
    """Chunks of 8: one chunk, a chunk and one row of a second, five
    chunks (the state carried across four boundaries). The kernel's
    matmuls take bfloat16 operands (the decayed matrix and the state among
    them): 0.2% of the output's norm here."""
    padded = -(-T // 8) * 8
    x, dt, A, Bm, Cm, D = _scan_inputs(padded, seed=T)
    got = np.asarray(ssd_scan(x, dt, A, Bm, Cm, D, 8, interpret=True))
    want = _recurrence(x[:, :T], dt[:, :T], A, Bm[:, :T], Cm[:, :T], D)
    assert _rel(got[:, :T], want) < 5e-3
    assert np.abs(got[:, :T] - want).max() < 0.05 * np.abs(want).max()


@pytest.mark.parametrize("heads,groups", [(4, 1), (4, 4), (8, 2)])
def test_every_head_reads_its_group(heads, groups):
    x, dt, A, Bm, Cm, D = _scan_inputs(24, H=heads, G=groups, seed=heads + groups)
    got = np.asarray(ssd_scan(x, dt, A, Bm, Cm, D, 8, interpret=True))
    assert _rel(got, _recurrence(x, dt, A, Bm, Cm, D)) < 5e-3


def test_rows_past_the_valid_ones_change_no_valid_row():
    """A request's padding lies after its valid rows: whatever it holds,
    the valid rows' outputs are the same bits (the scan is causal)."""
    x, dt, A, Bm, Cm, D = _scan_inputs(32)
    noise = jax.random.normal(jax.random.PRNGKey(9), x.shape) * 100.0
    later = jnp.arange(32)[None, :, None, None] >= 19
    y = ssd_scan(x, dt, A, Bm, Cm, D, 8, interpret=True)
    z = ssd_scan(jnp.where(later, noise, x), dt * jnp.where(later[..., 0], 5.0, 1.0), A,
                 jnp.where(later, 3.0, Bm), Cm, D, 8, interpret=True)
    np.testing.assert_array_equal(np.asarray(y)[:, :19], np.asarray(z)[:, :19])
    assert not np.array_equal(np.asarray(y)[:, 19:], np.asarray(z)[:, 19:])


def test_a_chunk_is_a_tiling_not_a_change_of_the_recurrence():
    x, dt, A, Bm, Cm, D = _scan_inputs(32, seed=4)
    by8, by32 = (np.asarray(ssd_scan(x, dt, A, Bm, Cm, D, c, interpret=True)) for c in (8, 32))
    assert _rel(by8, by32) < 5e-3
    # each request of the batch starts from a zero state
    one = np.asarray(ssd_scan(x[1:], dt[1:], A, Bm[1:], Cm[1:], D, 8, interpret=True))
    np.testing.assert_allclose(by8[1:], one, rtol=1e-6, atol=1e-6)


# ---------------------------------------------- the experts and the kernel


def _expert_params(n_experts=8, held=8, D=32, I=24, seed=3, gated=False):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    u = lambda key, shape: jax.random.uniform(key, shape, minval=-0.3, maxval=0.3).astype(jnp.bfloat16)
    params = {"router": jax.random.normal(k[0], (D, n_experts)),
              "router_bias": 0.02 * jax.random.normal(k[1], (n_experts,)),
              "up": u(k[2], (held, D, I)), "down": u(k[3], (held, I, D))}
    if gated:
        params["gate"] = u(k[4], (held, D, I))
    return params


def _by_loop(h, params, top_k, offset=0, gated=False):
    """The layer written out: every kept (row, expert) pair on a held
    expert, one expert at a time, in float32 from the bfloat16 operands."""
    weights, experts = moe.route(h, params["router"], top_k, bias=params["router_bias"], **ROUTING)
    x = h.astype(jnp.bfloat16).astype(jnp.float32)
    out = jnp.zeros_like(h)
    for e in range(params["up"].shape[0]):
        w_e = jnp.sum(jnp.where(experts == offset + e, weights, 0.0), axis=-1)
        up = x @ params["up"][e].astype(jnp.float32)
        hidden = jax.nn.silu(x @ params["gate"][e].astype(jnp.float32)) * up if gated else jnp.square(jax.nn.relu(up))
        hidden = hidden.astype(jnp.bfloat16).astype(jnp.float32)
        out = out + w_e[:, None] * (hidden @ params["down"][e].astype(jnp.float32))
    return out, experts


@pytest.mark.parametrize("held,offset,n_experts,D,I", [
    (8, 0, 8, 32, 24), (4, 4, 8, 32, 24), (2, 2, 8, 32, 24),
    # D / 128 = 3 rows of 128, not whole (8, 128) tiles, and an I that is not whole lanes: the up
    # stack is read swapped and the combine adds into padded rows; D 1024 takes neither
    (16, 16, 32, 384, 192), (16, 8, 32, 1024, 256),
], ids=["8-0", "4-4", "2-2", "16-16-D384-I192", "16-8-D1024-I256"])
def test_squared_relu_experts_are_a_plain_loop_over_the_experts(held, offset, n_experts, D, I):
    """No ``gate`` among the leaves: every held expert is
    ``down(relu(up h)^2)``, in one pass where every expert is held and in
    blocks of held pairs where a range is, at widths the combine pads and
    at widths it does not."""
    h = jax.random.normal(jax.random.PRNGKey(7), (64, D))
    whole = _expert_params(n_experts, n_experts, D, I)
    params = {**whole, "up": whole["up"][offset:offset + held], "down": whole["down"][offset:offset + held]}
    valid = jnp.ones((64,), bool)
    with jax.default_matmul_precision("highest"):
        out, experts, tokens, blocks = moe.expert_layer(h, params, 3, valid, True, expert_offset=offset, **ROUTING)
        want, want_experts = _by_loop(h, params, 3, offset)
    np.testing.assert_array_equal(experts, want_experts)
    np.testing.assert_allclose(out, want, rtol=2e-2, atol=2e-2 * float(jnp.abs(want).max()))
    assert int(tokens.sum()) == int(((want_experts >= offset) & (want_experts < offset + held)).sum())
    assert (int(blocks) == 0) == (held == n_experts)


def test_the_gated_experts_are_the_expressions_they_were():
    """With a ``gate`` the experts are the SwiGLU, op for op as before the
    squared-ReLU form existed: gate, up, silu(gate) * up, down."""
    h = jax.random.normal(jax.random.PRNGKey(8), (64, 32))
    params = _expert_params(gated=True)
    x = h.astype(jnp.bfloat16)
    sizes = jnp.asarray([8] * 8, jnp.int32)
    got = moe._experts(x, params, sizes, True)
    gate = moe._grouped(x, params["gate"], sizes, True)
    up = moe._grouped(x, params["up"], sizes, True)
    want = moe._grouped((jax.nn.silu(gate) * up).astype(jnp.bfloat16), params["down"], sizes, True)
    np.testing.assert_array_equal(got, want)
    with jax.default_matmul_precision("highest"):
        out, _, _, _ = moe.expert_layer(h, params, 3, jnp.ones((64,), bool), True, **ROUTING)
        np.testing.assert_allclose(out, _by_loop(h, params, 3, gated=True)[0], rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("T,chunk", [(64, 16), (96, 32), (48, 48)])
def test_the_kernel_without_a_mask_is_the_all_causal_masked_call(T, chunk):
    """No mask array: causality from the tiles' own indices, the same bits
    as the masked call handed every causal key."""
    k = jax.random.split(jax.random.PRNGKey(T), 3)
    q = jax.random.normal(k[0], (2, 3, T, 16)).astype(jnp.bfloat16)
    kk = jax.random.normal(k[1], (2, T, 16)).astype(jnp.bfloat16)
    v = jax.random.normal(k[2], (2, T, 16)).astype(jnp.bfloat16)
    causal = jnp.tril(jnp.ones((T, T), jnp.int8))
    masked = sparse_attention.masked_attention(q, kk, v, causal, chunk, True)
    free = sparse_attention.masked_attention(q, kk, v, None, chunk, True)
    np.testing.assert_array_equal(free, masked)
    logits = jnp.einsum("grtd,gsd->grts", q.astype(jnp.float32), kk.astype(jnp.float32)) / 4.0
    p = jax.nn.softmax(jnp.where(causal.astype(bool), logits, -jnp.inf), axis=-1)
    want = jnp.einsum("grts,gsd->grtd", p, v.astype(jnp.float32))
    assert _rel(free, want) < 1e-2


# ----------------------------------------------------- the trunk's layers


@pytest.fixture(scope="module")
def trunk():
    """The benchmark's seeded trunk as the program holds it."""
    return LAYOUT.trunk_to_program(CONFIG, TRUNK_SEED)


def _member(seed: int = 0):
    rng = np.random.default_rng(seed)
    w = {n: (rng.random(s, dtype=np.float32) * 2 - 1) * lim for n, s, lim in LAYOUT.layer_shapes(CONFIG)}
    return w, jax.tree.map(lambda a: jnp.asarray(a)[None], LAYOUT.to_program(CONFIG, w))


def test_the_layers_hold_what_the_pattern_says(trunk):
    assert [MODULE.kind(l) for l in range(5)] == list("MEM*E") == LAYOUT.kinds(CONFIG)
    for index, w in enumerate(trunk["layers"]):
        assert {k: v.shape for k, v in w.items()} == MODULE.layer_shapes(index)
    mixer, routed, attention = trunk["layers"][0], trunk["layers"][1], trunk["layers"][3]
    assert mixer["in_proj"].shape == (64, 2 * 32 + 2 * 2 * 16 + 4) and mixer["conv"].shape == (4, 96)
    assert routed["up"].shape == (4, 64, 32) and "gate" not in routed and routed["shared_up"].shape == (64, 48)
    assert set(attention) == {"input_norm", "wq", "wk", "wv", "wo"}
    with pytest.raises(ValueError):
        HybridMoEDecoder(n_features=F, **dict(SIZES, held_layers=(0, 1, 2)))
    with pytest.raises(ValueError):
        HybridMoEDecoder(n_features=F, **dict(SIZES, held_layers=(0, 1, 2, 3, 9)))


def test_init_draws_the_mixer_as_published():
    drawn = MODULE.init_trunk(jax.random.PRNGKey(0))["layers"][0]
    A = np.exp(np.asarray(drawn["A_log"]))
    assert 1.0 <= A.min() < A.max() < 16.0
    dt = np.log1p(np.exp(np.asarray(drawn["dt_bias"])))
    np.testing.assert_allclose([dt.min() >= 0.001 * 0.999, dt.max() <= 0.1 * 1.001], True)
    np.testing.assert_array_equal(drawn["D"], 1.0)
    np.testing.assert_array_equal(drawn["conv_bias"], 0.0)


def test_the_trunk_matches_the_plain_reference_on_seeded_weights(trunk):
    """``apply`` (one program: the kernels in interpret mode) against the
    sequential reference on the benchmark's seeded trunk. Tolerance: at
    this size a row is a tenth of the output's norm, and a near-tie that
    flips one of a row's 4 experts moves it by half: 0.12 of the output,
    as the benchmark's rehearsal of the cell (its stated arithmetic reads
    under 1%, its float8 control over 0.2)."""
    w, member = _member()
    xs = machine_rows(0, ROWS)
    out, seen = jax.jit(lambda *a: MODULE.apply(*a, interpret=True))(
        trunk, member, jnp.asarray(xs)[None], jnp.asarray([ROWS], jnp.int32))
    held = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), trunk)
    ref = FORWARD.forecast(CONFIG, lambda l: held["layers"][l], {k: jnp.asarray(v) for k, v in w.items()}, xs)
    control = FORWARD.forecast(CONFIG, lambda l: held["layers"][l], {k: jnp.asarray(v) for k, v in w.items()},
                               xs, operands="float8_e4m3fn")
    assert _rel(out[0], ref["out"]) < 0.12 < _rel(control["out"], ref["out"])
    experts = np.asarray(seen["experts"][:, 0]).astype(np.int64)  # (routed layers, rows, top-k)
    assert experts.shape == (2, ROWS, 4)
    assert np.take_along_axis(np.asarray(ref["experts"]), experts, axis=-1).mean() > 0.97
    assert seen["ssm_chunks"].shape == (2, 1) and int(seen["ssm_chunks"].sum()) == 2 * ROWS // 16


def test_padding_reaches_no_valid_row(trunk):
    """Padding lies after a request's valid rows, and every mixer is
    causal: whatever the padding holds, the valid rows come out the same."""
    _, member = _member(1)
    xs = jnp.asarray(machine_rows(1, ROWS))[None]
    noisy = xs.at[:, 70:].set(50.0)
    run = jax.jit(lambda x, n: MODULE.apply(trunk, member, x, n, interpret=True)[0])
    a, b = run(xs, jnp.asarray([70])), run(noisy, jnp.asarray([70]))
    np.testing.assert_array_equal(np.asarray(a)[:, :70], np.asarray(b)[:, :70])


@pytest.mark.parametrize("split", [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(split):
    """The share tied to the model: 8 experts divided over ``split`` chips,
    the routed parts of all the shares, with the shared expert (which every
    chip computes alike) counted once, are what the uncut reference gives
    for the whole layer: in the reference, and in the program's
    ``expert_layer`` handed each range in turn."""
    base = dict(CONFIG, published=dict(CONFIG["published"], n_routed_experts=8))
    whole = dict(base, expert_shard=dict(chips_sharing_a_layer=1, index=0, held=[0, 8]))
    each = 8 // split
    shares = [dict(base, expert_shard=dict(chips_sharing_a_layer=split, index=i, held=[each * i, each * (i + 1)]))
              for i in range(split)]
    w_whole = LAYOUT.trunk_layer(whole, TRUNK_SEED, 1)
    h = jax.random.normal(jax.random.PRNGKey(5), (ROWS, 64))
    with jax.default_matmul_precision("highest"):
        routed, shared, kept = FORWARD.ffn_parts(whole, w_whole, h)
        parts = [FORWARD.ffn_parts(c, LAYOUT.trunk_layer(c, TRUNK_SEED, 1), h) for c in shares]
    np.testing.assert_allclose(sum(p[0] for p in parts), routed, rtol=1e-5, atol=1e-5)
    for part in parts:
        np.testing.assert_array_equal(part[1], shared)  # every chip's shared expert is the same
        np.testing.assert_array_equal(part[2], kept)  # and so is the routing, over all 8
    as_program = lambda w: {k: (v if v.ndim == 1 else v.astype(jnp.bfloat16)) for k, v in w.items()}
    valid = jnp.ones((ROWS,), bool)
    full, experts, counts, _ = moe.expert_layer(h, as_program(w_whole), 4, valid, True, **ROUTING)
    total, held = jnp.zeros_like(full), 0
    for i, c in enumerate(shares):
        out, theirs, tokens, _ = moe.expert_layer(
            h, as_program(LAYOUT.trunk_layer(c, TRUNK_SEED, 1)), 4, valid, True, expert_offset=each * i, **ROUTING)
        np.testing.assert_array_equal(theirs, experts)
        np.testing.assert_array_equal(tokens, counts[each * i: each * (i + 1)])
        total, held = total + out, held + int(tokens.sum())
    assert held == ROWS * 4
    np.testing.assert_allclose(total, full, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(full, routed, rtol=3e-2, atol=3e-2 * float(jnp.abs(routed).max()))


def test_the_programs_bytes_and_flops_by_hand():
    published = HybridMoEDecoder(n_features=300, num_hidden_layers=9, experts_held=64)
    T = published.padded_rows(10080)
    assert T == 10240 and published.nominal_context_rows() == 10240
    per_row = 4 * (4096 + 6144 + 64) + 10 * 6144 + 8 * 4096 + 8 * 2688
    assert published.program_bytes(1, T) == T * per_row
    assert published.program_bytes(2, T) == 2 * published.program_bytes(1, T)
    n, D = 320, 64
    mixer = 2 * (64 * (2 * 32 + 64 + 4) + 32 * 64) + 2 * 16 * 2 * 16 + 2 * 16 * 32 + 4 * 32 * 16
    routed = 2 * (64 * 16 + 2 * 64 * 48 + 2 * 64 * 32 * 4 * 4 / 16)
    attention = 2 * (64 * 64 + 2 * 64 * 32 + 64 * 64) + 4 * 4 * 16 * (n + 1) / 2
    by_hand = 2 * mixer + 2 * routed + attention + 2 * 2 * F * D
    assert MODULE.forward_flops_per_row(n) == pytest.approx(by_hand)
    cell = families.load("nemotron3_trunk", "layout")
    config = __import__("json").load(open(os.path.join(BENCH, "configs", "nemotron3_trunk300.json")))
    ours = published.forward_flops_per_row(10080)
    theirs = cell.forward_flops_per_row(config)
    assert ours == pytest.approx(theirs, rel=1e-6)  # the program's count and the benchmark's agree


# ----------------------------------------------------- through the bank


@pytest.fixture(scope="module")
def tree(tmp_path_factory, trunk):
    """Three fitted machines that name one trunk artifact (the seeded
    trunk, written before the first fit) by a relative path."""
    root = tmp_path_factory.mktemp("hybrid-trunk-collection")
    serializer.dump_trunk(jax.tree.map(np.asarray, trunk), str(root / "trunk-h"))
    for i in range(MACHINES):
        det = serializer.from_definition(definition(str(root / "trunk-h"), seed=i))
        det.fit(machine_rows(i))
        det.base_estimator.steps[-1][1].trunk = "trunk-h"
        serializer.dump(det, str(root / f"m{i}"), metadata={"name": f"m{i}"})
    models = {f"m{i}": serializer.load(str(root / f"m{i}")) for i in range(MACHINES)}
    return str(root), models


@pytest.fixture(scope="module")
def bank(tree):
    return ModelBank.from_models(tree[1], registry=False)


def _reference(models, name: str, X: np.ndarray, **how):
    det = models[name]
    scaler, est = det.base_estimator.steps[0][1], det.base_estimator.steps[-1][1]
    held = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), est.trunk_params)
    w = {k: jnp.asarray(v) for k, v in LAYOUT.from_program(est.params_["params"]).items()}
    xs = np.asarray(scaler.transform(X), np.float32)
    got = FORWARD.forecast(CONFIG, lambda l: held["layers"][l], w, xs, **how)
    return xs, {k: np.asarray(v) for k, v in got.items()}


def test_the_bank_walks_the_layers_as_apply_does(bank, tree):
    """``_Bucket.score_batch`` (one ``score_layer`` call a layer) against
    ``module.apply`` (one program), and one compiled layer program for each
    of the three kinds whatever the depth."""
    (bucket,) = bank._buckets.values()
    det = tree[1]["m1"]
    scaler, est = det.base_estimator.steps[0][1], det.base_estimator.steps[-1][1]
    X = machine_rows(1, ROWS)
    got = bank.score("m1", X)
    xs = np.asarray(scaler.transform(X), np.float32)
    member = jax.tree.map(lambda a: jnp.asarray(a)[None], est.params_["params"])
    out, seen = jax.jit(lambda *a: MODULE.apply(*a, interpret=True))(
        est.trunk_params, member, jnp.asarray(xs)[None], jnp.asarray([ROWS], jnp.int32))
    assert _rel(got.model_output, out[0, :-1]) < 1e-2  # two compilations of one arithmetic
    assert (got.selections["expert-selection"] == np.asarray(seen["experts"][:, 0])).mean() > 0.99
    assert set(got.selections) == {"expert-selection"}  # no kind here selects keys
    assert bucket._layer._cache_size() == 3
    bank.score("m2", machine_rows(2, ROWS))
    assert bucket._layer._cache_size() == 3


@pytest.mark.parametrize("pair", [(0, 1), (1, 2)])
def test_two_machines_batched_get_the_answers_they_get_alone(bank, pair):
    Xs = [machine_rows(i, ROWS) for i in pair]
    together = bank.score_many([(f"m{i}", X, None) for i, X in zip(pair, Xs)])
    for i, X, both in zip(pair, Xs, together):
        alone = bank.score(f"m{i}", X)
        np.testing.assert_array_equal(both.model_output, alone.model_output)
        np.testing.assert_array_equal(both.selections["expert-selection"], alone.selections["expert-selection"])


@pytest.mark.parametrize("rows", [17, 50, 90])
def test_the_mixer_layers_and_their_chunks_are_counted(bank, rows):
    """``ssm_layers`` grows by the mixer layers a dispatch ran, and
    ``ssm_chunks`` by the chunks of VALID rows each scanned; padding is in
    neither (nor in the held experts' counters)."""
    before = dict(bank.shared_stats)
    got = bank.score("m1", machine_rows(1, rows))
    after = bank.shared_stats
    grew = lambda name: after[name] - before.get(name, 0)
    assert grew("dispatches") == 1 and grew("rows") == rows
    assert grew("tokens") == MODULE.padded_rows(rows)
    assert grew("ssm_layers") == 2
    assert grew("ssm_chunks") == 2 * math.ceil(rows / 16)
    assert MODULE.padded_rows(rows) == 32 * math.ceil(rows / 32)  # whole attention tiles of two chunks
    assert grew("routed_pairs") == rows * 4 * 2
    assert got.model_output.shape == (rows - 1, F)
    assert "selection_layers" not in after and "expert_tokens" not in after


def test_the_counters_are_scraped():
    assert {"ssm_layers", "ssm_chunks"} <= set(_SHARED_COUNTERS)


@pytest.mark.parametrize("free_gb,expect", [(0.0, 1), (1e3, 64)])
def test_the_batch_is_bounded_by_the_programs_bytes(bank, free_gb, expect):
    (bucket,) = bank._buckets.values()
    old = bucket._free_bytes
    try:
        bucket._free_bytes = int(free_gb * 1e9)
        limit = bank.batch_limit("m0", ROWS)
        assert limit == expect or (expect == 64 and limit >= 64)
    finally:
        bucket._free_bytes = old


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


@pytest.mark.parametrize("machine", [0, 2])
async def test_served_answer_matches_the_plain_reference(tree, machine):
    """``run-server``'s normal path: build_app -> ModelCollection ->
    ModelBank -> BatchingEngine -> POST, against the reference: the
    forecast, the experts each row was routed to, the counters on
    ``/stats`` and in the scrape. Tolerance as
    ``test_the_trunk_matches_the_plain_reference_on_seeded_weights`` (a
    fitted head leans on the input row more than a random one, and the
    float8 control, which that test shows failing, reads 0.107 here for
    machine 0: the control is the benchmark's business, at the cell's
    size)."""
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
    xs, exact = _reference(models, f"m{machine}", X)
    assert _rel(got["model-output"], exact["out"][:-1]) < 0.12
    diff = np.abs(xs[1:] - got["model-output"])
    np.testing.assert_allclose(got["tag-anomaly-unscaled"], diff, rtol=1e-5, atol=1e-6)
    experts = got["expert-selection"].astype(np.int64)
    assert experts.shape == (2, ROWS, 4) and experts.max() < 16 and "key-selection" not in got
    assert np.take_along_axis(exact["experts"], experts, axis=-1).mean() > 0.97
    shared = stats["bank_shared"]
    assert shared["ssm_layers"] == 2 * shared["dispatches"]
    assert shared["routed_pairs"] == ROWS * 4 * 2 * shared["dispatches"]
    for name in ("ssm_layers", "ssm_chunks", "held_pairs"):
        assert f"gordo_bank_shared_{name}_total {shared[name]}" in scrape.replace(".0\n", "\n")
