"""Plain reference of the latent-attention decoder trunk whose attention
runs under an indexer's selection that one layer makes and the next ones
reuse, with per-machine projections (configuration ``glm52_trunk300``):
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``, no
kernel, attention dense over ALL keys under the causal mask and the
selection in blocks of queries so that it fits, the indexer over ALL causal
keys with an exact sort-based top-k, the held experts dense over ALL rows
under a top-k mask. It imports nothing of the program and shares no code
with it.

One request is one causal sequence of ``T`` scaled sensor rows ``xs``
(T, F) of one machine. With the machine's own ``in_w, in_b, out_w, out_b``:

    x_0 = xs in_w + in_b                                   (T, D)
    x_{l+1}, S_{l+1} = layer_l(x_l, S_l)                   l = 0..L-1
    out = RMSNorm(x_L) out_w + out_b                       (T, F)

and ``out[i]`` is the forecast of ``xs[i + 1]`` from rows ``0..i``. The
final RMSNorm is *assumed* (the family's decoder ends in one). ``S`` is the
selection in force: for every query ``t`` the set ``S_t`` of keys it
attends to, made by a ``full`` layer and reused by the ``shared`` layers
after it (the published ``indexer_types``; the held layers' entries are the
configuration's ``held_layers``).

One layer (weights of ``layout.trunk_shapes``), as ISSUE 35 writes the
equations from the published config's keys (``model_type: glm_moe_dsa``:
the DeepSeek-V3 family's latent attention and router, DeepSeek Sparse
Attention's indexer as published, the selection shared between layers):

1. ``h = RMSNorm(x)``. ``c_q = RMSNorm(h W_qa)`` (``q_lora_rank``);
   ``[q_nope, q_rope] = c_q W_qb`` per head (``qk_nope_head_dim`` +
   ``qk_rope_head_dim``). ``[c_kv, k_rope] = h W_kva`` (``kv_lora_rank`` +
   ``qk_rope_head_dim``); ``c_kv <- RMSNorm(c_kv)``; ``[k_nope, v] = c_kv
   W_kvb`` per head (``qk_nope_head_dim`` + ``v_head_dim``). RoPE
   (``rope_parameters.rope_theta``, no scaling) on ``q_rope`` per head and
   on the ONE ``k_rope`` every head shares, dimension ``i`` paired with
   ``i + rope/2`` (*assumed*: ``rope_interleave`` is a relabelling under
   random weights).
2. A ``full`` layer's indexer: ``qI = c_q W_Iq`` (``index_n_heads`` heads of
   ``index_head_dim``; from the NORMED QUERY LATENT, not from ``h``),
   ``kI = LayerNorm(h W_Ik)`` (one head; scale and bias, eps 1e-6),
   ``wI = h W_Iw`` (one weight a head); RoPE on the first
   ``qk_rope_head_dim`` of ``qI`` and ``kI``, same pairing
   (``indexer_rope_interleave``: as above).
   ``I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s]) * n_heads^-1/2 *
   head_dim^-1/2`` for ``s <= t``; ``S_t`` = the ``min(t + 1, index_topk)``
   keys of largest ``I[t, .]``; keys that tie with the last one kept are
   all kept. The published FP8 rounding of ``qI`` and ``kI`` and their
   Hadamard rotation are left out (the rotation is orthogonal and changes
   no dot product). A ``shared`` layer has no indexer: its ``S`` is the
   ``S`` it was handed.
3. ``score = (q_nope . k_nope + q_rope . k_rope) (nope + rope)^-1/2``,
   softmax over ``s in S_t``, ``x2 = x + concat_heads(P v) W_o``. No biases.
4. ``h2 = RMSNorm(x2)``. A dense layer: ``y = W_down(silu(W_gate h2) * W_up
   h2)``. A routed layer: ``s = sigmoid(h2 W_r)`` over ALL the published
   experts in float32; kept = the ``num_experts_per_tok`` largest of ``s +
   b`` (``topk_method: noaux_tc``: ``b`` the correction bias; ``n_group``
   1: no group limit); ``w_e = routed_scaling_factor s_e / (sum of the kept
   s)`` from the UNBIASED scores (``norm_topk_prob``). ``y = sum over kept
   e HELD HERE of w_e SwiGLU_e(h2) + SwiGLU_shared(h2)``: the
   configuration's ``expert_shard.held`` says which experts this chip
   holds, and what the absent ones would add is left out, here as in the
   program. No pair on a held expert is dropped, whatever the load.
5. ``x_next = x2 + y``.

Precision. The configuration states: trunk matmuls with bfloat16 operands
accumulated in float32, the router's matmul in float32. ``operands`` rounds
every trunk matmul's operands to that dtype first (``"float32"``: the
reference; ``"bfloat16"``: what the configuration states, for a reading of
the arithmetic alone; ``"float8_e4m3fn"``: the control, one precision
below, which also rounds the router's operands to bfloat16). ``fault``
plants one of the faults ``correct`` has to catch.
"""

import functools
import json
import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = (
    "shared_attends_all", "stale_selection", "half_topk", "no_correction_bias", "bias_in_weights",
    "scale_192", "top_k_minus_one", "capacity_drop",
)


def _mm(a, b, operands: str):
    if operands != "float32":
        a, b = a.astype(operands).astype(F32), b.astype(operands).astype(F32)
    return jnp.matmul(a, b, precision="highest")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def layernorm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def published(config: dict, key: str):
    """A size as the source has it, where this chip's share changed it."""
    return config.get("published", {}).get(key, config[key])


def held_range(config: dict):
    """``(first, end)`` of the routed experts this chip holds."""
    first, end = config.get("expert_shard", {}).get(
        "held", [0, int(published(config, "n_routed_experts"))])
    return int(first), int(end)


def rope(x, theta: float, rotary_dim: int):
    """``x`` (T, heads, d): of its first ``rotary_dim`` dimensions, ``i``
    rotates with ``i + rotary_dim/2`` by ``position * theta^(-2i /
    rotary_dim)``; the rest pass."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / rotary_dim)
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _blocks(T: int):
    """Query positions in blocks of at most 256 that divide ``T``."""
    block = max(b for b in range(1, min(T, 256) + 1) if T % b == 0)
    return jnp.arange(T).reshape(T // block, block)


def select(qI, wI, kI, t, topk: int, operands: str):
    """``S_t`` for the queries at positions ``t``: (rows, T) bool. ``qI``
    (rows, J, dI), ``wI`` (rows, J), ``kI`` (T, dI)."""
    J, dI = qI.shape[1], qI.shape[2]
    dots = _mm(qI.transpose(1, 0, 2), kI.T, operands)  # (J, rows, T)
    index = jnp.einsum("jts,tj->ts", jax.nn.relu(dots), wI) * J ** -0.5 * dI ** -0.5
    causal = jnp.arange(kI.shape[0])[None, :] <= t[:, None]
    if kI.shape[0] <= topk:
        return causal
    index = jnp.where(causal, index, -jnp.inf)
    kth = jnp.sort(index, axis=-1)[:, -topk]  # exact: the whole row sorted
    return causal & (index >= kth[:, None])


def attention(config: dict, w, x, selection, operands: str, fault: Optional[str], first: bool):
    """``(MLA(RMSNorm(x)) under a selection (T, D), the selection the layer
    hands on (T, T) bool, the one it attended under)``: the same two but
    under a planted fault. A layer with an indexer (``idx_wq``) makes its
    own; ``first``: it is the first layer held (it is handed none)."""
    H, rkv = int(config["num_attention_heads"]), int(config["kv_lora_rank"])
    nope, dr, dv = (int(config[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    eps, T = float(config["rms_norm_eps"]), x.shape[0]
    theta = float(config["rope_parameters"]["rope_theta"])
    scale = (nope if fault == "scale_192" else nope + dr) ** -0.5
    h = rmsnorm(x, w["attn_norm"], eps)
    c_q = rmsnorm(_mm(h, w["q_a"], operands), w["q_a_norm"], eps)
    q = _mm(c_q, w["q_b"], operands).reshape(T, H, nope + dr)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta, dr)], -1).transpose(1, 0, 2)
    latent = _mm(h, w["kv_a"], operands)
    c_kv = rmsnorm(latent[:, :rkv], w["kv_a_norm"], eps)
    k_rope = rope(latent[:, None, rkv:], theta, dr)[:, 0]  # (T, dr): one for every head
    kv = _mm(c_kv, w["kv_b"], operands).reshape(T, H, nope + dv)
    keys = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope[:, None], (T, H, dr))], -1)
    keys, values = keys.transpose(1, 2, 0), kv[..., nope:].transpose(1, 0, 2)  # (H, k, T), (H, T, dv)
    blocks = _blocks(T)

    attend_under = selection
    if "idx_wq" in w:
        J, dI = int(config["index_n_heads"]), int(config["index_head_dim"])
        topk = int(config["index_topk"]) // (2 if fault == "half_topk" else 1)
        qI = rope(_mm(c_q, w["idx_wq"], operands).reshape(T, J, dI), theta, dr)
        kI = layernorm(_mm(h, w["idx_wk"], operands), w["idx_k_scale"], w["idx_k_bias"])
        kI = rope(kI[:, None, :], theta, dr)[:, 0]
        wI = _mm(h, w["idx_ww"], operands)
        made = jax.lax.map(lambda t: select(qI[t], wI[t], kI, t, topk, operands), blocks)
        selection = made.reshape(T, T)
        # the fault: a later full layer hands its selection on but attends under the old one
        attend_under = attend_under if fault == "stale_selection" and not first else selection
    elif fault == "shared_attends_all":
        attend_under = jnp.tril(jnp.ones((T, T), bool))

    def one(rows):
        logits = _mm(q[:, rows], keys, operands) * scale  # (H, block, T)
        p = jax.nn.softmax(jnp.where(attend_under[rows][None], logits, -jnp.inf), axis=-1)
        return _mm(p, values, operands)

    out = jax.lax.map(one, blocks)  # (blocks, H, block, dv)
    out = out.transpose(0, 2, 1, 3).reshape(T, H * dv)
    return _mm(out, w["wo"], operands), selection, attend_under


def router(config: dict, w, h, operands: str, fault: Optional[str]):
    """``(weight (T, E) float32, zero off the kept experts; kept (T, E)
    bool)`` over ALL the published experts."""
    k = int(config["num_experts_per_tok"])
    assert int(config["n_group"]) == 1 and int(config["topk_group"]) == 1, "no group limit is implemented"
    logits = _mm(h, w["router"], "bfloat16" if operands == "float8_e4m3fn" else "float32")
    s = jax.nn.sigmoid(logits)
    biased = s + w["router_bias"]
    choice = s if fault == "no_correction_bias" else biased
    kept_n = k - (1 if fault == "top_k_minus_one" else 0)
    kept = choice >= jnp.sort(choice, axis=-1)[:, -kept_n][:, None]
    weight = jnp.where(kept, biased if fault == "bias_in_weights" else s, 0.0)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True) * float(config["routed_scaling_factor"])
    return weight, kept


def swiglu(h, gate, up, down, operands: str):
    return _mm(silu(_mm(h, gate, operands)) * _mm(h, up, operands), down, operands)


def ffn_parts(config: dict, w, h, operands: str = "float32", fault: Optional[str] = None):
    """A routed layer's feed-forward in its parts: ``(the held experts'
    part (T, D), the shared expert's (T, D), kept (T, E) bool)``."""
    first, end = held_range(config)
    weight, kept = router(config, w, h, operands, fault)
    if fault == "capacity_drop":  # pairs beyond 1.25 x the mean load lose that expert
        E, k = kept.shape[1], int(config["num_experts_per_tok"])
        capacity = math.ceil(1.25 * h.shape[0] * k / E)
        weight = jnp.where(jnp.cumsum(kept, axis=0) <= capacity, weight, 0.0)

    def add_expert(y, e):
        gate, up, down, we = e
        return y + we[:, None] * swiglu(h, gate, up, down, operands), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h), (w["gate"], w["up"], w["down"], weight[:, first:end].T))
    shared = swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], operands)
    return routed, shared, kept


def layer(config: dict, w: Dict[str, jnp.ndarray], x, selection, operands: str = "float32",
          fault: Optional[str] = None, first: bool = False):
    """``(x_next, the selection handed on (T, T) bool, kept (T, E) bool,
    the selection attended under (T, T) bool)``; a layer without a router
    is dense and keeps nothing: (T, 0)."""
    attended, selection, under = attention(config, w, x, selection, operands, fault, first)
    x2 = x + attended
    h2 = rmsnorm(x2, w["mlp_norm"], float(config["rms_norm_eps"]))
    if "router" not in w:
        y = swiglu(h2, w["gate"], w["up"], w["down"], operands)
        return x2 + y, selection, jnp.zeros((x.shape[0], 0), bool), under
    routed, shared, kept = ffn_parts(config, w, h2, operands, fault)
    return x2 + routed + shared, selection, kept, under


_SIZES = (
    "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "rms_norm_eps", "rope_parameters", "index_n_heads", "index_head_dim", "index_topk",
    "num_experts_per_tok", "n_group", "topk_group", "routed_scaling_factor", "n_routed_experts",
    "published", "expert_shard",
)


@functools.lru_cache(maxsize=None)
def _layer_program(sizes_json: str):
    """One jitted ``layer`` per configuration (it compiles once for each
    kind of layer): a run's sampled answers trace and compile it once."""
    return jax.jit(
        functools.partial(layer, json.loads(sizes_json)),
        static_argnames=("operands", "fault", "first"),
    )


def forecast(config: dict, trunk_layer: Callable[[int], Dict[str, jnp.ndarray]],
             w: Dict[str, jnp.ndarray], xs, sampled, operands: str = "float32",
             fault: Optional[str] = None) -> Dict[str, jnp.ndarray]:
    """The whole model for one request. ``trunk_layer(l)`` hands layer
    ``l``'s weights (made and dropped one layer at a time); ``w`` the
    machine's leaves; ``sampled``: query positions whose selections are
    returned. ``out`` (T, F); ``experts`` (routed layers, T, E) bool: each
    row's kept experts of ALL the published ones; ``keys`` (layers,
    len(sampled), T) bool: the selection EVERY layer attended under, its
    own in a ``full`` layer, the one handed on in a ``shared`` one."""
    run = _layer_program(json.dumps({k: config[k] for k in _SIZES if k in config}, sort_keys=True))
    sampled = jnp.asarray(sampled, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = jnp.matmul(jnp.asarray(xs, F32), w["in_w"]) + w["in_b"]
        selection = jnp.zeros((0, 0), bool)  # the first layer is full and is handed none
        experts, keys = [], []
        for l in range(int(config["num_hidden_layers"])):
            weights = trunk_layer(l)
            x, selection, kept, under = run(
                weights, x, selection, operands=operands, fault=fault, first=l == 0)
            del weights  # one layer's weights at a time: the next is made when this one is done with
            x.block_until_ready()
            if kept.shape[1]:
                experts.append(kept)
            keys.append(under[sampled])
            del under
        out = jnp.matmul(rmsnorm(x, 1.0, float(config["rms_norm_eps"])), w["out_w"]) + w["out_b"]
    return {"out": out, "experts": jnp.stack(experts), "keys": jnp.stack(keys)}
