"""Plain reference of the shared decoder trunk with per-machine
projections (configuration ``keye_trunk300``): ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, dense over ALL experts with a
top-k mask, attention dense over ALL keys with a mask built from each
query's selection, in blocks of queries so that it fits. It imports nothing
of the program and shares no code with it.

One request is one causal sequence of ``T`` scaled sensor rows ``xs``
(T, F) of one machine. With the machine's own ``in_w, in_b, out_w, out_b``:

    x_0 = xs in_w + in_b                                   (T, D)
    x_{l+1} = layer_l(x_l)                                 l = 0..L-1
    out = RMSNorm(x_L) out_w + out_b                       (T, F)

and ``out[i]`` is the forecast of ``xs[i + 1]`` from rows ``0..i``. The
final RMSNorm is *assumed* (the family's decoder ends in one; the issue's
layer list stops at the last layer).

One layer (weights of ``layout.trunk_shapes``), as ISSUE 28 reads the
published config:

1. ``h1 = RMSNorm(x)``; ``q = h1 W_q`` (H heads of d), ``k = h1 W_k``,
   ``v = h1 W_v`` (G heads of d); RMSNorm over each head's d of ``q`` and
   ``k`` (*assumed*, the family's convention: the config has no key for
   it); multimodal RoPE (theta, ``mrope_section``) on ``q`` and ``k``,
   rotate-half convention. With its three position streams equal, as for a
   one-dimensional sequence, that is plain RoPE; the three sections are
   implemented here all the same (``mrope``).
2. Indexer: ``qI = h1 W_qI`` (J heads of dI, from ``h1`` and not from a
   low-rank query: *assumed*, the model has none), ``kI = LayerNorm(h1
   W_kI)`` (one head of dI), ``w = h1 W_w`` (J); RoPE on the first half of
   the dI dimensions, temporal stream (*assumed*);
   ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(dI)`` for
   ``s <= t``; ``S_t`` = the ``min(t + 1, topk)`` largest. Keys that tie
   with the last one kept are all kept (*assumed*; no tie at float32
   beyond exact zeros). ``q_chunk_size``/``kv_chunk_size`` are read as the
   tiling of this computation, with no effect on ``S_t`` (*assumed*).
3. ``a = softmax over s in S_t of (q . k / sqrt(d))``, grouped-query
   (H / G query heads a key-value head); ``x2 = x + (a v) W_o``.
4. ``h2 = RMSNorm(x2)``; ``p = softmax(h2 W_r)`` over the E experts; the
   top k, renormalised to sum 1 (``norm_topk_prob``);
   ``y = x2 + sum over e in top-k of p_e W_down,e (silu(W_gate,e h2) *
   W_up,e h2)``. No token is dropped, whatever the load; no shared expert.

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
FAULTS = ("top_k_minus_one", "half_topk", "no_renormalisation", "capacity_drop")


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


def mrope(x, positions3, theta: float, sections, rotary_dim: Optional[int] = None):
    """Multimodal RoPE on the first ``rotary_dim`` of ``x`` (T, heads, d):
    frequency ``i`` of the ``rotary_dim / 2`` rotates by the position of
    the stream its section names (``sections`` are counts of frequencies,
    temporal, height, width, scaled to the rotary half where that is not
    the head's own), rotate-half pairing ``(i, i + half)``."""
    d = x.shape[-1] if rotary_dim is None else rotary_dim
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / d))
    total = sum(sections)
    bounds = [round(half * sum(sections[: i + 1]) / total) for i in range(len(sections))]
    stream = jnp.searchsorted(jnp.asarray(bounds), jnp.arange(half), side="right")
    angle = positions3.astype(F32)[stream, :].T * inv_freq  # (T, half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def select(qI, w, kI, t, topk: int, operands: str):
    """``S_t`` for the queries at positions ``t``: (rows, T) bool."""
    dI = qI.shape[-1]
    dots = _mm(qI.transpose(1, 0, 2), kI.T, operands)  # (J, rows, T)
    index = jnp.einsum("jts,tj->ts", jax.nn.relu(dots), w) / math.sqrt(dI)
    causal = jnp.arange(kI.shape[0])[None, :] <= t[:, None]
    index = jnp.where(causal, index, -jnp.inf)
    if kI.shape[0] <= topk:
        return causal
    kth = jax.lax.top_k(index, topk)[0][:, -1]
    return causal & (index >= kth[:, None])


def layer(config: dict, w: Dict[str, jnp.ndarray], x, sampled, operands: str = "float32",
          fault: Optional[str] = None):
    """``(x_next, experts (T, E) bool, keys (len(sampled), T) bool)``."""
    sa = config["sa_config"]
    H, G, d = (int(config[n]) for n in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    J, dI = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    E, k = int(config["num_experts"]), int(config["num_experts_per_tok"])
    topk = int(sa["topk"]) // (2 if fault == "half_topk" else 1)
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    sections = config["rope_scaling"]["mrope_section"]
    T = x.shape[0]
    positions3 = jnp.broadcast_to(jnp.arange(T), (3, T))  # one-dimensional: equal streams

    h1 = rmsnorm(x, w["attn_norm"], eps)
    q = rmsnorm(_mm(h1, w["wq"], operands).reshape(T, H, d), w["q_norm"], eps)
    kk = rmsnorm(_mm(h1, w["wk"], operands).reshape(T, G, d), w["k_norm"], eps)
    v = _mm(h1, w["wv"], operands).reshape(T, G, d)
    q, kk = mrope(q, positions3, theta, sections), mrope(kk, positions3, theta, sections)
    qI = mrope(_mm(h1, w["idx_wq"], operands).reshape(T, J, dI), positions3, theta, [1], dI // 2)
    kI = layernorm(_mm(h1, w["idx_wk"], operands), w["idx_k_scale"], w["idx_k_bias"])
    kI = mrope(kI[:, None, :], positions3, theta, [1], dI // 2)[:, 0]
    wI = _mm(h1, w["idx_ww"], operands)

    block = max(b for b in range(1, min(T, 512) + 1) if T % b == 0)
    qg = q.reshape(T // block, block, G, H // G, d)

    def attend(args):
        qb, qIb, wb, tb = args
        S = select(qIb, wb, kI, tb, topk, operands)
        logits = _mm(qb.transpose(1, 2, 0, 3), kk.transpose(1, 2, 0)[:, None], operands) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(S[None, None], logits, -jnp.inf), axis=-1)  # (G, R, block, T)
        return _mm(a, v.transpose(1, 0, 2)[:, None], operands).transpose(2, 0, 1, 3)  # (block, G, R, d)

    blocks = (qg, qI.reshape(T // block, block, J, dI), wI.reshape(T // block, block, J),
              jnp.arange(T).reshape(T // block, block))
    o = jax.lax.map(attend, blocks).reshape(T, H * d)
    keys = select(qI[sampled], wI[sampled], kI, sampled, topk, operands)
    x2 = x + _mm(o, w["wo"], operands)

    h2 = rmsnorm(x2, w["mlp_norm"], eps)
    p = jax.nn.softmax(_mm(h2, w["router"], "bfloat16" if operands == "float8_e4m3fn" else "float32"), axis=-1)
    kept = k - (1 if fault == "top_k_minus_one" else 0)
    experts = p >= jax.lax.top_k(p, kept)[0][:, -1:]
    if fault == "capacity_drop":  # tokens beyond 1.25 x the mean load lose that expert
        capacity = math.ceil(1.25 * T * k / E)
        experts &= jnp.cumsum(experts, axis=0) <= capacity
    weight = jnp.where(experts, p, 0.0)
    if fault != "no_renormalisation":
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def add_expert(y, e):
        gate, up, down, pe = e
        act = jax.nn.silu(_mm(h2, gate, operands)) * _mm(h2, up, operands)
        return y + pe[:, None] * _mm(act, down, operands), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x2), (w["gate"], w["up"], w["down"], weight.T))
    return x2 + y, experts, keys


@functools.lru_cache(maxsize=None)
def _layer_program(sizes_json: str):
    """One jitted ``layer`` per configuration: a run's sampled answers
    trace and compile it once."""
    return jax.jit(
        functools.partial(layer, json.loads(sizes_json)), static_argnames=("operands", "fault")
    )


_SIZES = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts",
    "num_experts_per_tok", "rms_norm_eps", "rope_theta", "sa_config", "rope_scaling",
)


def forecast(config: dict, trunk_layer: Callable[[int], Dict[str, jnp.ndarray]],
             w: Dict[str, jnp.ndarray], xs, sampled, operands: str = "float32",
             fault: Optional[str] = None) -> Dict[str, jnp.ndarray]:
    """The whole model for one request. ``trunk_layer(l)`` hands layer
    ``l``'s weights (made and dropped one layer at a time); ``w`` the
    machine's leaves; ``sampled``: query positions whose selections are
    returned. ``out`` (T, F); ``experts`` (L, T, E) bool; ``keys``
    (L, len(sampled), T) bool."""
    run = _layer_program(json.dumps({k: config[k] for k in _SIZES}, sort_keys=True))
    sampled = jnp.asarray(sampled, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = jnp.matmul(jnp.asarray(xs, F32), w["in_w"]) + w["in_b"]
        experts, keys = [], []
        for l in range(int(config["num_hidden_layers"])):
            x, e, s = run(trunk_layer(l), x, sampled, operands=operands, fault=fault)
            experts.append(e)
            keys.append(s)
        out = jnp.matmul(rmsnorm(x, 1.0, float(config["rms_norm_eps"])), w["out_w"]) + w["out_b"]
    return {"out": out, "experts": jnp.stack(experts), "keys": jnp.stack(keys)}
