"""Plain reference of the latent-attention decoder trunk with per-machine
projections (configuration ``axk1_trunk300``): ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, no kernel, attention dense
over ALL keys under a causal mask in blocks of queries so that it fits, the
held experts dense over ALL rows under a top-k mask. It imports nothing of
the program and shares no code with it.

One request is one causal sequence of ``T`` scaled sensor rows ``xs``
(T, F) of one machine. With the machine's own ``in_w, in_b, out_w, out_b``:

    x_0 = xs in_w + in_b                                   (T, D)
    x_{l+1} = layer_l(x_l)                                 l = 0..L-1
    out = RMSNorm(x_L) out_w + out_b                       (T, F)

and ``out[i]`` is the forecast of ``xs[i + 1]`` from rows ``0..i``. The
final RMSNorm is *assumed* (the family's decoder ends in one).

One layer (weights of ``layout.trunk_shapes``), the DeepSeek-V3 family's
equations as ISSUE 33 reads the published config's keys:

1. ``h = RMSNorm(x)``. ``c_q = RMSNorm(h W_qa)`` (``q_lora_rank``);
   ``[q_nope, q_rope] = c_q W_qb`` per head (``qk_nope_head_dim`` +
   ``qk_rope_head_dim``). ``[c_kv, k_rope] = h W_kva`` (``kv_lora_rank`` +
   ``qk_rope_head_dim``); ``c_kv <- RMSNorm(c_kv)``; ``[k_nope, v] = c_kv
   W_kvb`` per head (``qk_nope_head_dim`` + ``v_head_dim``). RoPE on
   ``q_rope`` per head and on the ONE ``k_rope`` every head shares,
   dimension ``i`` paired with ``i + rope/2`` (*assumed*: the family's code
   permutes to this form, and with random weights the pairing is a
   relabelling).
2. YaRN (``rope_scaling``): ``f_i = theta^(-2i/d)``; ``dim(r) = d ln(orig /
   (2 pi r)) / (2 ln theta)``; ``low = floor(dim(beta_fast))``, ``high =
   ceil(dim(beta_slow))``, clamped to ``[0, d/2 - 1]``; ``ramp_i =
   clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = f_i / factor *
   ramp_i + f_i (1 - ramp_i)``. With ``m(s, a) = 0.1 a ln s + 1``: cos and
   sin times ``m(factor, mscale) / m(factor, mscale_all_dim)`` (1 here),
   and ``scale = (nope + rope)^(-1/2) m(factor, mscale_all_dim)^2``.
3. ``score = (q_nope . k_nope + q_rope . k_rope) scale``, causal softmax,
   ``x2 = x + concat_heads(P v) W_o``. No biases. (``form="absorbed"``
   computes the same through the latent: ``W_kvb`` folded into the query
   and the output, every head attending over ``[c_kv, k_rope]`` itself.)
4. ``h2 = RMSNorm(x2)``. A dense layer (``layer < first_k_dense_replace``):
   ``y = W_down(silu(W_gate h2) * W_up h2)``. A routed layer: ``s =
   sigmoid(h2 W_r)`` over ALL the published experts in float32; groups of
   ``E / n_group``, a group's score the sum of its two largest ``s``; the
   ``topk_group`` best groups kept; of their experts the
   ``num_experts_per_tok`` largest ``s``; ``w_e = routed_scaling_factor
   s_e / (sum of the kept s)`` (``norm_topk_prob``). ``topk_method:
   "none"`` is read as "no correction bias on the scores" (*assumed*).
   ``y = sum over kept e HELD HERE of w_e SwiGLU_e(h2) + SwiGLU_shared(h2)``:
   the configuration's ``expert_shard.held`` says which experts this chip
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
    "plain_rope", "no_group_limit", "softmax_router", "no_routed_scaling",
    "no_shared_expert", "top_k_minus_one", "no_q_norm", "capacity_drop",
)


def _mm(a, b, operands: str):
    if operands != "float32":
        a, b = a.astype(operands).astype(F32), b.astype(operands).astype(F32)
    return jnp.matmul(a, b, precision="highest")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


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


def yarn(config: dict, plain: bool = False):
    """``(inv_freq (rope/2,), softmax scale)``; ``plain``: RoPE and
    ``(nope + rope)^(-1/2)`` as if the config had no ``rope_scaling``."""
    d, theta = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    width = int(config["qk_nope_head_dim"]) + d
    freq = theta ** (-jnp.arange(d // 2, dtype=F32) * 2.0 / d)
    scaling = config.get("rope_scaling") or {}
    factor = float(scaling.get("factor", 1.0))
    if plain or factor == 1.0:
        return freq, width ** -0.5
    original = float(scaling["original_max_position_embeddings"])
    dim = lambda r: d * math.log(original / (2 * math.pi * r)) / (2 * math.log(theta))
    low = max(math.floor(dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(scaling["beta_slow"]))), d // 2 - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / (high - low), 0.0, 1.0)
    m = lambda a: 0.1 * float(a) * math.log(factor) + 1.0
    assert m(scaling["mscale"]) == m(scaling["mscale_all_dim"]), "cos and sin would be scaled"
    return freq / factor * ramp + freq * (1.0 - ramp), width ** -0.5 * m(scaling["mscale_all_dim"]) ** 2


def rope(x, inv_freq):
    """``x`` (T, heads, d): dimension ``i`` rotates with ``i + d/2`` by
    ``position * inv_freq_i``."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attend_in_blocks(queries, keys, values, scale: float, operands: str):
    """``softmax over s <= t of (queries . keys) scale``, times ``values``,
    in blocks of queries so that a week-long request fits. ``queries``
    (H, T, k); ``keys`` (H, k, T), or (k, T) where every head shares them;
    ``values`` (H, T, n) or (T, n). Returns (H, T, n)."""
    H, T, _ = queries.shape
    block = max(b for b in range(1, min(T, 256) + 1) if T % b == 0)

    def one(rows):
        logits = _mm(queries[:, rows], keys, operands) * scale  # (H, block, T)
        causal = jnp.arange(T)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), axis=-1)
        return _mm(p, values, operands)

    out = jax.lax.map(one, jnp.arange(T).reshape(T // block, block))  # (blocks, H, block, n)
    return out.transpose(1, 0, 2, 3).reshape(H, T, -1)


def attention(config: dict, w, x, operands: str, fault: Optional[str], form: str):
    """``MLA(RMSNorm(x))``: (T, D)."""
    H, rkv = int(config["num_attention_heads"]), int(config["kv_lora_rank"])
    nope, dr, dv = (int(config[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    eps, T = float(config["rms_norm_eps"]), x.shape[0]
    inv_freq, scale = yarn(config, plain=fault == "plain_rope")
    h = rmsnorm(x, w["attn_norm"], eps)
    c_q = _mm(h, w["q_a"], operands)
    if fault != "no_q_norm":
        c_q = rmsnorm(c_q, w["q_a_norm"], eps)
    q = _mm(c_q, w["q_b"], operands).reshape(T, H, nope + dr)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], inv_freq)
    latent = _mm(h, w["kv_a"], operands)
    c_kv = rmsnorm(latent[:, :rkv], w["kv_a_norm"], eps)
    k_rope = rope(latent[:, None, rkv:], inv_freq)[:, 0]  # (T, dr): one for every head
    w_kvb = w["kv_b"].reshape(rkv, H, nope + dv)
    per_head = lambda a, b: _mm(a.transpose(1, 0, 2), b, operands)  # (T, H, k), (H, k, n) -> (H, T, n)
    if form == "expanded":
        kv = _mm(c_kv, w["kv_b"], operands).reshape(T, H, nope + dv)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope[:, None], (T, H, dr))], -1)
        qq = jnp.concatenate([q_nope, q_rope], -1).transpose(1, 0, 2)  # (H, T, nope + dr)
        keys, values = k.transpose(1, 2, 0), kv[..., nope:].transpose(1, 0, 2)
        out = _attend_in_blocks(qq, keys, values, scale, operands)
    elif form == "absorbed":
        # W_kvb's key half folded into the query, its value half into the output
        q_lat = per_head(q_nope, w_kvb[..., :nope].transpose(1, 2, 0))  # (H, T, rkv)
        qq = jnp.concatenate([q_lat, q_rope.transpose(1, 0, 2)], -1)  # (H, T, rkv + dr)
        keys = jnp.concatenate([c_kv, k_rope], -1).T  # (rkv + dr, T): every head's
        lat = _attend_in_blocks(qq, keys, c_kv, scale, operands)  # (H, T, rkv)
        out = _mm(lat, w_kvb[..., nope:].transpose(1, 0, 2), operands)  # (H, T, dv)
    else:
        raise ValueError(form)
    return _mm(out.transpose(1, 0, 2).reshape(T, H * dv), w["wo"], operands)


def router(config: dict, w_router, h, operands: str, fault: Optional[str]):
    """``(weight (T, E) float32, zero off the kept experts; kept (T, E)
    bool)`` over ALL the published experts."""
    E, k = int(published(config, "n_routed_experts")), int(config["num_experts_per_tok"])
    n_group, topk_group = int(config["n_group"]), int(config["topk_group"])
    logits = _mm(h, w_router, "bfloat16" if operands == "float8_e4m3fn" else "float32")
    s = jax.nn.softmax(logits, axis=-1) if fault == "softmax_router" else jax.nn.sigmoid(logits)
    allowed = jnp.ones_like(s, bool)
    if n_group > 1 and fault != "no_group_limit":
        grouped = s.reshape(-1, n_group, E // n_group)
        group_score = jnp.sum(jnp.sort(grouped, axis=-1)[..., -2:], axis=-1)
        kth = jnp.sort(group_score, axis=-1)[:, -topk_group][:, None]
        allowed = jnp.repeat(group_score >= kth, E // n_group, axis=-1)
    choice = jnp.where(allowed, s, -1.0)
    kept_n = k - (1 if fault == "top_k_minus_one" else 0)
    kept = choice >= jnp.sort(choice, axis=-1)[:, -kept_n][:, None]
    weight = jnp.where(kept, s, 0.0)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    if fault != "no_routed_scaling":
        weight = weight * float(config["routed_scaling_factor"])
    return weight, kept


def swiglu(h, gate, up, down, operands: str):
    return _mm(silu(_mm(h, gate, operands)) * _mm(h, up, operands), down, operands)


def ffn_parts(config: dict, w, h, operands: str = "float32", fault: Optional[str] = None):
    """A routed layer's feed-forward in its parts: ``(the held experts'
    part (T, D), the shared expert's (T, D), kept (T, E) bool)``."""
    first, end = held_range(config)
    weight, kept = router(config, w["router"], h, operands, fault)
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
    if fault == "no_shared_expert":
        shared = jnp.zeros_like(shared)
    return routed, shared, kept


def layer(config: dict, w: Dict[str, jnp.ndarray], x, operands: str = "float32",
          fault: Optional[str] = None, form: str = "expanded"):
    """``(x_next, kept (T, E) bool)``; a layer without a router is dense
    and keeps nothing: (T, 0)."""
    x2 = x + attention(config, w, x, operands, fault, form)
    h2 = rmsnorm(x2, w["mlp_norm"], float(config["rms_norm_eps"]))
    if "router" not in w:
        y = swiglu(h2, w["gate"], w["up"], w["down"], operands)
        return x2 + y, jnp.zeros((x.shape[0], 0), bool)
    routed, shared, kept = ffn_parts(config, w, h2, operands, fault)
    return x2 + routed + shared, kept


_SIZES = (
    "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "rms_norm_eps", "rope_theta", "rope_scaling", "num_experts_per_tok", "n_group", "topk_group",
    "routed_scaling_factor", "n_routed_experts", "published", "expert_shard",
)


@functools.lru_cache(maxsize=None)
def _layer_program(sizes_json: str):
    """One jitted ``layer`` per configuration (it compiles once for each
    kind of layer): a run's sampled answers trace and compile it once."""
    return jax.jit(
        functools.partial(layer, json.loads(sizes_json)),
        static_argnames=("operands", "fault", "form"),
    )


def forecast(config: dict, trunk_layer: Callable[[int], Dict[str, jnp.ndarray]],
             w: Dict[str, jnp.ndarray], xs, operands: str = "float32",
             fault: Optional[str] = None, form: str = "expanded") -> Dict[str, jnp.ndarray]:
    """The whole model for one request. ``trunk_layer(l)`` hands layer
    ``l``'s weights (made and dropped one layer at a time); ``w`` the
    machine's leaves. ``out`` (T, F); ``experts`` (routed layers, T, E)
    bool: each row's kept experts of ALL the published ones."""
    run = _layer_program(json.dumps({k: config[k] for k in _SIZES if k in config}, sort_keys=True))
    with jax.default_matmul_precision("highest"):
        x = jnp.matmul(jnp.asarray(xs, F32), w["in_w"]) + w["in_b"]
        experts = []
        for l in range(int(config["num_hidden_layers"])):
            x, kept = run(trunk_layer(l), x, operands=operands, fault=fault, form=form)
            if kept.shape[1]:
                experts.append(kept)
        out = jnp.matmul(rmsnorm(x, 1.0, float(config["rms_norm_eps"])), w["out_w"]) + w["out_b"]
    return {"out": out, "experts": jnp.stack(experts)}
