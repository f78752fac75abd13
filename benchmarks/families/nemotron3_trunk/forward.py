"""Plain reference of the hybrid decoder trunk whose layers are each ONE
mixer, with per-machine projections (configuration ``nemotron3_trunk300``):
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``, no
kernel. The state-space mixer is the SEQUENTIAL recurrence, one row after
another (a ``lax.scan`` over rows: no chunks, so it is independent of the
program's chunk form); attention is dense over ALL keys under the causal
mask, in blocks of queries so that it fits; the held experts are dense
over ALL rows, weighted by the router's choice. It imports nothing of the
program and shares no code with it.

One request is one causal sequence of ``T`` scaled sensor rows ``xs``
(T, F) of one machine. With the machine's own ``in_w, in_b, out_w, out_b``:

    x_0 = xs in_w + in_b                                   (T, D)
    x_{l+1} = x_l + mixer_l(RMSNorm(x_l))                  l = 0..L-1
    out = RMSNorm(x_L) out_w + out_b                       (T, F)

and ``out[i]`` is the forecast of ``xs[i + 1]`` from rows ``0..i``. Every
RMSNorm has a scale and eps ``layer_norm_epsilon``; the last one's scale
is 1 (the published ``norm_f``'s, *assumed* untrained). A held layer's
mixer is its letter in the published ``hybrid_override_pattern`` at its
published index (the configuration's ``held_layers``), as the configuration
reads the published config's keys (``model_type: nemotron_h``):

- ``M``, Mamba-2 (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``,
  d_in = H P; G = ``n_groups`` groups of N = ``ssm_state_size``; K =
  ``conv_kernel``). ``[z, xBC, dt] = h W_in`` (d_in, d_in + 2 G N, H);
  ``xBC <- silu(conv(xBC) + b_conv)``, the convolution causal and depthwise:
  row t sees rows t - K + 1 .. t; ``[x, B, C] = xBC`` (H x P, G x N, G x
  N); head h reads group ``h // (H / G)``. ``Delta = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``, per head. Then, for t = 1..T from
  ``S_0 = 0``:

      S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T        (P, N) a head
      y_t = S_t C_t + D x_t

  ``y <- RMSNorm_grouped(y * silu(z))``: the gate first, then an RMSNorm
  over each of the G groups of d_in / G channels, one scale (d_in,);
  ``mixer = y W_out``.
- ``E``: ``s = sigmoid(h W_r)`` over ALL the published experts in float32;
  kept = the ``num_experts_per_tok`` largest of ``s + b`` (``b`` the
  correction bias; ``n_group`` 1: no group limit); ``w_e =
  routed_scaling_factor s_e / (sum of the kept s)`` from the UNBIASED scores
  (``norm_topk_prob``). ``mixer = sum over kept e HELD HERE of w_e
  W_down,e relu(W_up,e h)^2 + W_down,shared relu(W_up,shared h)^2``: the
  configuration's ``expert_shard.held`` says which experts this chip holds,
  and what the absent ones would add is left out, here as in the program.
  No pair on a held expert is dropped, whatever the load.
- ``*``: ``q = h W_q`` (``num_attention_heads`` of ``head_dim``), ``k = h
  W_k``, ``v = h W_v`` (``num_key_value_heads``; query head j reads
  key-value head ``j // (Hq / Hkv)``); no rotary embedding (NemotronH's
  attention applies none); softmax over every ``s <= t`` of ``q_t . k_s
  head_dim^-1/2``; ``mixer = concat_heads(P v) W_o``. No biases.

Precision. The configuration states: trunk matmuls with bfloat16 operands
accumulated in float32, the router's matmul in float32. ``operands`` rounds
every trunk matmul's operands, and the scan's x, B and C, to that dtype
first (``"float32"``: the reference; ``"bfloat16"``: what the configuration
states, for a reading of the arithmetic alone; ``"float8_e4m3fn"``: the
control, one precision below, which also rounds the router's operands to
bfloat16). ``fault`` plants one of the faults ``correct`` has to catch.
"""

import functools
import json
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = (
    "state_reset_each_chunk", "dt_without_softplus", "no_skip_d", "norm_over_all_groups",
    "head_reads_group_mod", "conv_looks_ahead", "relu_not_squared", "no_routed_scale",
    "top_k_minus_one", "bias_in_weights",
)


def _round(a, operands: str):
    return a if operands == "float32" else a.astype(operands).astype(F32)


def _mm(a, b, operands: str):
    return jnp.matmul(_round(a, operands), _round(b, operands), precision="highest")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def held_range(config: dict):
    """``(first, end)`` of the routed experts this chip holds."""
    first, end = config["expert_shard"]["held"]
    return int(first), int(end)


def mamba(config: dict, w, h, operands: str, fault: Optional[str]):
    """The Mamba-2 mixer of one layer over the normed rows ``h`` (T, D)."""
    H, P, G, N = (int(config[k]) for k in ("mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size"))
    K, Q, eps = int(config["conv_kernel"]), int(config["chunk_size"]), float(config["layer_norm_epsilon"])
    T, d_in = h.shape[0], H * P
    proj = _mm(h, w["in_proj"], operands)
    z, xBC, dt = proj[:, :d_in], proj[:, d_in:2 * d_in + 2 * G * N], proj[:, 2 * d_in + 2 * G * N:]
    ahead = 1 if fault == "conv_looks_ahead" else 0  # the fault: row t sees t - K + 2 .. t + 1
    padded = jnp.pad(xBC, ((K - 1 - ahead, ahead), (0, 0)))
    xBC = silu(sum(padded[k:k + T] * w["conv"][k] for k in range(K)) + w["conv_bias"])
    x = _round(xBC[:, :d_in], operands).reshape(T, H, P)
    Bm = _round(xBC[:, d_in:d_in + G * N], operands).reshape(T, G, N)
    Cm = _round(xBC[:, d_in + G * N:], operands).reshape(T, G, N)
    raw = dt + w["dt_bias"]
    delta = raw if fault == "dt_without_softplus" else jax.nn.softplus(raw)
    A = -jnp.exp(w["A_log"])
    heads = jnp.arange(H)
    group = heads % G if fault == "head_reads_group_mod" else heads // (H // G)

    def step(S, row):
        t, x_t, d_t, b_t, c_t = row
        if fault == "state_reset_each_chunk":  # the state not carried from one chunk to the next
            S = jnp.where(t % Q == 0, 0.0, S)
        S = jnp.exp(d_t * A)[:, None, None] * S + (d_t[:, None] * x_t)[:, :, None] * b_t[group][:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, c_t[group], precision="highest")

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (jnp.arange(T), x, delta, Bm, Cm))
    if fault != "no_skip_d":
        y = y + w["D"][:, None] * x
    y = y.reshape(T, d_in) * silu(z)
    if fault == "norm_over_all_groups":
        y = rmsnorm(y, w["mixer_norm"], eps)
    else:
        y = rmsnorm(y.reshape(T, G, d_in // G), 1.0, eps).reshape(T, d_in) * w["mixer_norm"]
    return _mm(y, w["out_proj"], operands)


def router(config: dict, w, h, operands: str, fault: Optional[str]):
    """``(weight (T, E) float32, zero off the kept experts; kept (T, E)
    bool)`` over ALL the published experts."""
    k = int(config["num_experts_per_tok"])
    assert int(config["n_group"]) == 1 and int(config["topk_group"]) == 1, "no group limit is implemented"
    logits = _mm(h, w["router"], "bfloat16" if operands == "float8_e4m3fn" else "float32")
    s = jax.nn.sigmoid(logits)
    biased = s + w["router_bias"]
    kept_n = k - (1 if fault == "top_k_minus_one" else 0)
    kept = biased >= jnp.sort(biased, axis=-1)[:, -kept_n][:, None]
    weight = jnp.where(kept, biased if fault == "bias_in_weights" else s, 0.0)
    scale = 1.0 if fault == "no_routed_scale" else float(config["routed_scaling_factor"])
    return weight / jnp.sum(weight, axis=-1, keepdims=True) * scale, kept


def relu2(h, up, down, operands: str, fault: Optional[str]):
    a = jax.nn.relu(_mm(h, up, operands))
    return _mm(a if fault == "relu_not_squared" else a * a, down, operands)


def ffn_parts(config: dict, w, h, operands: str = "float32", fault: Optional[str] = None):
    """A routed layer in its parts: ``(the held experts' part (T, D), the
    shared expert's (T, D), kept (T, E) bool)``."""
    first, end = held_range(config)
    weight, kept = router(config, w, h, operands, fault)

    def add_expert(y, e):
        up, down, we = e
        return y + we[:, None] * relu2(h, up, down, operands, fault), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (w["up"], w["down"], weight[:, first:end].T))
    shared = relu2(h, w["shared_up"], w["shared_down"], operands, fault)
    return routed, shared, kept


def _blocks(T: int):
    """Query positions in blocks of at most 256 that divide ``T``."""
    block = max(b for b in range(1, min(T, 256) + 1) if T % b == 0)
    return jnp.arange(T).reshape(T // block, block)


def attention(config: dict, w, h, operands: str):
    """Grouped-query attention over every causal key, no rotation."""
    Hq, Hkv, d = (int(config[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    T = h.shape[0]
    q = _mm(h, w["wq"], operands).reshape(T, Hq, d).transpose(1, 0, 2)  # (Hq, T, d)
    kv_head = jnp.arange(Hq) // (Hq // Hkv)
    keys = _mm(h, w["wk"], operands).reshape(T, Hkv, d)[:, kv_head].transpose(1, 2, 0)  # (Hq, d, T)
    values = _mm(h, w["wv"], operands).reshape(T, Hkv, d)[:, kv_head].transpose(1, 0, 2)  # (Hq, T, d)

    def one(rows):
        logits = _mm(q[:, rows], keys, operands) * d ** -0.5  # (Hq, block, T)
        causal = jnp.arange(T)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), axis=-1)
        return _mm(p, values, operands)

    out = jax.lax.map(one, _blocks(T))  # (blocks, Hq, block, d)
    return _mm(out.transpose(0, 2, 1, 3).reshape(T, Hq * d), w["wo"], operands)


def layer(config: dict, w: Dict[str, jnp.ndarray], x, operands: str = "float32",
          fault: Optional[str] = None):
    """``(x_next, kept (T, E) bool)``; a layer that is not routed keeps
    nothing: (T, 0)."""
    h = rmsnorm(x, w["input_norm"], float(config["layer_norm_epsilon"]))
    none = jnp.zeros((x.shape[0], 0), bool)
    if "A_log" in w:
        return x + mamba(config, w, h, operands, fault), none
    if "router" in w:
        routed, shared, kept = ffn_parts(config, w, h, operands, fault)
        return x + routed + shared, kept
    return x + attention(config, w, h, operands), none


_SIZES = (
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
    "layer_norm_epsilon", "num_attention_heads", "num_key_value_heads", "head_dim",
    "num_experts_per_tok", "n_group", "topk_group", "routed_scaling_factor", "expert_shard",
)


@functools.lru_cache(maxsize=None)
def _layer_program(sizes_json: str):
    """One jitted ``layer`` per configuration (it compiles once for each
    kind of layer): a run's sampled answers trace and compile it once."""
    return jax.jit(functools.partial(layer, json.loads(sizes_json)), static_argnames=("operands", "fault"))


def forecast(config: dict, trunk_layer: Callable[[int], Dict[str, jnp.ndarray]],
             w: Dict[str, jnp.ndarray], xs, operands: str = "float32",
             fault: Optional[str] = None) -> Dict[str, jnp.ndarray]:
    """The whole model for one request. ``trunk_layer(l)`` hands layer
    ``l``'s weights (made and dropped one layer at a time); ``w`` the
    machine's leaves. ``out`` (T, F); ``experts`` (routed layers, T, E)
    bool: each row's kept experts of ALL the published ones."""
    run = _layer_program(json.dumps({k: config[k] for k in _SIZES if k in config}, sort_keys=True))
    with jax.default_matmul_precision("highest"):
        x = jnp.matmul(jnp.asarray(xs, F32), w["in_w"]) + w["in_b"]
        experts = []
        for l in range(int(config["num_hidden_layers"])):
            weights = trunk_layer(l)
            x, kept = run(weights, x, operands=operands, fault=fault)
            del weights  # one layer's weights at a time: the next is made when this one is done with
            x.block_until_ready()
            if kept.shape[1]:
                experts.append(kept)
        out = jnp.matmul(rmsnorm(x, 1.0, float(config["layer_norm_epsilon"])), w["out_w"]) + w["out_b"]
    return {"out": out, "experts": jnp.stack(experts)}
