"""A hybrid decoder trunk whose layers are each ONE mixer (a Mamba-2
state-space mixer, a routed layer of squared-ReLU experts beside a shared
one, or grouped-query attention over every causal key), shared by every
machine of the bank, with per-machine projections (configuration
``nemotron3_trunk300``): the weight leaves in the reference's naming, the
same weights under the program's parameter names, and the counts of
operations and bytes the per-layer metrics divide by.

Two kinds of leaves:

- **per machine** (``layer_shapes``; drawn in numpy by
  ``harness/weights.py`` like every member's): ``in_w`` (F, D), ``in_b``
  (D,), ``out_w`` (D, F), ``out_b`` (F,), float32;
- **the trunk** (``trunk_shapes``; drawn by ``trunk_layer`` with
  ``jax.random`` where the arrays will live, a layer at a time): matrices
  (the convolution's (K, channels) among them) uniform with variance
  1/fan_in, **rounded to bfloat16 once and held in float32**; a routed
  expert's matrices drawn from ITS OWN number among the published experts,
  so every chip's share of a layer is a slice of one and the same layer;
  only the held ones are made. The mixer's ``A_log``, ``dt_bias`` and ``D``
  as the published initialisation draws them (``A`` uniform on [1, 16),
  ``dt`` log-uniform on [``time_step_min``, ``time_step_max``] floored at
  ``time_step_floor`` and ``dt_bias`` its inverse softplus, ``D`` one);
  every other vector AWAY from its neutral value, so that no comparison is
  blind to it: norm scales uniform on [0.5, 1.5), the convolution's bias on
  [-0.1, 0.1), the router's correction bias (``router_bias``, the published
  ``e_score_correction_bias``) on [-0.02, 0.02); the last norm's scale
  stays 1.

Which layers are held, and what each is, comes from the configuration's
``held_layers``: the published ``hybrid_override_pattern``'s letters at
the held layers' published indices (``M`` a mixer, ``E`` routed, ``*``
attention).

The counts depend on the configuration and the mix alone, never on which
kernel ran. The chip holds a share of each routed layer's experts
(``expert_shard``): the per-row count takes the share's part of a row's
``num_experts_per_tok`` at an even load, ``k * held / E``.
"""

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EXPERT_LEAVES = ("up", "down")
ROUTER_BIAS_LIMIT = 0.02


def sizes(config: dict) -> Dict[str, int]:
    first, end = config["expert_shard"]["held"]
    return dict(
        F=int(config["tags_per_machine"]), D=int(config["hidden_size"]),
        L=int(config["num_hidden_layers"]), H=int(config["mamba_num_heads"]),
        P=int(config["mamba_head_dim"]), G=int(config["n_groups"]), N=int(config["ssm_state_size"]),
        K=int(config["conv_kernel"]), Q=int(config["chunk_size"]),
        Hq=int(config["num_attention_heads"]), Hkv=int(config["num_key_value_heads"]),
        d=int(config["head_dim"]), I=int(config["moe_intermediate_size"]),
        S=int(config["moe_shared_expert_intermediate_size"]),
        E=int(config["published"]["n_routed_experts"]), first=int(first), held=int(end) - int(first),
        k=int(config["num_experts_per_tok"]),
    )


def kinds(config: dict) -> List[str]:
    """``M``, ``E`` or ``*``, one entry a held layer."""
    pattern = config["hybrid_override_pattern"]
    return [pattern[i] for i in config["held_layers"]["published_index"]]


def layer_shapes(config: dict) -> List[Tuple[str, Tuple[int, ...], float]]:
    """``(name, shape, limit)`` of every PER-MACHINE leaf, in the order the
    flat draw is cut (see the dense family's)."""
    z = sizes(config)
    F, D = z["F"], z["D"]
    return [
        ("in_w", (F, D), (3.0 / F) ** 0.5), ("in_b", (D,), 0.1),
        ("out_w", (D, F), (3.0 / D) ** 0.5), ("out_b", (F,), 0.1),
    ]


def trunk_shapes(config: dict, layer: int) -> List[Tuple[str, Tuple[int, ...]]]:
    """``(name, shape)`` of layer ``layer``'s leaves, the program's names
    (``models/factories/trunk.py``). A matrix's fan-in is its second-last
    dimension; ``up`` and ``down`` of a routed layer are the held
    experts', one matrix each."""
    z = sizes(config)
    D, kind = z["D"], kinds(config)[layer]
    if kind == "M":
        d_in, conv = z["H"] * z["P"], z["H"] * z["P"] + 2 * z["G"] * z["N"]
        return [
            ("input_norm", (D,)), ("in_proj", (D, d_in + conv + z["H"])), ("conv", (z["K"], conv)),
            ("conv_bias", (conv,)), ("dt_bias", (z["H"],)), ("A_log", (z["H"],)), ("D", (z["H"],)),
            ("mixer_norm", (d_in,)), ("out_proj", (d_in, D)),
        ]
    if kind == "E":
        return [
            ("input_norm", (D,)), ("router", (D, z["E"])), ("router_bias", (z["E"],)),
            ("up", (z["held"], D, z["I"])), ("down", (z["held"], z["I"], D)),
            ("shared_up", (D, z["S"])), ("shared_down", (z["S"], D)),
        ]
    return [
        ("input_norm", (D,)), ("wq", (D, z["Hq"] * z["d"])), ("wk", (D, z["Hkv"] * z["d"])),
        ("wv", (D, z["Hkv"] * z["d"])), ("wo", (z["Hq"] * z["d"], D)),
    ]


def _key(seed: int, layer: int, name: str):
    """A leaf's key: by the seed, the layer and the leaf's NAME (its bytes
    summed with their places), so that a layer's leaf is the same leaf
    whatever else the layer holds."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    leaf = sum((i + 1) * b for i, b in enumerate(name.encode()))
    for part in (seed >> 31, layer, leaf):
        key = jax.random.fold_in(key, part)
    return key


def _matrix(key, shape):
    limit = (3.0 / shape[-2]) ** 0.5
    return jax.random.uniform(key, shape, F32, -limit, limit).astype(jnp.bfloat16).astype(F32)


def _vector(config: dict, key, name: str, shape):
    u = jax.random.uniform(key, shape, F32)
    if name == "A_log":
        return jnp.log(1.0 + 15.0 * u)
    if name == "dt_bias":
        lo, hi = math.log(float(config["time_step_min"])), math.log(float(config["time_step_max"]))
        dt = jnp.maximum(jnp.exp(lo + (hi - lo) * u), float(config["time_step_floor"]))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) = dt
    if name == "D":
        return jnp.ones(shape, F32)
    if name == "router_bias":
        return (2.0 * u - 1.0) * ROUTER_BIAS_LIMIT
    if name == "conv_bias":
        return (2.0 * u - 1.0) * 0.1
    return 0.5 + u  # a norm's scale: 0.5 to 1.5, so that a norm left out shows


def trunk_layer(config: dict, seed: int, layer: int) -> Dict[str, jnp.ndarray]:
    """Layer ``layer`` of the trunk of ``--seed``, float32 values; the
    matrices' are values that bfloat16 holds exactly."""
    first = sizes(config)["first"]
    out = {}
    for name, shape in trunk_shapes(config, layer):
        key = _key(seed, layer, name)
        if len(shape) == 1:
            out[name] = _vector(config, key, name, shape)
        elif len(shape) == 3:  # expert e of the published ones, whichever chip holds it
            out[name] = jnp.stack([
                _matrix(jax.random.fold_in(key, first + e), shape[1:]) for e in range(shape[0])])
        else:
            out[name] = _matrix(key, shape)
    return out


def trunk_to_program(config: dict, seed: int) -> dict:
    """The whole trunk as the program holds it: bfloat16 matrices,
    float32 vectors, made a leaf at a time."""
    layers = []
    for layer in range(sizes(config)["L"]):
        drawn = trunk_layer(config, seed, layer)
        layers.append({
            name: (a if a.ndim == 1 else a.astype(jnp.bfloat16)) for name, a in drawn.items()
        })
    return {"layers": layers, "final_norm": jnp.ones((sizes(config)["D"],), F32)}


def to_program(config: dict, w: Dict[str, np.ndarray]) -> dict:
    """A machine's leaves under the program's parameter names."""
    return {
        "in_proj": {"kernel": w["in_w"], "bias": w["in_b"]},
        "head": {"kernel": w["out_w"], "bias": w["out_b"]},
    }


def from_program(params: dict) -> Dict[str, np.ndarray]:
    return {
        "in_w": np.asarray(params["in_proj"]["kernel"]), "in_b": np.asarray(params["in_proj"]["bias"]),
        "out_w": np.asarray(params["head"]["kernel"]), "out_b": np.asarray(params["head"]["bias"]),
    }


# ------------------------------------------------------------------ counts


def count(config: dict, kind: str) -> int:
    return kinds(config).count(kind)


def causal_pairs(rows: float) -> float:
    return rows * (rows + 1) / 2.0


def scan_flops(config: dict, rows: float) -> float:
    """One mixer layer's scan over ``rows`` rows, in whole chunks of
    ``chunk_size`` (the published kernel's form): a chunk's ``C B^T`` a
    group (Q x Q x N), its decayed matrix times x a head (Q x Q x P), the
    carried state's term and the state's update a head (Q x P x N each)."""
    z = sizes(config)
    d_in = z["H"] * z["P"]
    return rows * (2.0 * z["Q"] * z["G"] * z["N"] + 2.0 * z["Q"] * d_in + 4.0 * d_in * z["N"])


def scan_bytes(config: dict, rows: float) -> float:
    """HBM bytes one mixer layer's scan cannot avoid over ``rows`` rows:
    x, B and C in (bfloat16), the time step and the running sum of its
    decays in (float32), y out (float32). The state stays on chip."""
    z = sizes(config)
    d_in = z["H"] * z["P"]
    return rows * (2.0 * (d_in + 2 * z["G"] * z["N"]) + 4.0 * 2 * z["H"] + 4.0 * d_in)


def causal_attention_flops(config: dict, rows: int) -> float:
    """One attention layer, one request: scores and values of every query
    head over every causal pair."""
    z = sizes(config)
    return 4.0 * z["Hq"] * z["d"] * causal_pairs(rows)


def causal_attention_bytes(config: dict, rows: float) -> float:
    """One attention layer, ``rows`` rows: every query head's query and
    the key-value heads' keys and values in (bfloat16), every query head's
    output out (float32)."""
    z = sizes(config)
    return rows * (2.0 * (z["Hq"] + 2 * z["Hkv"]) * z["d"] + 4.0 * z["Hq"] * z["d"])


def held_experts_flops(config: dict, held_pairs: float) -> float:
    """Up and down of every (row, expert) pair on a held expert."""
    z = sizes(config)
    return held_pairs * 2.0 * 2 * z["D"] * z["I"]


def held_experts_bytes(config: dict, dispatches: float, rows: float) -> float:
    """HBM bytes the routed layers' held experts cannot avoid over
    ``dispatches`` bucket programs that carried ``rows`` request rows:
    every routed layer's held experts read once a dispatch (bfloat16), each
    row's state in and out once a routed layer (float32)."""
    z = sizes(config)
    layers = count(config, "E")
    weights = layers * z["held"] * 2 * z["D"] * z["I"] * 2.0
    return dispatches * weights + rows * layers * 2 * z["D"] * 4.0


def forward_flops_per_row(config: dict) -> float:
    """Forward FLOPs of one row of a request of the configuration's
    ``nominal_request_rows``, averaged over its positions: 2 a multiply-add
    of the matrices a row meets (a mixer's two projections, a routed
    layer's router, shared expert and the held share of the row's ``k``
    experts at an even load, attention's four), each mixer's scan in whole
    chunks, attention's scores and values over every causal pair, and the
    machine's two projections. Norms, the convolution, softplus, the
    gate, softmax and the epilogue are left out (under 1%)."""
    z = sizes(config)
    rows = int(config["nominal_request_rows"])
    D, H, P, G, N = z["D"], z["H"], z["P"], z["G"], z["N"]
    d_in = H * P
    mixer = D * (2 * d_in + 2 * G * N + H) + d_in * D
    routed = D * z["E"] + 2 * D * z["S"] + 2 * D * z["I"] * z["k"] * z["held"] / z["E"]
    attention = 2 * D * z["Hq"] * z["d"] + 2 * D * z["Hkv"] * z["d"]
    matrices = count(config, "M") * mixer + count(config, "E") * routed + count(config, "*") * attention
    per_row = (count(config, "M") * scan_flops(config, rows)
               + count(config, "*") * causal_attention_flops(config, rows)) / rows
    return 2.0 * matrices + per_row + 2.0 * 2 * z["F"] * D
