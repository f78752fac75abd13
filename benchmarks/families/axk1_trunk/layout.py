"""A latent-attention decoder trunk shared by every machine of the bank,
with per-machine projections (configuration ``axk1_trunk300``): the weight
leaves in the reference's naming, the same weights under the program's
parameter names, and the counts of operations and bytes the per-layer
metrics divide by.

Two kinds of leaves:

- **per machine** (``layer_shapes``; drawn in numpy by
  ``harness/weights.py`` like every member's): ``in_w`` (F, D), ``in_b``
  (D,), ``out_w`` (D, F), ``out_b`` (F,), float32;
- **the trunk** (``trunk_shapes``; drawn by ``trunk_layer`` with
  ``jax.random`` where the arrays will live, a layer at a time): uniform
  with variance 1/fan_in, **rounded to bfloat16 once and held in float32**;
  norm scales uniform on [0.5, 1.5), float32 (at 1, and with a variance of
  1/fan_in before it, ``c_q``'s RMSNorm changes nothing a comparison can
  see: on the chip a reference without it read like the stated
  arithmetic; the last norm's stays 1). A routed expert's matrices are drawn from ITS OWN
  number among the published experts, so every chip's share of a layer is a
  slice of one and the same layer; only the held ones are made.

The counts depend on the configuration and the mix alone, never on which
kernel ran. The chip holds a share of each routed layer's experts
(``expert_shard``): the per-row count takes the share's part of a row's
``num_experts_per_tok`` at an even load, ``k * held / E``.
"""

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EXPERT_LEAVES = ("gate", "up", "down")


def sizes(config: dict) -> Dict[str, int]:
    first, end = config["expert_shard"]["held"]
    return dict(
        F=int(config["tags_per_machine"]), D=int(config["hidden_size"]),
        L=int(config["num_hidden_layers"]), H=int(config["num_attention_heads"]),
        rq=int(config["q_lora_rank"]), rkv=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]), rope=int(config["qk_rope_head_dim"]),
        dv=int(config["v_head_dim"]), W=int(config["intermediate_size"]),
        I=int(config["moe_intermediate_size"]), dense=int(config["first_k_dense_replace"]),
        E=int(config["published"]["n_routed_experts"]), first=int(first), held=int(end) - int(first),
        shared=int(config["n_shared_experts"]), k=int(config["num_experts_per_tok"]),
    )


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
    dimension; ``gate``, ``up`` and ``down`` of a routed layer are the held
    experts', one matrix each."""
    z = sizes(config)
    D, H, I = z["D"], z["H"], z["I"]
    shapes = [
        ("attn_norm", (D,)), ("q_a", (D, z["rq"])), ("q_a_norm", (z["rq"],)),
        ("q_b", (z["rq"], H * (z["nope"] + z["rope"]))), ("kv_a", (D, z["rkv"] + z["rope"])),
        ("kv_a_norm", (z["rkv"],)), ("kv_b", (z["rkv"], H * (z["nope"] + z["dv"]))),
        ("wo", (H * z["dv"], D)), ("mlp_norm", (D,)),
    ]
    if layer < z["dense"]:
        return shapes + [("gate", (D, z["W"])), ("up", (D, z["W"])), ("down", (z["W"], D))]
    S = I * z["shared"]
    return shapes + [
        ("router", (D, z["E"])),
        ("gate", (z["held"], D, I)), ("up", (z["held"], D, I)), ("down", (z["held"], I, D)),
        ("shared_gate", (D, S)), ("shared_up", (D, S)), ("shared_down", (S, D)),
    ]


def _key(seed: int, layer: int, leaf: int):
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    for part in (seed >> 31, layer, leaf):
        key = jax.random.fold_in(key, part)
    return key


def _matrix(key, shape):
    limit = (3.0 / shape[-2]) ** 0.5
    return jax.random.uniform(key, shape, F32, -limit, limit).astype(jnp.bfloat16).astype(F32)


def trunk_layer(config: dict, seed: int, layer: int) -> Dict[str, jnp.ndarray]:
    """Layer ``layer`` of the trunk of ``--seed``, float32 values that
    bfloat16 holds exactly."""
    first = sizes(config)["first"]
    out = {}
    for i, (name, shape) in enumerate(trunk_shapes(config, layer)):
        key = _key(seed, layer, i)
        if len(shape) == 1:  # a norm's scale: 0.5 to 1.5, so that a norm left out shows
            out[name] = 0.5 + jax.random.uniform(key, shape, F32)
        elif len(shape) == 3:  # expert e of the published ones, whichever chip holds it
            out[name] = jnp.stack([
                _matrix(jax.random.fold_in(key, first + e), shape[1:]) for e in range(shape[0])])
        else:
            out[name] = _matrix(key, shape)
    return out


def trunk_to_program(config: dict, seed: int) -> dict:
    """The whole trunk as the program holds it: bfloat16 matrices,
    float32 norms, made a leaf at a time."""
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


def routed_layers(config: dict) -> int:
    z = sizes(config)
    return z["L"] - min(z["dense"], z["L"])


def causal_pairs(rows: int) -> float:
    return rows * (rows + 1) / 2.0


def attention_matrices(config: dict) -> float:
    """Parameters of the five matrices of one layer's latent attention."""
    z = sizes(config)
    H = z["H"]
    return float(z["D"] * z["rq"] + z["rq"] * H * (z["nope"] + z["rope"]) + z["D"] * (z["rkv"] + z["rope"])
                 + z["rkv"] * H * (z["nope"] + z["dv"]) + H * z["dv"] * z["D"])


def latent_attention_flops(config: dict, rows: int) -> float:
    """One layer, one request: scores (nope + rope wide) and values (dv
    wide) of every head over the causal pairs, in the expanded form, which
    is the fewest the equations need."""
    z = sizes(config)
    return 2.0 * z["H"] * (z["nope"] + z["rope"] + z["dv"]) * causal_pairs(rows)


def latent_attention_bytes(config: dict, rows: float) -> float:
    """One layer, ``rows`` rows: the least any form must move, bfloat16:
    every head's query and the row's latent (``kv_lora_rank`` + rope) in,
    every head's output out."""
    z = sizes(config)
    return rows * 2.0 * (z["H"] * (z["nope"] + z["rope"]) + z["rkv"] + z["rope"] + z["H"] * z["dv"])


def held_experts_flops(config: dict, held_pairs: float) -> float:
    """Gate, up and down of every (row, expert) pair on a held expert."""
    z = sizes(config)
    return held_pairs * 2.0 * 3 * z["D"] * z["I"]


def held_experts_bytes(config: dict, dispatches: float, rows: float) -> float:
    """HBM bytes the routed layers' held experts cannot avoid over
    ``dispatches`` bucket programs that carried ``rows`` request rows:
    every routed layer's held experts read once a dispatch (bfloat16), each
    row's state in and out once a routed layer (float32)."""
    z = sizes(config)
    layers = routed_layers(config)
    weights = layers * z["held"] * 3 * z["D"] * z["I"] * 2.0
    return dispatches * weights + rows * layers * 2 * z["D"] * 4.0


def forward_flops_per_row(config: dict) -> float:
    """Forward FLOPs of one row of a request of the configuration's
    ``nominal_request_rows``, averaged over its positions: 2 a multiply-add
    of the matrices a row meets (attention's five in every layer; the dense
    layers' three; a routed layer's router, shared expert and the held
    share of the row's ``k`` experts at an even load) plus the causal
    attention and the machine's two projections. Norms, RoPE, softmax and
    the epilogue are left out (under 1%)."""
    z = sizes(config)
    rows = int(config["nominal_request_rows"])
    D, I = z["D"], z["I"]
    dense = min(z["dense"], z["L"])
    routed = D * z["E"] + 3 * D * I * z["shared"] + 3 * D * I * z["k"] * z["held"] / z["E"]
    matrices = z["L"] * attention_matrices(config) + dense * 3 * D * z["W"] + routed_layers(config) * routed
    attend = z["L"] * latent_attention_flops(config, rows) / rows
    return 2.0 * matrices + attend + 2.0 * 2 * z["F"] * D
