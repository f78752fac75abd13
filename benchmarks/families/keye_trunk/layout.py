"""A decoder trunk shared by every machine of the bank, with per-machine
projections (configuration ``keye_trunk300``): the weight leaves in the
reference's naming, the same weights under the program's parameter names,
and the counts of operations and bytes the per-layer metrics divide by.

Two kinds of leaves:

- **per machine** (``layer_shapes``; drawn in numpy by
  ``harness/weights.py`` like every member's): ``in_w`` (F, D), ``in_b``
  (D,), ``out_w`` (D, F), ``out_b`` (F,), float32;
- **the trunk** (``trunk_shapes``; drawn by ``trunk_layer`` with
  ``jax.random`` where the arrays will live, a layer at a time: the largest
  leaf is 0.8 GB in float32): uniform with variance 1/fan_in, **rounded to
  bfloat16 once and held in float32**; norm scales are 1, norm biases 0.

The counts depend on the configuration and the mix alone, never on which
kernel ran.
"""

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def sizes(config: dict) -> Dict[str, int]:
    sa = config["sa_config"]
    return dict(
        F=int(config["tags_per_machine"]), D=int(config["hidden_size"]),
        L=int(config["num_hidden_layers"]), H=int(config["num_attention_heads"]),
        G=int(config["num_key_value_heads"]), d=int(config["head_dim"]),
        E=int(config["num_experts"]), k=int(config["num_experts_per_tok"]),
        I=int(config["moe_intermediate_size"]), J=int(sa["indexer_num_heads"]),
        dI=int(sa["indexer_head_dim"]), topk=int(sa["topk"]),
        chunk=int(sa["q_chunk_size"]),
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


def trunk_shapes(config: dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """``(name, shape)`` of one layer's leaves, the program's names
    (``models/factories/trunk.py``). A matrix's fan-in is its second-last
    dimension."""
    z = sizes(config)
    D, H, G, d, J, dI, E, I = (z[n] for n in ("D", "H", "G", "d", "J", "dI", "E", "I"))
    return [
        ("attn_norm", (D,)), ("wq", (D, H * d)), ("wk", (D, G * d)), ("wv", (D, G * d)),
        ("q_norm", (d,)), ("k_norm", (d,)), ("wo", (H * d, D)),
        ("idx_wq", (D, J * dI)), ("idx_wk", (D, dI)), ("idx_k_scale", (dI,)),
        ("idx_k_bias", (dI,)), ("idx_ww", (D, J)),
        ("mlp_norm", (D,)), ("router", (D, E)),
        ("gate", (E, D, I)), ("up", (E, D, I)), ("down", (E, I, D)),
    ]


def _key(seed: int, layer: int, leaf: int):
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    for part in (seed >> 31, layer, leaf):
        key = jax.random.fold_in(key, part)
    return key


def trunk_layer(config: dict, seed: int, layer: int) -> Dict[str, jnp.ndarray]:
    """Layer ``layer`` of the trunk of ``--seed``, float32 values that
    bfloat16 holds exactly."""
    out = {}
    for i, (name, shape) in enumerate(trunk_shapes(config)):
        if len(shape) == 1:
            out[name] = (jnp.zeros if name.endswith("_bias") else jnp.ones)(shape, F32)
        else:
            limit = (3.0 / shape[-2]) ** 0.5
            draw = jax.random.uniform(_key(seed, layer, i), shape, F32, -limit, limit)
            out[name] = draw.astype(jnp.bfloat16).astype(F32)
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


def selected_keys(rows: int, topk: int) -> float:
    """Sum over a request's queries of the keys each attends to."""
    full = max(0, rows - topk)
    head = min(rows, topk)
    return head * (head + 1) / 2.0 + full * float(topk)


def causal_pairs(rows: int) -> float:
    return rows * (rows + 1) / 2.0


def dense_flops_per_row(config: dict) -> float:
    """2 a multiply-add of the matrices every row meets in one layer:
    q, k, v, o, the indexer's three projections, the router."""
    z = sizes(config)
    D = z["D"]
    return 2.0 * D * (2 * z["H"] * z["d"] + 2 * z["G"] * z["d"] + z["J"] * z["dI"] + z["dI"] + z["J"] + z["E"])


def experts_flops_per_row(config: dict) -> float:
    """One layer: gate, up and down of the row's ``k`` experts."""
    z = sizes(config)
    return 2.0 * 3 * z["D"] * z["I"] * z["k"]


def sparse_attention_flops(config: dict, rows: int) -> float:
    """One layer, one request: scores and values over the selected keys
    (2 matmuls, all query heads), indexer scores over the causal pairs."""
    z = sizes(config)
    return (4.0 * z["H"] * z["d"] * selected_keys(rows, z["topk"])
            + 2.0 * z["J"] * z["dI"] * causal_pairs(rows))


def forward_flops_per_row(config: dict) -> float:
    """Forward FLOPs of one row of a request of the configuration's
    ``nominal_request_rows``, averaged over its positions: the row's own
    ``k`` experts and the keys it selected, not 128 and all; plus the
    machine's two projections. Norms, RoPE, softmax and the epilogue are
    left out (under 1%)."""
    z = sizes(config)
    rows = int(config["nominal_request_rows"])
    per_layer = (dense_flops_per_row(config) + experts_flops_per_row(config)
                 + sparse_attention_flops(config, rows) / rows)
    return z["L"] * per_layer + 2.0 * 2 * z["F"] * z["D"]


def experts_bytes(config: dict, dispatches: float, rows: float) -> float:
    """HBM bytes the expert layers cannot avoid over ``dispatches`` bucket
    programs that carried ``rows`` request rows: every layer's experts
    read once a dispatch (bfloat16), each row's state in and out once a
    layer (float32)."""
    z = sizes(config)
    weights = z["L"] * z["E"] * 3 * z["D"] * z["I"] * 2.0
    return dispatches * weights + rows * z["L"] * 2 * z["D"] * 4.0


def sparse_attention_bytes(config: dict, rows: float) -> float:
    """Per layer and row: queries, keys, values, indexer queries and keys
    read once (bfloat16), the output written once (float32)."""
    z = sizes(config)
    per_row = 2.0 * (z["H"] * z["d"] + 2 * z["G"] * z["d"] + z["J"] * z["dI"] + z["dI"]) + 4.0 * z["H"] * z["d"]
    return rows * z["L"] * per_row
