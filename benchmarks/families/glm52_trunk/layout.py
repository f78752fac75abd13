"""A latent-attention decoder trunk whose attention runs under an indexer's
selection that one layer makes and the next ones reuse, shared by every
machine of the bank, with per-machine projections (configuration
``glm52_trunk300``): the weight leaves in the reference's naming, the same
weights under the program's parameter names, and the counts of operations
and bytes the per-layer metrics divide by.

Two kinds of leaves:

- **per machine** (``layer_shapes``; drawn in numpy by
  ``harness/weights.py`` like every member's): ``in_w`` (F, D), ``in_b``
  (D,), ``out_w`` (D, F), ``out_b`` (F,), float32;
- **the trunk** (``trunk_shapes``; drawn by ``trunk_layer`` with
  ``jax.random`` where the arrays will live, a layer at a time): matrices
  uniform with variance 1/fan_in, **rounded to bfloat16 once and held in
  float32**; a routed expert's matrices drawn from ITS OWN number among the
  published experts, so every chip's share of a layer is a slice of one and
  the same layer; only the held ones are made. The vectors are drawn AWAY
  from their neutral values, so that no comparison is blind to them: norm
  scales (RMSNorm's and the indexer's LayerNorm's) uniform on [0.5, 1.5),
  **the query latent's (``q_a_norm``) on [2, 4)** (every row of a request
  carries the same mean, so with logits of unit variance a softmax over
  2048 such keys is flat, and neither its temperature nor which keys it
  runs over moves the stream; with logits three times as wide a softmax
  scale of 192^-1/2 shows. The indexer's queries come from the same latent:
  its scores scale with it and its ranking stays),
  the LayerNorm's bias on [-0.1, 0.1), and the router's correction bias
  (``router_bias``, the published ``e_score_correction_bias``) on
  [-0.02, 0.02): beside sigmoid scores whose eighth and ninth largest of
  256 lie 0.006 apart, leaving it out moves a tenth of the expert choices
  (``tests/test_selected_latent_trunk.py``); the last norm's scale stays 1.

Which layers are held, and what each is, comes from the configuration's
``held_layers`` (the published ``indexer_types`` and ``mlp_layer_types``
entries of the held layers): a ``full`` layer has an indexer's leaves, a
``shared`` one none; a ``dense`` layer a SwiGLU, a ``sparse`` one a router,
its bias, the held experts and a shared expert.

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
ROUTER_BIAS_LIMIT = 0.02
WITNESS_STRIDE = 64  # every 64th query's selection rides the answer (docs/observability.md)


def sizes(config: dict) -> Dict[str, int]:
    first, end = config["expert_shard"]["held"]
    return dict(
        F=int(config["tags_per_machine"]), D=int(config["hidden_size"]),
        L=int(config["num_hidden_layers"]), H=int(config["num_attention_heads"]),
        rq=int(config["q_lora_rank"]), rkv=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]), rope=int(config["qk_rope_head_dim"]),
        dv=int(config["v_head_dim"]), W=int(config["intermediate_size"]),
        I=int(config["moe_intermediate_size"]),
        J=int(config["index_n_heads"]), dI=int(config["index_head_dim"]), topk=int(config["index_topk"]),
        E=int(config["published"]["n_routed_experts"]), first=int(first), held=int(end) - int(first),
        shared=int(config["n_shared_experts"]), k=int(config["num_experts_per_tok"]),
    )


def indexer_types(config: dict) -> List[str]:
    """``full`` or ``shared``, one entry a held layer."""
    return list(config["held_layers"]["indexer_types"])


def mlp_types(config: dict) -> List[str]:
    """``dense`` or ``sparse``, one entry a held layer."""
    return list(config["held_layers"]["mlp_layer_types"])


def witness_stride(config: dict) -> int:
    """Every 64th query's selection; every chunk's last where a chunk is
    shorter (CPU tests)."""
    return min(WITNESS_STRIDE, int(config["chunk_size"]))


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
    if indexer_types(config)[layer] == "full":
        shapes += [
            ("idx_wq", (z["rq"], z["J"] * z["dI"])), ("idx_wk", (D, z["dI"])),
            ("idx_k_scale", (z["dI"],)), ("idx_k_bias", (z["dI"],)), ("idx_ww", (D, z["J"])),
        ]
    if mlp_types(config)[layer] == "dense":
        return shapes + [("gate", (D, z["W"])), ("up", (D, z["W"])), ("down", (z["W"], D))]
    S = I * z["shared"]
    return shapes + [
        ("router", (D, z["E"])), ("router_bias", (z["E"],)),
        ("gate", (z["held"], D, I)), ("up", (z["held"], D, I)), ("down", (z["held"], I, D)),
        ("shared_gate", (D, S)), ("shared_up", (D, S)), ("shared_down", (S, D)),
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


def _vector(key, name: str, shape):
    u = jax.random.uniform(key, shape, F32)
    if name == "router_bias":
        return (2.0 * u - 1.0) * ROUTER_BIAS_LIMIT
    if name.endswith("_bias"):  # the indexer's LayerNorm
        return (2.0 * u - 1.0) * 0.1
    if name == "q_a_norm":  # logits three times as wide: the softmax is no longer flat
        return 2.0 + 2.0 * u
    return 0.5 + u  # a norm's scale: 0.5 to 1.5, so that a norm left out shows


def trunk_layer(config: dict, seed: int, layer: int) -> Dict[str, jnp.ndarray]:
    """Layer ``layer`` of the trunk of ``--seed``, float32 values; the
    matrices' are values that bfloat16 holds exactly."""
    first = sizes(config)["first"]
    out = {}
    for name, shape in trunk_shapes(config, layer):
        key = _key(seed, layer, name)
        if len(shape) == 1:
            out[name] = _vector(key, name, shape)
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


def routed_layers(config: dict) -> int:
    return mlp_types(config).count("sparse")


def full_layers(config: dict) -> int:
    """Layers that make a selection."""
    return indexer_types(config).count("full")


def causal_pairs(rows: int) -> float:
    return rows * (rows + 1) / 2.0


def selected_pairs(config: dict, rows: int) -> float:
    """(query, key) pairs one layer attends over in a ``rows``-row request:
    ``min(t + 1, index_topk)`` a query."""
    topk = sizes(config)["topk"]
    head = min(rows, topk)
    return head * (head + 1) / 2.0 + max(0, rows - topk) * float(topk)


def attention_matrices(config: dict) -> float:
    """Parameters of the five matrices of one layer's latent attention."""
    z = sizes(config)
    H = z["H"]
    return float(z["D"] * z["rq"] + z["rq"] * H * (z["nope"] + z["rope"]) + z["D"] * (z["rkv"] + z["rope"])
                 + z["rkv"] * H * (z["nope"] + z["dv"]) + H * z["dv"] * z["D"])


def indexer_matrices(config: dict) -> float:
    """Parameters of a ``full`` layer's three indexer matrices."""
    z = sizes(config)
    return float(z["rq"] * z["J"] * z["dI"] + z["D"] * z["dI"] + z["D"] * z["J"])


def selected_attention_flops(config: dict, rows: int) -> float:
    """One layer, one request: scores (nope + rope wide) and values (dv
    wide) of every head over the SELECTED pairs: what the equations need,
    whatever the kernel computes (today every causal tile)."""
    z = sizes(config)
    return 2.0 * z["H"] * (z["nope"] + z["rope"] + z["dv"]) * selected_pairs(config, rows)


def selected_attention_bytes(config: dict, rows: float, request_rows: int) -> float:
    """One layer, ``rows`` rows of ``request_rows``-row requests: the least
    any form must move: every head's query and the row's latent
    (``kv_lora_rank`` + rope) in, every head's output out, in bfloat16, and
    the selection's bits (a causal row's ``(t + 1) / 8`` bytes, on average
    ``request_rows / 16`` a row)."""
    z = sizes(config)
    per_row = 2.0 * (z["H"] * (z["nope"] + z["rope"]) + z["rkv"] + z["rope"] + z["H"] * z["dv"])
    return rows * (per_row + (request_rows + 1) / 16.0)


def indexer_flops(config: dict, rows: int) -> float:
    """One ``full`` layer, one request: every indexer head's dot with
    every causal key."""
    z = sizes(config)
    return 2.0 * z["J"] * z["dI"] * causal_pairs(rows)


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
    of the matrices a row meets (attention's five in every layer, the
    indexer's three in the ``full`` ones, a dense layer's three, a routed
    layer's router, shared expert and the held share of the row's ``k``
    experts at an even load), the attention over the selected pairs, the
    indexer's scores over the causal pairs, and the machine's two
    projections. Norms, RoPE, softmax, the k-th largest and the epilogue
    are left out (under 1%)."""
    z = sizes(config)
    rows = int(config["nominal_request_rows"])
    D, I = z["D"], z["I"]
    sparse = routed_layers(config)
    routed = D * z["E"] + 3 * D * I * z["shared"] + 3 * D * I * z["k"] * z["held"] / z["E"]
    matrices = (z["L"] * attention_matrices(config) + full_layers(config) * indexer_matrices(config)
                + (z["L"] - sparse) * 3 * D * z["W"] + sparse * routed)
    attend = (z["L"] * selected_attention_flops(config, rows)
              + full_layers(config) * indexer_flops(config, rows)) / rows
    return 2.0 * matrices + attend + 2.0 * 2 * z["F"] * D
