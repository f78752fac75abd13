"""A routed expert layer: a learned router sends each token to its
``top_k`` of ``E`` experts (SwiGLU feed-forwards), and the token's output
is the renormalised-probability-weighted sum of theirs.

    p = softmax(h W_r)                      over the E experts, float32
    y = sum over e in top_k(p) of  p_e / (sum of the k kept)  *
        W_down,e ( silu(W_gate,e h) * W_up,e h )

No token is dropped, whatever the load: the (token, expert) pairs are
sorted by expert and the experts' matmuls run as ONE grouped matmul over
the ragged groups (``jax.experimental.pallas.ops.tpu.megablox.gmm``: each
row tile multiplies its own group's matrix, a tile that straddles two
groups is visited once for each), so an expert that takes every token is
just a long group. One path: the kernel on a TPU, the same kernel in
interpret mode elsewhere (``interpret``, decided once by the caller as the
bank decides its epilogue kernel).

Matmuls take bfloat16 operands and accumulate in float32; the router's
logits (float32 operands at full precision), its softmax and the combine
are float32.
"""

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

# the grouped matmul's tiles (rows, contraction, columns); rows must
# divide the pair count, which is a multiple of the sequence chunk
_TILE_ROWS, _TILE_K, _TILE_N = 512, 1024, 1024


def route(h: jnp.ndarray, w_router: jnp.ndarray, top_k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(weights, experts)``, each (tokens, top_k): the kept experts'
    probabilities renormalised to sum 1, and their ids."""
    logits = jnp.dot(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    p, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return p / jnp.sum(p, axis=-1, keepdims=True), experts.astype(jnp.int32)


def _grouped(lhs, rhs, group_sizes, interpret):
    m, k = lhs.shape
    n = rhs.shape[-1]
    rows = next(t for t in (_TILE_ROWS, 256, 128, 64, 32, 16, 8) if m % t == 0)
    return gmm(
        lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
        tiling=(rows, min(k, _TILE_K), min(n, _TILE_N)),
        interpret=interpret,
    )


def expert_layer(
    h: jnp.ndarray, params: Dict[str, jnp.ndarray], top_k: int, valid: jnp.ndarray,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``h`` (tokens, D) float32; ``params``: ``router`` (D, E), ``gate``
    and ``up`` (E, D, I), ``down`` (E, I, D); ``valid`` (tokens,) bool:
    padding is routed like any token (its rows are dropped later) but left
    out of the counts.

    Returns the layer's output (tokens, D) float32, the experts chosen
    (tokens, top_k) int32 and the valid tokens routed to each expert (E,).
    """
    n_tokens = h.shape[0]
    n_experts = params["router"].shape[-1]
    with jax.named_scope("trunk/route"):
        weights, experts = route(h, params["router"], top_k)
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=n_experts).astype(jnp.int32)
        counts = jnp.bincount(
            flat, weights=jnp.repeat(valid, top_k).astype(jnp.int32), length=n_experts
        ).astype(jnp.int32)
        x = h.astype(jnp.bfloat16)[order // top_k]  # (pairs, D), grouped by expert
    with jax.named_scope("trunk/experts"):
        gate = _grouped(x, params["gate"], sizes, interpret)
        up = _grouped(x, params["up"], sizes, interpret)
        y = _grouped((jax.nn.silu(gate) * up).astype(jnp.bfloat16), params["down"], sizes, interpret)
    with jax.named_scope("trunk/combine"):
        back = jnp.argsort(order)  # pair (token, slot) -> its row in the sorted order
        y = y[back].reshape(n_tokens, top_k, -1)
        out = jnp.sum(y * weights[..., None], axis=1)
    return out, experts, counts
