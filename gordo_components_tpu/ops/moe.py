"""A routed expert layer: a learned router sends each token to its
``top_k`` of ``E`` experts (SwiGLU feed-forwards, or squared-ReLU ones
``W_down,e relu(W_up,e h)^2`` where the layer has no ``gate``), and the
token's output is the weighted sum of theirs.

    s = softmax(h W_r)  or  sigmoid(h W_r)     over the E experts, float32
    kept = top_k(s + b)                        among the experts of the
                                               ``topk_group`` best of
                                               ``n_group`` groups (a group's
                                               score: its two largest s + b)
    y = sum over e in kept of  scale * s_e / (sum of the k kept s)  *
        W_down,e ( silu(W_gate,e h) * W_up,e h )

``n_group`` 1 is plain top-k; softmax, one group and scale 1 is the
renormalised-probability router. ``b`` is a per-expert correction bias
(the ``noaux_tc`` router's ``e_score_correction_bias``; a layer's
``router_bias`` leaf): it enters the CHOICE alone, the weights come from
the unbiased scores. No bias is ``b = 0``.

The layer may hold a *range* of the experts (``expert_offset`` and as many
as ``up`` has): one chip's share of a layer divided over chips by
experts. It still routes over all ``E`` (the router keeps its width) and
returns every token's ``top_k`` of ``E``; it computes the pairs that fall
on an expert it holds, and a token none of whose experts is held gets zero.
What the absent experts would add is another chip's to compute and add:
nothing here stands in for it.

No pair on a held expert is dropped, whatever the load: the (token, expert)
pairs are sorted by expert, those of absent experts last, and the held
experts' matmuls run as grouped matmuls over the ragged groups
(``jax.experimental.pallas.ops.tpu.megablox.gmm``: each row tile multiplies
its own group's matrix, a tile that straddles two groups is visited once
for each, row tiles past the last held pair are not visited), so an expert
that takes every token is just a long group. One path: the kernel on a
TPU, the same kernel in interpret mode elsewhere (``interpret``, decided
once by the caller as the bank decides its epilogue kernel).

How much of the sorted order is worked through follows what the layer
holds, which its leaves' shapes say. A layer that holds every expert takes
the sorted pairs in ONE pass: gather, the grouped matmuls (three, or two
for a squared-ReLU expert), the gather back into token order, the
weighted sum over ``top_k``. A layer that holds a range works through
the held pairs alone, which the sort has put first, in blocks of
``_BLOCK_PAIRS`` sorted pairs, by a loop whose trips are
``ceil(held pairs / block)``: a trip gathers its pairs' rows, runs the
grouped matmuls with the group sizes clipped to the block, zeroes the rows
at or past the held count (no matmul wrote them: they may hold anything),
weighs each row and adds it into its token's row of the output. Nothing
but the router, the top-k, the sort of the pairs' integers and the counts
is sized by all the pairs; no held pair at all is zero trips and an output
of exact zeros, every pair held is ``pairs / block`` trips and about the
one pass's work. A token's up-to-``top_k`` float32 additions happen in
expert order there, in slot order in the one pass.

Nothing is laid out again on the way, at any width. A stack whose last
dimension is not a multiple of 128 and whose second-to-last is (the up
stack (held, 2688, 1856) of a hidden width of 2688) lies in HBM with that
second-to-last dimension on the lanes, the TPU's default for such a shape:
the grouped matmul reads it swapped, as (held, n, k) with the kernel's
``transpose_rhs``, which is that layout (read as it is shaped, it would be
copied whole every call). The held range's output is added into
(tokens, 8 * ceil(D / 1024), 128), whole (8, 128) tiles a token: a D that
is not a multiple of 1024 is padded with zero columns, which the tiles
hold anyway, and sliced back once after the loop (unpadded, 21 rows of
128 at 2688, the block and the output would be relaid out twice a trip
and twice a call). Every other shape is read and added as it is.

Matmuls take bfloat16 operands and accumulate in float32; the router's
logits (float32 operands at full precision), its scores, the group and
expert top-k and the combine are float32.
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

# the grouped matmul's tiles (rows, contraction, columns); rows must
# divide the pair count, which is a multiple of the sequence chunk
_TILE_ROWS, _TILE_K, _TILE_N = 512, 1024, 1024
# sorted pairs a trip of a held range's loop (a multiple of ``_TILE_ROWS``)
_BLOCK_PAIRS = 2048


def route(h: jnp.ndarray, w_router: jnp.ndarray, top_k: int, scoring: str = "softmax",
          n_group: int = 1, topk_group: int = 1, scale: float = 1.0,
          bias: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(weights, experts)``, each (tokens, top_k): the kept experts'
    scores renormalised to sum ``scale``, and their ids. ``bias`` (E,)
    float32 is added to the scores for the choice (groups and experts)
    alone."""
    logits = jnp.dot(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scoring {scoring!r}: softmax or sigmoid")
    choice = scores if bias is None else scores + bias
    if n_group > 1:
        grouped = choice.reshape(scores.shape[0], n_group, -1)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        kept_groups = jax.lax.top_k(group_score, topk_group)[1]  # (tokens, topk_group)
        keep = jnp.any(kept_groups[:, :, None] == jnp.arange(n_group), axis=1)
        choice = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(scores.shape)
    p, experts = jax.lax.top_k(choice, top_k)
    if bias is not None:
        p = jnp.take_along_axis(scores, experts, axis=-1)
    weights = p / jnp.sum(p, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32)


def _grouped(lhs, rhs, group_sizes, interpret):
    m, k = lhs.shape
    n = rhs.shape[-1]
    rows = next(t for t in (_TILE_ROWS, 256, 128, 64, 32, 16, 8) if m % t == 0)
    # a stack whose columns are not whole lanes and whose rows are lies in HBM with its rows
    # on the lanes: read it as (experts, n, k), which is that layout, and not a copy of it
    swapped = n % 128 != 0 and k % 128 == 0
    return gmm(
        lhs, jnp.swapaxes(rhs, 1, 2) if swapped else rhs, group_sizes,
        preferred_element_type=jnp.float32,
        tiling=(rows, min(k, _TILE_K), min(n, _TILE_N)),
        transpose_rhs=swapped, interpret=interpret,
    )


def _experts(x, params, sizes, interpret):
    """Each row's own expert: ``x`` (rows, D) bfloat16 grouped by expert,
    ``sizes`` the groups' lengths; float32. The leaves say the expert's
    form: ``gate``, ``up`` and ``down`` a SwiGLU, ``up`` and ``down`` alone
    ``down(relu(up x)^2)``. Rows past the last group are not written."""
    if "gate" in params:
        gate = _grouped(x, params["gate"], sizes, interpret)
        up = _grouped(x, params["up"], sizes, interpret)
        hidden = jax.nn.silu(gate) * up
    else:
        hidden = jnp.square(jax.nn.relu(_grouped(x, params["up"], sizes, interpret)))
    return _grouped(hidden.astype(jnp.bfloat16), params["down"], sizes, interpret)


def expert_layer(
    h: jnp.ndarray, params: Dict[str, jnp.ndarray], top_k: int, valid: jnp.ndarray,
    interpret: bool = False, expert_offset: int = 0, **routing,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``h`` (tokens, D) float32; ``params``: ``router`` (D, E), where the
    router has one its ``router_bias`` (E,), ``up`` and, for a SwiGLU
    expert, ``gate`` (held, D, I), ``down`` (held, I, D): experts
    ``expert_offset .. expert_offset + held`` of the E; ``valid`` (tokens,)
    bool: padding is routed like any token (its rows are dropped later) but
    left out of the counts; ``routing``: ``route``'s.

    Returns the held experts' part of the layer's output (tokens, D)
    float32, the experts chosen (tokens, top_k) int32 of E, the valid
    tokens routed to each held expert (held,), and the blocks of held pairs
    worked through () int32: the loop's trips, padding's pairs among them;
    0 where every expert is held (one pass, no loop).
    """
    n_tokens = h.shape[0]
    n_experts = params["router"].shape[-1]
    held = params["up"].shape[0]
    whole = held == n_experts  # every pair is on a held expert
    with jax.named_scope("trunk/route"):
        weights, experts = route(
            h, params["router"], top_k, bias=params.get("router_bias"), **routing)
        flat = experts.reshape(-1)
        if not whole:  # a pair on an absent expert sorts last, into a group no matmul visits
            local = flat - expert_offset
            flat = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(flat, stable=True)
        groups = held if whole else held + 1
        sizes = jnp.bincount(flat, length=groups).astype(jnp.int32)[:held]
        counts = jnp.bincount(
            flat, weights=jnp.repeat(valid, top_k).astype(jnp.int32), length=groups
        ).astype(jnp.int32)[:held]
    if not whole:
        out, blocks = _held_pairs_in_blocks(h, params, top_k, weights, order, sizes, interpret)
        return out, experts, counts, blocks
    with jax.named_scope("trunk/route"):
        x = h.astype(jnp.bfloat16)[order // top_k]  # (pairs, D), grouped by expert
    with jax.named_scope("trunk/experts"):
        y = _experts(x, params, sizes, interpret)
    with jax.named_scope("trunk/combine"):
        back = jnp.argsort(order)  # pair (token, slot) -> its row in the sorted order
        y = y[back].reshape(n_tokens, top_k, -1)
        out = jnp.sum(y * weights[..., None], axis=1)
    return out, experts, counts, jnp.zeros((), jnp.int32)


def _held_pairs_in_blocks(h, params, top_k, weights, order, sizes, interpret):
    """The weighted sum of the held experts' outputs over the first
    ``sum(sizes)`` pairs of ``order`` (pairs sorted by expert, the held
    experts' first), a block of sorted pairs a trip; and the trips."""
    n_tokens, pairs = h.shape[0], order.shape[0]
    block = min(_BLOCK_PAIRS, pairs)
    with jax.named_scope("trunk/route"):
        ends = jnp.cumsum(sizes)
        n_held = ends[-1]
        order = jnp.pad(order, (0, -pairs % block))  # the last block's slice stays inside
        h = h.astype(jnp.bfloat16)
        weights = weights.reshape(-1)
    with jax.named_scope("trunk/combine"):
        # the output as (tokens, 8 * ceil(D / 1024), 128): a token's row is whole (8, 128) tiles,
        # contiguous, and a trip's scatter-add of 2048 rows of 7168 takes 1.8 ms less on a v5e than
        # into (tokens, D). A D that is not a multiple of 1024 is padded with zero columns, which
        # the tiles hold anyway (at 21 rows of 128, unpadded, the block is relaid out twice a trip)
        wide = h.shape[1]
        padded = -(-wide // 1024) * 1024
        zeros = jnp.zeros((n_tokens, padded // 128, 128), jnp.float32)

    def trip(b, out):
        first = b * block
        with jax.named_scope("trunk/route"):
            at = jax.lax.dynamic_slice(order, (first,), (block,))
            tokens = at // top_k
            x = h[tokens]  # (block, D), grouped by expert
            here = jnp.clip(ends - first, 0, block) - jnp.clip(ends - sizes - first, 0, block)
        with jax.named_scope("trunk/experts"):
            y = _experts(x, params, here, interpret)
        with jax.named_scope("trunk/combine"):
            live = first + jnp.arange(block) < n_held  # a row past the held pairs was never written
            if padded != wide:  # before the weighing, which the pad then joins in one fusion
                y = jnp.pad(y, ((0, 0), (0, padded - wide)))
            y = jnp.where(live[:, None], y * weights[at][:, None], 0.0)
            return out.at[tokens].add(y.reshape(block, *out.shape[1:]))

    blocks = (n_held + block - 1) // block
    out = jax.lax.fori_loop(0, blocks, trip, zeros)
    with jax.named_scope("trunk/combine"):
        return out.reshape(n_tokens, padded)[:, :wide], blocks
