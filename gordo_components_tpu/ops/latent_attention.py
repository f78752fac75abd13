"""Multi-head latent attention (MLA), causal, for one request, over every
causal key or over a selection of them.

A row's keys and values are one ``kv_lora_rank``-wide latent and one
RoPE key that every head shares; each head's keys and values are
expansions of the latent (``W_kvb``), and a head's score has two parts:

    score[t, s] = (q_nope[t] . k_nope[s] + q_rope[t] . k_rope[s]) * scale
    out[t]      = softmax over s in S_t of score[t, .]  .  v

``S_t`` being every ``s <= t``, or, where the caller hands a *selection*
(a (T, T) int8 mask that an indexer made: ``ops/sparse_attention.py``'s
``select_keys``; one for all heads, causal already), the keys it keeps.
With ``q_nope``/``k_nope`` (``qk_nope_head_dim``) and ``v``
(``v_head_dim``) per head, ``q_rope`` per head and ``k_rope``
(``qk_rope_head_dim``) ONE vector a row. The score width (nope + rope) and
the value width differ.

One path, the *expanded* form: keys and values per head come from HBM as
``W_kvb`` made them, a block of heads a grid step, and ``k_rope``'s tile is
read once for the block. (The *absorbed* form, ``W_kvb`` folded into the
query and the output so that every head attends over the 576-wide latent
itself, moves 1/28 of the bytes and does 3.4 times the operations; on the
chip at a week-long request it took 51.0 ms a layer against 24.2 at the
same 512-row tiles, and was deleted: PERF.md, PR 33.) The Pallas
kernel keeps the softmax online over (tile x tile) score tiles, so no
(heads, rows, rows) logits go to HBM; a tile above the diagonal is neither
fetched nor computed, and only the diagonal tiles build a mask. Under a
selection every tile at or below the diagonal reads its (tile x tile) part
of the mask beside its keys and masks its logits by it; without one the
program is what it was before selections existed, tile for tile. No tile
is skipped for holding no selected key: under random weights an indexer's
top 2048 of 10 080 fall in every causal tile, so nothing would exercise
the skip (PERF.md section 7). The same kernel runs in interpret mode off
the chip.

Matmuls take bfloat16 operands and accumulate in float32; logits and
softmax are float32; the caller multiplies the queries by the softmax
scale before it rounds them.

YaRN (``rope_scaling`` of type ``yarn``) changes RoPE's frequencies and the
softmax scale; both are computed here from the config's keys.
"""

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASKED = -1e30  # a finite "minus infinity"; every row keeps a key at or before its diagonal tile
# (rows of a score tile, heads a grid step) taken where the tile divides the
# request; any other request takes its granule by 4 heads. A step's heads
# share one fetch of the k_rope tile (and of the selection's). One value at
# both widths measured, on a v5e at 10 240 rows (tools/latent_trunk_ladder.py).
# 128 + 64 | 128 (PERF.md, PR 33): 1024 x 2 20.1 ms, 512 x 4 24.2, 512 x 2
# 30.4, 256 x 8 45.9; 512 x 8 does not fit VMEM. 192 + 64 | 256 under a
# selection, and without one (PERF.md, PR 35): 1024 x 2 34.3 ms (31.4), 1024
# x 1 34.4 (32.2), 512 x 4 35.0 (34.3), 512 x 2 35.3 (34.3); under a 64 MB
# limit 512 x 8 35.0 (34.5), 1024 x 4 68.5 (63.9), 2048 x 1 71.1 (66.2);
# under 96 MB 2048 x 2 55.6 (52.6).
_TILES = ((1024, 2),)
# 1024 x 2 asks for 16.4 MB inside the layer program at 128 + 64 | 128, over
# the 16 MB a kernel gets unasked; a v5e core has 128
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def yarn(rotary_dim: int, theta: float, scaling: Optional[dict]) -> Tuple[np.ndarray, float]:
    """``(inv_freq (rotary_dim // 2,), softmax multiplier)`` of RoPE under
    ``scaling`` (the config's ``rope_scaling``; ``None`` or factor 1: plain
    RoPE and 1).

    With ``f_i = theta^(-2i/d)``: dimension ``i`` keeps ``f_i`` where it
    turns more than ``beta_fast`` times over the original context, takes
    ``f_i / factor`` where it turns fewer than ``beta_slow`` times, and a
    linear ramp between. With ``m(s, a) = 0.1 a ln s + 1``, cos and sin are
    multiplied by ``m(factor, mscale) / m(factor, mscale_all_dim)`` (1 for
    every config this repo runs: refused otherwise) and the softmax scale by
    ``m(factor, mscale_all_dim)^2``."""
    half = rotary_dim // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / rotary_dim)
    factor = float((scaling or {}).get("factor", 1.0))
    if factor == 1.0:
        return freq.astype(np.float32), 1.0
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling of type {kind!r} is not implemented (yarn is)")
    original = float(scaling["original_max_position_embeddings"])

    def dim_of(rotations: float) -> float:
        return rotary_dim * math.log(original / (2 * math.pi * rotations)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(dim_of(float(scaling.get("beta_slow", 1)))), half - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = freq / factor * ramp + freq * (1.0 - ramp)
    m = lambda a: 0.1 * float(a) * math.log(factor) + 1.0
    mscale, all_dim = scaling.get("mscale", 1), scaling.get("mscale_all_dim", 0)
    if m(mscale) != m(all_dim):
        raise ValueError("yarn with mscale != mscale_all_dim scales cos and sin: not implemented")
    return inv_freq.astype(np.float32), m(all_dim) ** 2


def _kernel(*refs, selected: bool):
    """Grid (block of heads, query tile i, key tile j), j innermost.
    ``qn_ref``/``kn_ref`` (heads, tile, nope), ``qr_ref`` (heads, tile,
    rope), ``kr_ref`` (tile, rope) shared by the heads, ``v_ref``/``o_ref``
    (heads, tile, dv); ``selected``: then ``sel_ref`` (tile, tile) int8, the
    selection's part for this tile, shared by the heads too. Online softmax
    in the scratch: running max ``m``, sum ``l`` (heads, tile, 1) and
    unnormalised output ``acc``."""
    if selected:
        qn_ref, qr_ref, kn_ref, kr_ref, v_ref, sel_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    i, j = pl.program_id(1), pl.program_id(2)
    tile = qn_ref.shape[1]

    @pl.when(j == 0)
    def _start():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def _tile(diagonal: bool):
        contract_last = (((1,), (1,)), ((), ()))
        kr = kr_ref[...]
        if selected:  # causal already: the diagonal's rule is in it
            keep = sel_ref[...] != 0
        elif diagonal:
            keep = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
                    >= jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1))
        for r in range(qn_ref.shape[0]):
            logits = jax.lax.dot_general(
                qn_ref[r], kn_ref[r], contract_last, preferred_element_type=jnp.float32
            ) + jax.lax.dot_general(qr_ref[r], kr, contract_last, preferred_element_type=jnp.float32)
            if selected or diagonal:
                logits = jnp.where(keep, logits, _MASKED)
            m_prev = m_ref[r]
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
            # a masked logit: exp(-1e30 - m) is 0. A row that has met no selected
            # key yet sums ones at m = -1e30; the first key it keeps (there is one
            # at or before its diagonal tile) multiplies that by alpha = 0
            p = jnp.exp(logits - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[r] = alpha * l_ref[r] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[r] = alpha * acc_ref[r] + jnp.dot(
                p.astype(v_ref.dtype), v_ref[r], preferred_element_type=jnp.float32
            )
            m_ref[r] = m_new

    pl.when(j < i)(functools.partial(_tile, False))  # wholly below the diagonal: no mask
    pl.when(j == i)(functools.partial(_tile, True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def tiling(rows: int, granule: int) -> Tuple[int, int]:
    """``(rows of a score tile, heads a grid step)`` for a request of
    ``rows`` rows, a multiple of ``granule``: the best of ``_TILES`` that
    divides it, else the granule itself."""
    return next((t for t in _TILES if rows % t[0] == 0 and t[0] % granule == 0), (granule, 4))


def latent_attention(q_nope, q_rope, k_nope, k_rope, v, granule: int, interpret: bool = False,
                     selection=None):
    """MLA over one request's T rows, T a multiple of ``granule``; heads
    first, bfloat16, the queries already multiplied by the softmax scale:
    ``q_nope``/``k_nope`` (H, T, nope), ``q_rope`` (H, T, rope) and
    ``k_rope`` (T, rope) after RoPE, ``v`` (H, T, dv). ``selection``
    (T, T) int8: the keys each query attends to, one mask for all heads,
    causal, at least one key a row; ``None``: every causal key. Returns
    (H, T, dv) bfloat16: what the output projection's matmul reads."""
    H, T, nope = q_nope.shape
    rope_dim, dv = q_rope.shape[-1], v.shape[-1]
    tile, heads = tiling(T, granule)
    n = T // tile
    hb = next(b for b in range(min(H, heads), 0, -1) if H % b == 0)
    seen = lambda i, j: jnp.minimum(i, j)  # a tile above the diagonal is not fetched again
    in_specs = [
        pl.BlockSpec((hb, tile, nope), lambda h, i, j: (h, i, 0)),
        pl.BlockSpec((hb, tile, rope_dim), lambda h, i, j: (h, i, 0)),
        pl.BlockSpec((hb, tile, nope), lambda h, i, j: (h, seen(i, j), 0)),
        pl.BlockSpec((tile, rope_dim), lambda h, i, j: (seen(i, j), 0)),
        pl.BlockSpec((hb, tile, dv), lambda h, i, j: (h, seen(i, j), 0)),
    ]
    operands = (q_nope, q_rope, k_nope, k_rope, v)
    if selection is not None:
        in_specs.append(pl.BlockSpec((tile, tile), lambda h, i, j: (i, seen(i, j))))
        operands += (selection,)
    return pl.pallas_call(
        functools.partial(_kernel, selected=selection is not None),
        out_shape=jax.ShapeDtypeStruct((H, T, dv), jnp.bfloat16),
        grid=(H // hb, n, n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((hb, tile, dv), lambda h, i, j: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((hb, tile, 1), jnp.float32),
            pltpu.VMEM((hb, tile, 1), jnp.float32),
            pltpu.VMEM((hb, tile, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="latent_attention",
    )(*operands)
