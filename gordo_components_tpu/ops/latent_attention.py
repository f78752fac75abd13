"""Multi-head latent attention (MLA), causal, for one request.

A row's keys and values are one ``kv_lora_rank``-wide latent and one
RoPE key that every head shares; each head's keys and values are
expansions of the latent (``W_kvb``), and a head's score has two parts:

    score[t, s] = (q_nope[t] . k_nope[s] + q_rope[t] . k_rope[s]) * scale
    out[t]      = softmax over s <= t of score[t, .]  .  v

with ``q_nope``/``k_nope`` (``qk_nope_head_dim``) and ``v``
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
fetched nor computed, and only the diagonal tiles build a mask. The same
kernel runs in interpret mode off the chip.

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

_MASKED = -1e30  # a finite "minus infinity"; every row of a diagonal tile keeps a key
# (rows of a score tile, heads a grid step) taken where the tile divides the
# request; any other request takes its granule by 4 heads. A step's heads
# share one fetch of the k_rope tile. On a v5e at 10 240 rows
# (tools/latent_trunk_ladder.py; PERF.md, PR 33): 1024 x 2 20.1 ms, 512 x 4
# 24.2, 512 x 2 30.4, 256 x 8 45.9; 512 x 8 does not fit VMEM.
_TILES = ((1024, 2),)


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


def _kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    """Grid (block of heads, query tile i, key tile j), j innermost.
    ``qn_ref``/``kn_ref`` (heads, tile, nope), ``qr_ref`` (heads, tile,
    rope), ``kr_ref`` (tile, rope) shared by the heads, ``v_ref``/``o_ref``
    (heads, tile, dv). Online softmax in the scratch: running max ``m``,
    sum ``l`` (heads, tile, 1) and unnormalised output ``acc``."""
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
        if diagonal:
            keep = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
                    >= jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1))
        for r in range(qn_ref.shape[0]):
            logits = jax.lax.dot_general(
                qn_ref[r], kn_ref[r], contract_last, preferred_element_type=jnp.float32
            ) + jax.lax.dot_general(qr_ref[r], kr, contract_last, preferred_element_type=jnp.float32)
            if diagonal:
                logits = jnp.where(keep, logits, _MASKED)
            m_prev = m_ref[r]
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
            p = jnp.exp(logits - m_new)  # a masked logit: exp(-1e30 - m) is 0
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


def latent_attention(q_nope, q_rope, k_nope, k_rope, v, granule: int, interpret: bool = False):
    """Causal MLA over one request's T rows, T a multiple of ``granule``;
    heads first, bfloat16, the queries already multiplied by the softmax
    scale: ``q_nope``/``k_nope`` (H, T, nope), ``q_rope`` (H, T, rope) and
    ``k_rope`` (T, rope) after RoPE, ``v`` (H, T, dv). Returns (H, T, dv)
    bfloat16: what the output projection's matmul reads."""
    H, T, nope = q_nope.shape
    rope_dim, dv = q_rope.shape[-1], v.shape[-1]
    tile, heads = tiling(T, granule)
    n = T // tile
    hb = next(b for b in range(min(H, heads), 0, -1) if H % b == 0)
    seen = lambda i, j: jnp.minimum(i, j)  # a tile above the diagonal is not fetched again
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((H, T, dv), jnp.bfloat16),
        grid=(H // hb, n, n),
        in_specs=[
            pl.BlockSpec((hb, tile, nope), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((hb, tile, rope_dim), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((hb, tile, nope), lambda h, i, j: (h, seen(i, j), 0)),
            pl.BlockSpec((tile, rope_dim), lambda h, i, j: (seen(i, j), 0)),
            pl.BlockSpec((hb, tile, dv), lambda h, i, j: (h, seen(i, j), 0)),
        ],
        out_specs=pl.BlockSpec((hb, tile, dv), lambda h, i, j: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((hb, tile, 1), jnp.float32),
            pltpu.VMEM((hb, tile, 1), jnp.float32),
            pltpu.VMEM((hb, tile, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # 1024 x 2 asks for 16.4 MB inside the layer program, over the 16 MB a
            # kernel gets unasked; a v5e core has 128
            vmem_limit_bytes=32 * 1024 * 1024,
        ),
        interpret=interpret,
        name="latent_attention",
    )(q_nope, q_rope, k_nope, k_rope, v)
