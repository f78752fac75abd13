"""Indexer-selected sparse attention: a light *indexer* scores every
causal (query, key) pair, each query keeps its ``topk`` best keys, and the
grouped-query attention softmax runs over that selection alone.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]) / sqrt(dI)     s <= t
    S_t     = the min(t + 1, topk) keys with the largest I[t, .]
    a[t]    = softmax over s in S_t of (q[t] . k[s] / sqrt(d))

One path. The selection is applied as a MASK on dense causal score tiles,
not as a gather: gathered per query, the selected keys and values of one
10 080-row request are 42 GB a layer, a dense tile is one matmul. Queries
go in chunks of ``chunk`` rows (the tiling the published config names),
each against the keys up to its own end, so a causal half of the score
matrix is never computed.

- Indexer scores and the selection are plain XLA, and a function of their
  own (``select_keys``: scores by chunk, the exact k-th largest, the mask,
  the count and the witness) that ``select_and_attend`` calls before its
  grouped-query kernel and the latent-attention trunk calls before ITS
  kernel (``ops/latent_attention.py``), in the layers that make a selection;
  the mask it returns is what later layers that share it attend under.
  The k-th largest score
  of a row is found exactly, by a 32-step bisection on the scores' bit
  patterns (a sort of 10 240 scores a query is the slow way to learn one
  threshold); keys that tie with the k-th are all kept.
- The attention under the mask is a Pallas kernel (``masked_attention``):
  one (chunk x chunk) tile of logits at a time (with no mask handed, every
  causal key, read from the tiles' own indices: no mask array exists), all
  the query heads of a key-value head against it, the softmax accumulated
  online, so no (heads, chunk, keys) tile of float32 logits ever goes to
  HBM. As XLA ops over such tiles the softmax's reductions alone took 1.3 s
  of a week-long request's 1.4 s on the chip (PERF.md, PR 28). The same kernel
  runs in interpret mode off the chip.

Matmuls take bfloat16 operands and accumulate in float32; indexer scores,
thresholds, attention logits and softmax are float32.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# every ``WITNESS_STRIDE``-th query's selection is returned as a bit mask
# (``select_keys``): what a caller compares two runs' selections by
WITNESS_STRIDE = 64


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         rotary_dim: Optional[int] = None, inv_freq=None) -> jnp.ndarray:
    """Rotary embedding, rotate-half convention, on the first
    ``rotary_dim`` (default: all) of the last axis. ``x``: (T, heads, d);
    ``positions``: (T,). Multimodal RoPE with its three position streams
    equal (a one-dimensional sequence) is exactly this. ``inv_freq``
    (d // 2,): the frequencies, where they are not ``theta``'s own (YaRN:
    ``ops/latent_attention.py``)."""
    d = x.shape[-1] if rotary_dim is None else rotary_dim
    half = d // 2
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / d))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq  # (T, half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _sortable(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> int32 with the same order (negatives' magnitudes flipped)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def kth_largest(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """The k-th largest value of each row of ``scores`` (rows, n) float32,
    exactly: the largest ``v`` with ``count(row >= v) >= k``, by bisection
    over the 32 bits of the order-preserving integer image. A row with
    fewer than ``k`` entries above ``-inf`` yields ``-inf`` (keep all)."""
    keys = _sortable(scores)
    lo = jnp.full(scores.shape[:-1], jnp.iinfo(jnp.int32).min, jnp.int32)

    def step(i, lo):
        # candidates are built bit by bit from the top; the sign bit's
        # "one" is the non-negative half, so the first step adds 2**31
        # by wrapping from int32.min to 0
        cand = lo + jnp.left_shift(jnp.int32(1), 31 - i)
        enough = jnp.sum(keys >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, lo)

    lo = jax.lax.fori_loop(0, 32, step, lo)
    flipped = jnp.where(lo < 0, lo ^ jnp.int32(0x7FFFFFFF), lo)
    return jax.lax.bitcast_convert_type(flipped, jnp.float32)


def _bf16(x):
    return x.astype(jnp.bfloat16)


_MASKED = -1e30  # a finite "minus infinity": a fully masked tile stays NaN-free


def _accumulate(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale, keep):
    """One (chunk x chunk) tile into the online softmax of every query
    head of the block; ``keep`` (chunk, chunk) bool, or ``None``: every key
    of the tile."""
    k, v = k_ref[...], v_ref[...]
    for r in range(q_ref.shape[0]):
        logits = jax.lax.dot_general(
            q_ref[r], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if keep is not None:
            logits = jnp.where(keep, logits, _MASKED)
        m_prev = m_ref[r]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[r] = alpha * l_ref[r] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[r] = alpha * acc_ref[r] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[r] = m_new


def _attention_kernel(q_ref, k_ref, v_ref, *refs, scale):
    """Grid (key-value head, query chunk i, key chunk j), j innermost.
    ``q_ref``/``o_ref``: the head's R query heads, (R, chunk, d);
    ``k_ref``/``v_ref``: (chunk, d); then, under a mask, ``mask_ref``:
    (chunk, chunk) int8, shared by every head. Online softmax in the
    scratch: running max ``m``, sum ``l`` (R, chunk, 1) and unnormalised
    output ``acc``. Without a mask a tile below the diagonal keeps every
    key and the diagonal tile the keys at or before each query, by the
    tile's own row and column indices."""
    mask_ref, (o_ref, m_ref, l_ref, acc_ref) = (refs[0], refs[1:]) if len(refs) == 5 else (None, refs)
    i, j = pl.program_id(1), pl.program_id(2)
    accumulate = functools.partial(_accumulate, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale)

    @pl.when(j == 0)
    def _start():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    if mask_ref is not None:

        @pl.when(j <= i)  # a chunk sees the chunks up to its own; the mask is causal inside
        def _tile():
            accumulate(mask_ref[...] != 0)

    else:

        @pl.when(j < i)
        def _below():
            accumulate(None)

        @pl.when(j == i)
        def _diagonal():
            shape = (q_ref.shape[1], k_ref.shape[0])
            row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            accumulate(col <= row)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def masked_attention(q, k, v, mask: Optional[jnp.ndarray], chunk: int, interpret: bool = False):
    """softmax(q . k / sqrt(d), over the keys ``mask`` keeps) . v,
    grouped-query. ``q`` (G, R, T, d), ``k``/``v`` (G, T, d), bfloat16;
    ``mask`` (T, T) int8, causal, at least one key kept a row, or ``None``:
    every causal key, and no mask is read; T a multiple of ``chunk``.
    Returns (G, R, T, d) float32."""
    G, R, T, d = q.shape
    n = T // chunk
    seen = lambda i, j: jnp.minimum(i, j)  # a tile above the diagonal is not fetched again
    in_specs = [
        pl.BlockSpec((None, R, chunk, d), lambda g, i, j: (g, 0, i, 0)),
        pl.BlockSpec((None, chunk, d), lambda g, i, j: (g, seen(i, j), 0)),
        pl.BlockSpec((None, chunk, d), lambda g, i, j: (g, seen(i, j), 0)),
    ]
    operands = (q, k, v)
    if mask is not None:
        in_specs.append(pl.BlockSpec((chunk, chunk), lambda g, i, j: (i, seen(i, j))))
        operands += (mask,)
    return pl.pallas_call(
        functools.partial(_attention_kernel, scale=1.0 / d ** 0.5),
        out_shape=jax.ShapeDtypeStruct((G, R, T, d), jnp.float32),
        grid=(G, n, n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, R, chunk, d), lambda g, i, j: (g, 0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((R, chunk, 1), jnp.float32),
            pltpu.VMEM((R, chunk, 1), jnp.float32),
            pltpu.VMEM((R, chunk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="masked_attention",
    )(*operands)


def witness_of(selection: jnp.ndarray, stride: int) -> jnp.ndarray:
    """Every ``stride``-th query's keys of a selection (..., T, T) in the
    int8 form a kernel reads, as ``select_keys`` packs its own witness:
    (..., T // stride, T // 8) uint8."""
    return jnp.packbits(selection[..., stride - 1 :: stride, :] != 0, axis=-1, bitorder="little")


def select_keys(
    qi: jnp.ndarray, ki: jnp.ndarray, wi: jnp.ndarray, n_valid: jnp.ndarray,
    topk: int, chunk: int, divisor: Optional[float] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One request's selection, with no attention after it: indexer ``qi``
    (T, J, dI), ``ki`` (T, dI), ``wi`` (T, J); ``n_valid``: rows beyond it
    are padding, masked as keys; T a multiple of ``chunk``. The scores are
    ``sum_j wi relu(qi . ki) / divisor`` (default ``sqrt(dI)``).

    Returns the mask (T, T) bool, causal, at least one key kept a row; the
    number of (query, key) selections made for valid queries; and the
    witness: the selection of every ``WITNESS_STRIDE``-th query as packed
    bits, (T // stride, T // 8) uint8. What both kinds of trunk attend
    under (``select_and_attend`` here, ``ops/latent_attention.py``)."""
    T, _, dI = qi.shape
    divisor = dI ** 0.5 if divisor is None else divisor
    stride = min(WITNESS_STRIDE, chunk)
    qib, kib = _bf16(qi), _bf16(ki)
    masks = []
    selections = jnp.zeros((), jnp.int32)
    for start in range(0, T, chunk):
        S = start + chunk  # keys this chunk can see
        t = start + jnp.arange(chunk)[:, None]
        s = jnp.arange(S)[None, :]
        # padded keys hide from every valid query; a padded query (its
        # output is dropped) keeps its causal keys so its softmax is finite
        keep = (s <= t) & ((s < n_valid) | (t >= n_valid))
        if S > topk:  # static: earlier chunks keep every visible key
            with jax.named_scope("trunk/indexer"):
                dots = jnp.einsum(
                    "tjd,sd->tjs", qib[start:S], kib[:S], preferred_element_type=jnp.float32
                )
                index = jnp.einsum("tjs,tj->ts", jax.nn.relu(dots), wi[start:S]) / divisor
                index = jnp.where(keep, index, -jnp.inf)
            with jax.named_scope("trunk/select"):
                keep &= index >= kth_largest(index, topk)[:, None]
        with jax.named_scope("trunk/select"):
            selections += jnp.sum(keep & (t < n_valid), dtype=jnp.int32)
            masks.append(jnp.pad(keep, ((0, 0), (0, T - S))))
    with jax.named_scope("trunk/select"):
        mask = jnp.concatenate(masks)  # (T, T) bool
        witness = jnp.packbits(mask[stride - 1 :: stride], axis=-1, bitorder="little")
    return mask, selections, witness


def select_and_attend(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    qi: jnp.ndarray, ki: jnp.ndarray, wi: jnp.ndarray,
    n_valid: jnp.ndarray, topk: int, chunk: int, interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One request. ``q`` (T, H, d); ``k``, ``v`` (T, G, d) with H a
    multiple of G; the indexer's ``qi``, ``ki``, ``wi`` and ``n_valid`` as
    ``select_keys`` takes them. T is a multiple of ``chunk``.

    Returns the attention output (T, H, d) float32 and ``select_keys``'
    count and witness.
    """
    T, H, d = q.shape
    G = k.shape[1]
    mask, selections, witness = select_keys(qi, ki, wi, n_valid, topk, chunk)
    with jax.named_scope("trunk/attend"):
        out = masked_attention(
            _bf16(q).reshape(T, G, H // G, d).transpose(1, 2, 0, 3),
            _bf16(k).transpose(1, 0, 2), _bf16(v).transpose(1, 0, 2),
            mask.astype(jnp.int8), chunk, interpret,
        )
    return out.transpose(2, 0, 1, 3).reshape(T, H, d), selections, witness
