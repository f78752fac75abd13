"""The Mamba-2 selective state-space scan, computed in chunks (the *state
space duality* form).

Per head ``h`` of P channels, with a state of N per channel and the head's
group ``g`` (``h // (H / G)``) giving its input and output projections
``B_t`` and ``C_t`` (N,), the recurrence over the rows t = 1..T is

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        S_0 = 0, S (P, N)
    y_t = S_t C_t + D_h x_t

with ``dt_t > 0`` (the caller's softplus) and ``A_h < 0``. Unrolled,
``y_t = sum over s <= t of exp(a_{s+1} + ... + a_t) dt_s (C_t . B_s) x_s``
with ``a_t = dt_t A_h``. Cut the rows into chunks of Q and let ``c_t`` be
the sum of ``a`` from the chunk's first row to ``t``. For ``t`` in a chunk
that starts after row ``t0``:

    y_t = sum over s in the chunk, s <= t, of
              exp(c_t - c_s) dt_s (C_t . B_s) x_s            (within)
          + exp(c_t) S_{t0} C_t                              (carried)
          + D_h x_t
    S_{t0+Q} = exp(c_last) S_{t0}
               + sum over s in the chunk of exp(c_last - c_s) dt_s x_s B_s^T

which is the recurrence regrouped, term for term: within a chunk the
first sum is one masked (Q, Q) matrix ``(C B^T) * L * dt`` times x, with
``L[t, s] = exp(c_t - c_s)`` for ``s <= t`` and 0 above (a matmul on the
MXU), and the state is carried from one chunk to the next. Every
exponent is a sum of ``a <= 0`` and no larger than 0, so nothing
overflows.

One Pallas kernel, grid (request, block of heads, chunk), chunks
innermost and in order: the state of the block's heads lives in a VMEM
scratch for the whole sequence and is set to zero at the first chunk. A
block is the heads of one group, so a step reads one chunk of that
group's ``B`` and ``C`` and computes ``C B^T`` once for all of them. The
matmuls take bfloat16 operands and accumulate in float32; the decays come
from the per-chunk running sum of ``dt A`` in float32 (computed before
the kernel), and the state is float32. The same kernel runs in interpret
mode off the chip.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BF16, F32 = jnp.bfloat16, jnp.float32


def _column(row, eye):
    """A (1, Q) row as a (Q, 1) column, exactly: the diagonal of its
    broadcast, summed along the lanes."""
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, eye.shape), 0.0), axis=1, keepdims=True)


def _scan_kernel(d_ref, x_ref, dt_ref, cs_ref, b_ref, c_ref, y_ref, state_ref):
    """Grid (request, head block, chunk), chunk innermost. ``x_ref``/
    ``y_ref``: the block's heads, (heads, Q, P); ``dt_ref``/``cs_ref``:
    (heads, Q), dt and the chunk's running sum of dt A; ``b_ref``/``c_ref``:
    the group's (Q, N); ``d_ref``: D of every head (SMEM); ``state_ref``:
    the heads' states, TRANSPOSED (heads, N, P) so that both the carried
    term and the update are plain matmuls."""
    block, chunk = pl.program_id(1), pl.program_id(2)
    heads, Q, _ = x_ref.shape

    @pl.when(chunk == 0)
    def _start():
        state_ref[...] = jnp.zeros(state_ref.shape, F32)

    Bm, Cm = b_ref[...], c_ref[...]
    # C_t . B_s for every pair of the chunk's rows: one matmul for the group
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=F32)
    b_t = Bm.astype(F32).T.astype(BF16)  # (N, Q)
    t = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal, eye = s <= t, s == t
    for r in range(heads):
        cs_row, dt_row = cs_ref[r : r + 1, :], dt_ref[r : r + 1, :]  # (1, Q)
        cs_col, dt_col = _column(cs_row, eye), _column(dt_row, eye)  # (Q, 1)
        decay = jnp.exp(jnp.where(causal, cs_col - cs_row, -jnp.inf))  # L, zero above the diagonal
        x = x_ref[r]  # (Q, P) bfloat16
        within = jnp.dot((scores * decay * dt_row).astype(BF16), x, preferred_element_type=F32)
        state = state_ref[r]  # (N, P)
        carried = jnp.exp(cs_col) * jnp.dot(Cm, state.astype(BF16), preferred_element_type=F32)
        y_ref[r] = within + carried + d_ref[block * heads + r] * x.astype(F32)
        last = cs_col[Q - 1 :, :]  # (1, 1): the chunk's whole decay
        weighted = (x.astype(F32) * (dt_col * jnp.exp(last - cs_col))).astype(BF16)
        state_ref[r] = jnp.exp(last) * state + jnp.dot(b_t, weighted, preferred_element_type=F32)


def ssd_scan(x, dt, A, Bm, Cm, D, chunk: int, interpret: bool = False):
    """``x`` (B, T, H, P); ``dt`` (B, T, H) float32, positive; ``A`` (H,)
    float32, negative; ``Bm``, ``Cm`` (B, T, G, N), head ``h`` reading
    group ``h // (H / G)``; ``D`` (H,) float32; T a multiple of ``chunk``.
    Each of the B sequences starts from a zero state. Returns ``y`` (B, T,
    H, P) float32 (module docstring)."""
    Bsz, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    heads = H // G
    n = T // chunk
    # the running sum of dt A within each chunk: the decays' exponents
    a = (dt * A).astype(F32).reshape(Bsz, n, chunk, H)
    cs = jnp.cumsum(a, axis=2).reshape(Bsz, T, H).transpose(0, 2, 1)  # (B, H, T)
    head_major = lambda v: v.astype(BF16).transpose(0, 2, 1, 3)  # (B, heads or groups, T, width)
    y = pl.pallas_call(
        _scan_kernel,
        out_shape=jax.ShapeDtypeStruct((Bsz, H, T, P), F32),
        grid=(Bsz, G, n),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, heads, chunk, P), lambda b, g, c: (b, g, c, 0)),
            pl.BlockSpec((None, heads, chunk), lambda b, g, c: (b, g, c)),
            pl.BlockSpec((None, heads, chunk), lambda b, g, c: (b, g, c)),
            pl.BlockSpec((None, None, chunk, N), lambda b, g, c: (b, g, c, 0)),
            pl.BlockSpec((None, None, chunk, N), lambda b, g, c: (b, g, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, heads, chunk, P), lambda b, g, c: (b, g, c, 0)),
        scratch_shapes=[pltpu.VMEM((heads, N, P), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="ssd_scan",
    )(D.astype(F32), head_major(x), dt.astype(F32).transpose(0, 2, 1), cs, head_major(Bm),
      head_major(Cm))
    return y.transpose(0, 2, 1, 3)
