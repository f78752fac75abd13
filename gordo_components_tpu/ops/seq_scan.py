"""Time-major sequence fast path: scan-over-time with the member axis
innermost.

The fleet engine's original recurrent layout nests ``vmap`` (member axis)
OUTSIDE ``flax.linen.RNN`` (``lax.scan`` inside): every scan step issues M
interleaved small matmuls whose lane dimension is one member's hidden
width. Neither layout has a rate on the chip yet (PERF.md): the premise
that vmap-over-members loses for recurrent architectures rests on CPU
runs only.

This module inverts the nesting. One ``lax.scan`` over time; the carry and
activations keep members as the INNERMOST (lane-friendly) axis:

- inputs arrive member-major ``(M, B, T, F)`` (the fleet's stacking order)
  and are transposed ONCE to time-major ``(T, B, M, F)``;
- the input projection for ALL timesteps is hoisted out of the scan as one
  wide einsum per layer (``tbmf,mfg->tbmg``);
- each scan step is a single batched matmul ``bmh,mhg->bmg`` plus the gate
  nonlinearities and carry update.

Weight extraction targets ``flax.linen.OptimizedLSTMCell``'s param tree
(separate per-gate kernels ``ii/if/ig/io`` and ``hi/hf/hg/ho``, bias on the
hidden half only); gate math is the flax cell's exactly::

    z = x @ Wi + h @ Wh + b          # gate order i, f, g, o
    c' = sigmoid(f) * c + sigmoid(i) * tanh(g)
    h' = sigmoid(o) * tanh(c')

so the time-major forward matches ``vmap(module.apply)`` to fp32 rounding
(matmul re-association only: bitwise for a two-layer stack, 4e-9 absolute
for four layers; tests/test_seq_fastpath.py pins both).

Two env knobs, resolved ONCE per compiled program (never per call):

- ``GORDO_SEQ_LAYOUT`` = ``auto|time_major|legacy``. ``auto`` picks
  ``time_major`` on TPU/GPU backends and ``legacy`` on CPU: the layout win
  is a lane-utilization effect, and keeping single-device CPU on the
  legacy path preserves the byte-for-byte fleet-vs-single guarantees the
  CPU test suite pins (tests opt in explicitly).
- ``GORDO_SEQ_KERNEL`` = ``auto|pallas|interpret|jnp``: the fused
  recurrent-step kernel below (gate matmul + nonlinearities + carry update
  in one VMEM pass per step), ``GORDO_BANK_KERNEL``-style resolution
  (``auto`` = ``pallas`` on TPU, ``jnp`` elsewhere) with interpret mode
  as the tests' parity vehicle. The kernel is FORWARD-ONLY: it
  serves the bank's compiled scoring programs; training keeps the jnp step
  (its backward comes from autodiff through the scan — a custom VJP for
  the fused step is future work, see docs/architecture.md).
"""

import functools
import os

import jax
import jax.numpy as jnp

SEQ_LAYOUT_ENV = "GORDO_SEQ_LAYOUT"
SEQ_KERNEL_ENV = "GORDO_SEQ_KERNEL"
_SEQ_LAYOUTS = ("auto", "time_major", "legacy")
_SEQ_KERNEL_MODES = ("auto", "pallas", "interpret", "jnp")

_GATES = ("i", "f", "g", "o")  # flax OptimizedLSTMCell split order
LANE = 128  # TPU lane width (f32)
SUBLANE = 8


def _fast_backend() -> bool:
    return jax.default_backend() in ("tpu", "gpu")


def resolve_seq_layout(mode: str = None) -> str:
    """Concrete layout for sequence fleet programs: ``mode`` (or env
    ``GORDO_SEQ_LAYOUT``, default ``auto``) resolved against the backend.
    Resolved once per program build — the layout is baked into the
    bucket's compiled epoch/scoring program, not re-decided per call."""
    raw = (mode or os.environ.get(SEQ_LAYOUT_ENV) or "auto").strip().lower()
    if raw not in _SEQ_LAYOUTS:
        raise ValueError(
            f"{SEQ_LAYOUT_ENV} must be one of {'|'.join(_SEQ_LAYOUTS)}, "
            f"got {raw!r}"
        )
    if raw == "auto":
        return "time_major" if _fast_backend() else "legacy"
    return raw


def resolve_seq_kernel_mode(mode: str = None) -> str:
    """Dispatch mode for the fused recurrent-step kernel (scoring path):
    ``mode`` (or env ``GORDO_SEQ_KERNEL``, default ``auto``) resolved once
    per program build. ``auto`` is a pure function of the backend
    (``pallas`` on TPU, ``jnp`` elsewhere): no probe compile, no degrade —
    a kernel the chip refuses fails the program that uses it."""
    raw = (mode or os.environ.get(SEQ_KERNEL_ENV) or "auto").strip().lower()
    if raw not in _SEQ_KERNEL_MODES:
        raise ValueError(
            f"{SEQ_KERNEL_ENV} must be one of {'|'.join(_SEQ_KERNEL_MODES)}, "
            f"got {raw!r}"
        )
    if raw == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return raw


def supports_time_major(module) -> bool:
    """Duck-typed: the time-major forward understands exactly the
    LSTMStack architecture (per-layer ``OptimizedLSTMCell`` + elementwise
    activation, final-step Dense head). Anything else — conv (no
    recurrence; its fast path is the matmul formulation), VAE heads,
    custom modules — stays on the legacy layout."""
    return all(
        hasattr(module, a) for a in ("dims", "funcs", "out_func", "n_features")
    ) and not hasattr(module, "channels")


def extract_lstm_weights(module, params):
    """Per-layer ``(Wi, Wh, b)`` + Dense head from an LSTMStack param tree.

    Works on a single tree or a member-stacked one (leading M axis on
    every leaf): per-gate kernels concatenate on the LAST axis in flax's
    ``i, f, g, o`` split order, so each gate's output columns are the
    same dot products the cell computes — parity is limited only by
    accumulation order.

    Returns ``(layers, (Wd, bd))`` with ``layers[l] = (Wi, Wh, b)`` of
    shapes ``([M,] F_in, 4H)``, ``([M,] H, 4H)``, ``([M,] 4H)``.
    """
    p = params["params"] if "params" in params else params
    layers = []
    for l in range(len(module.dims)):
        cell = p[f"OptimizedLSTMCell_{l}"]
        Wi = jnp.concatenate(
            [cell[f"i{g}"]["kernel"] for g in _GATES], axis=-1
        )
        Wh = jnp.concatenate(
            [cell[f"h{g}"]["kernel"] for g in _GATES], axis=-1
        )
        b = jnp.concatenate([cell[f"h{g}"]["bias"] for g in _GATES], axis=-1)
        layers.append((Wi, Wh, b))
    head = p["Dense_0"]
    return layers, (head["kernel"], head["bias"])


def _lstm_gates(z, c):
    """flax OptimizedLSTMCell carry update from the fused gate block."""
    i, f, g, o = jnp.split(z, 4, axis=-1)
    c2 = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h2 = jax.nn.sigmoid(o) * jnp.tanh(c2)
    return c2, h2


def lstm_step_jnp(xz_t, h, c, Wh, b):
    """One recurrent step, member axis innermost. xz_t: (B, M, 4H)
    precomputed input projection; h/c: (B, M, H); Wh: (M, H, 4H);
    b: (M, 4H). Returns (c', h')."""
    z = xz_t + jnp.einsum("bmh,mhg->bmg", h, Wh) + b[None]
    return _lstm_gates(z, c)


# ------------------------------------------------------------------ #
# Fused recurrent-step Pallas kernel (forward/scoring only)
# ------------------------------------------------------------------ #


def _step_kernel(xz_ref, h_ref, c_ref, wh_ref, b_ref, c2_ref, h2_ref):
    """Grid step = one (member, batch-tile): gate matmul + nonlinearities
    + carry update in a single VMEM pass — the recurrent analogue of
    pallas_score's banked grid. Blocks carry a singleton LEADING member
    axis (1, B_tile, ·), so their last two dims are tile-aligned."""
    z = (
        xz_ref[0]
        + jnp.dot(h_ref[0], wh_ref[0], preferred_element_type=jnp.float32)
        + b_ref[0]
    )
    i, f, g, o = jnp.split(z, 4, axis=-1)
    c2 = jax.nn.sigmoid(f) * c_ref[0] + jax.nn.sigmoid(i) * jnp.tanh(g)
    c2_ref[0] = c2
    h2_ref[0] = jax.nn.sigmoid(o) * jnp.tanh(c2)


STEP_BATCH_TILE = 256  # batch rows per grid step (bounds the VMEM block)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_lstm_step(xz_t, h, c, Wh, b, interpret: bool = False):
    """Pallas fused step, MEMBER-MAJOR: xz_t (M, B, 4H) precomputed input
    projection; h/c (M, B, H); Wh (M, H, 4H); b (M, 4H) — the math of
    :func:`lstm_step_jnp` with the member axis leading instead of
    second-last (the TPU lowering refuses a size-1 block on a second-last
    axis of size M > 1). H is already padded to the lane tile
    (:func:`pad_gate_lanes`) and B to the sublane tile, or to a
    ``STEP_BATCH_TILE`` multiple when longer. Returns (c', h')."""
    from jax.experimental import pallas as pl

    M, B, H4 = xz_t.shape
    H = H4 // 4
    tb = min(B, STEP_BATCH_TILE)
    if B % tb:
        raise ValueError(
            f"batch axis {B} must be a multiple of {STEP_BATCH_TILE} once "
            "it exceeds it (the grid would drop the remainder)"
        )
    # the member's Wh/b block index does not change along the inner batch
    # axis, so the pipeline DMAs them once per member
    blk_h = pl.BlockSpec((1, tb, H), lambda m, j: (m, j, 0))
    blk_z = pl.BlockSpec((1, tb, H4), lambda m, j: (m, j, 0))
    return pl.pallas_call(
        _step_kernel,
        grid=(M, B // tb),
        in_specs=[
            blk_z,
            blk_h,
            blk_h,
            pl.BlockSpec((1, H, H4), lambda m, j: (m, 0, 0)),
            pl.BlockSpec((1, 1, H4), lambda m, j: (m, 0, 0)),
        ],
        out_specs=[blk_h, blk_h],
        out_shape=[
            jax.ShapeDtypeStruct((M, B, H), xz_t.dtype),
            jax.ShapeDtypeStruct((M, B, H), xz_t.dtype),
        ],
        interpret=interpret,
    )(xz_t, h, c, Wh, b[:, None, :])


def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def pad_gate_lanes(Wh, b, H: int, Hp: int):
    """Pad the hidden width to the lane tile GATE-ALIGNED: the fused gate
    block splits into four H-wide slices, so padding must go inside each
    gate's slice (zero kernel columns/rows and zero bias), not at the
    end. Padded lanes stay self-contained: their z is exactly 0, the
    resulting 0.5-sigmoid garbage multiplies only zero Wh rows on the
    next step, and the caller slices them off the final hidden state."""
    if Hp == H:
        return Wh, b
    pad_in = Hp - H

    def per_gate(a, axis):
        parts = jnp.split(a, 4, axis=-1)
        widths = [(0, 0)] * a.ndim
        widths[-1] = (0, pad_in)
        parts = [jnp.pad(x, widths) for x in parts]
        return jnp.concatenate(parts, axis=-1)

    Wh = per_gate(Wh, -1)
    rw = [(0, 0)] * Wh.ndim
    rw[-2] = (0, pad_in)
    Wh = jnp.pad(Wh, rw)
    b = per_gate(b, -1)
    return Wh, b


# ------------------------------------------------------------------ #
# Full time-major forward
# ------------------------------------------------------------------ #


def _lstm_layer(x, Wi, Wh, b, kernel: str):
    """One LSTM layer over time-major x: (T, B, M, F_in) -> (T, B, M, H).

    The input projection for ALL timesteps is one wide einsum hoisted out
    of the scan; each scan step is then a single batched matmul + gates.
    """
    T, B, M, _ = x.shape
    H = Wh.shape[-2]
    if kernel in ("pallas", "interpret"):
        # the fused step walks members on its grid, so its operands are
        # member-major (M, B, ·): the projection einsum emits that order
        # directly and the scan output is swapped back once per layer
        xz = jnp.einsum("tbmf,mfg->tmbg", x, Wi)
        Hp = _round_up(H, LANE)
        Whp, bp = pad_gate_lanes(Wh, b, H, Hp)
        Bp = _round_up(B, SUBLANE)
        if Bp > STEP_BATCH_TILE:
            Bp = _round_up(B, STEP_BATCH_TILE)
        if Hp != H:
            parts = jnp.split(xz, 4, axis=-1)
            parts = [
                jnp.pad(p, ((0, 0), (0, 0), (0, 0), (0, Hp - H)))
                for p in parts
            ]
            xz = jnp.concatenate(parts, axis=-1)
        if Bp != B:
            xz = jnp.pad(xz, ((0, 0), (0, 0), (0, Bp - B), (0, 0)))
        interpret = kernel == "interpret"

        def step(carry, xz_t):
            c, h = carry
            c2, h2 = fused_lstm_step(xz_t, h, c, Whp, bp, interpret=interpret)
            return (c2, h2), h2

        zeros = jnp.zeros((M, Bp, Hp), x.dtype)
        _, ys = jax.lax.scan(step, (zeros, zeros), xz)
        return jnp.swapaxes(ys[:, :, :B, :H], 1, 2)

    xz = jnp.einsum("tbmf,mfg->tbmg", x, Wi)

    def step(carry, xz_t):
        c, h = carry
        c2, h2 = lstm_step_jnp(xz_t, h, c, Wh, b)
        return (c2, h2), h2

    zeros = jnp.zeros((B, M, H), x.dtype)
    _, ys = jax.lax.scan(step, (zeros, zeros), xz)
    return ys


def lstm_time_major_forward(module, stacked_params, xb, kernel: str = "jnp"):
    """Time-major LSTMStack forward over member-stacked params.

    ``xb``: (M, B, T, F) — each member's batch of windows (the fleet's
    stacking order; the bank's scoring path passes (slots, windows, L, F)).
    Returns (M, B, F) predictions matching ``vmap(module.apply)`` to fp32
    rounding. ``kernel`` must already be RESOLVED (jnp|pallas|interpret) —
    training callers pass "jnp" (the fused kernel is forward-only)."""
    from gordo_components_tpu.models.factories.feedforward import (
        resolve_activation,
    )

    dtype = jnp.dtype(getattr(module, "compute_dtype", "float32"))
    layers, (Wd, bd) = extract_lstm_weights(module, stacked_params)
    x = jnp.transpose(xb, (2, 1, 0, 3)).astype(dtype)  # (T, B, M, F)
    for (Wi, Wh, b), func in zip(layers, module.funcs):
        x = _lstm_layer(
            x, Wi.astype(dtype), Wh.astype(dtype), b.astype(dtype), kernel
        )
        x = resolve_activation(func)(x)
    h_last = x[-1]  # (B, M, H) — final hidden state of the last layer
    out = jnp.einsum("bmh,mhf->bmf", h_last, Wd.astype(dtype))
    out = resolve_activation(module.out_func)(out + bd.astype(dtype)[None])
    return jnp.transpose(out, (1, 0, 2)).astype(jnp.float32)
