"""Fused anomaly-scoring epilogue as a Pallas TPU kernel.

The server's per-request hot loop (SURVEY.md §3.2; reference:
``DiffBasedAnomalyDetector.anomaly``) ends in an elementwise epilogue over
the reconstruction: ``|target - output|``, the per-feature error scaling,
and two row norms. As four separate XLA ops this reads the (rows, F)
operands from HBM several times and writes four results back; the Pallas
kernel streams each row tile through VMEM exactly once and emits all four
outputs from that single pass — one HBM read per operand, four writes,
zero intermediate round-trips.

Usage is transparent: :func:`fused_anomaly_score` dispatches to the kernel
on TPU backends and to an identical pure-jnp implementation elsewhere
(tests run it in interpreter mode via ``interpret=True`` to exercise the
kernel logic on CPU). Feature/row padding to hardware tiles (8 sublanes x
128 lanes for f32) happens in the wrapper; padded feature lanes are masked
inside the kernel so they contribute nothing to the scaled errors or the
norms.

Two entry points share the kernel math:

- :func:`fused_anomaly_score` — the *per-model* path
  (``DiffBasedAnomalyDetector.anomaly``: single model, one (rows, F)
  request), auto-dispatching per call.
- :func:`banked_anomaly_score` — the *banked* serving path
  (server/bank.py): a batched grid over (member, row-tile) that gathers
  each batch slot's per-member error-scaler vectors via scalar-prefetch
  indices and runs scale → reconstruction-error → row norms in one VMEM
  pass over the whole coalesced batch. It is traced INSIDE the bank's
  per-bucket jit program, so the dispatch decision (``mode``) is made
  once at bucket-finalize time — ``resolve_bank_kernel_mode`` reads
  ``GORDO_BANK_KERNEL`` (auto|pallas|interpret|jnp; auto = kernel on
  TPU, jnp elsewhere).

Error budget (the parity harness in tests/test_banked_kernel.py pins
this): the elementwise outputs (``diff``, ``scaled``) are BITWISE equal
to the jnp reference at fp32 — they never cross a reduction. The two
row norms reduce over the 128-lane padded feature axis, whose tree
order can differ from the unpadded jnp sum when ``F`` is not a lane
multiple: observed ≤2 ULP, asserted ≤4 ULP.
"""

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp

ROW_TILE = 256  # rows per grid step (multiple of the 8-sublane f32 tile)
LANE = 128


@jax.jit
def _jnp_score(target, output, shift, scale):
    """Reference implementation (also the non-TPU path)."""
    diff = jnp.abs(target - output)
    scaled = (diff - shift) * scale
    tot_u = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    tot_s = jnp.sqrt(jnp.sum(scaled * scaled, axis=-1))
    return diff, scaled, tot_u, tot_s


def _kernel(n_features: int, t_ref, o_ref, shift_ref, scale_ref,
            diff_ref, scaled_ref, tu_ref, ts_ref):
    t = t_ref[:]
    o = o_ref[:]
    diff = jnp.abs(t - o)
    # feature lanes beyond n_features are padding: zero them so the scaled
    # error's affine shift doesn't leak into the norms
    mask = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1) < n_features
    diff = jnp.where(mask, diff, 0.0)
    scaled = jnp.where(mask, (diff - shift_ref[:]) * scale_ref[:], 0.0)
    diff_ref[:] = diff
    scaled_ref[:] = scaled
    tu_ref[:] = jnp.sqrt(jnp.sum(diff * diff, axis=1, keepdims=True))
    ts_ref[:] = jnp.sqrt(jnp.sum(scaled * scaled, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_score(target, output, shift, scale, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, F = target.shape
    Fp = -(-F // LANE) * LANE
    # adaptive row tile: small requests shouldn't pad to a full ROW_TILE
    # (a 33-row request tiles at 40, not 256); multiples of the 8-sublane
    # f32 tile keep the hardware layout happy
    row_tile = min(ROW_TILE, -(-rows // 8) * 8)
    Rp = -(-rows // row_tile) * row_tile

    pad2 = lambda a: jnp.pad(a, ((0, Rp - rows), (0, Fp - F)))
    t = pad2(target.astype(jnp.float32))
    o = pad2(output.astype(jnp.float32))
    row_vec = lambda v: jnp.pad(v.astype(jnp.float32), (0, Fp - F))[None, :]
    sh, sc = row_vec(shift), row_vec(scale)

    grid = (Rp // row_tile,)
    tile = lambda: pl.BlockSpec(
        (row_tile, Fp), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    const = lambda: pl.BlockSpec((1, Fp), lambda i: (0, 0), memory_space=pltpu.VMEM)

    diff, scaled, tu, ts = pl.pallas_call(
        functools.partial(_kernel, F),
        grid=grid,
        in_specs=[tile(), tile(), const(), const()],
        out_specs=[
            tile(),
            tile(),
            pl.BlockSpec((row_tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((row_tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, Fp), jnp.float32),
            jax.ShapeDtypeStruct((Rp, Fp), jnp.float32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(t, o, sh, sc)
    return (
        diff[:rows, :F],
        scaled[:rows, :F],
        tu[:rows, 0],
        ts[:rows, 0],
    )


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused_anomaly_score(
    target: jnp.ndarray,
    output: jnp.ndarray,
    shift: jnp.ndarray,
    scale: jnp.ndarray,
    force: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(diff, scaled, total_unscaled, total_scaled)`` for a (rows, F)
    reconstruction — one fused pass on TPU, identical jnp math elsewhere.

    ``force``: "auto" (a pure function of the backend: the compiled
    kernel on TPU, jnp elsewhere), "pallas" (compiled kernel), "jnp"
    (XLA path), "interpret" (kernel in interpreter mode — tests only,
    never selected by auto). A kernel that fails to compile or run is an
    error that reaches the caller: nothing degrades to jnp on exception.
    """
    if force == "jnp" or (force == "auto" and not _on_tpu()):
        return _jnp_score(target, output, shift, scale)
    return _pallas_score(
        target, output, shift, scale, interpret=force == "interpret"
    )


# --------------------------------------------------------------------- #
# banked kernel: the whole coalesced batch in one grid
# --------------------------------------------------------------------- #

BANK_KERNEL_ENV = "GORDO_BANK_KERNEL"
_BANK_KERNEL_MODES = ("auto", "pallas", "interpret", "jnp")


def resolve_bank_kernel_mode(mode: str = None) -> str:
    """Concrete dispatch mode for the banked epilogue: ``mode`` (or env
    ``GORDO_BANK_KERNEL``, default ``auto``) resolved against the
    backend. Resolved ONCE per bank build — the choice is baked into the
    bucket's compiled program, not re-decided per request. ``auto`` is a
    pure function of the backend (``pallas`` on TPU, ``jnp`` elsewhere):
    nothing is probe-compiled and nothing degrades — a kernel the chip's
    compiler refuses fails the bucket's warm-up compile, loudly."""
    raw = (mode or os.environ.get(BANK_KERNEL_ENV) or "auto").strip().lower()
    if raw not in _BANK_KERNEL_MODES:
        raise ValueError(
            f"{BANK_KERNEL_ENV} must be one of {'|'.join(_BANK_KERNEL_MODES)}, "
            f"got {raw!r}"
        )
    if raw == "auto":
        return "pallas" if _on_tpu() else "jnp"
    return raw


def _jnp_banked_score(target, output, shift_bank, scale_bank, idx):
    """Batched reference/XLA path: same math as per-member ``_jnp_score``
    with the scaler gather hoisted to one take. target/output: (B, T, F);
    shift/scale banks: (M, F); idx: (B,) member indices."""
    shift = shift_bank[idx][:, None, :]
    scale = scale_bank[idx][:, None, :]
    diff = jnp.abs(target - output)
    scaled = (diff - shift) * scale
    tot_u = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    tot_s = jnp.sqrt(jnp.sum(scaled * scaled, axis=-1))
    return diff, scaled, tot_u, tot_s


def _banked_kernel(n_features: int, idx_ref, t_ref, o_ref, shift_ref,
                   scale_ref, diff_ref, scaled_ref, tu_ref, ts_ref):
    # one (member, row-tile) grid step: refs are (1, row_tile, Fp) batch
    # tiles and (1, 1, Fp) scaler rows already gathered by the scalar-
    # prefetched index map (idx_ref is consumed there, not here)
    t = t_ref[0]
    o = o_ref[0]
    diff = jnp.abs(t - o)
    # feature lanes beyond n_features are padding: zero them so the
    # scaled error's affine shift doesn't leak into the norms
    mask = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1) < n_features
    diff = jnp.where(mask, diff, 0.0)
    scaled = jnp.where(mask, (diff - shift_ref[0]) * scale_ref[0], 0.0)
    diff_ref[0] = diff
    scaled_ref[0] = scaled
    tu_ref[0] = jnp.sqrt(jnp.sum(diff * diff, axis=1, keepdims=True))
    ts_ref[0] = jnp.sqrt(jnp.sum(scaled * scaled, axis=1, keepdims=True))


def _pallas_banked_score(target, output, shift_bank, scale_bank, idx,
                         interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, F = target.shape
    Fp = -(-F // LANE) * LANE
    # adaptive row tile, exactly like the per-model kernel: short batch
    # rows tile at the next 8-sublane multiple, long ones at ROW_TILE
    row_tile = min(ROW_TILE, -(-T // 8) * 8)
    Rp = -(-T // row_tile) * row_tile
    pad3 = lambda a: jnp.pad(
        a.astype(jnp.float32), ((0, 0), (0, Rp - T), (0, Fp - F))
    )
    t = pad3(target)
    o = pad3(output)
    # banks ride as (M, 1, Fp): the TPU lowering wants a block's last two
    # dims to be (8, 128)-multiples or the array's own, and a one-row block
    # of an (M, Fp) bank is neither once M > 1 — with the member axis
    # leading, the (1, 1, Fp) block's last two dims ARE the array's
    pad_bank = lambda a: jnp.pad(
        a.astype(jnp.float32), ((0, 0), (0, Fp - F))
    )[:, None, :]
    sh, sc = pad_bank(shift_bank), pad_bank(scale_bank)

    # index maps receive (grid indices..., scalar-prefetch refs): the
    # scaler banks are gathered per batch slot by indexing the prefetched
    # member ids — the gather happens in the BlockSpec, so each grid step
    # DMAs exactly one member's scaler row into VMEM
    tile = lambda: pl.BlockSpec(
        (1, row_tile, Fp), lambda b, r, i: (b, r, 0), memory_space=pltpu.VMEM
    )
    gathered = lambda: pl.BlockSpec(
        (1, 1, Fp), lambda b, r, i: (i[b], 0, 0), memory_space=pltpu.VMEM
    )
    norm = lambda: pl.BlockSpec(
        (1, row_tile, 1), lambda b, r, i: (b, r, 0), memory_space=pltpu.VMEM
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Rp // row_tile),
        in_specs=[tile(), tile(), gathered(), gathered()],
        out_specs=[tile(), tile(), norm(), norm()],
    )
    diff, scaled, tu, ts = pl.pallas_call(
        functools.partial(_banked_kernel, F),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Rp, Fp), jnp.float32),
            jax.ShapeDtypeStruct((B, Rp, Fp), jnp.float32),
            jax.ShapeDtypeStruct((B, Rp, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Rp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(idx.astype(jnp.int32), t, o, sh, sc)
    return (
        diff[:, :T, :F],
        scaled[:, :T, :F],
        tu[:, :T, 0],
        ts[:, :T, 0],
    )


def banked_anomaly_score(
    target: jnp.ndarray,
    output: jnp.ndarray,
    shift_bank: jnp.ndarray,
    scale_bank: jnp.ndarray,
    idx: jnp.ndarray,
    mode: str = "jnp",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Banked scoring epilogue over a coalesced batch: ``(diff, scaled,
    total_unscaled, total_scaled)`` for (B, T, F) reconstructions against
    (M, F) stacked error scalers, member-selected by ``idx`` (B,).

    Traced inside the bank's per-bucket jit program; ``mode`` must
    already be resolved (:func:`resolve_bank_kernel_mode`): ``jnp`` is
    the XLA path (CPU fallback and parity reference), ``pallas`` the
    compiled TPU kernel, ``interpret`` the kernel in interpreter mode
    (how CI exercises the kernel logic without TPU hardware)."""
    if mode == "jnp":
        return _jnp_banked_score(target, output, shift_bank, scale_bank, idx)
    if mode == "pallas":
        return _pallas_banked_score(target, output, shift_bank, scale_bank, idx)
    if mode == "interpret":
        return _pallas_banked_score(
            target, output, shift_bank, scale_bank, idx, interpret=True
        )
    raise ValueError(
        f"banked_anomaly_score mode must be resolved to jnp|pallas|interpret, "
        f"got {mode!r}"
    )
