"""FleetTrainer: train thousands of per-machine models in one XLA program.

The reference trains its fleet as one Kubernetes pod per model (Argo DAG
fan-out, SURVEY.md §1 layer 8). Here the fleet IS the tensor:

- members are **bucketed by feature count** so every model in a bucket has
  identical parameter shapes (SURVEY.md §7 "hard part 1": heterogeneity vs
  vmap homogeneity);
- per-member data is padded to a common row count with sample masks;
- per-member min-max scalers are ``vmap(fit_minmax)`` — 10k scalers are one
  stacked ``ScalerParams`` pytree;
- params for all members are initialized and trained with
  ``vmap(epoch_fn)`` over the model axis — one jit'd program per bucket per
  epoch, with on-device shuffling per model;
- stacked arrays/params are sharded over the ``models`` mesh axis: each
  device trains its shard with **zero** collective traffic;
- per-model early stopping via an ``active`` mask: converged models stop
  updating (their params freeze) while the program keeps static shapes.

Results unstack into ordinary estimator objects (``FleetMemberModel`` →
``AutoEncoder`` / ``DiffBasedAnomalyDetector``) so artifacts, the server,
and the client treat fleet-trained models identically to single builds.
"""

import functools
import logging
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gordo_components_tpu.models import train_core
from gordo_components_tpu.models.register import lookup_factory
from gordo_components_tpu.observability import get_registry
from gordo_components_tpu.observability.tracing import (
    covered_seconds,
    current_trace,
    get_tracer,
    stage,
    use_trace,
)
from gordo_components_tpu.ops import dense_step
from gordo_components_tpu.ops.seq_scan import (
    resolve_seq_layout,
    supports_time_major,
)
from gordo_components_tpu.ops.scaler import (
    ScalerParams,
    fit_minmax,
    fit_standard,
    scaler_transform,
)
from gordo_components_tpu.parallel.autotune import resolve_fleet_width
from gordo_components_tpu.parallel.mesh import (
    MODEL_AXIS,
    device_block,
    fleet_mesh,
    pad_count_to_mesh,
    shard_model_axis,
)
from gordo_components_tpu.utils import capture_args

logger = logging.getLogger(__name__)


# ---- per-bucket jit'd programs, cached process-wide -------------------- #
# A fresh fit() must not retrace/recompile programs an earlier fit already
# built for the same (architecture, optimizer config, batch size): repeated
# builds (warm-up, build-cache reruns, server-side refits) hit the
# jit cache through these shared function objects. Flax modules are frozen
# dataclasses, so equal-config modules hash equal and share an entry.


@functools.partial(jax.jit, static_argnames="kind")
def _fit_scalers(X, mask, kind="minmax"):
    Xn = jnp.where(mask[..., None] > 0, X, jnp.nan)
    fit = fit_minmax if kind == "minmax" else fit_standard
    return jax.vmap(fit)(Xn)


@jax.jit
def _transform_all(scalers, X):
    return jax.vmap(scaler_transform)(scalers, X)


def _set_stacked_lr(states, lr_vec):
    """Overwrite the injected opt state's stacked learning-rate leaf with
    a per-member (M,) vector. TrainState and InjectHyperparamsState are
    both NamedTuples, so this is pure ``_replace`` surgery — no retrace,
    no program split."""
    os_ = states.opt_state
    current = os_.hyperparams["learning_rate"]
    hp = dict(os_.hyperparams)
    hp["learning_rate"] = jnp.asarray(lr_vec, current.dtype)
    return states._replace(opt_state=os_._replace(hyperparams=hp))


@jax.jit
def _merge_best(best_p, new_p, improved):
    """Per-model select: where ``improved`` (M,) is set, take the new
    leaves; else keep the best-so-far."""

    def sel(b, n):
        shape = (-1,) + (1,) * (n.ndim - 1)
        return jnp.where(improved.reshape(shape) > 0, n, b)

    return jax.tree.map(sel, best_p, new_p)


# A gang's block of more than STAGING_PIECE_BYTES, of members of at least
# STAGING_MEMBER_BYTES each, is not stacked on the host: its members cross to
# the device from their own memory, a piece of about STAGING_PIECE_BYTES a
# call, and the device writes each piece into the block. Every other block
# is one piece, stacked on the host. Both from tools/staging_ladder.py on a
# v5e host (PERF.md section 6, PR 32): a whole block of 1.2 GB is ready in
# 0.66 s, its 1.9 MB members put one by one in 0.13 s; an array costs the
# host 0.19 ms to put whatever it holds, so members of 512 KB still win
# (0.40 against 0.66 s) and members of 64 KB lose (0.75 against 0.07 s).
STAGING_PIECE_BYTES = 256 << 20
STAGING_MEMBER_BYTES = 512 << 10


@functools.partial(jax.jit, donate_argnums=0)
def _place(X, at, *rows):
    """Equal-length members ``rows`` written into ``X`` from slot ``at`` on,
    in place; the rows below them stay as they are (zero)."""
    return jax.lax.dynamic_update_slice(X, jnp.stack(rows), (at, 0, 0))


def stage_gang(
    members: List[np.ndarray],
    M: int,
    padded_rows: int,
    n_features: int,
    sharding: jax.sharding.NamedSharding,
) -> Tuple[jax.Array, np.ndarray, Dict[str, int]]:
    """``fleet_stack_pad``'s (M, padded_rows, n_features) block on the
    devices of ``sharding`` (members over its one mesh axis), its
    (M, padded_rows) mask on the host, and ``{"pieces", "bytes"}``.

    A block of more than ``STAGING_PIECE_BYTES``, of members of
    ``STAGING_MEMBER_BYTES`` or more, never exists on the host: stacking
    it moves every byte through host memory twice more, which costs more
    than the crossing itself. A piece of slots at a time, the members are
    handed to the device as they lie, and the device writes them into a
    zeroed block: that is the padding. Pieces end where shards do, so each
    goes to the device that holds it. One program serves every piece, so
    every array of a gang has the gang's longest row count: a shorter
    member crosses from a zero-padded copy of that length, a fresh one
    each piece (the transfer may read it after the call returns, and on
    the CPU backend the device array IS the host array)."""
    from gordo_components_tpu.native import fleet_stack_pad

    block = (padded_rows, n_features)
    nbytes = 4 * M * padded_rows * n_features
    if nbytes <= STAGING_PIECE_BYTES or nbytes < M * STAGING_MEMBER_BYTES:
        Xs, masks = fleet_stack_pad(members, M, *block)
        return jax.device_put(Xs, sharding), masks, {"pieces": 1, "bytes": nbytes}
    for a in members:  # what fleet_stack_pad refuses
        if a.ndim != 2 or a.shape[1] != n_features or a.shape[0] > padded_rows:
            raise ValueError(f"Bad member shape {a.shape} for ({padded_rows}, {n_features})")
    n = len(members)
    rows = np.array([a.shape[0] for a in members])
    longest = int(rows.max())
    masks = (np.arange(padded_rows) < rows[np.arange(M) % n, None]).astype(np.float32)
    devices = list(sharding.mesh.devices.flat)
    m = M // len(devices)  # slots a shard
    per_shard = -(-nbytes // (len(devices) * STAGING_PIECE_BYTES))
    p = -(-m // per_shard)  # slots a piece
    shards, pieces = [], 0
    for d, device in enumerate(devices):
        X = jnp.zeros((m,) + block, jnp.float32, device=device)
        for at in range(0, m, p):
            # slots past the real members hold member i % n, as in one piece
            piece = [members[(d * m + i) % n] for i in range(at, min(at + p, m))]
            short = [i for i, a in enumerate(piece) if a.shape[0] < longest]
            if short:
                copies, _ = fleet_stack_pad(
                    [piece[i] for i in short], len(short), longest, n_features
                )
                for i, a in zip(short, copies):
                    piece[i] = a
            X = _place(X, at, *jax.device_put(piece, device))
            pieces += 1
        shards.append(X)
    Xd = jax.make_array_from_single_device_arrays((M,) + block, sharding, shards)
    return Xd, masks, {"pieces": pieces, "bytes": nbytes}


# Bin count for the streaming-quantile histograms of the sequence error
# pass: absolute threshold error <= range/8192 (~1.2e-4 on the [0,1]
# scaled-feature axis), with (f+1)*8192 int32 histogram cells per member.
_QUANTILE_BINS = 8192
# Transient histogram budget for one vmapped quantile pass; wider fleets
# stream through run_error_scalers in member chunks under this cap — in
# particular at GORDO_FLEET_WIDTH=auto's 4096-member knee, where the
# un-chunked carry would be 4096*(f+1)*32KB of pure transient.
_QUANTILE_CHUNK_BYTES = 1 << 28


def _hist_quantile(hist, binw, q, n):
    """Approximate ``np.quantile(values, q)`` (linear interpolation
    between order statistics) from a fixed-bin histogram of the values
    over ``[0, len(hist)*binw)`` holding ``n`` valid samples: each order
    statistic is located by inverting the empirical CDF with
    uniform-within-bin interpolation, so the absolute error is bounded by
    one bin width. ``hist`` accumulates in int32 (f32 scatter-adds would
    saturate at 2^24 and silently push high quantiles to the range max);
    the f32 conversion here costs only ~1e-7 relative rank error."""
    cum = jnp.cumsum(hist).astype(jnp.float32)
    hist = hist.astype(jnp.float32)

    def order_stat(j):  # j: float 0-indexed rank
        b = jnp.clip(
            jnp.searchsorted(cum, j + 1.0, side="left"), 0, hist.shape[0] - 1
        )
        prev = jnp.where(b > 0, cum[b - 1], 0.0)
        frac = jnp.clip((j + 1.0 - prev) / jnp.maximum(hist[b], 1.0), 0.0, 1.0)
        return (b.astype(jnp.float32) + frac) * binw

    p = q * (n - 1.0)
    j0 = jnp.floor(p)
    g = p - j0
    j1 = jnp.minimum(j0 + 1.0, jnp.maximum(n - 1.0, 0.0))
    return (1.0 - g) * order_stat(j0) + g * order_stat(j1)


class _BucketPrograms:
    """All compiled programs for one (module, optimizer, batch-size[, seq])
    key. ``seq=(lookback, target_offset)`` switches every program to the
    gather-windowed sequence variants: X stays the raw (rows_pad, f) member
    block on device and masks index ITEMS (window starts), so sequence
    fleets train with O(rows) HBM per member instead of O(rows*lookback)."""

    def __init__(
        self, module, opt_name: str, lr: float, batch_size: int, seq=None,
        loss: str = "mse", kl_weight: float = 1.0,
        threshold_quantile: float = 1.0, layout: str = "legacy",
        fused_step: Tuple[Optional[str], Optional[str]] = (None, None), mesh=None,
    ):
        self.module = module
        self.seq = seq
        # ops/dense_step.resolve's answer: how the fused step runs here, or
        # why this bucket trains through another program (the fit span
        # carries it)
        step_mode, self.fused_step_refused = fused_step
        # the RESOLVED epoch program (resolved by _bucket_programs so it is
        # part of the cache key). Sequence buckets: "time_major" routes
        # run_epoch through the gang epoch whose scan keeps
        # members innermost (ops/seq_scan.resolve_seq_layout). Dense
        # buckets: "fused_step" through the gang epoch whose step is one
        # Pallas program over the members (ops/dense_step, run as
        # ``step_mode`` says, under shard_map where ``mesh`` spreads the
        # gang over devices). "legacy" is vmap(epoch).
        self.layout = "fused_step" if step_mode is not None else layout
        # inject=True: the learning rate lives in the (vmapped, stacked)
        # opt state, so _fit_bucket can overwrite it with a per-member
        # (M,) vector — members differing only in LR share this program
        optimizer = train_core.make_optimizer(opt_name, lr, inject=True)
        if seq is None:
            init_fn, epoch_fn = train_core.make_train_fns(
                module, optimizer, batch_size, loss=loss, kl_weight=kl_weight
            )
        else:
            lookback, t_offset = seq
            init_fn, epoch_fn = train_core.make_seq_train_fns(
                module, optimizer, batch_size, lookback, t_offset,
                loss=loss, kl_weight=kl_weight,
            )
        self.init_stacked = jax.jit(jax.vmap(init_fn))

        def masked_epoch(state, X, mask, active):
            new_state, loss = epoch_fn(state, X, X, mask)
            merged = jax.tree.map(
                lambda n, o: jnp.where(active > 0, n, o), new_state, state
            )
            return merged, jnp.where(active > 0, loss, jnp.nan)

        if self.layout == "time_major":
            gang_epoch = train_core.make_seq_gang_epoch(
                module, optimizer, batch_size, seq[0], seq[1]
            )

            def masked_gang(states, X, mask, active):
                new_states, losses = gang_epoch(states, X, mask)
                act = active > 0

                def sel(n, o):
                    return jnp.where(
                        act.reshape(act.shape + (1,) * (n.ndim - 1)), n, o
                    )

                merged = jax.tree.map(sel, new_states, states)
                return merged, jnp.where(act, losses, jnp.nan)

            self._vm_epoch = masked_gang
        elif self.layout == "fused_step":
            fused_epoch = train_core.make_dense_gang_epoch(
                dense_step.make_step(module, step_mode), batch_size
            )
            if mesh is not None and mesh.shape[MODEL_AXIS] > 1:
                # members are independent: each device steps its own block
                # of the gang (under GSPMD alone the kernel's operands
                # would be gathered to one device)
                spec = jax.sharding.PartitionSpec(MODEL_AXIS)
                fused_epoch = jax.shard_map(
                    fused_epoch, mesh=mesh, in_specs=spec, out_specs=spec,
                    check_vma=False,
                )

            # the name the device trace knows the epoch program by: jit
            # calls it ``jit_masked_epoch_fused``
            def masked_epoch_fused(states, X, mask, active):
                return fused_epoch(states, X, mask, active)

            self._vm_epoch = masked_epoch_fused
        else:
            self._vm_epoch = jax.vmap(masked_epoch)
        self.run_epoch = jax.jit(self._vm_epoch, donate_argnums=(0,))

        # per-member validation loss, same loss family and masked-mean
        # semantics as the single path's make_eval_fn. One deliberate
        # deviation: this evaluates in ONE full-block pass with a single
        # fixed rng draw, while make_eval_fn evaluates batchwise with a
        # per-batch fixed rng — for MSE the results agree to fp rounding,
        # but variational (ELBO) members sample different noise, so VAE
        # val losses are deterministic yet not bitwise the single-path
        # values and ES decisions can diverge slightly on a VAE fleet.
        if seq is None:
            # same loss family as training (VAE members validate with the
            # ELBO, like make_eval_fn's fixed-rng pass in the single path)
            val_loss_fn = train_core.make_loss_fn(
                module, loss=loss, kl_weight=kl_weight
            )

            def member_val_loss(params, x, vmask):
                return val_loss_fn(params, jax.random.PRNGKey(0), x, x, vmask)

        else:
            member_val_loss = train_core.make_seq_eval_fn(
                module, batch_size, seq[0], seq[1],
                loss=loss, kl_weight=kl_weight,
            )

        self.eval_stacked = jax.jit(jax.vmap(member_val_loss))
        self.threshold_quantile = float(threshold_quantile)
        self.fit_error_scalers = (
            self._make_error_scalers(module, threshold_quantile)
            if seq is None
            else self._make_seq_error_scalers(
                module, batch_size, *seq, q=threshold_quantile
            )
        )

    @property
    def threshold_method(self) -> str:
        """Provenance label for the thresholds ``run_error_scalers``
        produces — derived from the SAME predicate that selects the
        algorithm below, so the recorded metadata can never drift from
        what actually ran."""
        if self.seq is None or self.threshold_quantile >= 1.0:
            return "exact"
        return f"histogram-{_QUANTILE_BINS}"

    def run_error_scalers(self, params, X, mask):
        """``fit_error_scalers``, chunked over members for the sequence
        ``q < 1`` histogram pass: its (f+1)*8192-cell per-member scan
        carry scales the transient with the vmap width, so wide fleets
        stream through in member chunks capped at ~256 MB of histogram
        (at most two extra compiles: the chunk shape and the tail)."""
        if self.seq is None or self.threshold_quantile >= 1.0:
            return self.fit_error_scalers(params, X, mask)
        f = X.shape[-1]
        M = X.shape[0]
        ch = max(1, _QUANTILE_CHUNK_BYTES // ((f + 1) * _QUANTILE_BINS * 4))
        if M <= ch:
            return self.fit_error_scalers(params, X, mask)
        outs = []
        for i in range(0, M, ch):
            sl = slice(i, min(i + ch, M))
            outs.append(
                self.fit_error_scalers(
                    jax.tree.map(lambda a: a[sl], params), X[sl], mask[sl]
                )
            )
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *outs)

    @staticmethod
    def _make_error_scalers(module, q: float = 1.0):
        @jax.jit
        def fit_error_scalers(params, X, mask):
            def one(p, x, m):
                pred = module.apply(p, x)
                diff = jnp.abs(x - pred)
                diff = jnp.where(m[..., None] > 0, diff, jnp.nan)
                es = fit_minmax(diff)
                scaled = scaler_transform(es, diff)
                total = jnp.sqrt(jnp.nansum(scaled**2, axis=-1))
                total = jnp.where(m > 0, total, jnp.nan)
                if q >= 1.0:
                    return es, jnp.nanmax(scaled, axis=0), jnp.nanmax(total)
                # detector parity: quantile of training scaled errors
                # (np.quantile linear interpolation == jnp.nanquantile's)
                return (
                    es,
                    jnp.nanquantile(scaled, q, axis=0),
                    jnp.nanquantile(total, q),
                )

            return jax.vmap(one)(params, X, mask)

        return fit_error_scalers

    @staticmethod
    def _make_seq_error_scalers(module, batch_size, lookback, t_offset, q=1.0):
        """Two scan passes (min/max of |err|, then scaled thresholds) so
        windows are never materialized beyond one batch — the same anomaly
        contract as the dense path: es = minmax over training |err|,
        feature thresholds = max scaled |err| (``q >= 1``), total = max
        scaled norm.

        ``q < 1``: thresholds are STREAMING APPROXIMATE quantiles. The
        scaled per-feature errors lie exactly in [0, 1] (the scaler is the
        min-max of the same errors) and the scaled norm in [0, sqrt(f)],
        so pass 2 accumulates fixed-bin histograms over those known ranges
        and inverts the empirical CDF with the same linear order-statistic
        interpolation ``np.quantile`` uses — absolute error bounded by one
        bin width (range/8192), vs the single-build detector's exact
        ``np.quantile`` over materialized windows (models/anomaly/diff.py).
        """
        @jax.jit
        def fit_error_scalers(params, X, mask):
            def one(p, x, m):
                n_pad = m.shape[0]
                nb = n_pad // batch_size
                idxs = jnp.arange(n_pad).reshape((nb, batch_size))
                Ms = m.reshape((nb, batch_size))

                def diff_batch(ib, mb):
                    xb, yb = train_core.gather_window_batch(
                        x, ib, lookback, t_offset
                    )
                    d = jnp.abs(yb - module.apply(p, xb))
                    return jnp.where(mb[..., None] > 0, d, jnp.nan)

                def pass1(carry, batch):
                    lo, hi = carry
                    d = diff_batch(*batch)
                    return (
                        jnp.fmin(lo, jnp.nanmin(d, axis=0)),
                        jnp.fmax(hi, jnp.nanmax(d, axis=0)),
                    ), None

                f = x.shape[-1]
                (dmin, dmax), _ = jax.lax.scan(
                    pass1,
                    (jnp.full((f,), jnp.inf), jnp.full((f,), -jnp.inf)),
                    (idxs, Ms),
                )
                # mirror fit_minmax's (0,1) affine incl. the constant guard
                span = jnp.where(jnp.abs(dmax - dmin) < 1e-12, 1.0, dmax - dmin)
                es = ScalerParams(shift=dmin, scale=1.0 / span)

                if q >= 1.0:

                    def pass2(carry, batch):
                        ft, tt = carry
                        d = diff_batch(*batch)
                        scaled = scaler_transform(es, d)
                        total = jnp.sqrt(jnp.nansum(scaled**2, axis=-1))
                        # all-NaN (padded) rows: nansum=0 -> exclude via mask
                        total = jnp.where(
                            jnp.isnan(d).all(axis=-1), jnp.nan, total
                        )
                        return (
                            jnp.fmax(ft, jnp.nanmax(scaled, axis=0)),
                            jnp.fmax(tt, jnp.nanmax(total)),
                        ), None

                    (feat_thresh, total_thresh), _ = jax.lax.scan(
                        pass2,
                        (jnp.full((f,), -jnp.inf), jnp.float32(-jnp.inf)),
                        (idxs, Ms),
                    )
                    return es, feat_thresh, total_thresh

                # approximate quantile: histogram the scaled errors over
                # their statically known ranges ([0,1] per feature,
                # [0,sqrt(f)] for the norm) in one extra streamed pass
                B = _QUANTILE_BINS
                tmax = jnp.sqrt(jnp.float32(f))

                def pass2q(carry, batch):
                    hf, ht = carry
                    ib, mb = batch
                    d = diff_batch(ib, mb)
                    scaled = scaler_transform(es, d)
                    valid = mb > 0
                    # int32 counts: f32 scatter-adds saturate at 2^24
                    w = valid.astype(jnp.int32)
                    s = jnp.where(valid[:, None], scaled, 0.0)
                    sb = jnp.clip(jnp.floor(s * B), 0, B - 1).astype(jnp.int32)
                    fcols = jnp.broadcast_to(
                        jnp.arange(f, dtype=jnp.int32)[None, :], sb.shape
                    )
                    hf = hf.at[fcols, sb].add(
                        jnp.broadcast_to(w[:, None], sb.shape)
                    )
                    total = jnp.sqrt(jnp.sum(s * s, axis=-1))
                    tb = jnp.clip(
                        jnp.floor(total / tmax * B), 0, B - 1
                    ).astype(jnp.int32)
                    ht = ht.at[tb].add(w)
                    return (hf, ht), None

                (hf, ht), _ = jax.lax.scan(
                    pass2q,
                    (
                        jnp.zeros((f, B), jnp.int32),
                        jnp.zeros((B,), jnp.int32),
                    ),
                    (idxs, Ms),
                )
                n = jnp.sum(m)
                feat_thresh = jax.vmap(
                    lambda h: _hist_quantile(h, 1.0 / B, q, n)
                )(hf)
                total_thresh = _hist_quantile(ht, tmax / B, q, n)
                return es, feat_thresh, total_thresh

            return jax.vmap(one)(params, X, mask)

        return fit_error_scalers


def quantize_batch_count(n: int) -> int:
    """Round a per-member batch count UP to the {1, 2, 3, 4, 6, 8, 12, 16,
    24, 32, ...} ladder (powers of two and their 1.5x midpoints).

    Real fleets have ragged history lengths; bucketing on exact padded row
    counts would shatter 10k machines into O(distinct row counts) XLA
    programs with tiny vmap widths (SURVEY.md §7 hard part 1). The ladder
    caps the program count at O(log rows) per feature count while bounding
    padded-row waste at 33% — and the padding itself is a true no-op:
    ``epoch_fn`` packs real rows densely into the leading batches and skips
    fully-padded trailing batches without touching params or opt state.
    """
    if n <= 2:
        return max(1, n)
    p = 2
    while True:
        if n <= p + p // 2:
            return p + p // 2
        p *= 2
        if n <= p:
            return p


def quantize_member_count(n: int) -> int:
    """Round a gang's member count UP the {2^k, 1.25*2^k, 1.5*2^k,
    1.75*2^k} ladder (multiples of 2048 above 16384).

    The stacked programs bake the model-axis size M into their compiled
    shapes, so without quantization every distinct gang size recompiles
    the whole bucket program set — measured at ~34s per shape on one CPU
    core (2026-07-31, 100-member gang: 33.7s of a 42.4s build was XLA
    compilation). The quarter-octave ladder caps dummy-member waste at
    <25% worst-case (~11% mean) while collapsing arbitrary gang sizes
    onto O(log M) shapes; above 16384 a fixed 2048 step keeps waste
    <=12.5% and shrinking. Dummy slots replicate real members (same machinery as mesh
    padding) and their results are dropped by name, so quantization never
    changes any real member's training. Counts <=4 stay exact — dummies
    would outnumber real members for no compile win worth having.
    """
    if n <= 4:
        return n
    if n > 16384:
        return -(-n // 2048) * 2048
    p = 4
    while True:
        for m in (p, p + p // 4, p + p // 2, p + 3 * p // 4):
            if n <= m:
                return m
        p *= 2


# model families the fleet engine trains
_MODEL_TYPES = ("AutoEncoder", "LSTMAutoEncoder", "LSTMForecast", "ConvAutoEncoder")


def _target_offset_for(model_type: str) -> Optional[int]:
    """Target offset for a sequence family, None for the dense family.

    Read from the estimator class's ``_target_offset`` (models/models.py) —
    the same attribute the bank and anomaly paths consult — so the offset
    semantics have exactly one source of truth."""
    if model_type == "AutoEncoder":
        return None
    from gordo_components_tpu import models as _models

    return int(getattr(_models, model_type)._target_offset)


def _family_defaults(model_type: str) -> Tuple[str, int]:
    """(default kind, default lookback) read from the estimator class's
    own constructor signature — one source of truth with the single path."""
    import inspect

    from gordo_components_tpu import models as _models

    sig = inspect.signature(getattr(_models, model_type).__init__)
    kind = sig.parameters["kind"].default
    lb_param = sig.parameters.get("lookback_window")
    return kind, (int(lb_param.default) if lb_param is not None else 1)

_PROGRAM_CACHE: "OrderedDict[Any, _BucketPrograms]" = OrderedDict()
_PROGRAM_CACHE_MAX = 128
# monotone count of _BucketPrograms builds: lets tests (and operators
# debugging recompile storms) assert whether a fit hit the cache
_PROGRAM_BUILDS = 0
# the builder's gang scheduler (builder/fleet_build.py) trains small
# groups from worker threads; the shared LRU needs a lock (jit/tracing
# themselves are thread-safe)
_PROGRAM_LOCK = threading.Lock()


def _count_program_build() -> None:
    """One counted cache-miss program build (both the hashable and
    unhashable-kwargs paths must report into the SAME family)."""
    global _PROGRAM_BUILDS
    _PROGRAM_BUILDS += 1
    get_registry().counter(
        "gordo_fleet_program_builds_total",
        "Fleet bucket-program builds (cache misses; recompile storms "
        "show here)",
    ).inc()


def _bucket_programs(
    module, opt_name: str, lr: float, batch_size: int, seq=None,
    loss: str = "mse", kl_weight: float = 1.0, threshold_quantile: float = 1.0,
    mesh=None,
) -> _BucketPrograms:
    # the epoch program is resolved HERE (not inside _BucketPrograms) so
    # it participates in the cache key — flipping GORDO_SEQ_LAYOUT between
    # fits must never return a program compiled for the other layout. The
    # gang epoch understands exactly the LSTMStack/mse combination, the
    # fused step exactly what ops/dense_step.resolve lets through (a pure
    # function of module, loss, optimizer, shapes and the platform of the
    # devices the gang will sit on); everything else stays on the legacy
    # vmapped layout.
    layout = "legacy"
    if seq is not None and loss == "mse" and supports_time_major(module):
        layout = resolve_seq_layout()
    platform = (
        jax.default_backend() if mesh is None else mesh.devices.flat[0].platform
    )
    fused_step = dense_step.resolve(module, loss, opt_name, seq, batch_size, platform)
    if fused_step[0] is None:
        mesh = None  # only the fused step's program depends on it
    key = (
        module, opt_name, float(lr), int(batch_size), seq, loss,
        float(kl_weight), float(threshold_quantile), layout, fused_step, mesh,
    )

    def build() -> _BucketPrograms:
        return _BucketPrograms(
            module, opt_name, lr, batch_size, seq, loss, kl_weight,
            threshold_quantile, layout, fused_step, mesh,
        )

    with _PROGRAM_LOCK:
        try:
            prog = _PROGRAM_CACHE.get(key)
        except TypeError:  # unhashable factory kwargs: build uncached
            _count_program_build()
            return build()
        if prog is None:
            # LRU bound: a long-lived gang builder cycling many configs
            # keeps its hot programs warm instead of recompiling everything
            # from zero after a wholesale wipe
            while len(_PROGRAM_CACHE) >= _PROGRAM_CACHE_MAX:
                _PROGRAM_CACHE.popitem(last=False)
            _count_program_build()
            prog = _PROGRAM_CACHE[key] = build()
        else:
            _PROGRAM_CACHE.move_to_end(key)
    return prog


@dataclass
class FleetMemberModel:
    """One trained fleet member, unstacked: a self-contained scoring unit."""

    name: str
    kind: str
    factory_kwargs: Dict[str, Any]
    n_features: int
    params: Any  # numpy pytree
    scaler: ScalerParams  # numpy leaves; input scaling fitted on train data
    error_scaler: ScalerParams  # per-feature |err| scaling (anomaly contract)
    history: Dict[str, List[float]] = field(default_factory=dict)
    tags: Optional[List[str]] = None  # feature/tag names, when known
    feature_thresholds: Optional[np.ndarray] = None  # max scaled train error
    total_threshold: Optional[float] = None
    scaler_kind: str = "minmax"  # which fit produced ``scaler``
    model_type: str = "AutoEncoder"  # estimator family (registry namespace)
    lookback_window: int = 10  # sequence families only
    loss: str = "auto"  # the CONFIGURED loss (metadata/refit parity)
    kl_weight: float = 1.0
    threshold_quantile: float = 1.0
    require_thresholds: bool = False
    # threshold provenance: "exact" (max / jnp.nanquantile over the full
    # error set) or "histogram-8192" (sequence families with q < 1: the
    # streaming pass bounds the error by range/8192 instead of matching
    # the single build bit-for-bit) — surfaced via detector metadata
    threshold_method: str = "exact"

    def _module(self):
        factory = lookup_factory(self.model_type, self.kind)
        return factory(self.n_features, **self.factory_kwargs)

    def predict(self, X: np.ndarray) -> np.ndarray:
        """Model output in *input* space (scaling applied and inverted).
        Sequence members window X first; output row i is the model value
        for input row i + lookback_window - 1 (+1 for forecast)."""
        from gordo_components_tpu.ops.scaler import scaler_inverse_transform

        Xs = scaler_transform(ScalerParams(*self.scaler), jnp.asarray(X, jnp.float32))
        Xin = np.asarray(Xs)
        if self.model_type != "AutoEncoder":
            offset = _target_offset_for(self.model_type)
            lb = self.lookback_window
            if Xin.shape[0] < lb + offset:
                # same loud contract as SequenceBaseEstimator._window_inputs
                raise ValueError(
                    f"Need at least lookback_window+{offset}={lb + offset} "
                    f"rows, got {Xin.shape[0]}"
                )
            from gordo_components_tpu.native import sliding_windows_host

            Xin = sliding_windows_host(Xin, lb)
            if offset:
                Xin = Xin[:-offset]
        out = train_core.batched_apply(self._module(), self.params, Xin)
        return np.asarray(
            scaler_inverse_transform(ScalerParams(*self.scaler), jnp.asarray(out))
        )

    def to_estimator(self):
        """Convert to a fitted sklearn-style Pipeline(scaler, AutoEncoder)
        wrapped in a DiffBasedAnomalyDetector — artifact/server compatible.
        The scaler class mirrors what the trainer fitted (min-max or
        z-score) so artifact metadata round-trips honestly."""
        from sklearn.pipeline import Pipeline

        from gordo_components_tpu import models as _models
        from gordo_components_tpu.models import DiffBasedAnomalyDetector
        from gordo_components_tpu.models.transformers import (
            JaxMinMaxScaler,
            JaxStandardScaler,
        )

        est_cls = getattr(_models, self.model_type)
        # the CONFIGURED loss/kl_weight ride along so metadata and any
        # refit of the loaded artifact match a single build of the same
        # config (the fleet resolved "auto" the same way fit would)
        common = dict(loss=self.loss, kl_weight=self.kl_weight)
        if self.model_type == "AutoEncoder":
            est = est_cls(kind=self.kind, **common, **self.factory_kwargs)
        else:
            est = est_cls(
                kind=self.kind,
                lookback_window=self.lookback_window,
                **common,
                **self.factory_kwargs,
            )
        est.params_ = self.params
        est.n_features_ = self.n_features
        est.history = dict(self.history)

        scaler = (
            JaxStandardScaler() if self.scaler_kind == "standard"
            else JaxMinMaxScaler()
        )
        scaler.set_fitted(ScalerParams(*self.scaler), self.n_features)

        pipe = Pipeline([("scale", scaler), ("model", est)])
        det = DiffBasedAnomalyDetector(
            base_estimator=pipe,
            threshold_quantile=self.threshold_quantile,
            require_thresholds=self.require_thresholds,
        )
        det.error_scaler_ = ScalerParams(*jax.tree.map(np.asarray, self.error_scaler))
        det.tags_ = list(self.tags) if self.tags else [
            f"feature-{i}" for i in range(self.n_features)
        ]
        if self.feature_thresholds is not None:
            det.feature_thresholds_ = np.asarray(self.feature_thresholds)
            det.total_threshold_ = float(self.total_threshold)
            det.threshold_method_ = self.threshold_method
        return det


# the engine's base learning rate (BaseEstimator's default too) — exported
# so fleet_build can normalize "machine omitted learning_rate" to the same
# value the trainer would use, instead of inheriting another machine's
DEFAULT_LEARNING_RATE = 1e-3


class FleetTrainer:
    """Train one homogeneous architecture across many machines' datasets.

    Members may have heterogeneous feature counts and row counts; they are
    bucketed by ``n_features`` and padded to shared shapes per bucket.
    """

    @capture_args
    def __init__(
        self,
        kind: Optional[str] = None,  # default resolves per model family
        epochs: int = 10,
        batch_size: int = 100,  # matches BaseEstimator's default
        learning_rate: float = DEFAULT_LEARNING_RATE,
        optimizer: str = "adam",
        early_stopping_patience: Optional[int] = None,
        early_stopping_min_delta: float = 0.0,
        validation_split: float = 0.0,
        seed: int = 0,
        mesh=None,
        compute_dtype: str = "float32",
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1,
        epoch_callback=None,
        quantize_rows: bool = True,
        quantize_members: bool = True,
        input_scaler: str = "minmax",
        model_type: str = "AutoEncoder",
        lookback_window: Optional[int] = None,  # default per model family
        loss: str = "auto",
        kl_weight: float = 1.0,
        threshold_quantile: float = 1.0,
        require_thresholds: bool = False,
        **factory_kwargs,
    ):
        # sequence fleets: same many-model engine, windows gathered in-graph
        # (train_core.make_seq_train_fns) — item i trains window [i, i+L)
        # against row i+L-1(+1 for forecast), exactly the single-path
        # semantics of SequenceBaseEstimator._make_xy
        if model_type not in _MODEL_TYPES:
            raise ValueError(
                f"model_type must be one of {sorted(_MODEL_TYPES)}, "
                f"got {model_type!r}"
            )
        if "host_sync_every" in factory_kwargs:
            # the factories ignore keywords they do not know, so a caller
            # of the removed option would otherwise train on unaware
            raise TypeError(
                "FleetTrainer has no option 'host_sync_every': every epoch "
                "is one dispatch; drop the argument"
            )
        self.model_type = model_type
        default_kind, default_lb = _family_defaults(model_type)
        self.lookback_window = int(
            default_lb if lookback_window is None else lookback_window
        )
        # per-family defaults come from the estimator class's own ctor
        # signature; an EXPLICIT kind always passes through (a wrong-family
        # kind then fails loudly in lookup_factory, exactly like the
        # single-build path)
        self.kind = default_kind if kind is None else kind
        # "auto" resolves per module exactly like BaseEstimator._resolved_loss
        # (vae for modules exposing elbo_terms) — the fleet must never train
        # a variational kind with plain MSE
        self.loss = loss
        self.kl_weight = float(kl_weight)
        # detector knobs, honored so quantile-threshold configs keep fleet
        # speed. Dense-family quantiles are exact (jnp.nanquantile over the
        # full error block); sequence-family quantiles stream over window
        # chunks via fixed-bin histograms, approximate to within one bin
        # width of the scaled-error range (_make_seq_error_scalers).
        self.threshold_quantile = float(threshold_quantile)
        if not 0.0 <= self.threshold_quantile <= 1.0:
            # fail fast with the same contract np.quantile enforces in the
            # single-build detector — never after a full gang training run
            raise ValueError(
                f"threshold_quantile must be in [0, 1], got {threshold_quantile}"
            )
        self.require_thresholds = bool(require_thresholds)
        self._bucket_layout = "legacy"  # layout of the last-built bucket
        self._bucket_device = None  # device block of the last-built bucket
        self._bucket_staging = None  # stage_gang's account of the last-built bucket
        self.epochs = int(epochs)
        self.batch_size = int(batch_size)
        self.learning_rate = float(learning_rate)
        self.optimizer = optimizer
        self.early_stopping_patience = early_stopping_patience
        self.early_stopping_min_delta = float(early_stopping_min_delta)
        # per-member holdout: the LAST int(rows * split) rows of each
        # member are excluded from training and scored after every epoch;
        # when early stopping is on, val loss drives the ES mask (parity
        # with BaseEstimator.fit's validation_split semantics)
        self.validation_split = float(validation_split)
        self.seed = int(seed)
        self.mesh = mesh
        self.compute_dtype = compute_dtype
        # per-member input scaling fitted on device: "minmax" (the
        # reference's default pipeline) or "standard" (z-score)
        if input_scaler not in ("minmax", "standard"):
            raise ValueError(f"input_scaler must be minmax|standard, got {input_scaler!r}")
        self.input_scaler = input_scaler
        # preemption recovery: when set, stacked train state is checkpointed
        # every ``checkpoint_every`` epochs and fit() resumes a matching
        # interrupted run (parallel/checkpoint.py)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = max(1, int(checkpoint_every))
        # epoch_callback(info_dict) after every epoch: progress/metrics hook
        self.epoch_callback = epoch_callback
        # bucket members on the batch-count ladder (see
        # quantize_batch_count) instead of exact padded row counts
        self.quantize_rows = bool(quantize_rows)
        self.quantize_members = bool(quantize_members)
        self.factory_kwargs = factory_kwargs
        self.last_stats: Dict[str, Any] = {}
        # (trace, open ``fit:<bucket>`` span) of the bucket in training:
        # its stages nest under that span (observability/tracing)
        self._trace_span: Tuple[Any, Any] = (None, None)

    def _stage(self, name: str, **attributes: Any) -> stage:
        """One stage of the bucket in training: a span under its
        ``fit:<bucket>`` and a ``gordo:<name>`` profiler annotation. No
        fence is added anywhere: a span is the HOST's time in the stage
        (for ``epoch``, up to the blocking read of the losses), and the
        annotation puts it beside the device ops it launched."""
        trace, fit_span = self._trace_span
        return stage(name, trace, parent=fit_span, **attributes)

    # ------------------------------------------------------------------ #

    def fit(
        self,
        members: Dict[str, np.ndarray],
        member_hparams: Optional[Dict[str, Dict[str, Any]]] = None,
        initial_params: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, FleetMemberModel]:
        """``members``: name -> (n_rows_i, n_features_i) float array.
        Returns name -> FleetMemberModel. One compiled program per
        (n_features, padded_items) bucket, where items are the training
        units (rows for the dense family, window starts for sequences).

        ``member_hparams``: optional name -> {"learning_rate": float,
        "early_stopping_patience": int} overrides. These are STACKED
        (M,) vectors inside the bucket programs (LR rides the injected
        opt state, patience the ES carry), so members differing only in
        these knobs train in ONE program instead of separate gangs
        (SURVEY.md §7 hard part 4: per-model LR). A patience override
        requires ES to be enabled on the trainer — silently enabling it
        for one member would change the gang's program shape.

        ``initial_params``: optional name -> params pytree WARM START —
        the member's row of the stacked init is overwritten with the
        given leaves (optimizer state stays fresh), so a short
        ``epochs`` run fine-tunes serving weights on fresh data instead
        of training from scratch (the streaming plane's incremental
        refit). Trees must match the gang's architecture exactly; a
        structure or shape mismatch fails fast naming the member.

        A fit is a trace (observability/tracing.py): it records into the
        caller's (``build_fleet`` opens one per build), else into a
        ``fleet_fit`` trace of its own on the process tracer, always
        retained — a fit is rare and long; head sampling is for request
        volume. Every bucket is a ``fit:<bucket>`` span over its stages
        (:meth:`_stage`), ``checkpoint`` saves and the compiles JAX
        reports inside it.
        """
        trace = current_trace()
        own = None
        if trace is None:
            # None again with tracing off: the stages still annotate
            trace = own = get_tracer().start_trace("fleet_fit", force=True)
        try:
            out = self._fit(trace, members, member_hparams, initial_params)
        except BaseException:
            if own is not None:
                own.finish(error=True)
            raise
        if own is not None:
            own.finish(members=len(members))
        return out

    def _fit(
        self,
        trace,
        members: Dict[str, np.ndarray],
        member_hparams: Optional[Dict[str, Dict[str, Any]]],
        initial_params: Optional[Dict[str, Any]],
    ) -> Dict[str, FleetMemberModel]:
        t0 = time.time()
        # fleet-build progress, published to the process metrics registry
        # (observability/): a gang builder has no HTTP surface, but tools
        # in its process snapshot the registry — and the gauges cost one
        # set() per bucket/epoch, nothing per step
        reg = get_registry()
        self._g_members_total = reg.gauge(
            "gordo_fleet_members_total", "Members in the current fleet fit"
        ).labels()
        self._g_members_trained = reg.gauge(
            "gordo_fleet_members_trained",
            "Members whose bucket finished training in the current fit",
        ).labels()
        self._g_members_active = reg.gauge(
            "gordo_fleet_members_active",
            "Members still training (not early-stopped) in the current bucket",
        ).labels()
        self._member_hparams = {}
        for name, hp in (member_hparams or {}).items():
            if name not in members:
                raise ValueError(f"member_hparams for unknown member {name!r}")
            unknown = set(hp) - {"learning_rate", "early_stopping_patience"}
            if unknown:
                raise ValueError(
                    f"member_hparams[{name!r}]: unsupported keys {sorted(unknown)}"
                )
            if (
                hp.get("early_stopping_patience") is not None
                and self.early_stopping_patience is None
            ):
                raise ValueError(
                    f"member_hparams[{name!r}] sets early_stopping_patience "
                    "but the trainer has ES disabled"
                )
            self._member_hparams[name] = dict(hp)
        for name in initial_params or {}:
            if name not in members:
                raise ValueError(f"initial_params for unknown member {name!r}")
        self._initial_params = dict(initial_params or {})
        buckets: Dict[Tuple[int, int], List[str]] = {}
        # accept DataFrames: keep tag names for the anomaly contract
        self._tags_map = {
            k: [str(c) for c in v.columns] if hasattr(v, "columns") else None
            for k, v in members.items()
        }
        arrays = {
            k: np.asarray(v.values if hasattr(v, "values") else v, dtype=np.float32)
            for k, v in members.items()
        }
        # items = training units: rows for the dense family, window starts
        # for sequence families (rows - lookback + 1 - offset)
        t_offset = _target_offset_for(self.model_type)
        warmup = 0 if t_offset is None else self.lookback_window - 1 + t_offset
        for name, X in arrays.items():
            if X.ndim != 2 or X.shape[0] < 1:
                raise ValueError(f"Member {name!r}: need (rows, features), got {X.shape}")
            n_items = X.shape[0] - warmup
            if n_items < 1:
                raise ValueError(
                    f"Member {name!r}: need at least lookback_window"
                    f"+offset={warmup + 1} rows, got {X.shape[0]}"
                )
            n_batches = -(-n_items // self.batch_size)
            if self.quantize_rows:
                n_batches = quantize_batch_count(n_batches)
            key = (X.shape[1], n_batches * self.batch_size)
            buckets.setdefault(key, []).append(name)

        # ---- member-width cap (parallel/autotune.py): GORDO_FLEET_WIDTH
        # splits oversized gangs into near-equal chunks no wider than the
        # cap. Chunks share the bucket's compiled program whenever their
        # quantized member counts agree (quantize_member_count makes
        # near-equal chunk sizes land on the same ladder rung). NOTE: the
        # split changes each member's position in its gang, which reseeds
        # its init rng — capped runs train valid models, not bitwise the
        # uncapped ones.
        width_cap = resolve_fleet_width(f"{self.model_type}:{self.kind}")
        work: List[Tuple[Tuple[int, int], List[str]]] = []
        for key, names in sorted(buckets.items()):
            if width_cap and len(names) > width_cap:
                n_chunks = -(-len(names) // width_cap)
                size = -(-len(names) // n_chunks)
                for i in range(0, len(names), size):
                    work.append((key, names[i : i + size]))
            else:
                work.append((key, names))

        out: Dict[str, FleetMemberModel] = {}
        bucket_stats = []
        self._g_members_total.set(len(members))
        self._g_members_trained.set(0)
        for (n_features, padded_rows), names in work:
            tb = time.time()
            blabel = f"f{n_features}x{padded_rows}"
            self._active_ckpt = None
            fit_span = None
            if trace is not None:
                fit_span = trace.start_span(
                    f"fit:{blabel}", bucket=blabel, members=len(names)
                )
            self._trace_span = (trace, fit_span)
            try:
                # use_trace: what JAX traces, compiles or loads from its
                # cache inside this bucket lands under its fit span
                with use_trace(trace, fit_span):
                    res, epoch_seconds, padded_m = self._fit_bucket(
                        n_features, padded_rows, names, arrays
                    )
            except BaseException:
                # commit (best-effort) and release the async checkpoint
                # writer: the pending save is complete training state, so
                # committing improves the resume point, and closing stops
                # an orphaned background write from racing a retry
                ckpt = self._active_ckpt
                if ckpt is not None:
                    try:
                        ckpt.flush()
                    except Exception:
                        logger.warning("checkpoint flush failed", exc_info=True)
                    finally:
                        ckpt.close()
                if fit_span is not None:
                    fit_span.close(error=True)
                raise
            finally:
                self._active_ckpt = None
                self._trace_span = (None, None)
            out.update(res)
            self._g_members_trained.set(len(out))
            # per-bucket compile visibility, measured: the seconds JAX
            # itself reported for tracing, lowering and compiling (or
            # loading from the persistent cache) inside this bucket. The
            # spans say which call recompiled. 0 with tracing off.
            compile_s = 0.0
            if fit_span is not None:
                fit_span.attributes["epochs"] = len(epoch_seconds)
                fit_span.close()
                compile_s = covered_seconds(
                    s
                    for s in trace.children(fit_span)
                    if s.name in ("trace_lower", "backend_compile")
                )
            reg.counter(
                "gordo_fleet_bucket_builds_total",
                "Bucket training runs", ("bucket",),
            ).labels(blabel).inc()
            reg.counter(
                "gordo_fleet_bucket_epochs_total",
                "Epochs trained per bucket", ("bucket",),
            ).labels(blabel).inc(len(epoch_seconds))
            reg.gauge(
                "gordo_fleet_bucket_compile_seconds",
                "Seconds JAX reported tracing, lowering and compiling in the "
                "bucket's last fit",
                ("bucket",),
            ).labels(blabel).set(round(compile_s, 3))
            bucket_stats.append(
                {
                    "n_features": n_features,
                    # the bucket key counts ITEMS (training units: rows for
                    # the dense family, window starts for sequences);
                    # padded_rows is the actual padded row block (items +
                    # warmup), so the two differ for sequence fleets
                    "padded_items": padded_rows,
                    "padded_rows": padded_rows + warmup,
                    "n_members": len(names),
                    # compiled program shape: real members + quantization/
                    # mesh dummies — equal padded_members across gangs
                    # means a shared XLA program
                    "padded_members": padded_m,
                    "seconds": time.time() - tb,
                    # per-epoch host seconds (the ``epoch`` spans): a
                    # process's first epochs include tracing and the
                    # compile or cache load, steady-state is the rest
                    "epoch_seconds": epoch_seconds,
                    # which epoch program the bucket used ("time_major" =
                    # LSTM gang scan, members innermost; "fused_step" =
                    # dense gang whose step is one Pallas program;
                    # "legacy" = vmap(epoch)) — resolved per program,
                    # recorded per bucket
                    "layout": self._bucket_layout,
                    # the devices the bucket's stacked state sat on
                    "device": self._bucket_device,
                    # how the stacked block crossed to them: pieces (1 =
                    # one whole-block copy) and the block's bytes
                    "staging": self._bucket_staging,
                }
            )
        self.last_stats = {
            "total_seconds": time.time() - t0,
            "n_members": len(members),
            "buckets": bucket_stats,
            "width_cap": width_cap,
        }
        return out

    # ------------------------------------------------------------------ #

    def _fit_bucket(
        self,
        n_features: int,
        padded_items: int,
        names: List[str],
        arrays: Dict[str, np.ndarray],
    ) -> Tuple[Dict[str, FleetMemberModel], List[float], int]:
        mesh = self.mesh if self.mesh is not None else fleet_mesh()
        M_real = len(names)
        M = pad_count_to_mesh(
            quantize_member_count(M_real) if self.quantize_members else M_real,
            mesh,
        )
        bs = self.batch_size
        # sequence families: an "item" is a window start; the raw row block
        # carries warmup extra rows beyond the last item
        t_offset = _target_offset_for(self.model_type)
        seq = None if t_offset is None else (self.lookback_window, t_offset)
        warmup = 0 if seq is None else self.lookback_window - 1 + t_offset
        padded_rows = padded_items + warmup

        # ---- the gang's rows to the device (stage_gang): stacked and padded
        # on the host (multithreaded C++ when the native lib is available)
        # and put whole, or, a large block of wide members, put as they lie
        # and padded by the device; dummies replicate real members for mesh
        # padding either way ----
        sharding = shard_model_axis(mesh)
        fit_span = self._trace_span[1]
        with self._stage("stack_pad"):
            Xd, masks, self._bucket_staging = stage_gang(
                [arrays[n] for n in names], M, padded_rows, n_features, sharding
            )
            if fit_span is not None:
                fit_span.attributes["staging"] = self._bucket_staging

        with self._stage("to_device"):
            maskd = jax.device_put(jnp.asarray(masks), sharding)

            # ---- per-member train/validation masks in ITEM space (items ==
            # rows for the dense family, window starts for sequences): the LAST
            # int(items*split) real items of each member are holdout — exactly
            # BaseEstimator.fit's split over the (windowed) training units.
            # Input/error scalers keep the FULL row mask (the single-model
            # pipeline's scaler also fits before the estimator's internal
            # split). Members whose split floors to 0 val items monitor train
            # loss, like a single build with n_val == 0. ----
            use_val = self.validation_split > 0.0
            # mesh-padding dummy slots replicate real members CYCLICALLY
            # (fleet_stack_pad uses i % n), so their masks must use the row
            # count of the member whose data they actually hold
            n_rows = np.array(
                [arrays[names[i % M_real]].shape[0] for i in range(M)]
            )
            n_items = n_rows - warmup
            item_idx = np.arange(padded_items)[None, :]
            item_mask_np = (item_idx < n_items[:, None]).astype(np.float32)
            item_maskd = jax.device_put(jnp.asarray(item_mask_np), sharding)
            n_val = (n_items * self.validation_split).astype(np.int64)
            n_train = n_items - n_val
            has_val = n_val > 0
            if use_val:
                train_mask = (item_idx < n_train[:, None]).astype(np.float32)
                vmask_np = (
                    (item_idx >= n_train[:, None]) & (item_idx < n_items[:, None])
                ).astype(np.float32)
                train_maskd = jax.device_put(jnp.asarray(train_mask), sharding)
                val_maskd = jax.device_put(jnp.asarray(vmask_np), sharding)
            else:
                train_maskd = item_maskd
                val_maskd = jax.device_put(
                    jnp.zeros((M, padded_items), jnp.float32), sharding
                )

        with self._stage("scaler_fit"):
            # ---- per-member scalers, fitted on device (masked rows excluded
            # by writing NaNs, which the nan-aware fit ignores) ----
            scalers = _fit_scalers(Xd, maskd, self.input_scaler)
            Xd = _transform_all(scalers, Xd)
            # padded rows were NaN-protected during fit; re-zero them post-scale
            Xd = jnp.where(maskd[..., None] > 0, Xd, 0.0)

        with self._stage("init_state"):
            # ---- build module + stacked train state (programs are cached
            # process-wide per (module, optimizer, batch size, seq)) ----
            factory = lookup_factory(self.model_type, self.kind)
            module = factory(
                n_features, compute_dtype=self.compute_dtype, **self.factory_kwargs
            )
            loss = self.loss
            if loss == "auto":  # parity with BaseEstimator._resolved_loss
                loss = "vae" if hasattr(module, "elbo_terms") else "mse"
            progs = _bucket_programs(
                module, self.optimizer, self.learning_rate,
                min(bs, padded_items), seq, loss, self.kl_weight,
                self.threshold_quantile, mesh=mesh,
            )
            self._bucket_layout = progs.layout
            if fit_span is not None:
                fit_span.attributes["layout"] = progs.layout
                if progs.fused_step_refused is not None:
                    fit_span.attributes["fused_step_refused"] = progs.fused_step_refused
            self._bucket_device = device_block(Xd)
            init_stacked = progs.init_stacked
            run_epoch = progs.run_epoch

            rngs = jax.random.split(jax.random.PRNGKey(self.seed), M)
            # shape-inference sample: one row (dense) or one window (sequence)
            sample = Xd[:, 0, :] if seq is None else Xd[:, : self.lookback_window, :]
            states = init_stacked(rngs, sample)

            # ---- warm start (incremental refit): overwrite the stacked init's
            # member rows with the provided serving weights. Mesh-padding
            # dummies replicate their source member's warm leaves (i % M_real),
            # like the data; the optimizer state stays freshly initialized ----
            warm = getattr(self, "_initial_params", None) or {}
            if any(names[i % M_real] in warm for i in range(M)):
                host = jax.tree.map(np.array, states.params)
                treedef = jax.tree.structure(host)
                leaves = jax.tree.leaves(host)
                warm_leaves: Dict[str, List[np.ndarray]] = {}
                for name in set(names) & set(warm):
                    tree = jax.tree.map(np.asarray, warm[name])
                    if jax.tree.structure(tree) != treedef:
                        raise ValueError(
                            f"initial_params[{name!r}]: tree structure does not "
                            "match this gang's architecture"
                        )
                    wl = jax.tree.leaves(tree)
                    for li, leaf in enumerate(leaves):
                        if wl[li].shape != leaf.shape[1:]:
                            raise ValueError(
                                f"initial_params[{name!r}]: leaf {li} shape "
                                f"{wl[li].shape} != expected {leaf.shape[1:]}"
                            )
                    warm_leaves[name] = wl
                for i in range(M):
                    wl = warm_leaves.get(names[i % M_real])
                    if wl is None:
                        continue
                    for li, leaf in enumerate(leaves):
                        leaf[i] = wl[li]
                states = states._replace(
                    params=jax.tree.unflatten(
                        treedef,
                        [
                            jax.device_put(jnp.asarray(leaf), sharding)
                            for leaf in leaves
                        ],
                    )
                )

            # ---- per-member hyperparameter vectors (mesh-padding dummies
            # replicate their source member's values, like the data) ----
            hparams = getattr(self, "_member_hparams", {})

            def _mvec(key, base, dtype):
                return np.array(
                    [
                        hparams.get(names[i % M_real], {}).get(key, base)
                        for i in range(M)
                    ],
                    dtype=dtype,
                )

            lr_vec = _mvec("learning_rate", self.learning_rate, np.float32)
            if hparams:
                # the injected opt state carries learning_rate as a stacked
                # (M,) leaf (vmapped init broadcasts the base scalar):
                # overwrite it with the per-member vector — the ONLY surgery
                # per-member LR needs, no extra program or gang split
                states = _set_stacked_lr(states, lr_vec)
            state_treedef = jax.tree.structure(states)

            # ---- epoch loop: device does the work; host only sees (M,) losses
            # and drives per-model early stopping ----
            active = np.ones((M,), dtype=np.float32)
            best = np.full((M,), np.inf)
            es_enabled = self.early_stopping_patience is not None
            # patience RESET values, per member (scalar broadcast when no
            # overrides): both the host ES loop and the chunked device ES use
            # this vector, so per-member patience is free in either path
            p0_vec = (
                _mvec("early_stopping_patience", self.early_stopping_patience, np.int64)
                if es_enabled
                else np.full((M,), -1, dtype=np.int64)
            )
            patience = p0_vec.copy()
            histories: List[List[float]] = [[] for _ in range(M)]
            histories_val: List[List[float]] = [[] for _ in range(M)]

            # best-params restore, matching BaseEstimator.fit: each member ends
            # on the params of its best epoch, not the epoch it stopped at
            best_params = None

            # ---- preemption recovery: resume a matching interrupted run ----
            ckpt = None
            start_epoch = 0
            if self.checkpoint_dir:
                from gordo_components_tpu.parallel.checkpoint import (
                    FleetBucketCheckpoint,
                    bucket_checkpoint_key,
                )

                key = bucket_checkpoint_key(
                    [
                        self.model_type,
                        # lookback only shapes sequence programs; keying it for
                        # the dense family would invalidate resumable dense
                        # checkpoints whenever its (unused) default shifts
                        self.lookback_window if seq is not None else None,
                        self.kind,
                        sorted(self.factory_kwargs.items()),
                        self.compute_dtype,
                        self.input_scaler,
                        loss,
                        self.kl_weight,
                        n_features,
                        padded_rows,
                        list(names),
                        self.epochs,
                        self.batch_size,
                        self.learning_rate,
                        # per-member overrides change training: key them so a
                        # resume can't mix runs with different LR/patience
                        sorted(
                            (n, sorted(hp.items()))
                            for n, hp in hparams.items()
                            if n in names
                        ),
                        # warm-started members change the trajectory: a resume
                        # must not mix a warm run with a cold one (content is
                        # not keyed — refits don't checkpoint in practice, and
                        # the member names + data hash bound the blast radius)
                        sorted(n for n in warm if n in names),
                        self.optimizer,
                        self.early_stopping_patience,
                        self.early_stopping_min_delta,
                        self.validation_split,
                        self.seed,
                        int(mesh.shape[MODEL_AXIS]),
                        # the place of an option that is gone, at the one
                        # value the product gave it: checkpoints written
                        # before its removal stay resumable
                        1,
                    ],
                    # content hash per member (streamed, pre-padding): same-shaped
                    # but different data must not resume
                    data=(arrays[n] for n in names),
                )
                # async: the orbax write overlaps the next epochs; the commit
                # marker lands at the next save (or the post-loop flush). A
                # preemption can lose at most one extra checkpoint interval.
                ckpt = FleetBucketCheckpoint(self.checkpoint_dir, key, use_async=True)
                # fit() flushes/closes this on any exception so an orphaned
                # async writer can't race a same-process retry of the bucket
                self._active_ckpt = ckpt
                resumed = ckpt.restore()
                if resumed is not None:
                    try:
                        restore_leaves = lambda d: [
                            jax.device_put(jnp.asarray(d[str(i)]), sharding)
                            for i in range(len(d))
                        ]
                        states = jax.tree.unflatten(
                            state_treedef, restore_leaves(resumed["state"]["state"])
                        )
                        if "best" in resumed["state"]:
                            best_params = jax.tree.unflatten(
                                jax.tree.structure(states.params),
                                restore_leaves(resumed["state"]["best"]),
                            )
                        active = np.asarray(resumed["active"], np.float32)
                        best = np.asarray(resumed["best"], np.float64)
                        patience = np.asarray(resumed["patience"], np.int64)
                        histories = [list(h) for h in resumed["histories"]]
                        histories_val = [
                            list(h) for h in resumed.get("histories_val", [[]] * M)
                        ]
                        start_epoch = int(resumed["epoch"]) + 1
                        if es_enabled and not active.any():
                            # every member already early-stopped when preempted
                            # (during the post-loop scaler pass): skip the loop
                            # entirely instead of running one no-op epoch
                            start_epoch = self.epochs
                    except Exception:
                        # e.g. a library upgrade changed the opt-state pytree
                        # structure between preemption and restart: start fresh
                        # rather than crash every restarted gang
                        logger.warning(
                            "Fleet checkpoint structure mismatch; training from scratch",
                            exc_info=True,
                        )
                        states = init_stacked(rngs, sample)
                        if hparams:
                            # from-scratch restart must re-apply the same
                            # per-member LR surgery the initial path did
                            states = _set_stacked_lr(states, lr_vec)
                        best_params = None
                        active = np.ones((M,), dtype=np.float32)
                        best = np.full((M,), np.inf)
                        patience = p0_vec.copy()
                        histories = [[] for _ in range(M)]
                        histories_val = [[] for _ in range(M)]
                        start_epoch = 0

        def save_checkpoint(epoch):
            with self._stage("checkpoint", epoch=int(epoch)) as saving:
                try:
                    tosave = {"state": dict(
                        (str(i), leaf)
                        for i, leaf in enumerate(jax.tree.leaves(states))
                    )}
                    if best_params is not None:
                        tosave["best"] = dict(
                            (str(i), leaf)
                            for i, leaf in enumerate(jax.tree.leaves(best_params))
                        )
                    # start EVERY leaf's device->host copy before the first
                    # blocking np.asarray: the copies overlap instead of
                    # paying one full round-trip per leaf (checkpoint.py
                    # then materializes them)
                    for leaf in jax.tree.leaves(tosave):
                        if hasattr(leaf, "copy_to_host_async"):
                            leaf.copy_to_host_async()
                    ckpt.save(
                        epoch,
                        tosave,
                        {
                            "active": active.tolist(),
                            "best": best.tolist(),
                            "patience": patience.tolist(),
                            "histories": histories,
                            "histories_val": histories_val,
                        },
                    )
                except Exception:
                    # best-effort by contract: a full checkpoint volume (or
                    # an injected checkpoint.write fault) costs
                    # resumability, not the hours of training it was
                    # protecting
                    logger.warning(
                        "fleet checkpoint save failed at epoch %d; training "
                        "continues without it", epoch, exc_info=True,
                    )
                    saving.error = True

        epoch_times: List[float] = []
        for epoch in range(start_epoch, self.epochs):
            with self._stage("epoch", epoch=epoch) as dispatched:
                active_pre = active
                states, losses = run_epoch(
                    states, Xd, train_maskd, jnp.asarray(active)
                )
                losses = np.asarray(losses)
                if use_val:
                    vals = np.asarray(
                        progs.eval_stacked(states.params, Xd, val_maskd)
                    )
                    vals = np.where(active_pre > 0, vals, np.nan)
                    monitored = np.where(has_val, vals, losses)
                else:
                    vals = np.full_like(losses, np.nan)
                    monitored = losses
            epoch_times.append(dispatched.seconds)
            with self._stage("epoch_host", epoch=epoch):
                if es_enabled:
                    improved = (
                        monitored < best - self.early_stopping_min_delta
                    ) & (active > 0)
                    best = np.where(improved, monitored, best)
                    if best_params is None:
                        best_params = jax.tree.map(jnp.copy, states.params)
                    else:
                        best_params = _merge_best(
                            best_params, states.params,
                            jnp.asarray(improved, jnp.float32),
                        )
                    patience = np.where(
                        improved, p0_vec, patience - (active > 0)
                    )
                    # patience=0 parity with BaseEstimator.fit: a model
                    # stops only after a NON-improving epoch exhausts
                    # patience — an epoch that just improved (patience
                    # reset) keeps going.
                    active = np.where(
                        (patience <= 0) & ~improved, 0.0, active
                    ).astype(np.float32)
                # histories: a model that was active records its loss even
                # if that loss is NaN — divergence must stay visible
                for i in range(M):
                    if active_pre[i] > 0:
                        histories[i].append(float(losses[i]))
                        if use_val and has_val[i]:
                            histories_val[i].append(float(vals[i]))
                n_active = int((active > 0).sum())
                self._g_members_active.set(n_active)
                if self.epoch_callback is not None:
                    self.epoch_callback(
                        {
                            "n_features": n_features,
                            "padded_rows": padded_rows,
                            "epoch": epoch,
                            "losses": losses[: len(names)],
                            "n_active": n_active,
                        }
                    )
                if (
                    ckpt is not None
                    and (epoch + 1) % self.checkpoint_every == 0
                    and epoch + 1 < self.epochs
                ):
                    save_checkpoint(epoch)
            if es_enabled and not active.any():
                logger.info(
                    "All %d models early-stopped at epoch %d", M, epoch + 1
                )
                break

        if ckpt is not None:
            # commit the in-flight async save: a preemption during the
            # error-scaler pass / unstacking below can then resume from
            # the last epoch checkpoint (the write already overlapped the
            # epochs, so this wait is near-free)
            with self._stage("checkpoint", flush=True):
                ckpt.flush()

        # ---- error scalers + thresholds for the anomaly contract: one
        # vmapped pass (parity with DiffBasedAnomalyDetector.fit, which
        # records max scaled training error as the default threshold);
        # item mask == row mask for the dense family ----
        with self._stage("error_scalers"):
            final_params = best_params if best_params is not None else states.params
            err_scalers, feat_thresh, total_thresh = progs.run_error_scalers(
                final_params, Xd, item_maskd
            )
            feat_thresh = np.asarray(feat_thresh)
            total_thresh = np.asarray(total_thresh)

        # ---- unstack to host (pipeline every leaf's device->host copy
        # before the first blocking materialization — per-leaf fetches pay
        # a full round-trip each otherwise) ----
        with self._stage("unstack"):
            device_trees = (final_params, scalers, err_scalers)
            for leaf in jax.tree.leaves(device_trees):
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()
            params_np, scalers_np, err_np = jax.tree.map(np.asarray, device_trees)

        out = {}
        with self._stage("members"):
            for i, name in enumerate(names):  # drop dummy pads (i >= M_real)
                history = {"loss": histories[i]}
                if use_val and has_val[i]:
                    history["val_loss"] = histories_val[i]
                out[name] = FleetMemberModel(
                    name=name,
                    kind=self.kind,
                    factory_kwargs=dict(
                        self.factory_kwargs, compute_dtype=self.compute_dtype
                    ),
                    n_features=n_features,
                    params=jax.tree.map(lambda a: np.asarray(a[i]), params_np),
                    scaler=ScalerParams(
                        shift=scalers_np.shift[i], scale=scalers_np.scale[i]
                    ),
                    error_scaler=ScalerParams(
                        shift=err_np.shift[i], scale=err_np.scale[i]
                    ),
                    history=history,
                    tags=self._tags_map.get(name),
                    feature_thresholds=feat_thresh[i],
                    total_threshold=float(total_thresh[i]),
                    scaler_kind=self.input_scaler,
                    model_type=self.model_type,
                    lookback_window=self.lookback_window,
                    loss=self.loss,
                    kl_weight=self.kl_weight,
                    threshold_quantile=self.threshold_quantile,
                    require_thresholds=self.require_thresholds,
                    threshold_method=progs.threshold_method,
                )
            # clear only once results are unstacked on host: a preemption
            # during the error-scaler pass / unstacking above can still
            # resume from the last epoch checkpoint instead of retraining
            # from scratch
            if ckpt is not None:
                ckpt.clear()
        return out, epoch_times, M
