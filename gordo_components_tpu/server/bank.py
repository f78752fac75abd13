"""HBM-resident model bank: many models, one compiled program per bucket.

The reference serves one model per Flask process (gordo_components/server,
unverified; SURVEY.md §2 "server") — scoring N machines means N processes
each holding one Keras graph. The TPU-native inversion (BASELINE.json
config 5, SURVEY.md §7 stage 5): every *bankable* model in the collection
is stacked into one params pytree per (kind, n_features, architecture)
bucket, resident in device HBM, member-major (``_stored_shape``). A request
for any model becomes a slice of that member out of the stack inside a
single jit'd scoring program (``_select_members``), so

- loading 1,000 models costs one ``device_put`` per bucket, not 1,000
  processes;
- concurrent requests for *different* models coalesce into one batched XLA
  call (see :class:`BatchingEngine`) — the MXU sees (B, T, F) matmuls
  instead of B separate (T, F) launches;
- request shapes are bucketed to powers of two so the number of compiled
  programs stays O(log(max_rows) * log(max_batch)) regardless of traffic.

Bankable = DiffBasedAnomalyDetector over any zoo estimator (feedforward,
LSTM, forecast, conv — sequence windowing runs in-graph per bucket with
its static lookback) with any chain of affine scalers in front. Bespoke
pipelines (non-affine preprocessing, custom estimator classes) fall back
to the per-model scoring path in views.py — same response schema either
way, via the shared ``assemble_anomaly_frame`` — and the fallback set is
surfaced per model through ``ModelBank.coverage`` and ``GET /models``.
"""

import asyncio
import contextlib
import functools
import inspect
import json
import logging
import os
import time
import weakref
from collections import deque
from concurrent.futures import Future as ConcurrentFuture
from concurrent.futures import InvalidStateError as ConcurrentInvalidState
from concurrent.futures import TimeoutError as FuturesTimeoutError
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gordo_components_tpu.models.anomaly.diff import (
    DiffBasedAnomalyDetector,
    assemble_anomaly_frame,
)
from gordo_components_tpu.models.factories.trunk import stack_observed
from gordo_components_tpu.models.register import lookup_factory
from gordo_components_tpu.models.train_core import _next_pow2
from gordo_components_tpu.observability import get_registry
from gordo_components_tpu.observability.cost import estimate_flops_per_row
from gordo_components_tpu.observability.tracing import group_span, stage
from gordo_components_tpu.ops.pallas_score import (
    banked_anomaly_score,
    resolve_bank_kernel_mode,
)
from gordo_components_tpu.ops.quantize import (
    dequantize_params,
    normalize_bank_dtype,
    quantize_stacked,
    tree_weight_bytes,
)
from gordo_components_tpu.ops.scaler import ScalerParams
from gordo_components_tpu.parallel.mesh import device_block
from gordo_components_tpu.ops.seq_scan import (
    LANE,
    SUBLANE,
    lstm_time_major_forward,
    resolve_seq_kernel_mode,
    resolve_seq_layout,
    supports_time_major,
)
from gordo_components_tpu.resilience.deadline import Deadline, DeadlineExceeded
from gordo_components_tpu.resilience.faults import faultpoint
from gordo_components_tpu.server.arena import PaddedArena

logger = logging.getLogger(__name__)

# chaos sites (tests/test_chaos.py): bucket stack/compile, low-precision
# weight quantization, batched scoring dispatch, and engine admission.
# Module-level points so the disabled cost on the serving hot loop is one
# attribute check (see the 5% guard test).
_FP_FINALIZE = faultpoint("bank.finalize")
_FP_QUANTIZE = faultpoint("bank.quantize")
_FP_SCORE = faultpoint("bank.score")
_FP_ENGINE_QUEUE = faultpoint("engine.queue")

# short dtype tags for bucket metric labels (bounded, readable)
_DTYPE_TAGS = {"bfloat16": "bf16", "int8": "int8"}


# --------------------------------------------------------------------- #
# extraction: estimator object -> bankable pieces
# --------------------------------------------------------------------- #


@dataclass
class _BankEntry:
    name: str
    registry_type: str  # estimator class name -> factory registry
    kind: str
    factory_kwargs: Dict[str, Any]
    compute_dtype: str
    n_features: int
    lookback: int  # 1 for feedforward
    target_offset: int  # sequence models: 0 reconstruct, 1 forecast
    params: Any  # numpy pytree
    in_shift: np.ndarray
    in_scale: np.ndarray
    err_shift: np.ndarray
    err_scale: np.ndarray
    # leaves the member does NOT own: a trunk every member of the bucket
    # shares (``(artifact path, parameter tree)``; models/factories/trunk.py)
    shared: Optional[Tuple[str, Any]] = None


def _affine_from_scaler(step, n_features: int):
    """Return (shift, scale) arrays for a supported scaler step, or None.

    Supports the JAX scalers (already affine) and sklearn's affine family —
    MinMaxScaler (``x*scale_ + min_`` == ``(x - (-min_/scale_)) * scale_``),
    StandardScaler, RobustScaler, MaxAbsScaler.
    """
    params = getattr(step, "scaler_params_", None)
    if params is not None:  # JaxMinMaxScaler / JaxStandardScaler
        return np.asarray(params.shift), np.asarray(params.scale)
    cls = type(step).__name__
    if cls == "MinMaxScaler" and getattr(step, "scale_", None) is not None:
        scale = np.asarray(step.scale_, np.float32)
        return (-np.asarray(step.min_, np.float32) / scale), scale
    # StandardScaler/RobustScaler both compute (x - shift) / scale_, with
    # the respective attribute set to None when centering/scaling is off
    shift_attr = {"StandardScaler": "mean_", "RobustScaler": "center_"}.get(cls)
    if shift_attr and hasattr(step, "scale_"):
        center = getattr(step, shift_attr, None)
        shift = np.asarray(
            center if center is not None else np.zeros(n_features), np.float32
        )
        scale_ = step.scale_
        if scale_ is None:
            return shift, np.ones((n_features,), np.float32)
        return shift, 1.0 / np.asarray(scale_, np.float32)
    if cls == "MaxAbsScaler" and getattr(step, "scale_", None) is not None:
        return (
            np.zeros((n_features,), np.float32),
            1.0 / np.asarray(step.scale_, np.float32),
        )
    return None


# estimator classes whose scoring the bank can reproduce exactly; the
# registry type doubles as the factory namespace (models/register.py)
_BANKABLE_TYPES = {
    "AutoEncoder", "LSTMAutoEncoder", "LSTMForecast", "ConvAutoEncoder", "TrunkForecast",
}


def _extract_entry(name: str, model) -> Tuple[Optional[_BankEntry], Optional[str]]:
    """Decompose a served model into bank pieces.

    Returns ``(entry, None)`` when bankable, else ``(None, reason)`` — the
    reason is surfaced through :meth:`ModelBank.coverage` so an operator
    can see exactly which models fell back to the per-model path and why.
    """
    if not isinstance(model, DiffBasedAnomalyDetector):
        return None, f"not a DiffBasedAnomalyDetector ({type(model).__name__})"
    if model.error_scaler_ is None:
        return None, "detector is unfitted (no error scaler)"
    base = model.base_estimator
    pre_steps: Sequence = []
    if hasattr(base, "steps"):
        pre_steps, est = base.steps[:-1], base.steps[-1][1]
    else:
        est = base
    registry_type = type(est).__name__
    if registry_type not in _BANKABLE_TYPES:
        return None, f"unsupported estimator class {registry_type}"
    if getattr(est, "params_", None) is None:
        return None, "estimator is unfitted"
    n_features = est.n_features_
    # compose the (possibly chained) affine scalers into one:
    # t(x) = (x - in_shift) * in_scale; appending ((t - s) * k) gives
    # (x - (in_shift + s/in_scale)) * (in_scale * k)
    in_shift = np.zeros((n_features,), np.float32)
    in_scale = np.ones((n_features,), np.float32)
    for step_name, step in pre_steps:
        aff = _affine_from_scaler(step, n_features)
        if aff is None:
            return None, f"non-affine preprocessing step {step_name!r}"
        s, k = np.asarray(aff[0], np.float32), np.asarray(aff[1], np.float32)
        safe_scale = np.where(in_scale == 0, 1.0, in_scale)
        in_shift = in_shift + s / safe_scale
        in_scale = in_scale * k
    err = ScalerParams(*model.error_scaler_)
    return (
        _BankEntry(
            name=name,
            registry_type=registry_type,
            kind=est.kind,
            factory_kwargs=dict(est.factory_kwargs),
            compute_dtype=getattr(est, "compute_dtype", "float32"),
            n_features=int(n_features),
            lookback=int(getattr(est, "lookback_window", 1)),
            target_offset=int(getattr(est, "_target_offset", 0)),
            params=jax.tree.map(np.asarray, est.params_),
            in_shift=in_shift.astype(np.float32),
            in_scale=in_scale.astype(np.float32),
            err_shift=np.asarray(err.shift, np.float32),
            err_scale=np.asarray(err.scale, np.float32),
            shared=(
                (est.trunk_path, est.trunk_params)
                if registry_type == "TrunkForecast"
                else None
            ),
        ),
        None,
    )


# --------------------------------------------------------------------- #
# bucket: stacked device state + compiled scoring program
# --------------------------------------------------------------------- #


def _prev_pow2(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _stored_shape(shape: Tuple[int, ...], dtype) -> Tuple[Tuple[int, ...], bool]:
    """``(shape, swapped)`` of one member's leaf as the bank stores it:
    the last dimension padded to whole lanes and the second-last to whole
    sublanes, the two swapped first where that pads less.

    HBM holds an array's two minor-most dimensions in tiles of LANE x
    SUBLANE 32-bit elements (narrower ones pack along the sublanes). For
    a ``(4096, 300, 250)`` stack the TPU's default layout therefore puts
    the 4096 on the lanes (it pads best): ONE member is then one lane of
    every tile of the bank, and a program that scores two members reads
    all of it. Where the member's own dimensions already fill whole
    tiles, no order pads less than row-major, the default layout keeps
    the member axis major-most, and one member is one contiguous run of
    tiles — what the padding would cost in HBM either way, a member-major
    layout being made of whole tiles per member. (Asking for that layout
    of the unpadded stack, ``device_put`` with a ``Format``, is not safe
    with JAX 0.9.0: the placing program, once it comes from the persistent
    compilation cache, returns arrays that report the default layout and
    hold the other, and the bucket program then refuses its own bank.)

    A (250, 300) kernel takes 256 x 384 elements as it is and 304 x 256
    with the 250 on the lanes, so it is stored swapped. Which order the
    matmul wants is the compiler's business either way: it lays out the B
    selected members, not the bank."""
    up = lambda n, m: -(-n // m) * m
    sublanes = SUBLANE * max(1, 4 // np.dtype(dtype).itemsize)
    if len(shape) < 2:
        return tuple(up(n, LANE) for n in shape), False
    *lead, rows, cols = shape
    straight = (up(rows, sublanes), up(cols, LANE))
    swapped = (up(cols, sublanes), up(rows, LANE))
    swap = swapped[0] * swapped[1] < straight[0] * straight[1]
    return (*lead, *(swapped if swap else straight)), swap


@functools.partial(jax.jit, static_argnames="sharding")
def _store_members(stacked, sharding=None):
    """One stacked ``(M, ...)`` leaf as the bank keeps it on the device
    (``_stored_shape``), zeros in the padding. The leaf arrives as the
    host stacked it and is laid out again once, on the device, where that
    takes milliseconds (padding 5.5 GB on the host took ten seconds of a
    server's start); under a mesh it stays on its ``sharding``."""
    shape, swap = _stored_shape(stacked.shape[1:], stacked.dtype)
    if swap:
        stacked = jnp.swapaxes(stacked, -1, -2)
    stored = jnp.pad(
        stacked, [(0, 0)] + [(0, n - m) for m, n in zip(stacked.shape[1:], shape)]
    )
    if sharding is not None:
        stored = jax.lax.with_sharding_constraint(stored, sharding)
    return stored


def _restore_members(stored, like):
    """Traced inverse of ``_store_members`` for a ``(B, ...)`` selection:
    ``like`` is one member's leaf as the model takes it (shape, dtype)."""
    _, swap = _stored_shape(like.shape, like.dtype)
    shape = like.shape[:-2] + like.shape[:-3:-1] if swap else like.shape
    stored = stored[(slice(None),) + tuple(slice(n) for n in shape)]
    return jnp.swapaxes(stored, -1, -2) if swap else stored


@jax.jit
def _select_member(stacks, i):
    """Member ``i`` of every ``(M, ...)`` leaf, as a ``(1, ...)`` slice.
    Jitted so that a program of B slots traces and lowers it once and
    calls it B times (inlined by XLA): unrolled in Python, B = 64 spent
    1.6 s of every server start tracing 1152 slices."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=True), stacks
    )


def _select_members(stacks, idx):
    """The ``(B, ...)`` stack of members ``idx`` (B,) of every ``(M, ...)``
    leaf of ``stacks``, by B dynamic slices of each leaf (B is static).

    Slices, not ``a[idx]``: XLA's layout assignment hands a gather's
    operand the layout its CONSUMER wants, the operand here is the bank,
    and the relayout is then a copy of all M members on every dispatch.
    A slice is taken in the bank's own layout; whatever order the matmul
    wants is made of the B selected members."""
    rows = [
        _select_member(stacks, jax.lax.index_in_dim(idx, b, keepdims=False))
        for b in range(idx.shape[0])
    ]
    return jax.tree.map(lambda *slot: jnp.concatenate(slot), *rows)


class _Bucket:
    """All models sharing (type, kind, n_features, lookback, factory
    kwargs, dtype): one stacked params pytree + scaler stacks in HBM, one
    scoring fn reused for every (batch, rows) shape bucket.

    Sequence models bank too: windowing runs in-graph
    (``ops/windows.sliding_windows``) with the bucket's static lookback,
    and outputs carry the warm-up ``offset`` (output row i <- input row
    i + offset), exactly like the per-model path.

    The stacks live member-major (``_stored_shape``) and the scoring
    program slices the batch's members out before it computes
    (``_select_members``): its device time and bytes go with the batch,
    not with the bank.

    With a ``mesh`` (1-D ``models`` axis, ``parallel/mesh.py``), the
    stacked params/scalers are placed under a ``NamedSharding`` on their
    leading (model) axis — the same layout ``FleetTrainer`` trains under
    (``parallel/fleet.py``) — so a D-chip server holds each model's
    weights exactly once. Requests are ROUTED: the host groups chunks by
    the shard that owns their model (the leading axis is split into D
    contiguous blocks), and a ``shard_map`` program scores each device's
    sub-batch against its local params with NO collectives — per-request
    compute stays local to the shard that owns the model, the total FLOPs
    equal the single-device program's, and the only cross-device traffic
    is the result fetch. (The alternative — replicating every request to
    all devices and masking — costs D× the FLOPs; routing costs one
    host-side groupby.)"""

    def __init__(
        self,
        kind: str,
        n_features: int,
        factory_kwargs: Dict[str, Any],
        compute_dtype: str = "float32",
        registry_type: str = "AutoEncoder",
        lookback: int = 1,
        target_offset: int = 0,
        mesh=None,
        bank_dtype: str = "float32",
        kernel_mode: str = "jnp",
        shared: Optional[Tuple[str, Any]] = None,
    ):
        self.kind = kind
        self.n_features = n_features
        self.factory_kwargs = factory_kwargs
        self.compute_dtype = compute_dtype
        self.registry_type = registry_type
        self.lookback = int(lookback)
        self.target_offset = int(target_offset)
        # low-precision weight bank (ops/quantize.py): the REQUESTED
        # storage dtype; ``effective_dtype`` records what finalize
        # actually shipped to HBM (a failed quantization falls back to
        # fp32 for this bucket only, with the reason in quantize_error)
        self.bank_dtype = bank_dtype
        self.kernel_mode = kernel_mode
        self.effective_dtype = "float32"
        self.quantize_error: Optional[str] = None
        self.weight_bytes = 0  # stacked params bytes as stored (HBM cost)
        self.weight_bytes_fp32 = 0  # same stack at fp32 (the baseline)
        # short stable id for per-bucket metric labels (the full bucket key
        # is a JSON blob; labels need something bounded and readable). The
        # readable prefix alone is NOT unique — buckets differing only in
        # factory kwargs / dtype / target offset are separate compiled
        # programs and must not blend into one series — so those ride in
        # as a short content hash suffix when non-default.
        self.label = f"{registry_type}:{kind}:f{n_features}:l{self.lookback}"
        if self.target_offset:
            self.label += f":o{self.target_offset}"
        if bank_dtype != "float32":
            # storage dtype in the label: a bf16 bank and an fp32 bank
            # compile DIFFERENT programs over different HBM layouts and
            # must not blend into one metric series (bucket keying by
            # dtype; the tag stays even if quantization falls back, so
            # the fallback is visible as a q-tagged bucket serving fp32
            # alongside the gordo_bank_quantize_fallback_total counter)
            self.label += f":q{_DTYPE_TAGS.get(bank_dtype, bank_dtype)}"
        if factory_kwargs or compute_dtype != "float32":
            import hashlib

            extra = json.dumps(
                [sorted(factory_kwargs.items()), compute_dtype], default=str
            )
            self.label += ":" + hashlib.sha1(extra.encode()).hexdigest()[:6]
        self.mesh = mesh
        self.names: List[str] = []
        self._entries: List[_BankEntry] = []
        # device state, built by finalize()
        self.params = None
        self.scalers = None  # (in_shift, in_scale, err_shift, err_scale)
        self._score = None
        self.n_shards = 1  # mesh model-axis size after finalize()
        self.shard_size = 0  # models per shard (padded stack / n_shards)
        self._sharding = None  # NamedSharding on the model axis (mesh mode)
        # static cost-attribution feed (observability/cost.py), computed
        # once by finalize(): analytic forward FLOPs for one routed row
        # (one scoring window for sequence models) through this bucket's
        # compiled program
        self.flops_per_row = 0.0
        self.flops_method = "unknown"
        self.params_per_member = 0
        # sequence fast-path provenance, resolved by finalize()
        self.seq_layout = "legacy"
        self.seq_kernel = "jnp"
        # leaves held ONCE beside the per-member stacks: a trunk the
        # bucket's members share. ``(artifact path, tree)`` until
        # finalize() places the tree on the device. Such a bucket scores a
        # request as one causal sequence: it never cuts one into calls,
        # pads it to whole chunks of its module instead of a power of two,
        # and bounds a batch by the bytes its program needs (``max_batch``)
        self.shared = shared
        self.shared_bytes = 0
        self._module = None
        self._free_bytes: Optional[int] = None  # device memory left for a call

    @property
    def offset(self) -> int:
        return self.lookback - 1 + self.target_offset

    def rows_per_call(self, rows: int, max_rows: int) -> int:
        """``T`` of a call that carries requests of up to ``rows`` rows."""
        if self.shared is not None:
            return self._module.padded_rows(rows)
        T = min(_next_pow2(max(1, int(rows))), _prev_pow2(max_rows))
        # always at least one window + one output row
        return max(T, _next_pow2(self.offset + 1))

    def max_batch(self, T: int) -> Optional[int]:
        """Requests of ``T`` padded rows one call may carry, from the bytes
        the bucket's program needs beside what the device already holds;
        ``None``: as many as the engine collects."""
        if self.shared is None or self._free_bytes is None:
            return None
        B = 1
        while self._module.program_bytes(2 * B, T) <= self._free_bytes:
            B *= 2
        return B

    def add(self, entry: _BankEntry) -> None:
        self._entries.append(entry)
        self.names.append(entry.name)

    def finalize(self) -> None:
        _FP_FINALIZE.fire()
        entries = self._entries
        sharding = None
        if self.mesh is not None:
            from gordo_components_tpu.parallel.mesh import (
                MODEL_AXIS,
                pad_count_to_mesh,
                shard_model_axis,
            )

            self.n_shards = int(self.mesh.shape[MODEL_AXIS])
            # the leading axis must divide the mesh: pad by repeating the
            # last entry (real params — zero-padding would still be
            # correct, since no routed slot ever points at a pad row, but
            # repeats keep every row's numerics in-distribution)
            n_pad = pad_count_to_mesh(len(entries), self.mesh)
            entries = entries + [entries[-1]] * (n_pad - len(entries))
            self.shard_size = n_pad // self.n_shards
            sharding = self._sharding = shard_model_axis(self.mesh)
        # one member's leaves as the bank will store them (dtype, and for
        # int8 the per-member scale beside the codes): what the program
        # restores a selection to, the HBM accounting, and the one place a
        # failing quantization shows, before anything large is made
        one = jax.tree.map(lambda a: np.asarray(a)[None], entries[0].params)
        self.weight_bytes_fp32 = len(entries) * tree_weight_bytes(one)
        self.effective_dtype = "float32"
        if self.bank_dtype != "float32":
            # low-precision weight bank (ops/quantize.py): HBM holds the
            # bf16/int8 stack, the compiled program dequantizes the
            # selected members back to fp32. A failed quantization is an
            # IMPAIRMENT of capacity, not of correctness — this bucket
            # falls back to fp32 storage (counted by the bank) instead of
            # failing the whole build.
            try:
                _FP_QUANTIZE.fire()
                one = quantize_stacked(one, self.bank_dtype)
                self.effective_dtype = self.bank_dtype
            except Exception as exc:
                self.quantize_error = f"{type(exc).__name__}: {exc}"
                logger.warning(
                    "Bucket %s: %s quantization failed (%s); storing fp32 "
                    "for this bucket",
                    self.label, self.bank_dtype, exc,
                )
        self.weight_bytes = len(entries) * tree_weight_bytes(one)
        scaler_fields = ("in_shift", "in_scale", "err_shift", "err_scale")
        members_like = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
            (one, tuple(getattr(entries[0], f)[None] for f in scaler_fields)),
        )

        # every stacked leaf goes to the device with its members' own
        # dimensions filling whole tiles (``_stored_shape``), so that it
        # lies member-major there, on one device and per shard of the mesh
        # alike: the program below touches only the members a batch names
        # (``_select_members``), and that is only cheap where one member's
        # bytes lie together. Leaf by leaf: the host stacks the next leaf
        # while this one's copy is under way, and the device holds one
        # leaf twice (as stacked, as stored), never the bank.
        def place(leaves, dtype="float32"):
            stacked = quantize_stacked(np.stack(leaves), dtype)
            return jax.tree.map(
                lambda a: _store_members(jax.device_put(a, sharding), sharding=sharding),
                stacked,
            )

        if self.shared is not None:
            if self.mesh is not None or self.bank_dtype != "float32":
                raise NotImplementedError(
                    "a bucket with shared leaves serves from one device at its "
                    "own dtypes (no mesh, no GORDO_BANK_DTYPE)"
                )
            # placed once, in the dtypes the artifact holds (bfloat16
            # matrices), leaf by leaf; a leaf already on the device stays
            self.shared = jax.tree.map(jax.device_put, self.shared[1])
            self.shared_bytes = tree_weight_bytes(self.shared)
        self.params = jax.tree.map(
            lambda *leaves: place(leaves, self.effective_dtype),
            *[e.params for e in entries],
        )
        self.scalers = tuple(
            place([getattr(e, f) for e in entries]) for f in scaler_fields
        )
        module = self._module = lookup_factory(self.registry_type, self.kind)(
            self.n_features, compute_dtype=self.compute_dtype, **self.factory_kwargs
        )
        # one member's param count + analytic FLOPs, once per compiled
        # program — the cost model joins these static numbers to the
        # ledger's measured device seconds (entries[0]: pad repeats share
        # the real members' shapes, so any entry works)
        self.params_per_member = int(
            sum(np.asarray(l).size for l in jax.tree.leaves(entries[0].params))
        )
        self.flops_per_row, self.flops_method = estimate_flops_per_row(
            module, self.n_features, self.lookback, self.params_per_member
        )
        lookback, t_off, off = self.lookback, self.target_offset, self.offset
        dequant = self.effective_dtype != "float32"
        kernel_mode = self.kernel_mode
        # sequence fast path (ops/seq_scan.py): LSTM buckets can score
        # through the time-major scan — batch slots become the member
        # axis, kept innermost — with the fused recurrent-step kernel
        # when GORDO_SEQ_KERNEL resolves to it. Resolved ONCE here (like
        # kernel_mode): the choice is baked into the compiled program.
        use_tm = (
            resolve_seq_layout() == "time_major"
            and lookback > 1
            and supports_time_major(module)
        )
        self.seq_layout = "time_major" if use_tm else "legacy"
        self.seq_kernel = resolve_seq_kernel_mode() if use_tm else "jnp"
        seq_kernel = self.seq_kernel
        if use_tm:
            self.flops_method += f":time_major(T={lookback})"

        def forward_tm(p, in_shift, in_scale, X, Y):
            # p, in_shift, in_scale: the B selected members, (B, ...);
            # X/Y: (B, T, F) raw-space. One scan over time scores all
            # slots' windows with the slot axis innermost.
            from gordo_components_tpu.ops.windows import sliding_windows

            sh = in_shift[:, None, :]
            sc = in_scale[:, None, :]
            xs = (X - sh) * sc
            ys = (Y - sh) * sc
            W = jax.vmap(lambda x: sliding_windows(x, lookback))(xs)
            if t_off:
                W = W[:, :-t_off]
            recon = lstm_time_major_forward(module, p, W, kernel=seq_kernel)
            target = ys[:, off : off + recon.shape[1]]
            return recon, target

        def forward(p, in_shift, in_scale, x, y):
            # ONE selected member (vmapped over the batch's B below):
            # p its params, in_shift/in_scale (F,); x/y: (T, F) raw-space;
            # returns (recon, target) — the epilogue runs batched below
            from gordo_components_tpu.ops.windows import sliding_windows

            xs = (x - in_shift) * in_scale
            ys = (y - in_shift) * in_scale
            if lookback > 1:
                W = sliding_windows(xs, lookback)
                if t_off:
                    W = W[:-t_off]
                recon = module.apply(p, W)  # (T - off, F)
                target = ys[off : off + recon.shape[0]]
            else:
                recon = module.apply(p, xs)
                target = ys
            return recon, target

        # A bucket with shared leaves runs as three programs (score_batch):
        # ``score_enter`` (the B selected members' input projections),
        # ``score_layer`` once per layer of the shared trunk (compiled once
        # for each kind of layer the trunk has, whatever the depth: layers
        # of one kind have the same leaves and shapes, and each call is
        # handed its own layer's leaves in place; what one call hands the
        # next is the residual stream and the selection of keys in force,
        # ``None`` where the trunk shares none), and ``score`` below, which then
        # starts from the trunk's last state. All three names begin with
        # ``score``: the device trace's readers sum the bucket's programs
        # by that prefix.
        def score_enter(params, in_shift, in_scale, idx, X):
            p, (in_shift, in_scale) = jax.tree.map(
                _restore_members,
                _select_members((params, (in_shift, in_scale)), idx),
                (members_like[0], members_like[1][:2]),
            )
            xs = (X - in_shift[:, None, :]) * in_scale[:, None, :]
            return module.embed(p["params"]["in_proj"], xs)

        def score_layer(w, x, n_valid, selection=None):
            return module.layer(w, x, n_valid, selection, interpret=kernel_mode != "pallas")

        # the jitted function is named ``score`` on one device and on the
        # mesh: the XLA module is then ``jit_score``, the name the device
        # trace's readers find the bucket program by
        def score(params, in_shift, in_scale, err_shift, err_scale, idx, X, Y,
                  state=None, final_norm=None):
            # idx: (B,) int32 into the (local) stacks; X/Y: (B, T, F)
            # raw-space. Select, then compute: the B members' params and
            # scaler rows are sliced out of the bank first, and everything
            # after this line has B, never M, as its leading dimension.
            p, (in_shift, in_scale, err_shift, err_scale) = jax.tree.map(
                _restore_members,
                _select_members(
                    (params, (in_shift, in_scale, err_shift, err_scale)), idx
                ),
                members_like,
            )
            if dequant:
                # dequantization INSIDE the compiled program: only the
                # selected members' weights round-trip to fp32, compute
                # accumulates in fp32 throughout
                p = dequantize_params(p)
            # the model forward runs per member; the scoring epilogue
            # (scale -> reconstruction error -> row norms) runs over the
            # WHOLE batch in one banked pass — the Pallas kernel's (member,
            # row-tile) grid on TPU, identical jnp math elsewhere
            # (ops/pallas_score.banked_anomaly_score) — against the selected
            # error scalers, slot b's row being row b
            if state is not None:
                # the shared trunk's last state through the B members'
                # heads: output row i forecasts input row i + 1
                out = module.head(final_norm, p["params"]["head"], state)
                recon = out[:, :-off]
                target = ((Y - in_shift[:, None, :]) * in_scale[:, None, :])[:, off:]
            else:
                recon, target = (forward_tm if use_tm else jax.vmap(forward))(
                    p, in_shift, in_scale, X, Y
                )
            return (recon,) + banked_anomaly_score(
                target, recon, err_shift, err_scale,
                jnp.arange(idx.shape[0], dtype=jnp.int32), mode=kernel_mode,
            )

        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            from gordo_components_tpu.parallel.mesh import MODEL_AXIS

            spec = P(MODEL_AXIS)
            score_shard = score

            def score(params, in_shift, in_scale, err_shift, err_scale, idx, X, Y):
                # idx: (D, Blocal) LOCAL indices; X/Y: (D, Blocal, T, F);
                # leading axis sharded over the mesh — each device scores
                # its own sub-batch against its local (shard_size, ...)
                # params and scaler blocks; no collectives.
                def local(p, ish, isc, esh, esc, i, x, y):
                    out = score_shard(p, ish, isc, esh, esc, i[0], x[0], y[0])
                    return jax.tree.map(lambda t: t[None], out)

                # check_vma off: the program is collective-free by design
                # (every output row depends only on the local shard), and
                # the varying-axes checker rejects the LSTM scan's
                # unvarying initial carry under a varying input
                return jax.shard_map(
                    local,
                    mesh=self.mesh,
                    in_specs=(spec,) * 8,
                    out_specs=spec,
                    check_vma=False,
                )(params, in_shift, in_scale, err_shift, err_scale, idx, X, Y)

        self._score = jax.jit(score)
        if self.shared is not None:
            self._enter, self._layer = jax.jit(score_enter), jax.jit(score_layer)
        self._entries = []  # host copies no longer needed

    def score_batch(self, indices: np.ndarray, X: np.ndarray, Y: np.ndarray,
                    n_valid: Optional[np.ndarray] = None):
        """Single-device path. indices: (B,), X/Y: (B, T, F) — already
        padded to pow2 B and ``rows_per_call`` T. A bucket with shared
        leaves takes each slot's real rows (``n_valid``, 0 for a pad slot)
        and returns, after the five arrays, what each layer observed."""
        if self.shared is None:
            return self._score(
                self.params, *self.scalers, jnp.asarray(indices), jnp.asarray(X),
                jnp.asarray(Y),
            )
        idx, X, n_valid = jnp.asarray(indices), jnp.asarray(X), jnp.asarray(n_valid, jnp.int32)
        x, selection = self._enter(self.params, *self.scalers[:2], idx, X), None
        observed = []
        for w in self.shared["layers"]:
            # the selection stays on the device, in the form the kernel reads
            x, selection, seen = self._layer(w, x, n_valid, selection)
            observed.append(seen)
        return self._score(
            self.params, *self.scalers, idx, X, jnp.asarray(Y), x, self.shared["final_norm"]
        ) + (observed,)

    def score_batch_sharded(self, indices: np.ndarray, X: np.ndarray, Y: np.ndarray):
        """Mesh path. indices: (D, Blocal) LOCAL indices (into each
        device's shard), X/Y: (D, Blocal, T, F), routed by the caller so
        row d only references models owned by shard d."""
        sh = self._sharding  # built once in finalize()
        return self._score(
            self.params,
            *self.scalers,
            jax.device_put(np.ascontiguousarray(indices), sh),
            jax.device_put(np.ascontiguousarray(X), sh),
            jax.device_put(np.ascontiguousarray(Y), sh),
        )


# --------------------------------------------------------------------- #
# the bank
# --------------------------------------------------------------------- #


@dataclass
class ScoreResult:
    """Raw-space arrays for one request, sliced back to its true length.

    ``offset`` is the sequence warm-up: output row i corresponds to input
    row i + offset (0 for feedforward). ``model_input`` holds the FULL
    request; ``to_frame`` trims it (and the index) to the output rows,
    matching ``DiffBasedAnomalyDetector.anomaly``'s frame exactly."""

    tags: List[str]
    model_input: np.ndarray
    model_output: np.ndarray
    diff: np.ndarray
    scaled: np.ndarray
    total_unscaled: np.ndarray
    total_scaled: np.ndarray
    offset: int = 0
    # this request's share of its group's useful device window (seconds),
    # assigned when a goodput ledger is attached (observability/goodput.py)
    # — the HTTP layer commits it to the goodput/wasted cells once the
    # request's final outcome is known; 0.0 when accounting is off
    device_s: float = 0.0
    # a bucket with shared leaves: which experts each row was routed to,
    # (routed layers, rows, top_k) uint8, and, where the kind selects keys,
    # which keys every 64th row attended to, as packed bits (layers that
    # select, sampled rows, padded rows // 8) uint8 (ops/sparse_attention.py).
    # They ride the tensor response as further frames: what a client
    # compares two servers' selections by.
    selections: Optional[Dict[str, np.ndarray]] = None

    def to_frame(self, index=None):
        n_out = len(self.model_output)
        if index is not None:
            index = index[self.offset :][:n_out]
        return assemble_anomaly_frame(
            self.tags,
            self.model_input[self.offset :][:n_out],
            self.model_output,
            self.diff,
            self.scaled,
            self.total_unscaled,
            self.total_scaled,
            index,
        )

    def to_arrays(self) -> Dict[str, np.ndarray]:
        """ndarray-out counterpart of :meth:`to_frame` for the binary
        wire path (server/model_io.py): the same trimmed arrays a frame
        would hold, keyed by the frame's top-level column names — but as
        the fetched device buffers themselves, with no DataFrame
        assembly, no per-column ``tolist``, and no float64 upcast. The
        input trim is a view; everything else is returned as-is."""
        n_out = len(self.model_output)
        return {
            "model-input": np.asarray(self.model_input)[self.offset :][:n_out],
            "model-output": self.model_output,
            "tag-anomaly-unscaled": self.diff,
            "tag-anomaly-scaled": self.scaled,
            "total-anomaly-unscaled": self.total_unscaled,
            "total-anomaly-scaled": self.total_scaled,
            **(self.selections or {}),
        }


# counters of the buckets with shared leaves (``ModelBank.shared_stats``)
_SHARED_COUNTERS = {
    "dispatches": "Dispatches of bucket programs with shared leaves",
    "rows": "Request rows those dispatches carried",
    "tokens": "Rows they computed, padding included",
    "expert_tokens": "Valid (row, expert) pairs routed, all layers",
    "expert_tokens_busiest": "Pairs routed to each layer's busiest expert",
    "key_selections": "(query, key) pairs the indexer selected, all layers that select",
    "selection_layers": "Layers that made a selection of keys",
    "selection_uses": "Layers that attended under a selection, their own or one handed on",
    "routed_pairs": "Valid (row, expert) pairs routed by layers that hold a range of their experts",
    "held_pairs": "Those of them that fell on an expert held here",
    "held_tokens_busiest": "Pairs routed to each such layer's busiest held expert",
    "held_pair_blocks": "Blocks of held pairs such layers worked through, padding's pairs among them",
    "ssm_layers": "State-space mixer layers the dispatches ran",
    "ssm_chunks": "Chunks of valid rows those layers scanned, summed over the layers",
}


def _selection_frames(observed: Dict[str, np.ndarray], slot: int, rows: int) -> Dict[str, np.ndarray]:
    """One request's frames of what the layers observed: its rows'
    experts in every routed layer, and its sampled rows' keys where the
    kind selects keys."""
    frames = {"expert-selection": observed["experts"][:, slot, :rows].copy()}
    if "witness" in observed:
        frames["key-selection"] = observed["witness"][:, slot].copy()
    return frames


def _slice_single(outs, slot, n_out: int):
    """Single-chunk reassembly (the serving-path norm): one sliced copy
    per output array instead of concatenate machinery. The copy is
    deliberate: a view would pin the whole (B, T, ...) batch output alive
    as long as any one result is held, and would be read-only where the
    multi-chunk path returns writable arrays."""
    return tuple(a[slot][:n_out].copy() for a in outs)


def _concat_chunks(outs, slots, cis, valids, n_out: int):
    """Multi-chunk reassembly: each chunk contributes its VALID output
    rows (rows computed from real, unpadded input)."""
    return tuple(
        np.concatenate(
            [a[slots[ci]][:v] for ci, v in zip(cis, valids)], axis=0
        )[:n_out]
        for a in outs
    )


class _GroupRun:
    """One bucket group's trip through the scoring pipeline.

    Built by ``_host_prep`` (coalesce + pad into arena buffers), handed
    to ``_dispatch`` (async XLA call — ``out`` holds device arrays whose
    computation may still be in flight), finished by ``_postprocess``
    (fence, fetch, reassemble, release buffers). Keeping the whole group
    state in one object is what lets ``score_many`` hold several groups
    in flight at once."""

    __slots__ = (
        "bucket", "req_ids", "req_plans", "slots", "n_chunks",
        "Xb", "Yb", "idx", "score_fn", "out", "off", "traces", "exec_span",
        "coalesce_s", "pad_s", "postprocess_s", "t_dispatch", "t_ready",
        "t_device_done", "_bufs", "routed_rows", "total_rows", "shard_rows",
    )

    def __init__(self):
        self.out = None
        # the group's traced requests (observability/tracing.py) and the
        # ``device_execute`` span they share, open from dispatch to fence
        self.traces: Sequence[Any] = ()
        self.exec_span: Any = None
        # host seconds per stage, for the goodput ledger
        self.coalesce_s = self.pad_s = self.postprocess_s = 0.0
        # the device window's bounds: ``enqueue`` start, ``device_wait`` end
        self.t_dispatch = 0.0
        self.t_ready = 0.0
        # goodput accounting feed (observability/goodput.py): real vs
        # dispatched rows for the padded-waste split, per shard
        self.routed_rows = 0
        self.total_rows = 0
        self.shard_rows: Tuple[Tuple[str, int, int], ...] = ()
        # earliest time the outputs were OBSERVED ready (polled at host
        # stage boundaries); 0.0 until then — the fence time is only an
        # upper bound that absorbs whatever host work ran in between
        self.t_device_done = 0.0
        self._bufs = ()

    def poll_ready(self, now: float) -> None:
        """Stamp ``t_device_done`` if the device outputs have become
        ready — called at host stage boundaries so the overlap
        accounting sees device completion near when it happened instead
        of at the (possibly much later) fence."""
        if self.t_device_done or self.out is None:
            return
        try:
            if all(a.is_ready() for a in self.out):
                self.t_device_done = now
        except Exception:
            # no is_ready on this array type, or the async computation
            # already failed device-side: a poll must never raise — the
            # fence in _postprocess surfaces device errors inside the
            # owning group's handler, keeping per-group isolation intact
            pass

    def release(self, arena: PaddedArena) -> None:
        """Return the padded input buffers to the arena (idempotent)."""
        bufs, self._bufs = self._bufs, ()
        for buf in bufs:
            arena.release(buf)


class ModelBank:
    """Stacked scoring bank over a model collection (HBM-resident).

    ``mesh`` (optional, a 1-D ``models``-axis mesh from
    ``parallel/mesh.fleet_mesh``) shards every bucket's stacked state over
    the devices and routes requests to the owning shard — see
    :class:`_Bucket`. Without it the bank is single-device, exactly as
    before."""

    def __init__(
        self,
        max_rows_per_call: int = 8192,
        mesh=None,
        registry=None,
        inflight: Optional[int] = None,
        arena_max_mb: Optional[float] = None,
        bank_dtype: Optional[str] = None,
        bank_kernel: Optional[str] = None,
        ledger=None,
        heat=None,
    ):
        self.max_rows = int(max_rows_per_call)
        self.mesh = mesh
        # access-heat accountant (observability/heat.py): APP-level state
        # handed to every bank generation — a /reload or rebalance swap
        # changes which bank feeds it without resetting the decayed
        # history (the model_rows cumulative-loss fix). None = heat off,
        # one attribute check on the scoring path (GORDO_HEAT=0), held
        # by the tests/test_heat_cost.py hot-loop guard.
        self.heat = heat
        if heat is not None:
            heat.bind_bank(self)
        # goodput ledger (observability/goodput.py): when attached, each
        # bucket group's device window, padded-row split, and host stage
        # seconds are accounted, and every ScoreResult carries its share
        # of the useful device window (device_s). None = accounting off,
        # one attribute check on the scoring path (the GORDO_SLO=0
        # contract, held by the tests/test_goodput.py hot-loop guard)
        self.ledger = ledger
        # low-precision weight bank (ops/quantize.py): storage dtype for
        # the stacked bucket params (env GORDO_BANK_DTYPE, default
        # float32 — the bitwise-parity baseline; bf16 halves and int8
        # ~quarters HBM per member, with the error budget documented in
        # docs/operations.md "Precision & capacity tuning")
        if bank_dtype is None:
            bank_dtype = os.environ.get("GORDO_BANK_DTYPE", "float32")
        self.bank_dtype = normalize_bank_dtype(bank_dtype)
        # banked epilogue dispatch (env GORDO_BANK_KERNEL, default auto:
        # the fused Pallas kernel on TPU, identical jnp math elsewhere) —
        # resolved ONCE here, baked into every bucket's compiled program
        self.kernel_mode = resolve_bank_kernel_mode(bank_kernel)
        # bucket label -> reason, for buckets whose low-precision
        # quantization failed and fell back to fp32 storage (capacity
        # impairment, surfaced via /stats bank_capacity + the
        # gordo_bank_quantize_fallback_total counter)
        self.quantize_fallbacks: Dict[str, str] = {}
        # pipeline depth: how many bucket groups may be in flight on the
        # device at once (env GORDO_BANK_INFLIGHT, default 2). While
        # group k executes, group k+1 is padded on the host and group
        # k-1's outputs are fetched — 1 disables the overlap (serial
        # prep->dispatch->fetch per group, the parity baseline).
        if inflight is None:
            raw = os.environ.get("GORDO_BANK_INFLIGHT", "2")
            try:
                inflight = int(raw)
            except ValueError:
                raise ValueError(
                    f"GORDO_BANK_INFLIGHT must be an integer, got {raw!r}"
                ) from None
        self._inflight_window = max(1, int(inflight))
        self._inflight_now = 0
        self.arena = PaddedArena(
            None if arena_max_mb is None else int(arena_max_mb * 1024 * 1024)
        )
        # host/device overlap accounting, aggregated across multi-group
        # calls: device_busy sums the non-overlapping per-group device
        # windows, wall the whole call — their ratio is the overlap the
        # pipeline buys (serial padding+fetching shows up as ratio << 1)
        self._pipe = {
            "calls": 0,
            "multi_group_calls": 0,
            "wall_s": 0.0,
            "device_busy_s": 0.0,
        }
        # what the buckets with shared leaves observed (``_count_observed``),
        # from arrays their program returns with each dispatch; served in
        # ``/stats`` as ``bank_shared`` and scraped as gordo_bank_shared_*.
        # Empty for a bank that has no such bucket.
        self.shared_stats: Dict[str, int] = {}
        self._buckets: Dict[str, _Bucket] = {}
        self._index: Dict[str, Tuple[str, int]] = {}  # name -> (bucket_key, i)
        self._tags: Dict[str, List[str]] = {}
        # bank generation: bumped by the placement control plane's swap
        # (placement/swap.py) every time a rebuilt bank replaces this one
        # — exported as gordo_bank_generation, 0 for the boot bank
        self.generation = 0
        # per-model routed rows, the placement planner's load signal
        # (placement/planner.py): one dict get+set per request against a
        # multi-ms scoring dispatch. Set to None to disable entirely
        # (the rebalance hot-loop overhead guard's control arm).
        self.model_rows: Optional[Dict[str, int]] = {}
        # name -> human-readable reason the model serves per-model instead
        self.fallback: Dict[str, str] = {}
        # bucket label -> error for buckets whose finalize (stack/compile)
        # failed: those members still serve via the per-model path, but
        # unlike the by-design fallback set this is an IMPAIRMENT —
        # /healthz reports degraded while any entry is present
        self.finalize_failures: Dict[str, str] = {}
        # metrics registry (observability/): None = process default,
        # False = uninstrumented (the hot-loop overhead guard's control).
        # The router records per-shard routed/padded-row counters here —
        # the per-shard visibility VERDICT r5 weak #2 flagged as missing
        # (a hot model concentrates traffic on one shard while the others
        # idle, and nothing surfaced it).
        if registry is None:
            registry = get_registry()
        elif registry is False:
            registry = None
        self.registry = registry
        if registry is not None:
            self._m_shard_rows = registry.counter(
                "gordo_bank_shard_routed_rows_total",
                "Input rows routed to each model-axis shard",
                ("shard",),
            )
            self._m_shard_pad = registry.counter(
                "gordo_bank_shard_padded_rows_total",
                "Pad rows dispatched to each shard (batch padded to the max "
                "per-shard load; high on one shard = skewed routing)",
                ("shard",),
            )
            self._m_shard_reqs = registry.counter(
                "gordo_bank_shard_requests_total",
                "Request chunks routed to each shard",
                ("shard",),
            )
            self._m_bucket_calls = registry.counter(
                "gordo_bank_bucket_calls_total",
                "Batched XLA scoring dispatches per bucket",
                ("bucket",),
            )
            self._m_bucket_reqs = registry.counter(
                "gordo_bank_bucket_requests_total",
                "Requests scored per bucket",
                ("bucket",),
            )
            self._m_bucket_batch = registry.histogram(
                "gordo_bank_bucket_batch_size",
                "Coalesced chunks per batched XLA call, per bucket",
                ("bucket",),
                lo=1.0,
                hi=1e5,
            )
            self._m_quant_fallback = registry.counter(
                "gordo_bank_quantize_fallback_total",
                "Bucket quantizations that failed and fell back to fp32 "
                "storage (capacity impairment, not a correctness one)",
                ("bucket",),
            )
            # weakref: these read-through closures live in a potentially
            # process-global registry; a strong self capture would pin a
            # discarded bank's stacked params (GBs at fleet scale) forever
            ref = weakref.ref(self)
            registry.gauge(
                "gordo_bank_models", "Models resident in the HBM bank"
            ).labels().set_function(
                lambda: len(b._index) if (b := ref()) is not None else 0
            )
            registry.gauge(
                "gordo_bank_buckets", "Compiled bucket programs in the bank"
            ).labels().set_function(
                lambda: len(b._buckets) if (b := ref()) is not None else 0
            )

            # pipeline/arena series, read-through from the live counters
            # (stability contract, docs/observability.md). A collector —
            # not mirrored cells — so the hot loop pays nothing beyond
            # the plain-int increments it already makes; keyed so a
            # /reload's rebuilt bank replaces the old bank's emission.
            # The series carry the replaced bank's values: a /reload
            # passes the same registry exactly so counters stay
            # monotonic, and a scrape must never see hits/misses drop
            # back to zero. The predecessor's values stay LIVE (re-read
            # from its collector at render time) while the old bank is
            # still serving during the reload's construct+warmup window
            # — its gauges (pooled bytes, in-flight groups) are summed
            # in so that window doesn't mask a working pipeline — and
            # once the old bank is collected the counter baseline
            # freezes at its last observed values while the gauge
            # contribution drops to zero (gauges are point-in-time).
            base = {
                "hits": 0, "misses": 0, "bytes": 0, "inflight": 0,
                "prev": registry.get_collector("bank_pipeline"),
            }

            def _refresh_base():
                prev = base["prev"]
                if prev is None:
                    return
                rows = ()
                with contextlib.suppress(Exception):
                    rows = tuple(prev())
                if not rows:
                    # predecessor bank was GC'd (its collector yields
                    # nothing): freeze the counter baseline, zero the
                    # gauge carry, and drop the chain link so renders
                    # stop walking dead closures
                    base["prev"] = None
                    base["bytes"] = base["inflight"] = 0
                    return
                for pname, _t, _h, _l, pval in rows:
                    if pname == "gordo_bank_arena_hits_total":
                        base["hits"] = int(pval)
                    elif pname == "gordo_bank_arena_misses_total":
                        base["misses"] = int(pval)
                    elif pname == "gordo_bank_arena_bytes":
                        base["bytes"] = int(pval)
                    elif pname == "gordo_bank_inflight_groups":
                        base["inflight"] = int(pval)

            _refresh_base()

            def _pipeline_collect():
                bank = ref()
                if bank is None:
                    return ()
                _refresh_base()
                arena = bank.arena
                return (
                    (
                        "gordo_bank_arena_hits_total", "counter",
                        "Padded-buffer arena reuses on the coalesced loop",
                        {}, base["hits"] + arena.hits,
                    ),
                    (
                        "gordo_bank_arena_misses_total", "counter",
                        "Padded-buffer arena allocations (pool miss)",
                        {}, base["misses"] + arena.misses,
                    ),
                    (
                        "gordo_bank_arena_bytes", "gauge",
                        "Bytes held in the padded-buffer arena pool",
                        {}, base["bytes"] + arena.pooled_bytes,
                    ),
                    (
                        "gordo_bank_inflight_groups", "gauge",
                        "Bucket groups currently in flight in the scoring "
                        "pipeline", {}, base["inflight"] + bank._inflight_now,
                    ),
                )

            registry.collector(_pipeline_collect, key="bank_pipeline")

            def _capacity_collect():
                # per-dtype HBM weight bytes + models-per-GB, read from
                # the live buckets at render time (gauges are point-in-
                # time: a /reload's replacement collector under the same
                # key simply takes over). One capacity_stats() call is
                # the single source for both series — no second
                # aggregation to drift from it.
                bank = ref()
                if bank is None:
                    return ()
                cap = bank.capacity_stats()
                rows = [
                    (
                        "gordo_bank_weight_bytes", "gauge",
                        "Stacked bank weight bytes resident in HBM, by "
                        "storage dtype",
                        {"dtype": d}, nbytes,
                    )
                    for d, nbytes in sorted(
                        cap["weight_bytes_by_dtype"].items()
                    )
                ]
                if cap["models_per_gb"] is not None:
                    rows.append(
                        (
                            "gordo_bank_models_per_gb", "gauge",
                            "Bank members per GB of stacked-weight HBM at "
                            "the current dtype mix",
                            {}, cap["models_per_gb"],
                        )
                    )
                return tuple(rows)

            registry.collector(_capacity_collect, key="bank_capacity")

            def _shared_collect():
                bank = ref()
                return () if bank is None else tuple(
                    (f"gordo_bank_shared_{name}_total", "counter", text, {},
                     bank.shared_stats.get(name, 0))
                    for name, text in _SHARED_COUNTERS.items()
                    if bank.shared_stats
                )

            registry.collector(_shared_collect, key="bank_shared")
        else:
            # all of them, not just the one score_many guards on: a future
            # call site guarding on its own attribute must get None, not
            # AttributeError only in the registry=False configuration
            self._m_shard_rows = self._m_shard_pad = self._m_shard_reqs = None
            self._m_bucket_calls = self._m_bucket_reqs = None
            self._m_bucket_batch = self._m_quant_fallback = None

    # -------------------------- construction -------------------------- #

    @classmethod
    def from_models(cls, models: Dict[str, Any], **kwargs) -> "ModelBank":
        bank = cls(**kwargs)
        for name, model in models.items():
            try:
                entry, reason = _extract_entry(name, model)
            except Exception as exc:
                # one malformed model must not abort bank construction for
                # the whole collection (this runs at server startup and in
                # /reload); the model still serves via the per-model path
                logger.warning(
                    "Model %r: bank extraction failed; per-model path",
                    name,
                    exc_info=True,
                )
                bank.fallback[name] = f"extraction error: {type(exc).__name__}: {exc}"
                continue
            if entry is None:
                logger.debug("Model %r not bankable (%s); per-model path", name, reason)
                bank.fallback[name] = reason or "not bankable"
                continue
            key = json.dumps(
                [
                    entry.registry_type,
                    entry.kind,
                    entry.n_features,
                    entry.lookback,
                    entry.target_offset,
                    entry.compute_dtype,
                    sorted(entry.factory_kwargs.items()),
                    # storage dtype is part of the bucket identity: an
                    # fp32 and a bf16 stack are different HBM layouts
                    # compiled into different programs
                    bank.bank_dtype,
                    # members of one trunk share a bucket; another trunk
                    # is other leaves, so another bucket
                    entry.shared[0] if entry.shared else None,
                ],
                default=str,
            )
            bucket = bank._buckets.get(key)
            if bucket is None:
                bucket = bank._buckets[key] = _Bucket(
                    entry.kind,
                    entry.n_features,
                    entry.factory_kwargs,
                    compute_dtype=entry.compute_dtype,
                    registry_type=entry.registry_type,
                    lookback=entry.lookback,
                    target_offset=entry.target_offset,
                    mesh=bank.mesh,
                    bank_dtype=bank.bank_dtype,
                    kernel_mode=bank.kernel_mode,
                    shared=entry.shared,
                )
            bank._index[name] = (key, len(bucket.names))
            bucket.add(entry)
            tags = getattr(models[name], "tags_", None)
            bank._tags[name] = (
                list(tags) if tags else [f"feature-{i}" for i in range(entry.n_features)]
            )
        # per-bucket finalize isolation: one bucket whose stack/compile
        # fails (OOM on a huge stack, a factory bug for one architecture,
        # an injected fault) must not abort bank construction — its
        # members fall back to the per-model scoring path with the reason
        # surfaced through coverage()/GET /models, and every OTHER bucket
        # still serves from HBM
        for key in list(bank._buckets):
            bucket = bank._buckets[key]
            try:
                bucket.finalize()
                if bucket.quantize_error is not None:
                    # the bucket SERVES (fp32 storage), but the capacity
                    # win was lost for its members — counted and surfaced
                    # so an operator sees a quarter-full chip coming
                    bank.quantize_fallbacks[bucket.label] = bucket.quantize_error
                    if bank._m_quant_fallback is not None:
                        bank._m_quant_fallback.labels(bucket.label).inc()
            except Exception as exc:
                logger.error(
                    "Bucket %s finalize FAILED (%d member(s) fall back to "
                    "the per-model path): %s",
                    bucket.label, len(bucket.names), exc, exc_info=True,
                )
                del bank._buckets[key]
                reason = f"bucket finalize failed: {type(exc).__name__}: {exc}"
                bank.finalize_failures[bucket.label] = reason
                for name in bucket.names:
                    bank._index.pop(name, None)
                    bank._tags.pop(name, None)
                    bank.fallback[name] = reason
        # what the device has left once every bucket is placed bounds a
        # call of a bucket whose program's bytes go with its rows
        stats = jax.devices()[0].memory_stats() or {}
        if "bytes_limit" in stats:
            free = int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))
            for bucket in bank._buckets.values():
                bucket._free_bytes = free
        if bank._index:
            logger.info(
                "Model bank: %d models in %d bucket(s)%s",
                len(bank._index),
                len(bank._buckets),
                ""
                if bank.mesh is None
                else f", sharded over {bank.mesh.devices.size} device(s)",
            )
        # coverage is an operator signal: at 10k models a DEBUG line per
        # fallback is invisible — surface the aggregate loudly (and per
        # model through /models; see views.list_models)
        if bank.fallback:
            logger.warning(
                "Model bank: %d/%d model(s) NOT banked (per-model scoring "
                "path): %s",
                len(bank.fallback),
                len(bank.fallback) + len(bank._index),
                ", ".join(
                    f"{n} ({r})" for n, r in sorted(bank.fallback.items())[:10]
                )
                + (" ..." if len(bank.fallback) > 10 else ""),
            )
        return bank

    def release(self) -> None:
        """Drop every bucket, and with them the stacked weights, the
        scalers and any shared trunk on the device: the bank scores nothing
        after this. For a server that is being cleaned up: its ``app``
        outlives ``cleanup()`` in caches the bank cannot reach (aiohttp
        keeps the last 1024 applications in its middleware cache), and the
        device memory must not stay with it (a week-long trunk and its bank
        are 9.3 GB: PERF.md section 6, PR 35)."""
        self._buckets.clear()
        self._index.clear()

    def coverage(self) -> Dict[str, Any]:
        """Operator-facing bank coverage summary."""
        return {
            "banked": len(self._index),
            "fallback": dict(self.fallback),
            "n_buckets": len(self._buckets),
            # how many chips the stacked state is sharded over (1 =
            # single-device bank) — lets an operator confirm an 8-chip
            # server is actually using its slice from /models alone
            "devices": int(self.mesh.devices.size) if self.mesh is not None else 1,
            "bank_dtype": self.bank_dtype,
            "kernel": self.kernel_mode,
            # where the stacked weights actually sit, read from the
            # arrays' own devices (None for an empty bank); one leaf per
            # bucket — finalize() places a bucket's whole stack together
            "device": device_block(
                [jax.tree.leaves(b.params)[0] for b in self._buckets.values()]
            ),
        }

    def capacity_stats(self) -> Dict[str, Any]:
        """Operator-facing HBM capacity summary (served in ``/stats`` as
        ``bank_capacity``; the north-star check records it).

        ``weight_bytes`` is the stacked params' storage footprint at the
        effective dtype mix; ``fp32_bytes`` the same stack at fp32 —
        their ratio is the capacity win low-precision storage bought.
        Buckets whose quantization fell back to fp32 appear in
        ``quantize_fallbacks`` and drag the ratio toward 1."""
        # a shared leaf counts once, whatever the members that use it
        shared = sum(b.shared_bytes for b in self._buckets.values())
        total = sum(b.weight_bytes for b in self._buckets.values()) + shared
        fp32 = sum(b.weight_bytes_fp32 for b in self._buckets.values()) + shared
        by_dtype: Dict[str, int] = {}
        for b in self._buckets.values():
            d = b.effective_dtype
            by_dtype[d] = by_dtype.get(d, 0) + b.weight_bytes
        if shared:
            by_dtype["shared"] = shared  # at the dtypes their artifact holds
        members = len(self._index)
        bpm = total / members if members else None
        return {
            "dtype": self.bank_dtype,
            "kernel": self.kernel_mode,
            "members": members,
            "weight_bytes": total,
            "shared_bytes": shared,
            "weight_bytes_by_dtype": by_dtype,
            "fp32_bytes": fp32,
            "capacity_ratio": round(fp32 / total, 3) if total else None,
            "bytes_per_member": round(bpm, 1) if bpm is not None else None,
            "models_per_gb": (
                round(1024**3 / bpm, 1) if bpm else None
            ),
            "quantize_fallbacks": dict(self.quantize_fallbacks),
        }

    def flops_stats(self) -> Dict[str, Any]:
        """Static per-bucket FLOPs table (cost model's numerator feed,
        observability/cost.py): bucket label -> the analytic forward
        FLOPs per routed row computed once at finalize, plus the shape
        facts a capacity advisor needs. Finalize-failed buckets are
        absent — they never burn device time."""
        out: Dict[str, Any] = {}
        for b in self._buckets.values():
            out[b.label] = {
                "flops_per_row": float(b.flops_per_row),
                "flops_method": b.flops_method,
                "params_per_member": int(b.params_per_member),
                "members": len(b.names),
                "kind": b.kind,
                "registry_type": b.registry_type,
                "n_features": int(b.n_features),
                "lookback": int(b.lookback),
                "weight_bytes": int(b.weight_bytes),
                "effective_dtype": b.effective_dtype,
                # sequence fast-path provenance (ops/seq_scan.py):
                # which layout/kernel the compiled scoring program uses
                "seq_layout": getattr(b, "seq_layout", "legacy"),
                "seq_kernel": getattr(b, "seq_kernel", "jnp"),
            }
        return out

    def pipeline_stats(self) -> Dict[str, Any]:
        """Operator-facing pipeline/arena summary (served in ``/stats``
        as ``bank_pipeline``; the north-star check snapshots it)."""
        pipe = self._pipe
        wall = pipe["wall_s"]
        return {
            "inflight_window": self._inflight_window,
            "arena": self.arena.stats(),
            "overlap": {
                "calls": pipe["calls"],
                "multi_group_calls": pipe["multi_group_calls"],
                "device_busy_s": round(pipe["device_busy_s"], 6),
                "wall_s": round(wall, 6),
                "overlap_ratio": (
                    round(pipe["device_busy_s"] / wall, 4) if wall > 0 else None
                ),
            },
        }

    def placement(self) -> Dict[str, Any]:
        """The live model->shard assignment (placement control plane's
        input; served through ``GET /placement``): per bucket, the
        members in stack order — member i of a bucket lives on shard
        ``i // shard_size`` (contiguous blocks along the stacked model
        axis, ``_Bucket.finalize``). Single-device banks report one
        shard holding everything."""
        buckets = []
        for key, b in self._buckets.items():
            buckets.append(
                {
                    "bucket": b.label,
                    "key": key,
                    "n_shards": int(b.n_shards),
                    "shard_size": int(b.shard_size or len(b.names)),
                    "members": list(b.names),
                }
            )
        return {
            "bank_generation": int(self.generation),
            "devices": (
                int(self.mesh.devices.size) if self.mesh is not None else 1
            ),
            "buckets": buckets,
        }

    @staticmethod
    def _warmup_grid_env(name: str, default: Tuple[int, ...]) -> Tuple[int, ...]:
        raw = os.environ.get(name)
        if not raw:
            return default
        try:
            vals = tuple(int(v) for v in raw.split(",") if v.strip())
        except ValueError:
            logger.warning(
                "%s must be comma-separated integers, got %r; using %s",
                name, raw, default,
            )
            return default
        return vals or default

    def warmup(self, rows=None, batch_sizes=None) -> int:
        """Pre-compile each bucket's scoring program over a (B, T) shape
        grid so neither the first request NOR the first coalesced burst
        pays an XLA compile (seconds) — run at server startup, off the
        request path. Returns the number of buckets warmed.

        ``rows`` is an int or sequence of row counts (default env
        ``GORDO_WARMUP_ROWS``, else 256); ``batch_sizes`` a sequence of
        batch widths (default env ``GORDO_WARMUP_BATCHES``, else ``1``).
        Both are rounded up to the pow2 ladder score_many actually
        dispatches, and the grid is their cross product — with the
        persistent compilation cache (``GORDO_COMPILE_CACHE_DIR``) the
        grid compiles once per fleet, not once per restart."""
        if rows is None:
            row_list = self._warmup_grid_env("GORDO_WARMUP_ROWS", (256,))
        elif isinstance(rows, int):
            row_list = (rows,)
        else:
            row_list = tuple(rows)
        if batch_sizes is None:
            batch_sizes = self._warmup_grid_env("GORDO_WARMUP_BATCHES", (1,))
        batches = sorted({_next_pow2(max(1, int(b))) for b in batch_sizes})
        warmed = 0
        total_shapes = 0
        for bucket in self._buckets.values():
            shapes = sorted(
                {
                    # EXACTLY score_many's T computation — warming any
                    # other shape leaves the dispatched one cold and
                    # compiles a dead program
                    (bucket.rows_per_call(r, self.max_rows), B)
                    for r in row_list
                    for B in batches
                }
            )
            # a compile failure here propagates: the programs warmed are
            # the ones requests dispatch, so a bucket that cannot compile
            # cannot serve (the server's background warm-up lands it in
            # app["warmup_future"], which /healthz reports)
            for T, B in shapes:
                if self.mesh is None:
                    X = np.zeros((B, T, bucket.n_features), np.float32)
                    out = bucket.score_batch(
                        np.zeros((B,), np.int32), X, X, n_valid=np.full((B,), T)
                    )
                else:
                    D = bucket.n_shards
                    X = np.zeros((D, B, T, bucket.n_features), np.float32)
                    out = bucket.score_batch_sharded(
                        np.zeros((D, B), np.int32), X, X
                    )
                # dispatch is async: a kernel that compiles but faults on
                # the device would otherwise surface in the first request
                jax.block_until_ready(out)
            warmed += 1
            total_shapes += len(shapes)
        if warmed:
            logger.info(
                "Model bank warmed: %d bucket(s) pre-compiled over %d "
                "(rows, batch) shape(s)",
                warmed, total_shapes,
            )
        return warmed

    def __contains__(self, name: str) -> bool:
        return name in self._index

    def __len__(self) -> int:
        return len(self._index)

    def batch_limit(self, name: str, rows: int) -> Optional[int]:
        """Requests like this one (``rows`` rows for ``name``) that one
        call may carry, where ``name``'s bucket bounds its calls by the
        bytes its program needs (``_Bucket.max_batch``); else ``None``."""
        entry = self._index.get(name)
        if entry is None:
            return None
        bucket = self._buckets[entry[0]]
        if bucket.shared is None:
            return None
        return bucket.max_batch(bucket.rows_per_call(rows, self.max_rows))

    @property
    def n_buckets(self) -> int:
        return len(self._buckets)

    # --------------------------- scoring ------------------------------ #

    def score(
        self,
        name: str,
        X: np.ndarray,
        y: Optional[np.ndarray] = None,
        trace=None,
    ) -> ScoreResult:
        """Score one request (convenience wrapper over ``score_many``)."""
        return self.score_many(
            [(name, X, y)], traces=None if trace is None else [trace]
        )[0]

    def score_many(
        self,
        requests: Sequence[Tuple[str, np.ndarray, Optional[np.ndarray]]],
        traces: Optional[Sequence[Any]] = None,
        deadline: Optional[Deadline] = None,
        return_exceptions: bool = False,
    ) -> List[Any]:
        """Score a heterogeneous batch of (name, X, y) requests.

        Requests are grouped by bucket and each group runs through a
        three-stage software pipeline — :meth:`_host_prep` (coalesce +
        pad into arena scratch buffers), :meth:`_dispatch` (the XLA call,
        returned WITHOUT fetching so JAX async dispatch keeps the device
        queue full), :meth:`_postprocess` (fence + fetch + reassemble) —
        with up to ``GORDO_BANK_INFLIGHT`` (default 2) groups in flight:
        while group k executes on the device, group k+1 is padded on the
        host and group k-1's outputs are fetched. Heterogeneous
        multi-bucket batches no longer serialize host and device work;
        outputs are bitwise identical to the serial (window=1) order.

        ``deadline`` (optional, the batch's earliest
        :class:`~gordo_components_tpu.resilience.deadline.Deadline`) is
        checked BETWEEN bucket-group dispatches: a multi-group call whose
        budget runs out mid-way raises :class:`DeadlineExceeded` instead
        of burning device time on groups nobody is still waiting for.
        The caller (the batching engine) resolves each pending against
        its own deadline — expired ones 504, the rest re-score
        individually.

        ``traces`` (optional, request-aligned; entries may be None) are
        :class:`~gordo_components_tpu.observability.tracing.Trace`
        objects to record the hot-path stage spans into — ``coalesce``,
        ``pad``, ``device_execute`` (dispatch -> fenced-ready, the
        group's device window; children ``enqueue``, ``device_wait``),
        ``postprocess`` (children ``fetch``, ``reassemble``), plus one
        ``pipeline_overlap`` span per multi-group call carrying the
        measured overlap ratio. The stages run either way (each is a
        profiler annotation and two clock reads; see the tracing hot-loop
        overhead guard); only the span appends depend on a trace.

        ``return_exceptions`` (the batching engine's mode): instead of
        raising on the first failure, a failed bucket group's requests
        get their exception as their result-list entry while every other
        group still returns real :class:`ScoreResult` objects — one
        poisoned group no longer discards a whole coalesced batch.
        """
        results: List[Any] = [None] * len(requests)
        errors: Dict[int, Exception] = {}
        by_bucket: Dict[str, List[int]] = {}
        for ri, (name, X, _y) in enumerate(requests):
            entry = self._index.get(name)
            if entry is None:
                exc = KeyError(f"Model {name!r} not in bank")
                if not return_exceptions:
                    raise exc
                errors[ri] = exc
                continue
            by_bucket.setdefault(entry[0], []).append(ri)

        # a bucket with shared leaves bounds its call by bytes: a longer
        # group goes as several calls, one at a time
        groups = []
        for key, req_ids in by_bucket.items():
            bucket = self._buckets[key]
            limit = None
            if bucket.shared is not None:
                limit = bucket.max_batch(bucket.rows_per_call(
                    max(np.shape(requests[ri][1])[0] for ri in req_ids), self.max_rows
                ))
            step = limit or len(req_ids)
            groups += [(key, req_ids[i : i + step]) for i in range(0, len(req_ids), step)]
        n_groups = len(groups)
        window = self._inflight_window
        inflight: "deque[_GroupRun]" = deque()
        t_call = time.monotonic()
        device_busy = 0.0
        last_ready = t_call

        def poll_inflight() -> None:
            # stamp device completions at host stage boundaries: without
            # this, a group's device window would only close at its
            # fence — absorbing any host work run in between and pinning
            # the measured overlap ratio near 1.0 no matter how long the
            # device actually idled
            if inflight:
                now = time.monotonic()
                for r in inflight:
                    r.poll_ready(now)

        def finish(run: _GroupRun) -> None:
            nonlocal device_busy, last_ready
            poll_inflight()
            ok = True
            try:
                self._postprocess(run, requests, results)
            except Exception as exc:
                ok = False
                if not return_exceptions:
                    raise
                for ri in run.req_ids:
                    errors[ri] = exc
            # window end: the earliest OBSERVED completion — the polled
            # stamp when the device finished during host work, the fence
            # time when the host genuinely waited (then the fence end IS
            # the completion). Windows never overlap: queue wait behind
            # the previous group's execution must not be counted twice.
            t_done = run.t_device_done or run.t_ready
            window = max(0.0, t_done - max(run.t_dispatch, last_ready))
            device_busy += window
            last_ready = max(last_ready, t_done)
            if self.ledger is not None:
                self._account_group(run, results, window, ok)

        try:
            for gi, (key, req_ids) in enumerate(groups):
                if deadline is not None and deadline.expired():
                    # stop between group dispatches: the budget the engine
                    # admitted this batch under has run out, and the next
                    # XLA call would compute answers nobody reads
                    exc = DeadlineExceeded(
                        f"batch deadline expired before all {n_groups} "
                        f"bucket group(s) dispatched "
                        f"(budget {deadline.budget_s * 1e3:.0f}ms)"
                    )
                    if not return_exceptions:
                        raise exc
                    for _key, rids in groups[gi:]:
                        for ri in rids:
                            errors[ri] = exc
                    break
                run = None
                try:
                    run = self._host_prep(key, req_ids, requests, traces)
                    if run.bucket.shared is not None:
                        # its program's bytes are sized to what the device
                        # has left: nothing else in flight beside it
                        while inflight:
                            finish(inflight.popleft())
                    self._dispatch(run)
                except Exception as exc:
                    # the failed group's own buffers (host_prep cleans up
                    # after itself, but a dispatch failure leaves them on
                    # the run) go back to the arena either way
                    if run is not None:
                        run.release(self.arena)
                    if not return_exceptions:
                        raise
                    for ri in req_ids:
                        errors[ri] = exc
                    continue
                inflight.append(run)
                self._inflight_now = len(inflight)
                poll_inflight()  # completions during this group's prep
                if len(inflight) >= window:
                    finish(inflight.popleft())
                    self._inflight_now = len(inflight)
            while inflight:
                finish(inflight.popleft())
                self._inflight_now = len(inflight)
        except BaseException:
            # an aborted call must not leak arena buffers or abandon
            # device work mid-flight: fence and release every in-flight
            # group before the exception propagates, so no buffer is
            # ever handed to a later request while still referenced
            for run in inflight:
                with contextlib.suppress(Exception):
                    jax.block_until_ready(run.out)
                run.release(self.arena)
            self._inflight_now = 0
            raise
        self._inflight_now = 0

        self._pipe["calls"] += 1
        if n_groups > 1:
            t_end = time.monotonic()
            wall = t_end - t_call
            self._pipe["multi_group_calls"] += 1
            self._pipe["wall_s"] += wall
            self._pipe["device_busy_s"] += device_busy
            if traces is not None:
                ratio = device_busy / wall if wall > 0 else 0.0
                for ri, tr in enumerate(traces):
                    # only requests that actually rode the pipeline: a
                    # never-grouped (unknown-model) or deadline-dropped
                    # request must not show device work in its trace
                    if tr is None or ri in errors:
                        continue
                    tr.add_span(
                        "pipeline_overlap", t_call, t_end,
                        groups=n_groups, window=window,
                        device_busy_ms=round(device_busy * 1e3, 3),
                        overlap_ratio=round(ratio, 4),
                    )
        for ri, exc in errors.items():
            results[ri] = exc
        return results

    def _host_prep(
        self,
        key: str,
        req_ids: List[int],
        requests: Sequence[Tuple[str, np.ndarray, Optional[np.ndarray]]],
        traces: Optional[Sequence[Any]],
    ) -> _GroupRun:
        """Pipeline stage 1 — ``coalesce`` + ``pad`` (pure host work), each
        a :class:`~gordo_components_tpu.observability.tracing.stage`: the
        spans land in the group's traced requests, the seconds in the
        goodput ledger."""
        bucket = self._buckets[key]
        run = _GroupRun()
        run.bucket = bucket
        run.req_ids = req_ids
        run.off = bucket.offset
        if traces is not None:
            run.traces = [
                t for t in (traces[ri] for ri in req_ids) if t is not None
            ]
        with stage(
            "coalesce", *run.traces, bucket=bucket.label, requests=len(req_ids)
        ) as coalesce:
            chunks, T = self._coalesce(run, requests)
            coalesce.attributes["chunks"] = run.n_chunks
        with stage("pad", *run.traces) as pad:
            self._pad(run, chunks, T, requests)
        run.coalesce_s, run.pad_s = coalesce.seconds, pad.seconds
        return run

    def _coalesce(
        self,
        run: _GroupRun,
        requests: Sequence[Tuple[str, np.ndarray, Optional[np.ndarray]]],
    ) -> Tuple[List[Tuple[int, np.ndarray, np.ndarray]], int]:
        """Validate the group's requests and chunk long ones (sequence
        chunks OVERLAP by the warm-up so no output rows are lost at chunk
        boundaries). Returns the chunks and the rows-per-call ``T``."""
        bucket, req_ids = run.bucket, run.req_ids
        F = bucket.n_features
        off = run.off
        rows = [np.asarray(requests[ri][1], np.float32) for ri in req_ids]
        mrows = self.model_rows
        heat = self.heat
        # the heat accountant's hot-path mailbox, cached once per group
        # (observability/heat.py): one dict get+set per request below,
        # decay math amortized into the accountant's sampling cadence
        pend = heat.pending if heat is not None else None
        for ri, X in zip(req_ids, rows):
            if X.ndim != 2 or X.shape[1] != F:
                raise ValueError(
                    f"Request for {requests[ri][0]!r}: expected (rows, {F}), "
                    f"got {X.shape}"
                )
            if X.shape[0] == 0:
                raise ValueError(f"Request for {requests[ri][0]!r}: empty input")
            if X.shape[0] <= off:
                raise ValueError(
                    f"Request for {requests[ri][0]!r}: need more than "
                    f"{off} rows (sequence warm-up), got {X.shape[0]}"
                )
            if mrows is not None:
                # the planner's per-model load signal (rebalancing acts
                # on rows, the unit the shard counters already speak)
                name = requests[ri][0]
                mrows[name] = mrows.get(name, 0) + X.shape[0]
                if pend is not None:
                    pend[name] = pend.get(name, 0.0) + X.shape[0]
            elif pend is not None:
                name = requests[ri][0]
                pend[name] = pend.get(name, 0.0) + X.shape[0]
        # rows-per-call stays a power of two and never exceeds max_rows
        # (but must always cover at least one window + one output row); a
        # bucket of whole sequences covers its longest request instead
        T = bucket.rows_per_call(max(x.shape[0] for x in rows), self.max_rows)
        step = T - off
        chunks: List[Tuple[int, np.ndarray, np.ndarray]] = []
        # per-request reassembly plan, built once here instead of the
        # post-hoc per_req/valid dict churn the reassembly loop used to
        # re-derive per call (each chunk yields rows [start+off, start+T))
        req_plans: List[Tuple[int, np.ndarray, List[int], List[int], int]] = []
        for ri, X in zip(req_ids, rows):
            yv = requests[ri][2]
            if yv is None:
                Y = X
            else:
                Y = np.asarray(yv, np.float32)
                if Y.shape != X.shape:
                    raise ValueError(
                        f"Request for {requests[ri][0]!r}: y shape {Y.shape} "
                        f"must match X shape {X.shape}"
                    )
            cis: List[int] = []
            valids: List[int] = []
            for start in range(0, X.shape[0] - off, step):
                xc = X[start : start + T]
                cis.append(len(chunks))
                valids.append(xc.shape[0] - off)
                chunks.append((ri, xc, Y[start : start + T]))
            # the already-converted array rides into
            # ScoreResult.model_input, so the response path stops paying
            # a second np.asarray(X, float32) per request
            req_plans.append((ri, X, cis, valids, X.shape[0] - off))
        run.req_plans = req_plans
        run.n_chunks = len(chunks)
        return chunks, T

    def _pad(
        self,
        run: _GroupRun,
        chunks: List[Tuple[int, np.ndarray, np.ndarray]],
        T: int,
        requests: Sequence[Tuple[str, np.ndarray, Optional[np.ndarray]]],
    ) -> None:
        """Assemble the pow2-padded batch arrays in arena scratch buffers
        (zeroing only the pad tail of reused buffers), routing each chunk
        to the shard that owns its model under a mesh."""
        bucket, req_ids = run.bucket, run.req_ids
        F = bucket.n_features
        if self._m_shard_rows is not None:
            # per-bucket coalescing visibility: dispatches, request
            # fan-in, and the coalesced batch-size distribution
            blabel = bucket.label
            self._m_bucket_calls.labels(blabel).inc()
            self._m_bucket_reqs.labels(blabel).inc(len(req_ids))
            self._m_bucket_batch.labels(blabel).record(float(len(chunks)))
        try:
            if self.mesh is None:
                B = _next_pow2(len(chunks))
                Xb, x_clean = self.arena.acquire((B, T, F))
                run._bufs = (Xb,)  # attached NOW: a failed second acquire
                # must not strand the first buffer outside the arena
                Yb, y_clean = self.arena.acquire((B, T, F))
                run._bufs = (Xb, Yb)
                idx = np.zeros((B,), np.int32)
                # slots[ci]: where chunk ci landed in the batched output —
                # a flat index here, a (device, local-slot) pair under
                # mesh routing
                slots: List[Any] = list(range(len(chunks)))
                routed0 = 0
                for ci, (ri, xc, yc) in enumerate(chunks):
                    n = xc.shape[0]
                    Xb[ci, :n] = xc
                    Yb[ci, :n] = yc
                    if n < T:
                        if not x_clean:
                            Xb[ci, n:] = 0.0
                        if not y_clean:
                            Yb[ci, n:] = 0.0
                    idx[ci] = self._index[requests[ri][0]][1]
                    routed0 += n
                if not x_clean:
                    Xb[len(chunks):] = 0.0
                if not y_clean:
                    Yb[len(chunks):] = 0.0
                if self._m_shard_rows is not None:
                    self._m_shard_rows.labels("0").inc(routed0)
                    self._m_shard_pad.labels("0").inc(B * T - routed0)
                    self._m_shard_reqs.labels("0").inc(len(chunks))
                run.routed_rows = routed0
                run.total_rows = B * T
                run.shard_rows = (("0", routed0, B * T - routed0),)
                run.score_fn = bucket.score_batch
                if bucket.shared is not None:
                    n_valid = np.zeros((B,), np.int32)
                    n_valid[: len(chunks)] = [xc.shape[0] for _ri, xc, _yc in chunks]
                    run.score_fn = functools.partial(bucket.score_batch, n_valid=n_valid)
            else:
                # route each chunk to the shard owning its model: the
                # stacked leading axis is split into n_shards contiguous
                # blocks of shard_size (parallel/mesh.shard_model_axis)
                D, shard = bucket.n_shards, bucket.shard_size
                per_dev: List[List[int]] = [[] for _ in range(D)]
                for ci, (ri, _xc, _yc) in enumerate(chunks):
                    per_dev[self._index[requests[ri][0]][1] // shard].append(ci)
                Bl = _next_pow2(max(1, max(len(c) for c in per_dev)))
                Xb, x_clean = self.arena.acquire((D, Bl, T, F))
                run._bufs = (Xb,)
                Yb, y_clean = self.arena.acquire((D, Bl, T, F))
                run._bufs = (Xb, Yb)
                idx = np.zeros((D, Bl), np.int32)
                slots = [None] * len(chunks)
                shard_rows: List[Tuple[str, int, int]] = []
                for d, dev_cis in enumerate(per_dev):
                    routed_d = 0
                    for j, ci in enumerate(dev_cis):
                        ri, xc, yc = chunks[ci]
                        n = xc.shape[0]
                        Xb[d, j, :n] = xc
                        Yb[d, j, :n] = yc
                        if n < T:
                            if not x_clean:
                                Xb[d, j, n:] = 0.0
                            if not y_clean:
                                Yb[d, j, n:] = 0.0
                        idx[d, j] = self._index[requests[ri][0]][1] - d * shard
                        slots[ci] = (d, j)
                        routed_d += n
                    if not x_clean:
                        Xb[d, len(dev_cis):] = 0.0
                    if not y_clean:
                        Yb[d, len(dev_cis):] = 0.0
                    if self._m_shard_rows is not None:
                        # every device executes Bl * T rows regardless of
                        # how many are real: the routed/padded split is the
                        # per-shard skew an operator needs to SEE (a hot
                        # model concentrates routed rows on one shard while
                        # the rest burn the same FLOPs on padding)
                        sl = str(d)
                        self._m_shard_rows.labels(sl).inc(routed_d)
                        self._m_shard_pad.labels(sl).inc(Bl * T - routed_d)
                        self._m_shard_reqs.labels(sl).inc(len(dev_cis))
                    shard_rows.append((str(d), routed_d, Bl * T - routed_d))
                run.routed_rows = sum(r for _s, r, _p in shard_rows)
                run.total_rows = D * Bl * T
                run.shard_rows = tuple(shard_rows)
                run.score_fn = bucket.score_batch_sharded
        except BaseException:
            run.release(self.arena)
            raise
        run.Xb, run.Yb, run.idx = Xb, Yb, idx
        run.slots = slots

    def _dispatch(self, run: _GroupRun) -> None:
        """Pipeline stage 2 — async device dispatch.

        The XLA call returns device arrays WITHOUT fetching them (JAX
        async dispatch), so the host is free to pad the next group and
        fetch the previous one while this group executes. ``enqueue`` is
        the call itself (argument transfer and launch); the
        ``device_execute`` span it opens closes at :meth:`_postprocess`'s
        fence, the end of ``device_wait``."""
        _FP_SCORE.fire()
        run.exec_span = group_span(
            "device_execute", run.traces, time.monotonic(), bucket=run.bucket.label
        )
        with stage("enqueue", *run.traces, parent=run.exec_span) as enqueue:
            run.out = run.score_fn(run.idx, run.Xb, run.Yb)
        run.t_dispatch = enqueue.start

    def _postprocess(
        self,
        run: _GroupRun,
        requests: Sequence[Tuple[str, np.ndarray, Optional[np.ndarray]]],
        results: List[Any],
    ) -> None:
        """Pipeline stage 3 — fence (``device_wait``), then ``postprocess``:
        ``fetch``, ``reassemble``; release. The stage boundaries are per
        coalesced GROUP: every traced request in it gets the same span
        timestamps — per-request attribution of the shared batch's cost,
        which is exactly what coalescing makes invisible in a plain
        latency histogram."""
        try:
            wait = stage("device_wait", *run.traces, parent=run.exec_span)
            try:
                with wait:
                    # fence: this group's device window ends HERE (a
                    # device-side error surfaces here too, after the
                    # timestamp, so overlap accounting stays sane)
                    jax.block_until_ready(run.out)
            finally:
                run.t_ready = run.exec_span.end = wait.end
            # a group that fails from here on leaves its ``postprocess``
            # open: Trace.finish closes it as an error
            post = group_span("postprocess", run.traces, run.t_ready)
            with stage("fetch", *run.traces, parent=post):
                # one transfer for all five outputs (device_get batches the
                # D2H copies) instead of five blocking np.asarray round-trips
                outs = jax.device_get(run.out)
            with stage("reassemble", *run.traces, parent=post) as reassemble:
                slots = run.slots
                observed = None
                if len(outs) > 5:  # a bucket with shared leaves (_Bucket.score_batch)
                    outs, observed = outs[:5], stack_observed(outs[5], np.stack)
                    self._count_observed(run, observed)
                for ri, X_conv, cis, valids, n_out in run.req_plans:
                    if len(cis) == 1:
                        vals = _slice_single(outs, slots[cis[0]], n_out)
                    else:
                        vals = _concat_chunks(outs, slots, cis, valids, n_out)
                    results[ri] = ScoreResult(
                        tags=self._tags[requests[ri][0]],
                        model_input=X_conv,
                        model_output=vals[0],
                        diff=vals[1],
                        scaled=vals[2],
                        total_unscaled=vals[3],
                        total_scaled=vals[4],
                        offset=run.off,
                        selections=(
                            None if observed is None
                            else _selection_frames(observed, slots[cis[0]], X_conv.shape[0])
                        ),
                    )
            post.end = reassemble.end
            run.postprocess_s = post.duration_s
        finally:
            run.release(self.arena)

    def _count_observed(self, run: _GroupRun, observed: Dict[str, np.ndarray]) -> None:
        """Counters from the arrays a shared-leaf bucket's program returned
        with this dispatch (executor thread, one dispatch at a time). Each
        array counts valid rows only and is stacked over the layers that
        observed it: a dense layer routes nothing, a layer without an
        indexer selects no keys (it may attend under another's), only a
        state-space mixer scans chunks."""
        counted = [("dispatches", 1), ("rows", run.routed_rows), ("tokens", run.total_rows)]
        if "expert_tokens" in observed:  # (layers, experts): every expert is held
            tokens = observed["expert_tokens"]
            counted += [("expert_tokens", int(tokens.sum())),
                        ("expert_tokens_busiest", int(tokens.max(axis=-1).sum()))]
        if "selections" in observed:  # (layers that select, batch)
            counted += [("key_selections", int(observed["selections"].sum())),
                        ("selection_layers", observed["selections"].shape[0]),
                        ("selection_uses", int(observed["selection_uses"].sum()))]
        if "held_tokens" in observed:  # (routed layers, held experts): one chip's share
            held = observed["held_tokens"]
            layers, top_k = observed["experts"].shape[0], observed["experts"].shape[-1]
            counted += [("routed_pairs", run.routed_rows * top_k * layers),
                        ("held_pairs", int(held.sum())),
                        ("held_tokens_busiest", int(held.max(axis=-1).sum())),
                        ("held_pair_blocks", int(observed["held_blocks"].sum()))]
        if "ssm_chunks" in observed:  # (mixer layers, batch): chunks of each request's valid rows
            counted += [("ssm_layers", observed["ssm_chunks"].shape[0]),
                        ("ssm_chunks", int(observed["ssm_chunks"].sum()))]
        stats = self.shared_stats
        for name, value in counted:
            stats[name] = stats.get(name, 0) + value

    def _account_group(
        self, run: _GroupRun, results: List[Any], window_s: float, ok: bool
    ) -> None:
        """Goodput accounting for one finished group (executor thread;
        observability/goodput.py). The group's device window splits by
        real-vs-pad dispatched rows: the padded share is waste the
        ledger books directly, the useful share is apportioned to the
        group's requests by their row counts (``ScoreResult.device_s``)
        so the HTTP layer can commit it as goodput or waste once each
        request's outcome is known. A failed group's useful share is
        wasted outright — the device computed answers nobody received."""
        led = self.ledger
        total = run.total_rows
        pad_frac = (1.0 - run.routed_rows / total) if total else 0.0
        padded_s = window_s * pad_frac
        useful_s = window_s - padded_s
        led.account_group(
            bucket=run.bucket.label,
            window_s=window_s,
            useful_s=useful_s,
            padded_s=padded_s,
            ok=ok,
            coalesce_s=run.coalesce_s,
            pad_s=run.pad_s,
            postprocess_s=run.postprocess_s,
            shard_rows=run.shard_rows,
        )
        if ok and useful_s > 0.0:
            req_rows = sum(plan[1].shape[0] for plan in run.req_plans)
            if req_rows:
                per_row = useful_s / req_rows
                for ri, X_conv, _cis, _valids, _n_out in run.req_plans:
                    r = results[ri]
                    if isinstance(r, ScoreResult):
                        r.device_s = per_row * X_conv.shape[0]


# --------------------------------------------------------------------- #
# continuous batching
# --------------------------------------------------------------------- #


@dataclass
class _Pending:
    name: str
    X: np.ndarray
    y: Optional[np.ndarray]
    future: asyncio.Future
    enqueued: float  # monotonic seconds at score() submission (required:
    # a forgotten timestamp would record ~uptime into the histograms)
    # request-id propagated from the HTTP layer (client header or
    # server-generated): failures inside the coalesced batch stay
    # traceable to the access-log line that admitted the request
    request_id: Optional[str] = None
    # request-scoped Trace (observability/tracing.py) riding through the
    # queue: the engine records queue_wait at dispatch and the bank
    # records the batch stage spans into it; None when tracing is off
    trace: Optional[Any] = None
    # per-request time budget (resilience/deadline.py): an entry whose
    # deadline passes while it waits in the queue is dropped BEFORE
    # device dispatch and resolved with DeadlineExceeded (HTTP 504) —
    # saturated replicas must spend TPU time only on answers someone is
    # still waiting for; None = no budget, never expires
    deadline: Optional[Deadline] = None
    # QoS identity stamped at admission (qos/classify.py): the fair
    # queue dequeues by qos_class, and sheds/deadline-expiries attribute
    # to the right (tenant, class) in /stats and the per-class ledger
    # cells even when the drop happens long after the HTTP layer let go
    tenant: str = "default"
    qos_class: str = "interactive"


class EngineOverloaded(Exception):
    """The engine's queue is full: offered load exceeds capacity.

    Carries ``retry_after_s`` — a drain-time estimate the HTTP layer
    surfaces as ``Retry-After`` on its 429 (views.py)."""

    def __init__(self, depth: int, retry_after_s: float):
        self.depth = depth
        self.retry_after_s = retry_after_s
        super().__init__(
            f"scoring queue full ({depth} pending); retry in ~{retry_after_s:.1f}s"
        )


class BatchingEngine:
    """Coalesce concurrent scoring requests into batched bank calls.

    Work-conserving: when the loop takes a request it drains whatever is
    already queued and dispatches at once, so a batch is what arrived
    while the last call was out (up to ``max_batch``, or the bucket's
    ``batch_limit``): one XLA dispatch for all of them. A lone request at
    an idle engine is never held for company. ``flush_ms`` > 0 (default
    0) is the opt-in trade of latency for batch size: the batch then
    stays open that long after its first request. XLA execution runs in
    a thread-pool executor so the event loop keeps accepting requests —
    continuous batching in the LLM-serving sense, applied to anomaly
    scoring. ``stats["requests_behind"]`` counts the requests that were
    already queued when the loop came back, i.e. met a busy engine.

    Under the worker pool (a shared ``dispatch_lock``) each engine
    dispatches at once as well; a call waiting for another engine's lock
    shows as ``handoff``, and what arrives meanwhile forms that engine's
    next batch. Collecting while the lock is held would need a wake-up
    across loops, which this engine does not have.

    Backpressure: the queue is bounded at ``max_queue`` (default
    ``8 * max_batch``). When it is full, ``score()`` raises
    :class:`EngineOverloaded` immediately instead of enqueueing — offered
    load past capacity sheds with a 429 at the HTTP layer rather than
    growing an unbounded queue whose every waiter times out. Sheds are
    counted in ``stats["shed"]``.
    """

    def __init__(
        self,
        bank: ModelBank,
        max_batch: int = 64,
        flush_ms: float = 0.0,
        max_queue: Optional[int] = None,
        registry=None,
        dispatch_lock=None,
        class_weights=None,
    ):
        self.bank = bank
        # multi-worker serving (server/workers.py): each worker loop
        # runs its OWN engine over the ONE shared bank, and this shared
        # threading.Lock serializes their bank calls on the executor
        # threads — the device was never going to run two batches at
        # once anyway, and per-worker engines mean a request never pays
        # a cross-loop hop (measured at multiple GIL-switch intervals
        # per request) while XLA's GIL release lets the other workers
        # parse/coalesce DURING a dispatch. None (the default) is the
        # classic single-engine layout with zero added work.
        self.dispatch_lock = dispatch_lock
        self.max_batch = int(max_batch)
        self.flush_s = float(flush_ms) / 1e3
        if max_queue is None:
            max_queue = 8 * self.max_batch
        if int(max_queue) <= 0:
            raise ValueError(f"max_queue must be positive, got {max_queue!r}")
        self.max_queue = int(max_queue)
        # weighted-fair queue (qos/fair.py): duck-compatible with the
        # asyncio.Queue it replaced — per-class virtual-time dequeue so
        # a batch-class flood cannot starve interactive traffic, and
        # deadline-ordered pops inside each class. With every request in
        # the default class (no QoS config) this degenerates to FIFO.
        from gordo_components_tpu.qos.fair import WeightedFairQueue, parse_weights

        if class_weights is None:
            class_weights = parse_weights()
        self._queue: "WeightedFairQueue" = WeightedFairQueue(class_weights)
        self._task: Optional[asyncio.Task] = None
        # the loop that owns the queue + consumer task, captured at
        # start(): every engine-internal future/queue op must happen on
        # THIS loop. Other loops (multi-worker serving, server/workers.py)
        # and plain threads (the shm transport) enter through submit() /
        # score_blocking(), which hop here thread-safely.
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        # group-isolation capability of the current bank (score_many's
        # ``return_exceptions``), probed once per bank object: proxies
        # and stubs with the minimal score_many(requests) signature keep
        # the legacy whole-batch retry path. Held as a weakref: a strong
        # reference would pin a /reload-replaced bank's HBM-resident
        # params (and arena pool) until the next batch re-probes.
        self._partial_bank: Any = None
        self._partial_ok = False
        self.stats = {
            "requests": 0,
            "requests_behind": 0,
            "batches": 0,
            "max_batch_seen": 0,
            "shed": 0,
            "deadline_expired": 0,
        }
        # per-class attribution of the same events (ISSUE 19 satellite:
        # sheds and deadline-expiry drops must name the class/tenant that
        # ate them, retroactively visible in /stats and /metrics)
        from gordo_components_tpu.qos.classify import CLASSES

        self.class_stats = {
            c: {"requests": 0, "shed": 0, "deadline_expired": 0}
            for c in CLASSES
        }
        # queue_wait = submit -> batch dispatch (the wait behind the call
        # in flight, plus the flush_ms window where one is set),
        # service = submit -> result
        from gordo_components_tpu.server.stats import LatencyHistogram

        # registry default: inherit the bank's (already resolved there; a
        # bank built with registry=False propagates "uninstrumented").
        # The engine's own counters stay in the plain ``stats`` dict and
        # are exposed through a read-at-render-time collector, so the
        # scrape endpoint and /stats read the SAME integers — no mirrored
        # counters, no drift, zero extra work on the hot loop.
        if registry is None:
            registry = getattr(bank, "registry", None)
        elif registry is False:
            registry = None
        self.registry = registry
        if registry is not None:
            self.queue_wait = registry.histogram(
                "gordo_engine_queue_wait_seconds",
                "Submit -> batch-dispatch wait (behind the call in flight, "
                "plus any flush_ms window)",
            ).labels()
            self.service = registry.histogram(
                "gordo_engine_service_seconds",
                "Submit -> result service time through the batching engine",
            ).labels()
            # weakref: the collector lives as long as the registry (which
            # may be process-global); it must not pin a discarded engine —
            # and, through engine.bank, a whole bank's device state
            ref = weakref.ref(self)

            def collect():
                engine = ref()
                return engine._collect_metrics() if engine is not None else ()

            registry.collector(collect, key="bank_engine")
        else:
            self.queue_wait = LatencyHistogram()
            self.service = LatencyHistogram()

    @staticmethod
    def _resolve(fut, result=None, exc=None) -> None:
        """Resolve a pending's future, tolerating a concurrent
        cancellation. Cross-loop/thread submissions carry
        ``concurrent.futures.Future``s whose ``cancel()`` runs on the
        CALLER's thread — a ``done()`` pre-check on the engine loop is
        a TOCTOU, and an unguarded ``set_result`` racing it would raise
        ``InvalidStateError`` out of ``_run_loop`` and kill the engine
        task (every later request would then hang). A cancelled caller
        no longer wants the result; dropping it is the correct
        outcome."""
        try:
            if fut.done():
                return
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(result)
        except (ConcurrentInvalidState, asyncio.InvalidStateError):
            pass

    def _bank_call(self, fn, *args, handoff=None, **kwargs):
        """Run a bank entrypoint (executor thread), serialized by the
        shared dispatch lock when several worker engines front one
        bank. ``handoff`` = (the loop's dispatch time, the batch's
        traces) records the ``handoff`` span: dispatch -> the bank call
        starts on this thread (executor pick-up, and the wait for the
        dispatch lock where there is one)."""
        with self.dispatch_lock or contextlib.nullcontext():
            if handoff is not None:
                dispatched, traces = handoff
                group_span("handoff", traces, dispatched, time.monotonic())
            return fn(*args, **kwargs)

    def _collect_metrics(self):
        """Read-through exposition of the engine's counters/queue state."""
        s = self.stats
        yield (
            "gordo_engine_requests_total", "counter",
            "Requests accepted by the batching engine", {}, s["requests"],
        )
        yield (
            "gordo_engine_requests_behind_total", "counter",
            "Requests already queued when the engine came back from a call "
            "(they met a busy engine)", {}, s["requests_behind"],
        )
        yield (
            "gordo_engine_batches_total", "counter",
            "Coalesced batches dispatched", {}, s["batches"],
        )
        yield (
            "gordo_engine_shed_total", "counter",
            "Requests shed with 429 because the queue was full", {}, s["shed"],
        )
        yield (
            "gordo_engine_deadline_expired_total", "counter",
            "Requests whose deadline expired before device dispatch "
            "(dropped from the batch and answered 504)", {},
            s["deadline_expired"],
        )
        yield (
            "gordo_engine_max_batch_seen", "gauge",
            "Largest coalesced batch observed", {}, s["max_batch_seen"],
        )
        yield (
            "gordo_engine_queue_depth", "gauge",
            "Live scoring-queue depth", {}, self._queue.qsize(),
        )
        yield (
            "gordo_engine_max_queue", "gauge",
            "Queue bound before requests shed", {}, self.max_queue,
        )
        # per-class attribution (ISSUE 19): separate families rather than
        # extra labels on the aggregates above, so existing dashboards'
        # unlabeled series stay byte-identical
        depths = self._queue.depths() if hasattr(self._queue, "depths") else {}
        for cls, cs in self.class_stats.items():
            yield (
                "gordo_engine_class_requests_total", "counter",
                "Requests dispatched by the engine, by priority class",
                {"class": cls}, cs["requests"],
            )
            yield (
                "gordo_engine_class_shed_total", "counter",
                "Full-queue sheds by priority class",
                {"class": cls}, cs["shed"],
            )
            yield (
                "gordo_engine_class_deadline_expired_total", "counter",
                "Deadline-expiry drops by priority class",
                {"class": cls}, cs["deadline_expired"],
            )
            yield (
                "gordo_engine_class_queue_depth", "gauge",
                "Live scoring-queue depth by priority class",
                {"class": cls}, depths.get(cls, 0),
            )

    def qos_snapshot(self) -> dict:
        """Engine-side half of GET /qos: fair-queue state + per-class
        counters (read-through, same dicts the metrics render), plus
        each banked target's feature width — the promotion gate's flood
        driver needs a VALID body shape (a wrong-width flood would end
        as model errors and could trip the quarantine breaker on the
        very canary being gated)."""
        queue = (
            self._queue.snapshot() if hasattr(self._queue, "snapshot") else {}
        )
        widths: Dict[str, int] = {}
        bank = self.bank
        index = getattr(bank, "_index", None)
        buckets = getattr(bank, "_buckets", None)
        if index and buckets:
            for name, (bucket_key, _i) in index.items():
                bucket = buckets.get(bucket_key)
                if bucket is not None:
                    widths[name] = int(bucket.n_features)
        return {
            "queue": queue,
            "max_queue": self.max_queue,
            "classes": {c: dict(cs) for c, cs in self.class_stats.items()},
            "feature_widths": widths,
        }

    def start(self) -> None:
        if self._task is None:
            self._loop = asyncio.get_running_loop()
            self._task = self._loop.create_task(self._run())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
            self._loop = None

    async def submit(
        self,
        name: str,
        X: np.ndarray,
        y: Optional[np.ndarray] = None,
        request_id: Optional[str] = None,
        trace=None,
        deadline: Optional[Deadline] = None,
        tenant: str = "default",
        qos_class: str = "interactive",
    ) -> ScoreResult:
        """:meth:`score` from WHICHEVER event loop is running.

        The engine's queue belongs to the loop that called :meth:`start`
        (the primary serving loop). A multi-worker server
        (server/workers.py) parses requests on N other loops; their
        scoring hops here with ONE ``call_soon_threadsafe`` enqueue of a
        thread-safe ``concurrent.futures.Future``-backed pending — NOT a
        scheduled coroutine per request, whose wake-up jitter was
        measured to spread arrivals across flush windows and collapse
        the coalesced batch size (the whole point of the engine).
        Admission checks (expiry, shed) run caller-side against an
        approximate queue depth; their counters bump on the engine loop.
        Same-loop callers (workers=1, the default) take the direct
        path: one loop identity check, nothing else.
        """
        # local capture: stop() nulls self._loop from another thread —
        # the check and every use below must see ONE value
        loop = self._loop
        if loop is None or asyncio.get_running_loop() is loop:
            return await self.score(
                name, X, y, request_id=request_id, trace=trace,
                deadline=deadline, tenant=tenant, qos_class=qos_class,
            )
        _FP_ENGINE_QUEUE.fire()
        if deadline is not None and deadline.expired():
            self._bump_threadsafe("deadline_expired", qos_class)
            raise DeadlineExceeded(
                f"deadline expired before admission (rid={request_id}, "
                f"budget {deadline.budget_s * 1e3:.0f}ms)"
            )
        depth = self._queue.qsize()  # racy read: shed is a heuristic gate
        if depth >= self.max_queue:
            self._bump_threadsafe("shed", qos_class)
            raise EngineOverloaded(depth, self.drain_estimate(depth))
        fut: Any = ConcurrentFuture()  # thread-safe resolve from the engine loop
        pending = _Pending(
            name, X, y, fut, time.monotonic(), request_id, trace, deadline,
            tenant, qos_class,
        )
        loop.call_soon_threadsafe(self._queue.put_nowait, pending)
        # wrap_future bridges resolution (and caller-side cancellation)
        # back onto this worker's loop
        return await asyncio.wrap_future(fut)

    def _bump_threadsafe(self, key: str, qos_class: Optional[str] = None) -> None:
        """Counter increment from a foreign loop/thread, serialized onto
        the engine's loop so stats never lose increments."""
        loop = self._loop

        def bump():
            self.stats[key] = self.stats[key] + 1
            self._bump_class(qos_class, key)

        try:
            if loop is not None:
                loop.call_soon_threadsafe(bump)
        except RuntimeError:
            pass  # engine loop already closed (shutdown race): drop the count

    def _bump_class(self, qos_class: Optional[str], key: str) -> None:
        """Per-class twin of a ``stats`` bump (engine loop / same-loop
        callers only — cross-loop paths go through _bump_threadsafe)."""
        cs = self.class_stats.get(qos_class)
        if cs is not None and key in cs:
            cs[key] += 1

    def drain_estimate(self, depth: Optional[int] = None) -> float:
        """Honest Retry-After for a shed: backlog batches x per-batch
        EXECUTION time. Service p50 includes queue wait, which under
        saturation IS the backlog — subtract it or the estimate
        double-counts the queue and clients back off max_queue/max_batch
        times longer than the true drain. One estimator for every shed
        path (HTTP, cross-loop, shm) and for the admission controller.
        Never less than one batch: admission asks at depth 0 too."""
        if depth is None:
            depth = self._queue.qsize()
        if self.service.count:
            batch_s = max(
                self.service.percentile(0.5) - self.queue_wait.percentile(0.5),
                1e-3,
            )
        else:
            batch_s = 0.05
        return max(batch_s, depth / self.max_batch * batch_s)

    def score_blocking(
        self,
        name: str,
        X: np.ndarray,
        y: Optional[np.ndarray] = None,
        request_id: Optional[str] = None,
        timeout: Optional[float] = None,
        tenant: str = "default",
        qos_class: str = "interactive",
    ) -> ScoreResult:
        """:meth:`score` from a plain thread (the shared-memory transport
        server, utils/shm_ring.py): blocks the calling thread — never an
        event loop — until the engine resolves the result. Same direct
        thread-safe enqueue as cross-loop :meth:`submit`, so concurrent
        shm slots coalesce into the same batches as HTTP traffic."""
        loop = self._loop  # local: stop() nulls the attribute cross-thread
        if loop is None or not loop.is_running():
            raise RuntimeError(
                "engine loop is not running (start() the engine on a live "
                "event loop before submitting from threads)"
            )
        _FP_ENGINE_QUEUE.fire()
        depth = self._queue.qsize()
        if depth >= self.max_queue:
            self._bump_threadsafe("shed", qos_class)
            raise EngineOverloaded(depth, self.drain_estimate(depth))
        fut: Any = ConcurrentFuture()
        pending = _Pending(
            name, X, y, fut, time.monotonic(), request_id, None, None,
            tenant, qos_class,
        )
        loop.call_soon_threadsafe(self._queue.put_nowait, pending)
        try:
            return fut.result(timeout)
        except FuturesTimeoutError:
            fut.cancel()
            raise

    async def score(
        self,
        name: str,
        X: np.ndarray,
        y: Optional[np.ndarray] = None,
        request_id: Optional[str] = None,
        trace=None,
        deadline: Optional[Deadline] = None,
        tenant: str = "default",
        qos_class: str = "interactive",
    ) -> ScoreResult:
        _FP_ENGINE_QUEUE.fire()
        self.start()
        if deadline is not None and deadline.expired():
            # the budget ran out before admission (e.g. injected latency
            # upstream, or a client that stamped a near-zero budget):
            # refusing here costs nothing — queueing it would only grow
            # the backlog by work already known to be waste
            self.stats["deadline_expired"] += 1
            self._bump_class(qos_class, "deadline_expired")
            if trace is not None:
                now = time.monotonic()
                trace.add_span(
                    "deadline_expired", now, now, error=True, where="admission"
                )
            raise DeadlineExceeded(
                f"deadline expired before admission (rid={request_id}, "
                f"budget {deadline.budget_s * 1e3:.0f}ms)"
            )
        depth = self._queue.qsize()
        if depth >= self.max_queue:
            # shed NOW rather than enqueue-and-time-out: with the queue
            # this deep, a new waiter's latency is already >= the whole
            # backlog's service time, so the honest answer is "retry"
            self.stats["shed"] += 1
            self._bump_class(qos_class, "shed")
            raise EngineOverloaded(depth, self.drain_estimate(depth))
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        await self._queue.put(
            _Pending(
                name, X, y, fut, time.monotonic(), request_id, trace,
                deadline, tenant, qos_class,
            )
        )
        return await fut

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        batch: List[_Pending] = []
        try:
            await self._run_loop(loop, batch)
        finally:
            # stop()/cancellation: resolve every future still waiting (the
            # partially-collected batch plus anything queued) so callers
            # awaiting score() don't hang forever at shutdown
            pending = list(batch)
            while True:
                try:
                    pending.append(self._queue.get_nowait())
                except asyncio.QueueEmpty:
                    break
            for p in pending:
                if not p.future.done():
                    p.future.cancel()

    async def _run_loop(self, loop, batch: List[_Pending]) -> None:
        requests = results = live = failed = None
        called = False  # the first batch follows no call
        while True:
            batch.clear()
            # release the previous batch's references BEFORE blocking on
            # the queue: an idle engine must not pin the last requests'
            # arrays (for the shm transport those are np.frombuffer
            # views over the mapped ring) until new traffic arrives
            requests = results = live = failed = None  # noqa: F841
            # what is queued now waited behind the call that just returned
            queued = self._queue.qsize() if called else 0
            called = True
            first = await self._queue.get()
            batch.append(first)
            # the loop is back at the queue: whatever a request waited
            # before this is the wait behind the batch in flight
            # (``queue_behind``), the rest of its ``queue_wait`` the
            # drain and, where ``flush_ms`` is set, its window
            # (``queue_flush``)
            taken = time.monotonic()
            # a batch counts requests, except behind a request whose
            # bucket bounds a call by its program's bytes: one week-long
            # sequence is 10^4 rows and gigabytes of activations, and the
            # third would only wait for a second call inside this batch
            max_batch = self.max_batch
            limit = getattr(self.bank, "batch_limit", None)
            if limit is not None:
                max_batch = min(max_batch, limit(first.name, len(first.X)) or max_batch)
            while len(batch) < max_batch:
                # drain whatever is already queued without arming a timer
                # per item — wait_for's per-call timer handle was real
                # heap churn in the coalesced hot loop (profiled round 5)
                try:
                    while len(batch) < max_batch:
                        batch.append(self._queue.get_nowait())
                    break
                except asyncio.QueueEmpty:
                    pass
                if not self.flush_s:
                    break  # work-conserving: later arrivals ride the next call
                timeout = taken + self.flush_s - time.monotonic()
                if timeout <= 0:
                    break
                try:
                    batch.append(
                        await asyncio.wait_for(self._queue.get(), timeout=timeout)
                    )
                except asyncio.TimeoutError:
                    break
            self.stats["requests"] += len(batch)
            self.stats["requests_behind"] += min(queued, len(batch))
            for p in batch:
                self._bump_class(p.qos_class, "requests")
            self.stats["batches"] += 1
            self.stats["max_batch_seen"] = max(self.stats["max_batch_seen"], len(batch))
            dispatch = time.monotonic()
            # goodput ledger, resolved through the bank so a /reload's
            # replacement bank keeps feeding the same app-level ledger
            led = getattr(self.bank, "ledger", None)
            # drop already-expired entries BEFORE device dispatch: their
            # clients stopped waiting, and under saturation executing
            # them anyway is exactly the goodput collapse the deadline
            # exists to prevent. One clock read covers the whole batch.
            live: List[_Pending] = []
            for p in batch:
                if p.deadline is not None and p.deadline.expired(dispatch):
                    self.stats["deadline_expired"] += 1
                    self._bump_class(p.qos_class, "deadline_expired")
                    self.queue_wait.record(dispatch - p.enqueued)
                    if led is not None:
                        led.record_queue_wait(dispatch - p.enqueued)
                    if p.trace is not None:
                        p.trace.add_span(
                            "deadline_expired", p.enqueued, dispatch,
                            error=True, where="queue",
                        )
                    self._resolve(
                        p.future,
                        exc=DeadlineExceeded(
                            f"deadline expired in scoring queue after "
                            f"{(dispatch - p.enqueued) * 1e3:.0f}ms wait "
                            f"(rid={p.request_id}, budget "
                            f"{p.deadline.budget_s * 1e3:.0f}ms)"
                        ),
                    )
                    self.service.record(dispatch - p.enqueued)
                else:
                    live.append(p)
            # keep the shutdown sweep's view (the caller-owned list) in
            # sync: expired entries are resolved, only live ones remain
            batch[:] = live
            if not batch:
                continue  # whole batch expired: no device dispatch at all
            traced = False
            batch_deadline: Optional[Deadline] = None
            for p in batch:
                self.queue_wait.record(dispatch - p.enqueued)
                if led is not None:
                    led.record_queue_wait(dispatch - p.enqueued)
                if p.deadline is not None and (
                    batch_deadline is None
                    or p.deadline.expires_at < batch_deadline.expires_at
                ):
                    # the EARLIEST deadline bounds the whole batch: the
                    # bank stops between bucket-group dispatches when it
                    # passes, and each pending is then re-judged against
                    # its own deadline on the retry path below
                    batch_deadline = p.deadline
                if p.trace is not None:
                    traced = True
                    # the batching's per-request cost, named: submit ->
                    # batch dispatch, with the batch size as an
                    # attribute. A request that arrived after the loop
                    # took the batch's first waited behind nothing.
                    wait = p.trace.add_span(
                        "queue_wait", p.enqueued, dispatch, batch=len(batch)
                    )
                    flush_from = max(p.enqueued, taken)
                    p.trace.add_span(
                        "queue_behind", p.enqueued, flush_from, parent=wait
                    )
                    p.trace.add_span(
                        "queue_flush", flush_from, dispatch, parent=wait
                    )
            requests = [(p.name, p.X, p.y) for p in batch]
            traces = [p.trace for p in batch] if traced else None
            handoff = (dispatch, traces) if traced else None
            try:
                if self._supports_partial():
                    # group-isolated scoring: a failed bucket group (or a
                    # mid-pipeline deadline expiry) comes back as
                    # per-request exception entries while every other
                    # group's results survive — the healthy majority of
                    # a coalesced batch is never rescored
                    results = await loop.run_in_executor(
                        None,
                        functools.partial(
                            self._bank_call,
                            self.bank.score_many,
                            requests,
                            handoff=handoff,
                            traces=traces,
                            deadline=batch_deadline,
                            return_exceptions=True,
                        ),
                    )
                # the traces/deadline arguments only ride along when
                # actually present: bank proxies/stubs with the minimal
                # score_many(requests) signature keep working
                elif batch_deadline is not None:
                    results = await loop.run_in_executor(
                        None,
                        functools.partial(
                            self._bank_call,
                            self.bank.score_many,
                            requests,
                            handoff=handoff,
                            traces=traces,
                            deadline=batch_deadline,
                        ),
                    )
                elif traced:
                    results = await loop.run_in_executor(
                        None,
                        functools.partial(
                            self._bank_call,
                            self.bank.score_many,
                            requests,
                            traces,
                            handoff=handoff,
                        ),
                    )
                else:
                    results = await loop.run_in_executor(
                        None, self._bank_call, self.bank.score_many, requests
                    )
            except Exception:
                # one bad request must not poison the batch: retry each
                # request alone so errors land only on their own future.
                # A DeadlineExceeded from score_many (the batch's
                # earliest budget ran out between group dispatches)
                # lands here too: _retry_one re-judges each pending
                # against its OWN deadline — expired ones 504 without
                # another dispatch, the rest re-score individually
                for p in batch:
                    await self._retry_one(loop, p)
                continue
            done = time.monotonic()
            failed: List[_Pending] = []
            for p, r in zip(batch, results):
                if isinstance(r, Exception):
                    # only the owning group's requests walk the
                    # per-request recovery path
                    failed.append(p)
                    continue
                self._resolve(p.future, result=r)
                self.service.record(done - p.enqueued)
            # healthy futures resolve BEFORE any retry work: a failed
            # group's sequential per-request rescores must not sit in
            # front of already-computed results later in the batch order
            for p in failed:
                await self._retry_one(loop, p)

    def _supports_partial(self) -> bool:
        """Whether the current bank's ``score_many`` takes
        ``return_exceptions`` (probed once per bank object — reload swaps
        banks, and signature inspection is not hot-loop cheap)."""
        bank = self.bank
        prev = (
            self._partial_bank()
            if isinstance(self._partial_bank, weakref.ref)
            else self._partial_bank
        )
        if bank is not prev:
            try:
                self._partial_bank = weakref.ref(bank)
            except TypeError:  # non-weakref-able stub: strong ref is fine
                self._partial_bank = bank
            try:
                self._partial_ok = (
                    "return_exceptions"
                    in inspect.signature(bank.score_many).parameters
                )
            except (TypeError, ValueError):
                self._partial_ok = False
        return self._partial_ok

    async def _retry_one(self, loop, p: _Pending) -> None:
        """Per-request recovery after its batch (or just its bucket
        group) failed: re-judge the pending against its own deadline,
        then re-score it alone so an error lands only on its own
        future."""
        if p.deadline is not None and p.deadline.expired():
            self.stats["deadline_expired"] += 1
            self._bump_class(p.qos_class, "deadline_expired")
            if p.trace is not None:
                now = time.monotonic()
                p.trace.add_span(
                    "deadline_expired", p.enqueued, now,
                    error=True, where="retry",
                )
            self._resolve(
                p.future,
                exc=DeadlineExceeded(
                    f"deadline expired before retry "
                    f"(rid={p.request_id}, budget "
                    f"{p.deadline.budget_s * 1e3:.0f}ms)"
                ),
            )
            self.service.record(time.monotonic() - p.enqueued)
            return
        try:
            # carry the trace into the retry ONLY if this request's
            # bucket group did not complete in the failed batch call (its
            # trace then holds the failed attempt's stages, the last one
            # flagged, and no closed ``postprocess``) — a request whose
            # group completed before another group raised would otherwise
            # get a duplicate coalesce/pad/execute/postprocess set
            retry_trace = p.trace
            if retry_trace is not None and any(
                s.name == "postprocess" and s.end is not None
                for s in retry_trace.spans
            ):
                retry_trace = None
            if retry_trace is not None:
                r = await loop.run_in_executor(
                    None, self._bank_call, self.bank.score, p.name, p.X, p.y,
                    retry_trace,
                )
            else:
                r = await loop.run_in_executor(
                    None, self._bank_call, self.bank.score, p.name, p.X, p.y
                )
        except Exception as exc:
            # rid ties this failure back to the access-log line (and
            # the client header) that admitted it
            logger.warning(
                "engine request for %r failed (rid=%s): %s",
                p.name, p.request_id, exc,
            )
            self._resolve(p.future, exc=exc)
        else:
            self._resolve(p.future, result=r)
        self.service.record(time.monotonic() - p.enqueued)
