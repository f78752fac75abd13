"""Per-bucket device-cost attribution: FLOPs, MFU, and pad waste.

The benchmark reports one MFU for a whole cell (``mfu.serve``); this
module attributes it to buckets. An analytic per-architecture forward-FLOPs model
(dense AE / LSTM / conv1d, from the bucket's config shapes, computed
once at bucket build) is multiplied by the goodput ledger's MEASURED
per-bucket device seconds and real-vs-padded row split to yield, per
bucket: MFU, FLOPs/row, device-seconds-per-1k-rows, and a pad-waste
score — the ranked work-list ROADMAP item 4 (the LSTM/conv 0.5x
problem) needs. "MFU-per-program is the metric that exposes layout and
scheduling waste" (Exploring the Limits of Concurrency on TPUs,
PAPERS.md #3); the ledger supplies the program-level device time, this
supplies the numerator.

Contracts, same as ``/slo``:

- **No-drift** — ``snapshot()`` computes from one ledger read, caches,
  and the registry collector, the ``GET /costs`` body, the ``/stats``
  embed, and the watchman rollup read that SAME cache (byte-identical
  between samples; :func:`merge_cost_snapshots` with one replica
  reproduces the replica body exactly because both sides go through
  :func:`bucket_cost_row`).
- **Bounded cardinality** — all series are labeled by BUCKET (a handful
  per fleet), never by member.
- **Honest provenance** — the peak-FLOPs denominator is stamped with
  where it came from (``env`` knob, ``device`` spec table, or
  ``assumed`` fallback so a CPU dev loop still exercises the MFU
  plumbing); the FLOPs numerator is stamped ``analytic`` or the
  ``params`` 2·N fallback. A rate against an assumed peak is a
  RELATIVE ranking signal, not a utilization claim — consumers can see
  which they have.

FLOPs accounting convention: multiply-accumulates count as 2 FLOPs;
bias adds, activations, and normalization are omitted (sub-percent for
these architectures). The analytic numbers are cross-checked against
``jax.jit(...).lower().compile().cost_analysis()`` in
tests/test_heat_cost.py within a documented tolerance band.
"""

import math
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "dense_chain_flops",
    "lstm_stack_flops",
    "conv1d_autoencoder_flops",
    "estimate_flops_per_row",
    "resolve_peak_flops",
    "bucket_cost_row",
    "CostModel",
    "cost_from_env",
    "merge_cost_snapshots",
]

# Dense bf16 peak FLOP/s per chip (public spec sheets). The benchmark
# keeps its own (benchmarks/peaks.json): the serving path imports nothing
# of it.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e / Trillium
    "TPU v6e": 918e12,
}


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"{name} must be a number, got {raw!r}") from None


# ---------------------------------------------------------------------- #
# analytic forward FLOPs per architecture family
# ---------------------------------------------------------------------- #


def dense_chain_flops(n_features: int, encoding_dim, decoding_dim) -> float:
    """Forward FLOPs for one row through a FeedForwardAutoEncoder:
    the dense chain n_features -> *encoding_dim -> *decoding_dim ->
    n_features, 2·in·out per layer."""
    dims = [int(n_features), *map(int, encoding_dim), *map(int, decoding_dim),
            int(n_features)]
    return float(sum(2 * a * b for a, b in zip(dims, dims[1:])))


def lstm_step_flops(n_features: int, dims) -> float:
    """FLOPs for ONE recurrent scan step through every layer of an
    LSTMStack: 4 gates of (in + hidden)·hidden matmuls per cell, i.e.
    8·h·(in + h) per layer. This is the scan-trip unit both layouts
    execute — the legacy vmap(member)-outside-RNN nesting and the
    time-major gang scan (ops/seq_scan.py) run IDENTICAL math per step;
    the layouts differ only in which axis the matmul batches over, so
    the closed form is layout-invariant by construction."""
    per_step = 0.0
    prev = int(n_features)
    for h in (int(d) for d in dims):
        per_step += 8.0 * h * (prev + h)
        prev = h
    return per_step


def lstm_stack_flops(n_features: int, dims, lookback: int) -> float:
    """Forward FLOPs for one WINDOW through an LSTMStack: exactly
    ``lookback`` scan trips of :func:`lstm_step_flops` (the time-major
    path makes the trip count explicit — one ``lax.scan`` of length
    ``lookback``; the legacy flax RNN runs the same count per layer),
    then the last step's Dense head back to n_features."""
    dims = [int(d) for d in dims]
    return (
        float(lookback) * lstm_step_flops(n_features, dims)
        + 2.0 * dims[-1] * int(n_features)
    )


def conv1d_autoencoder_flops(
    n_features: int, channels, kernel_size: int, lookback: int
) -> float:
    """Forward FLOPs for one WINDOW through a Conv1DAutoEncoder:
    stride-2 SAME encoder convs (length ceil-halves per layer), stride-2
    transposed decoder convs over reversed channels (length doubles),
    and a final stride-1 full-length conv back to n_features. A conv
    layer is 2·out_len·K·in_ch·out_ch. Impl-invariant: the fleet's
    default matmul formulation (K strided slices, one matmul each —
    models/factories/conv.py) performs exactly these multiply-adds, just
    batched lane-friendly, so one closed form covers both
    ``conv_impl`` paths."""
    channels = [int(c) for c in channels]
    k = int(kernel_size)
    total = 0.0
    length = int(lookback)
    in_ch = int(n_features)
    for out_ch in channels:
        length = -(-length // 2)  # SAME stride-2: ceil(L/2)
        total += 2.0 * length * k * in_ch * out_ch
        in_ch = out_ch
    for out_ch in reversed(channels):
        length *= 2  # transposed stride-2 doubles the length
        total += 2.0 * length * k * in_ch * out_ch
        in_ch = out_ch
    total += 2.0 * length * k * in_ch * int(n_features)
    return total


def estimate_flops_per_row(
    module,
    n_features: int,
    lookback: int,
    params_per_member: Optional[int] = None,
) -> Tuple[float, str]:
    """(forward FLOPs for one routed row, method tag) for a bucket's
    flax module. Duck-typed on the factory module's config attributes so
    cost.py never imports the model registry (bank imports cost, not
    the reverse). Unknown architectures fall back to the classic
    2·params·timesteps estimate, tagged ``params`` so consumers can see
    the number is a coarser bound."""
    enc = getattr(module, "encoding_dim", None)
    dec = getattr(module, "decoding_dim", None)
    if enc is not None and dec is not None:
        return dense_chain_flops(n_features, enc, dec), "analytic"
    dims = getattr(module, "dims", None)
    if dims is not None:
        return lstm_stack_flops(n_features, dims, lookback), "analytic"
    channels = getattr(module, "channels", None)
    kernel = getattr(module, "kernel_size", None)
    if channels is not None and kernel is not None:
        return (
            conv1d_autoencoder_flops(n_features, channels, kernel, lookback),
            "analytic",
        )
    routed = getattr(module, "forward_flops_per_row", None)
    if routed is not None:
        # a trunk of routed experts counts what a row meets (its top-k
        # experts, or the held ones' share of them; the keys it attends
        # to), not every parameter; a row's keys depend on its request's
        # length, taken as the kind's own nominal one (10 240 rows)
        context = int(module.nominal_context_rows())
        return routed(context), f"analytic:context={context}"
    if params_per_member:
        return 2.0 * float(params_per_member) * max(1, int(lookback)), "params"
    return 0.0, "unknown"


# ---------------------------------------------------------------------- #
# peak-FLOPs resolution
# ---------------------------------------------------------------------- #


def resolve_peak_flops() -> Tuple[Optional[float], str]:
    """(per-device peak FLOP/s, provenance) for the MFU denominator.

    Order: ``GORDO_DEVICE_PEAK_FLOPS`` (operator knows their chip) ->
    the public spec table keyed by jax device_kind. A device in neither
    (a CPU dev loop, an unlisted chip) has NO peak: ``(None,
    "unknown")``, and every MFU field derived from it is null — a
    utilization against an assumed peak is not a measurement."""
    raw = os.environ.get("GORDO_DEVICE_PEAK_FLOPS")
    if raw:
        return float(raw), "env"
    import jax

    peak = PEAK_BF16_FLOPS.get(jax.devices()[0].device_kind)
    if peak:
        return peak, "device"
    return None, "unknown"


# ---------------------------------------------------------------------- #
# per-bucket cost row (shared by snapshot AND the fleet merge so the
# two render byte-identically)
# ---------------------------------------------------------------------- #


def bucket_cost_row(
    flops_per_row: float,
    flops_method: str,
    routed_rows: float,
    padded_rows: float,
    useful_s: float,
    padded_s: float,
    failed_s: float,
    peak_flops: Optional[float],
    members: Optional[int] = None,
    kind: Optional[str] = None,
) -> Dict[str, Any]:
    """One bucket's cost attribution from raw tallies. Pure — the
    single place the MFU/waste arithmetic and rounding live, so the
    replica snapshot and the watchman fleet merge cannot drift.

    Inputs are rounded FIRST and every derived field computed from the
    rounded values: the fleet merge only ever sees the rounded tallies
    from replica JSON bodies, so deriving from anything more precise
    here would break the single-replica byte-for-byte identity."""
    flops_per_row = round(flops_per_row, 3)
    routed_rows = round(routed_rows, 3)
    padded_rows = round(padded_rows, 3)
    useful_s = round(useful_s, 6)
    padded_s = round(padded_s, 6)
    failed_s = round(failed_s, 6)
    device_s = useful_s + padded_s + failed_s
    dispatched_rows = routed_rows + padded_rows
    achieved = (flops_per_row * routed_rows / device_s) if device_s > 0 else 0.0
    achieved_disp = (
        (flops_per_row * dispatched_rows / device_s) if device_s > 0 else 0.0
    )
    row = {
        "flops_per_row": round(flops_per_row, 3),
        "flops_method": flops_method,
        "routed_rows": round(routed_rows, 3),
        "padded_rows": round(padded_rows, 3),
        "device_s": round(device_s, 6),
        "useful_s": round(useful_s, 6),
        "padded_s": round(padded_s, 6),
        "failed_s": round(failed_s, 6),
        "device_s_per_1k_rows": round(
            1000.0 * device_s / routed_rows, 6
        ) if routed_rows > 0 else None,
        "achieved_flops_per_sec": round(achieved, 3),
        # mfu counts only ROUTED (real) rows against peak; mfu_dispatched
        # includes pad rows — the gap between them IS the pad tax
        # (null when the device's peak is unknown)
        "mfu": round(achieved / peak_flops, 9) if peak_flops else None,
        "mfu_dispatched": round(achieved_disp / peak_flops, 9)
        if peak_flops
        else None,
        # fraction of this bucket's device time spent on padding — the
        # per-bucket half of the ranking key
        "pad_waste_score": round(padded_s / device_s, 6) if device_s > 0 else 0.0,
    }
    if members is not None:
        row["members"] = int(members)
    if kind is not None:
        row["kind"] = kind
    return row


def _ranked(buckets: Dict[str, Dict[str, Any]], total_device_s: float) -> List[Dict[str, Any]]:
    """Buckets ranked by wasted device time = pad-waste fraction × share
    of fleet device time — "fix this bucket first" order."""
    rows = []
    for label, row in buckets.items():
        share = (row["device_s"] / total_device_s) if total_device_s > 0 else 0.0
        rows.append(
            {
                "bucket": label,
                "device_share": round(share, 6),
                "pad_waste_score": row["pad_waste_score"],
                "wasted_device_score": round(row["pad_waste_score"] * share, 6),
            }
        )
    rows.sort(key=lambda r: (-r["wasted_device_score"], r["bucket"]))
    return rows


# ---------------------------------------------------------------------- #
# CostModel
# ---------------------------------------------------------------------- #


class CostModel:
    """Joins the bank's static FLOPs table to the ledger's measured
    device seconds on a sampling cadence (``GORDO_COST_SAMPLE_S``).

    ``bank_supplier`` is a zero-arg callable returning the CURRENT bank
    (the app dict holds swap generations; the cost model must follow
    them, not pin one), whose ``flops_stats()`` provides
    ``{bucket_label: {flops_per_row, method, members, kind, ...}}``.
    """

    def __init__(
        self,
        ledger,
        bank_supplier: Callable[[], Any],
        registry=None,
        sample_interval_s: Optional[float] = None,
        peak_flops: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.ledger = ledger
        self._bank_supplier = bank_supplier
        if sample_interval_s is None:
            sample_interval_s = _env_float("GORDO_COST_SAMPLE_S", 10.0)
        self.sample_interval_s = max(0.001, float(sample_interval_s))
        if peak_flops is None:
            peak_flops, peak_source = resolve_peak_flops()
        else:
            peak_source = "explicit"
        self.peak_flops = None if peak_flops is None else float(peak_flops)
        self.peak_source = peak_source
        self._clock = clock
        self._lock = threading.Lock()
        self._cached: Optional[Dict[str, Any]] = None
        self._last_sample: Optional[float] = None
        self._n_samples = 0
        if registry is not None:
            # keyed for the swap's collector-preservation path, like
            # "slo"/"bank_heat" — a rolled-back swap restores it
            registry.collector(self._collect, key="bank_cost")

    def sample(self, now: Optional[float] = None, force: bool = False) -> bool:
        if now is None:
            now = self._clock()
        with self._lock:
            if (
                not force
                and self._last_sample is not None
                and now - self._last_sample < self.sample_interval_s
            ):
                return False
            self._cached = self._build()
            self._last_sample = now
            self._n_samples += 1
            self._cached["n_samples"] = self._n_samples
            return True

    def _build(self) -> Dict[str, Any]:
        """One consistent join of ledger tallies × bank FLOPs table
        (lock held)."""
        led = self.ledger.snapshot() if self.ledger is not None else {}
        per_bucket = led.get("per_bucket") or {}
        bank = self._bank_supplier() if self._bank_supplier else None
        flops_stats = {}
        if bank is not None:
            try:
                flops_stats = bank.flops_stats()
            except Exception:
                flops_stats = {}
        buckets: Dict[str, Dict[str, Any]] = {}
        total_device_s = 0.0
        # every LIVE bucket gets a row (the acceptance contract), even
        # before its first ledger tally; ledger-only labels (a bucket
        # retired by a swap) keep their measured history too
        for label in sorted(set(flops_stats) | set(per_bucket)):
            stats = flops_stats.get(label) or {}
            tallies = per_bucket.get(label) or {}
            row = bucket_cost_row(
                flops_per_row=float(stats.get("flops_per_row") or 0.0),
                flops_method=str(stats.get("flops_method") or "unknown"),
                routed_rows=float(tallies.get("routed_rows") or 0.0),
                padded_rows=float(tallies.get("padded_rows") or 0.0),
                useful_s=float(tallies.get("useful_s") or 0.0),
                padded_s=float(tallies.get("padded_s") or 0.0),
                failed_s=float(tallies.get("failed_s") or 0.0),
                peak_flops=self.peak_flops,
                members=stats.get("members"),
                kind=stats.get("kind"),
            )
            row["live"] = label in flops_stats
            buckets[label] = row
            total_device_s += row["device_s"]
        return {
            "peak_flops": self.peak_flops,
            "peak_source": self.peak_source,
            "sample_interval_s": self.sample_interval_s,
            "total_device_s": round(total_device_s, 6),
            "buckets": buckets,
            "ranking": _ranked(buckets, total_device_s),
        }

    def snapshot(self) -> Dict[str, Any]:
        """The cached join — registry collector, ``GET /costs``,
        ``/stats`` embed, and watchman all read this (no-drift)."""
        self.sample()
        with self._lock:
            if self._cached is None:
                self._cached = self._build()
                self._cached["n_samples"] = self._n_samples
            return self._cached

    def _collect(self):
        snap = self.snapshot()
        for label, row in snap["buckets"].items():
            lab = {"bucket": label}
            if row["mfu"] is not None:
                yield (
                    "gordo_bucket_mfu", "gauge",
                    "Model FLOPs utilization per bucket: analytic "
                    "routed-row FLOPs / measured device seconds / peak "
                    "(see peak_source for provenance)", lab, row["mfu"],
                )
            yield (
                "gordo_bucket_flops_per_row", "gauge",
                "Analytic forward FLOPs per routed row for this "
                "bucket's architecture", lab, row["flops_per_row"],
            )
            if row["device_s_per_1k_rows"] is not None:
                yield (
                    "gordo_bucket_device_seconds_per_1k_rows", "gauge",
                    "Measured device seconds per 1000 routed rows",
                    lab, row["device_s_per_1k_rows"],
                )
            yield (
                "gordo_bucket_pad_waste_score", "gauge",
                "Fraction of this bucket's device time spent on pad "
                "rows", lab, row["pad_waste_score"],
            )


def cost_from_env(
    ledger, bank_supplier, registry=None, clock=None
) -> Optional[CostModel]:
    """A cost model, or ``None`` when ``GORDO_COST=0`` (on by default —
    it costs one ledger read per sample interval, nothing on the hot
    path). ``clock`` is the app's replay-aware Clock; the cadence runs
    on its monotonic seam."""
    if os.environ.get("GORDO_COST", "1") == "0":
        return None
    mono = clock.monotonic if clock is not None else time.monotonic
    return CostModel(ledger, bank_supplier, registry=registry, clock=mono)


# ---------------------------------------------------------------------- #
# fleet rollup (watchman)
# ---------------------------------------------------------------------- #


def merge_cost_snapshots(
    bodies: Sequence[Optional[Dict[str, Any]]],
) -> Dict[str, Any]:
    """Merge per-replica ``GET /costs`` bodies into one fleet view.

    Raw tallies (rows, seconds) SUM per bucket label across replicas,
    then the derived fields are recomputed through the same
    :func:`bucket_cost_row` the replicas used — so with one replica the
    merged buckets/ranking reproduce that replica's body byte-for-byte
    (the no-drift contract, asserted in tests). Peak FLOPs comes from
    the first enabled body; a mixed-chip fleet would need per-replica
    normalization this deliberately does not pretend to do (the
    ``peak_sources`` list shows the spread)."""
    acc: Dict[str, Dict[str, float]] = {}
    meta: Dict[str, Dict[str, Any]] = {}
    peak_flops = None
    peak_sources: List[str] = []
    scraped = 0
    for body in bodies:
        if not body or not body.get("enabled", True):
            continue
        scraped += 1
        if peak_flops is None and body.get("peak_flops"):
            peak_flops = float(body["peak_flops"])
        src = body.get("peak_source")
        if src and src not in peak_sources:
            peak_sources.append(src)
        for label, row in (body.get("buckets") or {}).items():
            cell = acc.setdefault(
                label,
                {
                    "routed_rows": 0.0,
                    "padded_rows": 0.0,
                    "useful_s": 0.0,
                    "padded_s": 0.0,
                    "failed_s": 0.0,
                },
            )
            for key in cell:
                cell[key] += float(row.get(key) or 0.0)
            info = meta.setdefault(
                label,
                {
                    "flops_per_row": float(row.get("flops_per_row") or 0.0),
                    "flops_method": row.get("flops_method") or "unknown",
                    "members": row.get("members"),
                    "kind": row.get("kind"),
                    "live": False,
                },
            )
            info["live"] = bool(info["live"] or row.get("live"))
    buckets: Dict[str, Dict[str, Any]] = {}
    total_device_s = 0.0
    for label in sorted(acc):
        cell, info = acc[label], meta[label]
        row = bucket_cost_row(
            flops_per_row=info["flops_per_row"],
            flops_method=info["flops_method"],
            routed_rows=cell["routed_rows"],
            padded_rows=cell["padded_rows"],
            useful_s=cell["useful_s"],
            padded_s=cell["padded_s"],
            failed_s=cell["failed_s"],
            peak_flops=peak_flops,
            members=info["members"],
            kind=info["kind"],
        )
        row["live"] = info["live"]
        buckets[label] = row
        total_device_s += row["device_s"]
    return {
        "replicas_scraped": scraped,
        "peak_flops": peak_flops,
        "peak_source": peak_sources[0] if len(peak_sources) == 1 else "mixed"
        if peak_sources
        else "assumed",
        "peak_sources": peak_sources,
        "total_device_s": round(total_device_s, 6),
        "buckets": buckets,
        "ranking": _ranked(buckets, total_device_s),
    }
