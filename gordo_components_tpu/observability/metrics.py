"""Dependency-free metrics registry with Prometheus text exposition.

The reference stack leaned on gunicorn access logs plus Prometheus
sidecars for per-pod visibility (SURVEY.md §5); the in-process bank/gang
rebuild has to carry its own metrics instead. This module is the one
primitive layer every long-running process threads through: the serving
stack (per-shard router counters, per-bucket coalescing histograms), the
fleet builder (compile counts/seconds, members-trained progress) and
watchman (fleet-wide rollup).

Hot-path contract (the serving loop must not notice it):

- ``Counter.inc`` / ``Gauge.set`` are plain attribute writes on a
  ``__slots__`` object — no locks, no allocation per record;
- ``Histogram.record`` is two float ops + an int increment (the same
  log-binned design ``server/stats.LatencyHistogram`` proved out);
- label lookup (``family.labels(...)``) is one dict hit on a cached
  tuple key — call sites on hot loops should cache the child instead;
- all writers of one metric run on one thread (the aiohttp event loop or
  the engine's executor), the same single-writer contract the serving
  stats already rely on. Readers (render/snapshot) may observe a
  mid-update value, never a corrupt one.

Function-backed values (``set_function``) and whole-process collectors
(``MetricsRegistry.collector``) exist so pre-existing counter stores
(``app["stats"]``, ``BatchingEngine.stats``) are *read at render time*
instead of mirrored — mirrored counters drift, read-through ones cannot.
"""

import math
import os
import re
import threading
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

__all__ = [
    "Histogram",
    "LATENCY_BINS_PER_DECADE",
    "MetricsRegistry",
    "get_registry",
    "parse_prometheus_text",
    "render_samples",
]

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

# defaults match server/stats.py's proven latency bins:
# 50us .. ~100s at 10 bins/decade, overflow above
_DEF_LO = 5e-5
_DEF_HI = 100.0
_DEF_BPD = 10

# latency-histogram resolution, ONE knob shared by
# server.stats.LatencyHistogram and the goodput ledger's SLO histogram:
# 32 bins/decade bounds percentile error at 10^(1/32)-1 ~ 7.5%, which is
# what the documented <=10% low-ms contract (docs/observability.md
# "Latency histogram resolution") rests on — tune it here or the serving
# histograms and the SLO good-event counts silently diverge
LATENCY_BINS_PER_DECADE = 32

# per-metric labeled-series cap (GORDO_METRIC_MAX_SERIES): a family that
# tries to grow past this many children drops the new series and counts
# the drop instead of growing the exposition unboundedly. 1024 is far
# above every legitimate family (buckets, shards, stages, tiers are all
# O(10)) and far below per-member cardinality at 1M-fleet scale — the
# guard exists because gordo_drift_score{model} already made that
# mistake once and the heat/cost series must be unable to repeat it.
_DEF_MAX_SERIES = 1024


class Histogram:
    """Log-spaced fixed-bin histogram with percentile reads.

    O(1) record (two float ops + an int increment), O(bins) percentile
    read, zero allocation on the hot path, bounded memory regardless of
    how many values pass through — the standard histogram trade (one bin
    width of relative error; ~26%/bin at 10 bins/decade) that
    Prometheus/HDRHistogram users expect. Values at or below ``lo`` land
    in bin 0; values above ``hi`` land in the overflow bin, where the
    tracked exact ``max`` is the only honest upper bound.
    """

    __slots__ = ("counts", "count", "sum", "max", "_lo", "_log_lo", "_bpd", "_n_bins")

    def __init__(
        self,
        lo: float = _DEF_LO,
        hi: float = _DEF_HI,
        bins_per_decade: int = _DEF_BPD,
    ):
        if lo <= 0 or hi <= lo:
            raise ValueError(f"need 0 < lo < hi, got lo={lo!r} hi={hi!r}")
        self._lo = float(lo)
        self._bpd = int(bins_per_decade)
        self._n_bins = int(math.ceil(math.log10(hi / lo) * self._bpd)) + 1
        self._log_lo = math.log10(lo)
        self.counts = [0] * (self._n_bins + 1)  # +1: overflow bin
        self.count = 0
        self.sum = 0.0
        self.max = 0.0

    def _idx(self, value: float) -> int:
        """Bin index for ``value`` — the ONE copy of the log-bin math
        ``record``/``bucket_le``/``count_le`` must all agree on."""
        if value <= self._lo:
            return 0
        return min(
            self._n_bins,
            1 + int((math.log10(value) - self._log_lo) * self._bpd),
        )

    def record(self, value: float) -> None:
        if value < 0:  # clock weirdness must not corrupt the histogram
            value = 0.0
        self.counts[self._idx(value)] += 1
        self.count += 1
        self.sum += value
        if value > self.max:
            self.max = value

    def _edge(self, i: int) -> float:
        """Upper edge of bin i (i < n_bins)."""
        return 10 ** (self._log_lo + i / self._bpd)

    def bucket_le(self, value: float) -> float:
        """Upper edge of the bucket ``record(value)`` lands in (``inf``
        for the overflow bin) — the key an exemplar attaches to, matching
        the ``le`` edges :meth:`buckets` exposes."""
        if value <= self._lo:
            return self._lo
        idx = self._idx(value)
        return math.inf if idx >= self._n_bins else self._edge(idx)

    def count_le(self, value: float) -> int:
        """Observations recorded at or below the bucket containing
        ``value`` (cumulative, bucket-resolution granular — the "good
        event" count an SLO latency objective reads). Counting the whole
        containing bucket matches the exposition's ``le`` semantics: the
        answer is exact at bucket edges, otherwise an over-count bounded
        by one bin width."""
        return sum(self.counts[: self._idx(value) + 1])

    def percentile(self, q: float) -> float:
        """Upper edge of the bin containing the q-quantile observation
        (<= one bin width above the true value); 0.0 when empty.

        ``q`` is clamped to [0, 1]: q >= 1 returns the exact max, q <= 0
        the first observation's bin. Observations in the overflow bin
        report ``max`` — exact for the top-rank query, an upper bound for
        any lower rank that still lands in the overflow bin.
        """
        if self.count == 0:
            return 0.0
        if q >= 1.0:
            return self.max
        # rank >= 1: the q-quantile of n observations is an actual
        # observation's rank, so q <= 0 must resolve to the FIRST
        # observation, not fall through empty leading bins arbitrarily
        rank = max(1.0, q * self.count)
        seen = 0
        for i, c in enumerate(self.counts):
            if not c:
                continue
            seen += c
            if seen >= rank:
                if i >= self._n_bins:
                    return self.max  # overflow bin: max bounds it
                # clamp to the exact max: a bin's upper edge can exceed
                # every value ever recorded into it
                return min(self.max, self._edge(i))
        return self.max

    def snapshot(self) -> dict:
        """Compact JSON-ready summary in milliseconds (the serving
        ``/stats`` contract this class grew out of)."""
        if self.count == 0:
            return {"count": 0}
        ms = 1e3
        return {
            "count": self.count,
            "mean_ms": round(self.sum / self.count * ms, 3),
            "p50_ms": round(self.percentile(0.50) * ms, 3),
            "p95_ms": round(self.percentile(0.95) * ms, 3),
            "p99_ms": round(self.percentile(0.99) * ms, 3),
            "max_ms": round(self.max * ms, 3),
        }

    def summary(self) -> dict:
        """JSON-ready summary in raw units (for non-latency histograms)."""
        if self.count == 0:
            return {"count": 0}
        return {
            "count": self.count,
            "sum": round(self.sum, 6),
            "mean": round(self.sum / self.count, 6),
            "p50": round(self.percentile(0.50), 6),
            "p95": round(self.percentile(0.95), 6),
            "p99": round(self.percentile(0.99), 6),
            "max": round(self.max, 6),
        }

    def buckets(self) -> List[Tuple[float, int]]:
        """Cumulative (upper_edge, count) pairs for exposition; the final
        edge is ``inf`` and carries the total count."""
        out: List[Tuple[float, int]] = []
        cum = 0
        for i in range(self._n_bins):
            cum += self.counts[i]
            out.append((self._lo if i == 0 else self._edge(i), cum))
        out.append((math.inf, cum + self.counts[self._n_bins]))
        return out


class _Value:
    """One labeled counter/gauge series: a plain int/float cell."""

    __slots__ = ("value", "_fn")

    def __init__(self):
        self.value = 0
        self._fn: Optional[Callable[[], float]] = None

    def inc(self, n: float = 1) -> None:
        self.value += n

    def set(self, v: float) -> None:
        self.value = v

    def set_function(self, fn: Callable[[], float]) -> None:
        """Read-through series: ``fn()`` is called at render/snapshot time
        instead of storing a mirrored value (mirrors drift; reads cannot)."""
        self._fn = fn

    def get(self) -> float:
        fn = self._fn
        if fn is None:
            return self.value
        try:
            return fn()
        except Exception:  # a dead closure must not take down the scrape
            return float("nan")


class MetricFamily:
    """All series of one metric name (children keyed by label values)."""

    def __init__(
        self,
        name: str,
        mtype: str,
        help: str,
        labelnames: Tuple[str, ...],
        child_factory: Callable[[], Any],
        max_series: Optional[int] = None,
    ):
        self.name = name
        self.type = mtype
        self.help = help
        self.labelnames = labelnames
        self._child_factory = child_factory
        self._children: Dict[Tuple[str, ...], Any] = {}
        self._max_series = max_series
        # series dropped by the cardinality guard; exposed by the
        # registry as gordo_metrics_dropped_series_total{metric=...}
        self.dropped = 0

    def labels(self, *values: Any, **kv: Any):
        if kv:
            if values:
                raise ValueError("pass label values positionally or by name, not both")
            values = tuple(kv[l] for l in self.labelnames)
        key = tuple(str(v) for v in values)
        if len(key) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, got {key}"
            )
        child = self._children.get(key)
        if child is None:
            if (
                self._max_series is not None
                and len(self._children) >= self._max_series
            ):
                # cardinality guard: hand back a DETACHED child — the
                # call site's writes land in a cell nothing ever renders
                # (a runaway label set must not grow the exposition, and
                # raising here would turn a telemetry bug into a serving
                # outage)
                self.dropped += 1
                return self._child_factory()
            child = self._children[key] = self._child_factory()
        return child

    # unlabeled-family conveniences
    def inc(self, n: float = 1) -> None:
        self.labels().inc(n)

    def set(self, v: float) -> None:
        self.labels().set(v)

    def record(self, v: float) -> None:
        self.labels().record(v)

    def samples(self) -> Iterable[Tuple[Dict[str, str], Any]]:
        # snapshot the children atomically before yielding: the scoring
        # executor thread can insert a first-seen label child mid-render,
        # and a generator iterating the live dict would race it (a child
        # born mid-scrape simply appears on the next scrape)
        for key, child in sorted(list(self._children.items())):
            labels = dict(zip(self.labelnames, key))
            yield labels, (child if isinstance(child, Histogram) else child.get())


class MetricsRegistry:
    """Process/app-scoped metric registry.

    Re-registering an existing name returns the existing family (counters
    survive a server ``/reload`` monotonic), but a type conflict raises —
    one name must never render as two types. ``collector(fn, key=...)``
    registers a read-at-render-time sample source; re-registering the same
    key replaces the previous collector (a rebuilt engine must not leave a
    dead one emitting)."""

    def __init__(self, max_series_per_metric: Optional[int] = None):
        self._families: Dict[str, MetricFamily] = {}
        self._collectors: Dict[str, Callable[[], Iterable[tuple]]] = {}
        self._lock = threading.Lock()  # registration only, never the hot path
        if max_series_per_metric is None:
            raw = os.environ.get("GORDO_METRIC_MAX_SERIES")
            max_series_per_metric = int(raw) if raw else _DEF_MAX_SERIES
        # <=0 disables the guard (an operator's explicit escape hatch)
        self._max_series = (
            max_series_per_metric if max_series_per_metric > 0 else None
        )

    # --------------------------- registration ------------------------- #

    def _family(
        self,
        name: str,
        mtype: str,
        help: str,
        labelnames: Tuple[str, ...],
        child_factory: Callable[[], Any],
    ) -> MetricFamily:
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        for l in labelnames:
            if not _LABEL_RE.match(l):
                raise ValueError(f"invalid label name {l!r} for {name}")
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.type != mtype or fam.labelnames != tuple(labelnames):
                    raise ValueError(
                        f"metric {name!r} already registered as {fam.type}"
                        f"{fam.labelnames}, not {mtype}{tuple(labelnames)}"
                    )
                return fam
            fam = MetricFamily(
                name, mtype, help, tuple(labelnames), child_factory,
                max_series=self._max_series,
            )
            self._families[name] = fam
            return fam

    def counter(self, name: str, help: str = "", labelnames=()) -> MetricFamily:
        return self._family(name, "counter", help, tuple(labelnames), _Value)

    def gauge(self, name: str, help: str = "", labelnames=()) -> MetricFamily:
        return self._family(name, "gauge", help, tuple(labelnames), _Value)

    def histogram(
        self,
        name: str,
        help: str = "",
        labelnames=(),
        lo: float = _DEF_LO,
        hi: float = _DEF_HI,
        bins_per_decade: int = _DEF_BPD,
    ) -> MetricFamily:
        factory = lambda: Histogram(lo=lo, hi=hi, bins_per_decade=bins_per_decade)
        return self._family(name, "histogram", help, tuple(labelnames), factory)

    def collector(self, fn: Callable[[], Iterable[tuple]], key: str) -> None:
        """``fn()`` yields ``(name, type, help, labels_dict, value)`` tuples
        at render time; ``value`` may be a number or a Histogram."""
        with self._lock:
            self._collectors[key] = fn

    def get_collector(self, key: str) -> Optional[Callable[[], Iterable[tuple]]]:
        """The collector currently registered under ``key`` (None if
        absent) — lets a replacement collector read its predecessor's
        final values so counter series stay monotonic across swaps."""
        with self._lock:
            return self._collectors.get(key)

    # ----------------------------- reads ------------------------------ #

    def _all_samples(self):
        """-> ordered {name: (type, help, [(labels, value), ...])}."""
        out: Dict[str, Tuple[str, str, List[Tuple[Dict[str, str], Any]]]] = {}
        dropped: List[Tuple[Dict[str, str], Any]] = []
        for fam in list(self._families.values()):
            out[fam.name] = (fam.type, fam.help, list(fam.samples()))
            if fam.dropped:
                dropped.append(({"metric": fam.name}, fam.dropped))
        if dropped:
            out["gordo_metrics_dropped_series_total"] = (
                "counter",
                "Labeled series dropped by the per-metric cardinality "
                "guard (GORDO_METRIC_MAX_SERIES)",
                dropped,
            )
        for fn in list(self._collectors.values()):
            try:
                rows = list(fn())
            except Exception:
                continue  # a broken collector must not take down the scrape
            for name, mtype, help, labels, value in rows:
                if name not in out:
                    out[name] = (mtype, help, [])
                out[name][2].append((labels, value))
        return out

    def render(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        lines: List[str] = []
        for name, (mtype, help, samples) in self._all_samples().items():
            if help:
                lines.append(f"# HELP {name} {_escape_help(help)}")
            lines.append(f"# TYPE {name} {mtype}")
            for labels, value in samples:
                if isinstance(value, Histogram):
                    for edge, cum in value.buckets():
                        le = "+Inf" if math.isinf(edge) else _fmt(edge)
                        lines.append(
                            f"{name}_bucket{_labels({**labels, 'le': le})} {cum}"
                        )
                    lines.append(f"{name}_sum{_labels(labels)} {_fmt(value.sum)}")
                    lines.append(f"{name}_count{_labels(labels)} {value.count}")
                else:
                    lines.append(f"{name}{_labels(labels)} {_fmt(value)}")
        return "\n".join(lines) + "\n"

    def snapshot(self) -> Dict[str, Any]:
        """JSON view of the registry — the same cells ``render`` reads, so
        ``/stats`` and ``/metrics`` cannot drift."""
        out: Dict[str, Any] = {}
        for name, (mtype, help, samples) in self._all_samples().items():
            values = []
            for labels, value in samples:
                if isinstance(value, Histogram):
                    values.append({"labels": labels, **value.summary()})
                else:
                    v = float(value)
                    if not math.isfinite(v):
                        # JSON has no NaN/Inf; null keeps /stats parseable
                        values.append({"labels": labels, "value": None})
                    else:
                        values.append(
                            {"labels": labels, "value": int(v) if v == int(v) else v}
                        )
            out[name] = {"type": mtype, "values": values}
        return out


# process-default registry: builder processes record here without
# plumbing; the server builds a per-app registry instead (tests run many
# apps per process, and their series must not bleed together)
_DEFAULT = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _DEFAULT


# ------------------------------------------------------------------ #
# exposition helpers + parser (watchman's fleet rollup scrapes peers)
# ------------------------------------------------------------------ #


def _escape_help(s: str) -> str:
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _labels(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{_escape_label(str(v))}"' for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


def _fmt(v: float) -> str:
    f = float(v)
    if math.isnan(f):
        return "NaN"  # a dead set_function closure reads as NaN by design;
        # the scrape must render it, not 500 on int(nan)
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return format(f, ".9g")


def render_samples(
    samples: Iterable[Tuple[str, Dict[str, str], float]],
    types: Optional[Dict[str, str]] = None,
    help_texts: Optional[Dict[str, str]] = None,
) -> str:
    """Render flat ``(name, labels, value)`` samples as Prometheus text,
    grouped by FAMILY with one TYPE line each (watchman's rollup output).

    Histogram awareness: ``<base>_bucket``/``_sum``/``_count`` samples
    whose base name is declared ``histogram`` in ``types`` group under the
    base family — its TYPE line precedes them and bucket lines sort by
    numeric ``le`` (``+Inf`` last), so a re-emitted scraped histogram
    stays a valid histogram, not a pile of untyped series."""
    types = types or {}
    help_texts = help_texts or {}
    hist_bases = {n for n, t in types.items() if t == "histogram"}

    def family_of(name: str) -> str:
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in hist_bases:
                return name[: -len(suffix)]
        return name

    by_family: Dict[str, Dict[str, List[Tuple[Dict[str, str], float]]]] = {}
    for name, labels, value in samples:
        by_family.setdefault(family_of(name), {}).setdefault(name, []).append(
            (labels, value)
        )

    def le_key(labels: Dict[str, str]):
        le = labels.get("le", "")
        try:
            return (0, float("inf") if le == "+Inf" else float(le))
        except ValueError:
            return (1, 0.0)

    lines: List[str] = []
    for family, names in by_family.items():
        if family in help_texts:
            lines.append(f"# HELP {family} {_escape_help(help_texts[family])}")
        if family in types:
            lines.append(f"# TYPE {family} {types[family]}")
        # histogram sample order: buckets, then sum, then count (a stray
        # base-named sample, while not expected, must not be dropped)
        order = (
            [family, f"{family}_bucket", f"{family}_sum", f"{family}_count"]
            if family in hist_bases
            else sorted(names)
        )
        for name in order:
            for labels, value in sorted(
                names.get(name, ()),
                key=lambda r: (
                    sorted((k, v) for k, v in r[0].items() if k != "le"),
                    le_key(r[0]),
                ),
            ):
                lines.append(f"{name}{_labels(labels)} {_fmt(value)}")
    return "\n".join(lines) + "\n"


_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+([^\s]+)(?:\s+\d+)?$"
)
_LABEL_PAIR_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


_UNESCAPE_RE = re.compile(r"\\(.)")
_UNESCAPE_MAP = {"n": "\n", '"': '"', "\\": "\\"}


def _unescape_label(s: str) -> str:
    # single-pass: chained str.replace corrupts values like 'a\\nb'
    # (literal backslash + n), where the later replace re-reads characters
    # an earlier one produced
    return _UNESCAPE_RE.sub(
        lambda m: _UNESCAPE_MAP.get(m.group(1), m.group(1)), s
    )


def parse_prometheus_text(
    text: str,
) -> Tuple[Dict[str, str], List[Tuple[str, Dict[str, str], float]]]:
    """Parse exposition text into ``(types, samples)`` where ``types`` maps
    family name -> declared type and ``samples`` is a flat list of
    ``(name, labels, value)``. Malformed lines are skipped (a scraped peer
    mid-deploy must not take down the rollup)."""
    types: Dict[str, str] = {}
    samples: List[Tuple[str, Dict[str, str], float]] = []
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[parts[2]] = parts[3]
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            continue
        name, labelstr, valuestr = m.group(1), m.group(2), m.group(3)
        try:
            value = float(valuestr)
        except ValueError:
            continue
        labels = (
            {k: _unescape_label(v) for k, v in _LABEL_PAIR_RE.findall(labelstr)}
            if labelstr
            else {}
        )
        samples.append((name, labels, value))
    return types, samples
