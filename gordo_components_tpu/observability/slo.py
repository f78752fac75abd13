"""Rolling multi-window SLO objectives and burn-rate computation.

The :class:`GoodputLedger` (goodput.py) accumulates monotonic counters;
this module turns them into the operator question: *at the current error
rate, how fast is the error budget burning?* A :class:`SLOTracker`
samples the ledger's cumulative cells on a fixed cadence into a bounded
ring and computes, per configured objective and per window (default
5m/1h/6h), the windowed good/total delta, its ratio, and the classic
burn rate::

    burn_rate = (1 - windowed_good_ratio) / (1 - target)

1.0 = burning the budget exactly as fast as the objective allows; 14.4
on a 5m window is the canonical "page now" fast burn. The 5m window is
the FAST signal (reacts in minutes, noisy), 1h/6h the SLOW confirmation
(smooth, laggy) — the standard multi-window pattern, computed here
without a Prometheus server in the loop so the north-star check and the
chaos suite can assert on burn rates in-process.

Objectives (env ``GORDO_SLO_OBJECTIVES``, JSON; see DEFAULT_OBJECTIVES):

- ``availability`` — good = requests that did NOT fail server-side
  (5xx, incl. deadline 504s, and finite-input/non-finite-output
  responses). Budget = ``1 - target``.
- ``p<NN>_latency_ms`` — good = requests whose service time was <= the
  ``target`` milliseconds; the quantile in the name sets the budget
  (p99 -> 1% may exceed). Bucket-resolution granular (the ledger's
  latency histogram, ~7.5%/bin).
- ``goodput_ratio`` — good/total = the ledger's wall-second goodput
  split; burns when wasted/expired wall seconds grow.

Snapshot determinism (the no-drift contract): windows are computed from
the sample ring alone — never from "now" — and the result is cached
until the next sample lands. ``GET /slo``, the ``/stats`` embed, and the
``gordo_slo_burn_rate{objective,window}`` registry gauges therefore
return byte-identical numbers between samples; the acceptance test
asserts exactly that.

Threading: ``sample``/``snapshot`` take a lock (they run on the event
loop, the registry render path, and a driver's thread); nothing here
is on the scoring hot path.
"""

import json
import os
import re
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "DEFAULT_OBJECTIVES",
    "DEFAULT_WINDOWS",
    "SLOTracker",
    "merge_slo_snapshots",
    "parse_objectives",
    "parse_windows",
]

DEFAULT_OBJECTIVES: Tuple[Dict[str, Any], ...] = (
    {"name": "availability", "target": 0.999},
    {"name": "p99_latency_ms", "target": 100.0},
    {"name": "goodput_ratio", "target": 0.9},
)

DEFAULT_WINDOWS: Tuple[Tuple[str, float], ...] = (
    ("5m", 300.0),
    ("1h", 3600.0),
    ("6h", 21600.0),
)

# canonical multi-window fast-burn threshold (5m window): burning the
# whole 30-day budget in ~2 days
DEFAULT_FAST_BURN = 14.4

_LATENCY_RE = re.compile(r"^p(\d{1,2})_latency_ms$")
_WINDOW_RE = re.compile(r"^(\d+(?:\.\d+)?)([smh])$")
_WINDOW_UNITS = {"s": 1.0, "m": 60.0, "h": 3600.0}


class _Objective:
    """One parsed objective: name, target, budget, and its sample key."""

    __slots__ = ("name", "target", "quantile", "budget")

    def __init__(self, name: str, target: float, quantile: Optional[float] = None):
        self.name = name
        self.target = float(target)
        m = _LATENCY_RE.match(name)
        if m:
            self.quantile = (
                float(quantile) if quantile is not None else int(m.group(1)) / 100.0
            )
            if not 0.0 < self.quantile < 1.0:
                raise ValueError(
                    f"objective {name!r}: quantile must be in (0, 1), "
                    f"got {self.quantile!r}"
                )
            self.budget = 1.0 - self.quantile
            if self.target <= 0:
                raise ValueError(
                    f"objective {name!r}: target must be positive "
                    f"milliseconds, got {target!r}"
                )
        elif name in ("availability", "goodput_ratio"):
            self.quantile = None
            if not 0.0 < self.target < 1.0:
                raise ValueError(
                    f"objective {name!r}: target must be a ratio in (0, 1), "
                    f"got {target!r}"
                )
            self.budget = 1.0 - self.target
        else:
            raise ValueError(
                f"unknown SLO objective {name!r} (availability, "
                f"p<NN>_latency_ms, goodput_ratio)"
            )

    def describe(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"name": self.name, "target": self.target}
        if self.quantile is not None:
            out["quantile"] = self.quantile
        out["budget"] = round(self.budget, 6)
        return out


def parse_objectives(raw: Optional[str] = None) -> List[_Objective]:
    """``GORDO_SLO_OBJECTIVES`` (JSON list of ``{"name", "target"}``)
    -> objectives; malformed config raises loudly — a typo'd fleet-wide
    SLO knob must not silently monitor nothing."""
    if raw is None:
        raw = os.environ.get("GORDO_SLO_OBJECTIVES", "")
    if not raw.strip():
        specs: Sequence[Dict[str, Any]] = DEFAULT_OBJECTIVES
    else:
        try:
            specs = json.loads(raw)
        except json.JSONDecodeError as exc:
            raise ValueError(
                f"GORDO_SLO_OBJECTIVES must be JSON: {exc}"
            ) from None
        if not isinstance(specs, list):
            raise ValueError("GORDO_SLO_OBJECTIVES must be a JSON list")
    out = []
    for spec in specs:
        if not isinstance(spec, dict) or "name" not in spec or "target" not in spec:
            raise ValueError(
                f"each SLO objective needs name+target, got {spec!r}"
            )
        out.append(
            _Objective(
                str(spec["name"]), float(spec["target"]), spec.get("quantile")
            )
        )
    if len({o.name for o in out}) != len(out):
        raise ValueError("duplicate SLO objective names")
    return out


def parse_windows(raw: Optional[str] = None) -> List[Tuple[str, float]]:
    """``GORDO_SLO_WINDOWS`` (e.g. ``"5m,1h,6h"``) -> [(label, seconds)],
    sorted ascending (the first window is the fast-burn signal)."""
    if raw is None:
        raw = os.environ.get("GORDO_SLO_WINDOWS", "")
    if not raw.strip():
        return list(DEFAULT_WINDOWS)
    out = []
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        m = _WINDOW_RE.match(part)
        if not m:
            raise ValueError(
                f"GORDO_SLO_WINDOWS entry {part!r} must look like 5m/1h/30s"
            )
        out.append((part, float(m.group(1)) * _WINDOW_UNITS[m.group(2)]))
    if not out:
        raise ValueError("GORDO_SLO_WINDOWS parsed to no windows")
    return sorted(out, key=lambda w: w[1])


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"{name} must be a number, got {raw!r}") from None


class SLOTracker:
    """Samples a :class:`GoodputLedger` into a bounded ring and computes
    multi-window burn rates per objective."""

    def __init__(
        self,
        ledger,
        objectives: Optional[Sequence] = None,
        windows: Optional[Sequence[Tuple[str, float]]] = None,
        sample_interval_s: Optional[float] = None,
        fast_burn: Optional[float] = None,
        registry=None,
        clock=time.monotonic,
    ):
        self.ledger = ledger
        self.objectives = (
            list(objectives) if objectives is not None else parse_objectives()
        )
        if self.objectives and isinstance(self.objectives[0], dict):
            self.objectives = [
                _Objective(o["name"], o["target"], o.get("quantile"))
                for o in self.objectives
            ]
        self.windows = (
            list(windows) if windows is not None else parse_windows()
        )
        if sample_interval_s is None:
            sample_interval_s = _env_float("GORDO_SLO_SAMPLE_S", 10.0)
        self.sample_interval_s = max(0.001, float(sample_interval_s))
        self.fast_burn_threshold = (
            float(fast_burn)
            if fast_burn is not None
            else _env_float("GORDO_SLO_FAST_BURN", DEFAULT_FAST_BURN)
        )
        self._clock = clock
        max_window = max(s for _, s in self.windows)
        # bounded ring: enough samples to cover the longest window at the
        # configured cadence, capped so a test-grade ms cadence cannot
        # grow an unbounded deque (windows past the cap degrade to the
        # partial window the ring still covers, flagged via window_s)
        self._samples: deque = deque(
            maxlen=min(8192, int(max_window / self.sample_interval_s) + 8)
        )
        self._lock = threading.Lock()
        self._cached: Optional[Dict[str, Any]] = None
        # per-(tenant, priority-class) burn uses ONE budget: the
        # availability objective's when configured (per-class burn is an
        # availability-style "share of requests that weren't goodput"),
        # 0.001 otherwise — per-class latency/goodput-second objectives
        # would need per-class histograms the ledger deliberately
        # doesn't keep (cardinality)
        self._class_budget = next(
            (o.budget for o in self.objectives if o.name == "availability"),
            0.001,
        )
        if registry is not None:
            registry.collector(self._collect, key="slo")

    # ------------------------------------------------------------------ #
    # sampling
    # ------------------------------------------------------------------ #

    def _take_sample(self, now: float) -> Dict[str, float]:
        led = self.ledger
        sample: Dict[str, float] = {
            "t": now,
            "total": float(sum(led.requests.values())),
            "err": float(led.errors_5xx),
            "wall_good_s": led.wall_goodput_s,
            "wall_total_s": led.wall_goodput_s + led.wall_wasted_s,
            # latency objectives rate over SERVED requests only (the
            # ledger's histogram excludes failures — a fast-failing
            # outage must not read as a healthy p99)
            "latency_total": float(led.latency.count),
        }
        for obj in self.objectives:
            if obj.quantile is not None:
                sample[f"le:{obj.name}"] = float(
                    led.latency.count_le(obj.target / 1e3)
                )
        # per-(tenant, priority-class) cells (ISSUE 19) ride in the same
        # flat sample as "tc:<tenant>|<class>:good/:total" keys, so
        # _window_delta's generic subtraction windows them for free (a
        # key first seen mid-ring deltas against 0 — correct for
        # monotonic counters). Bounded: the ledger bounds tenant labels.
        cells = getattr(led, "tenant_cells", None)
        if cells:
            for (tenant, cls), cell in sorted(list(cells.items())):
                key = f"tc:{tenant}|{cls}"
                sample[f"{key}:good"] = float(cell[0])
                sample[f"{key}:total"] = float(cell[0] + cell[1] + cell[2])
        return sample

    def sample(self, now: Optional[float] = None, force: bool = False) -> bool:
        """Append a sample if the cadence (or ``force``) says so; returns
        whether one landed. Idempotent under concurrent callers (the
        background task, the `/slo` handler, the registry render)."""
        if now is None:
            now = self._clock()
        with self._lock:
            if (
                not force
                and self._samples
                and now - self._samples[-1]["t"] < self.sample_interval_s
            ):
                return False
            self._samples.append(self._take_sample(now))
            self._cached = None
            return True

    # ------------------------------------------------------------------ #
    # windows + burn
    # ------------------------------------------------------------------ #

    def _window_delta(
        self, window_s: float
    ) -> Optional[Tuple[Dict[str, float], float]]:
        """(latest - baseline, actual_window_s) where baseline is the
        oldest sample inside the window (the ring's oldest when the
        window outruns history — a partial window, honestly labeled)."""
        if len(self._samples) < 2:
            return None
        latest = self._samples[-1]
        start = latest["t"] - window_s
        baseline = None
        for s in self._samples:
            if s["t"] >= start:
                baseline = s
                break
        if baseline is None or baseline is latest:
            # every older sample predates the window: use the newest
            # sample that still precedes the latest one so short bursts
            # between two samples stay visible
            baseline = self._samples[-2]
        delta = {
            k: latest[k] - baseline.get(k, 0.0)
            for k in latest
            if k != "t"
        }
        return delta, max(1e-9, latest["t"] - baseline["t"])

    def _objective_windows(self, obj: _Objective) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for wname, wsec in self.windows:
            got = self._window_delta(wsec)
            if got is None:
                out[wname] = {
                    "window_s": 0.0, "good": 0.0, "total": 0.0,
                    "ratio": None, "burn_rate": 0.0,
                }
                continue
            delta, actual = got
            if obj.name == "availability":
                total = delta["total"]
                good = total - delta["err"]
            elif obj.quantile is not None:
                total = delta.get("latency_total", 0.0)
                good = delta.get(f"le:{obj.name}", 0.0)
            else:  # goodput_ratio
                total = delta["wall_total_s"]
                good = delta["wall_good_s"]
            if total <= 0:
                ratio, burn = None, 0.0
            else:
                ratio = good / total
                burn = max(0.0, (1.0 - ratio)) / obj.budget
            # ACTUAL covered span, never the nominal label: when the
            # sample cadence outruns a window the burst-visibility
            # fallback spans MORE than the window, and reporting the
            # label would hide exactly the dilution it causes (a "5m"
            # burn silently averaged over 10m)
            out[wname] = {
                "window_s": round(actual, 3),
                "good": round(good, 6),
                "total": round(total, 6),
                "ratio": None if ratio is None else round(ratio, 6),
                "burn_rate": round(burn, 4),
            }
        return out

    def snapshot(self) -> Dict[str, Any]:
        """Per-objective windowed ratios + burn rates. Computed from the
        sample ring alone and cached until the next sample — consecutive
        reads between samples are byte-identical (the no-drift
        contract)."""
        self.sample()  # lands only if the cadence is due
        with self._lock:
            if self._cached is not None:
                return self._cached
            fast_window = self.windows[0][0]
            objectives = []
            worst: Optional[Dict[str, Any]] = None
            for obj in self.objectives:
                windows = self._objective_windows(obj)
                entry = {**obj.describe(), "windows": windows}
                fast = windows[fast_window]["burn_rate"]
                entry["fast_burn"] = bool(
                    fast is not None and fast >= self.fast_burn_threshold
                )
                obj_worst = max(
                    (
                        (w["burn_rate"], name)
                        for name, w in windows.items()
                        if w["burn_rate"] is not None
                    ),
                    default=(0.0, fast_window),
                )
                entry["worst_burn"] = {
                    "window": obj_worst[1], "burn_rate": obj_worst[0]
                }
                if worst is None or obj_worst[0] > worst["burn_rate"]:
                    worst = {
                        "objective": obj.name,
                        "window": obj_worst[1],
                        "burn_rate": obj_worst[0],
                    }
                objectives.append(entry)
            self._cached = {
                "sample_interval_s": self.sample_interval_s,
                "n_samples": len(self._samples),
                "fast_burn_threshold": self.fast_burn_threshold,
                "windows": {name: sec for name, sec in self.windows},
                "objectives": objectives,
                "worst": worst,
                "classes": self._class_windows(),
            }
            return self._cached

    def _class_windows(self) -> Dict[str, Any]:
        """Per-(tenant, priority-class) windowed burn (lock held).
        Availability-style: good = goodput-classified requests, total =
        all classified, burn = (1 - ratio) / class budget."""
        latest = self._samples[-1] if self._samples else {}
        keys = sorted(
            k[3:-5]
            for k in latest
            if k.startswith("tc:") and k.endswith(":good")
        )
        if not keys:
            return {}
        # one delta per window, shared across every class key (the
        # objectives path recomputes per objective; class keys can be
        # tenants x classes wide, so share the subtraction here)
        deltas = {wname: self._window_delta(wsec) for wname, wsec in self.windows}
        fast_window = self.windows[0][0]
        out: Dict[str, Any] = {}
        for key in keys:
            windows: Dict[str, Any] = {}
            for wname, _wsec in self.windows:
                got = deltas[wname]
                if got is None:
                    windows[wname] = {
                        "window_s": 0.0, "good": 0.0, "total": 0.0,
                        "ratio": None, "burn_rate": 0.0,
                    }
                    continue
                delta, actual = got
                good = delta.get(f"tc:{key}:good", 0.0)
                total = delta.get(f"tc:{key}:total", 0.0)
                if total <= 0:
                    ratio, burn = None, 0.0
                else:
                    ratio = good / total
                    burn = max(0.0, 1.0 - ratio) / self._class_budget
                windows[wname] = {
                    "window_s": round(actual, 3),
                    "good": round(good, 6),
                    "total": round(total, 6),
                    "ratio": None if ratio is None else round(ratio, 6),
                    "burn_rate": round(burn, 4),
                }
            fast = windows[fast_window]["burn_rate"]
            out[key] = {
                "budget": round(self._class_budget, 6),
                "windows": windows,
                "fast_burn": bool(fast >= self.fast_burn_threshold),
            }
        return out

    def class_burn(self, qos_class: str) -> Optional[float]:
        """Fast-window burn for one priority class, summed across
        tenants — the admission controller's goodput-shed signal
        (qos/admission.py). None when the class served nothing in the
        window (no evidence is not a burn)."""
        snap = self.snapshot()
        fast_window = self.windows[0][0]
        good = total = 0.0
        for key, entry in snap.get("classes", {}).items():
            if key.rsplit("|", 1)[-1] != qos_class:
                continue
            w = entry["windows"].get(fast_window)
            if w:
                good += w["good"]
                total += w["total"]
        if total <= 0:
            return None
        return max(0.0, 1.0 - good / total) / self._class_budget

    def _collect(self):
        """Registry gauges from the SAME cached snapshot ``/slo`` serves
        — the no-drift contract between the scrape and the endpoint."""
        snap = self.snapshot()
        for obj in snap["objectives"]:
            for wname, w in obj["windows"].items():
                yield (
                    "gordo_slo_burn_rate", "gauge",
                    "Error-budget burn rate per objective and window "
                    "(1.0 = burning exactly at budget)",
                    {"objective": obj["name"], "window": wname},
                    w["burn_rate"],
                )
                if w["ratio"] is not None:
                    yield (
                        "gordo_slo_objective_ratio", "gauge",
                        "Windowed good-event ratio per objective",
                        {"objective": obj["name"], "window": wname},
                        w["ratio"],
                    )
        # per-(tenant, class) burn in the SAME family — alerting joins
        # "which objective is burning" with "whose traffic is burning it"
        # on one metric name. Tenant labels were bounded at classification
        # time (qos/classify.py), so this block cannot explode series.
        for key, entry in snap.get("classes", {}).items():
            tenant, _, qos_class = key.rpartition("|")
            for wname, w in entry["windows"].items():
                yield (
                    "gordo_slo_burn_rate", "gauge",
                    "Error-budget burn rate per objective and window "
                    "(1.0 = burning exactly at budget)",
                    {"tenant": tenant, "class": qos_class, "window": wname},
                    w["burn_rate"],
                )


# ---------------------------------------------------------------------- #
# fleet rollup (watchman)
# ---------------------------------------------------------------------- #


def merge_slo_snapshots(
    bodies: Sequence[Optional[Dict[str, Any]]],
) -> Dict[str, Any]:
    """Merge per-replica ``GET /slo`` bodies into one fleet view.

    Good/total deltas sum across replicas per (objective, window) — they
    are counts (availability, latency) or wall seconds (goodput), both
    additive — and the fleet burn rate recomputes from the summed ratio
    against the objective's budget. ``worst_burn`` attributes the
    hottest burn to the replica index reporting it, so "who is burning
    the fleet's budget" is one field, not a per-replica spelunk.
    Replicas that failed to answer (``None``) or have SLO disabled are
    counted out, never an error."""
    merged: Dict[str, Dict[str, Any]] = {}
    order: List[str] = []
    worst: Optional[Dict[str, Any]] = None
    classes: Dict[str, Dict[str, Any]] = {}
    scraped = 0
    for idx, body in enumerate(bodies):
        if not body or not body.get("enabled", True):
            continue
        objectives = body.get("objectives")
        if not isinstance(objectives, list):
            continue
        scraped += 1
        for key, cent in (body.get("classes") or {}).items():
            agg = classes.setdefault(
                key, {"budget": cent.get("budget"), "windows": {}}
            )
            for wname, w in (cent.get("windows") or {}).items():
                cell = agg["windows"].setdefault(
                    wname, {"good": 0.0, "total": 0.0}
                )
                cell["good"] += float(w.get("good") or 0.0)
                cell["total"] += float(w.get("total") or 0.0)
        for obj in objectives:
            name = obj.get("name")
            if not name:
                continue
            entry = merged.get(name)
            if entry is None:
                entry = merged[name] = {
                    "name": name,
                    "target": obj.get("target"),
                    "budget": obj.get("budget"),
                    "windows": {},
                }
                order.append(name)
            for wname, w in (obj.get("windows") or {}).items():
                cell = entry["windows"].setdefault(
                    wname, {"good": 0.0, "total": 0.0}
                )
                cell["good"] += float(w.get("good") or 0.0)
                cell["total"] += float(w.get("total") or 0.0)
                burn = w.get("burn_rate")
                if burn is not None and (
                    worst is None or burn > worst["burn_rate"]
                ):
                    worst = {
                        "objective": name,
                        "window": wname,
                        "replica": idx,
                        "burn_rate": burn,
                    }
    objectives_out = []
    for name in order:
        entry = merged[name]
        budget = entry.get("budget") or 1.0
        for w in entry["windows"].values():
            if w["total"] > 0:
                ratio = w["good"] / w["total"]
                w["ratio"] = round(ratio, 6)
                w["burn_rate"] = round(max(0.0, 1.0 - ratio) / budget, 4)
            else:
                w["ratio"] = None
                w["burn_rate"] = 0.0
            w["good"] = round(w["good"], 6)
            w["total"] = round(w["total"], 6)
        objectives_out.append(entry)
    for agg in classes.values():
        budget = agg.get("budget") or 0.001
        for w in agg["windows"].values():
            if w["total"] > 0:
                ratio = w["good"] / w["total"]
                w["ratio"] = round(ratio, 6)
                w["burn_rate"] = round(max(0.0, 1.0 - ratio) / budget, 4)
            else:
                w["ratio"] = None
                w["burn_rate"] = 0.0
            w["good"] = round(w["good"], 6)
            w["total"] = round(w["total"], 6)
    out = {
        "replicas_scraped": scraped,
        "objectives": objectives_out,
        "worst_burn": worst,
    }
    if classes:
        out["classes"] = dict(sorted(classes.items()))
    return out
