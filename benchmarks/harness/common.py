"""Pieces every kind of cell shares: the device block, the profiler
window, the result line."""

import contextlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict, Optional

from harness import check, spec, trace as trace_mod


def work_dir() -> str:
    """Scratch inside the checkout (``.bench_work/`` is git-ignored),
    removed when the run ends. Holds stub artifacts and the profiler's
    trace; nothing large."""
    base = os.path.join(spec.ROOT, ".bench_work")
    os.makedirs(base, exist_ok=True)
    return tempfile.mkdtemp(prefix="run-", dir=base)


def remove(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)


def device_block() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes() -> Optional[int]:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


@contextlib.contextmanager
def annotate(name: str):
    """A host span on the profiler's clock; idle gaps are named by these."""
    import jax

    with jax.profiler.TraceAnnotation("bench:" + name):
        yield


class TracedWindow:
    """Profiler on for a stretch of the measured window; the reduction is
    read after the run's peak memory has been taken."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.window_s = 0.0
        self._t0 = None

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # TraceMe spans only: cheap enough to leave the server alone
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self._t0 = time.monotonic()

    def stop(self) -> None:
        import jax

        self.window_s = time.monotonic() - self._t0
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        path = trace_mod.find_xplane(self.log_dir)
        if path is None:
            return {}
        out = trace_mod.reduce_file(path)
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return out


def median_span_ms(obs: dict, name: str) -> Optional[float]:
    """Median of one of the server's own stage spans (host clock) over the
    request traces it retained in the window; ``None`` where it has none."""
    durations = (obs.get("spans") or {}).get(name)
    return statistics.median(durations) if durations else None


def idle_share(obs: dict) -> Optional[float]:
    """Share (%) of the traced stretch in which no operation ran on the device."""
    trace = obs.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / obs["traced_window_s"])


def per_layer_metrics(cell, obs: dict) -> Dict[str, dict]:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for metric in cell.per_layer:
        value = spec.load_reader(metric["name"])(obs)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def emit(cell, traced: bool, values: Dict[str, float], obs: dict, attempted: int,
         failed: int, checks: Dict[str, dict], memory_peak: Optional[int]) -> dict:
    """Print the numbers compared (stderr) and the one result line (stdout,
    last)."""
    device = dict(device_block(), memory_peak_bytes=memory_peak)
    if traced:
        metrics = per_layer_metrics(cell, obs)
        reduction = obs.get("trace") or {}
        if reduction:
            device["busy_s"] = reduction["busy_s"]
            device["window_s"] = obs["traced_window_s"]
    else:
        metrics = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end
        }
    result = {
        "correct": check.is_correct(checks),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if traced and obs.get("trace"):
        result["breakdown"] = {
            "device_ops": obs["trace"]["device_ops"],
            "idle_gaps": obs["trace"]["idle_gaps"],
        }
    result["checks"] = {
        k: {"value": c["value"], "limit": c["limit"]} for k, c in checks.items()
    }
    sys.stdout.flush()
    check.report(checks)
    print(json.dumps(result), flush=True)
    return result
