"""The trainer's own stage spans, for the ``.train`` readers.

The refit driver puts no spans in ``obs``. A ``FleetTrainer.fit`` with no
caller trace leaves one ``fleet_fit`` trace on the process tracer
(``observability.tracing.get_tracer()``), in the run's own process, so the
readers take the last ``len(obs["fits"])`` of them by start time: the
window's fits, without the set-up fit. A program that keeps no such trace
gives no fits, and every reader then returns ``None``.
"""

import statistics
from typing import Dict, List, Optional, Sequence

PREPARE = ("stack_pad", "to_device", "scaler_fit", "init_state")
FINISH = ("error_scalers", "unstack", "members")
STAGES = PREPARE + ("epoch", "epoch_host") + FINISH
BUCKET = "fit:"  # the span over one bucket's stages is named fit:<bucket>


def window_fits(obs: dict) -> List[Dict[str, float]]:
    """One dict per fit of the window: seconds by span name, summed over
    the fit's spans of that name (``fit:`` holds its bucket spans)."""
    n = len(obs.get("fits") or ())
    if not n:
        return []
    from gordo_components_tpu.observability.tracing import get_tracer

    traces = sorted(
        (t for t in get_tracer().recent() if t.name == "fleet_fit"),
        key=lambda t: t.root.start,
    )[-n:]
    fits = []
    for trace in traces:
        seconds: Dict[str, float] = {}
        for span in trace.spans:
            if span.end is None:
                continue
            name = BUCKET if span.name.startswith(BUCKET) else span.name
            seconds[name] = seconds.get(name, 0.0) + (span.end - span.start)
        fits.append(seconds)
    return fits


def median_ms(obs: dict, names: Sequence[str]) -> Optional[float]:
    """Median over the window's fits of the seconds a fit spent in the
    stages named, in milliseconds; ``None`` where no fit has any of them."""
    per_fit = [
        sum(fit[name] for name in names if name in fit)
        for fit in window_fits(obs)
        if any(name in fit for name in names)
    ]
    return 1e3 * statistics.median(per_fit) if per_fit else None
