"""Unified fleet metrics layer.

One dependency-free registry abstraction shared by every long-running
process (serving, fleet builder, watchman): label-aware Counter /
Gauge / log-binned Histogram primitives with Prometheus text-format
exposition and a JSON snapshot view, so the human-readable ``/stats``
endpoint and the ``/metrics`` scrape endpoint read the same underlying
integers and can never drift.
"""

from gordo_components_tpu.observability.events import (
    Event,
    EventLog,
    get_event_log,
    set_event_log,
)
from gordo_components_tpu.observability.cost import (
    CostModel,
    cost_from_env,
    merge_cost_snapshots,
)
from gordo_components_tpu.observability.goodput import (
    GoodputLedger,
    attribute_trace,
)
from gordo_components_tpu.observability.heat import (
    HeatAccountant,
    heat_from_env,
    merge_heat_snapshots,
)
from gordo_components_tpu.observability.metrics import (
    Histogram,
    MetricsRegistry,
    get_registry,
    parse_prometheus_text,
    render_samples,
)
from gordo_components_tpu.observability.slo import (
    SLOTracker,
    merge_slo_snapshots,
)
from gordo_components_tpu.observability.timeseries import (
    HistoryStore,
    history_from_env,
)
from gordo_components_tpu.observability.tracing import (
    Span,
    Trace,
    Tracer,
    chrome_trace,
    current_trace,
    format_traceparent,
    get_tracer,
    parse_traceparent,
    use_trace,
)

__all__ = [
    "CostModel",
    "Event",
    "EventLog",
    "GoodputLedger",
    "HeatAccountant",
    "Histogram",
    "HistoryStore",
    "MetricsRegistry",
    "SLOTracker",
    "Span",
    "Trace",
    "Tracer",
    "attribute_trace",
    "chrome_trace",
    "cost_from_env",
    "current_trace",
    "format_traceparent",
    "get_event_log",
    "get_registry",
    "get_tracer",
    "heat_from_env",
    "history_from_env",
    "merge_cost_snapshots",
    "merge_heat_snapshots",
    "merge_slo_snapshots",
    "parse_prometheus_text",
    "parse_traceparent",
    "render_samples",
    "set_event_log",
    "use_trace",
]
