"""Share (%) of the scoring requests' root spans that their top-level stage
spans account for: the sum of every retained ``parse``, ``admit``,
``queue_wait``, ``handoff``, ``coalesce``, ``pad``, ``device_execute``,
``postprocess``, ``resolve`` and ``encode`` span over the sum of the
``anomaly`` root spans. The top-level stages of a request do not overlap, so
what is missing from 100 is time no span names."""

TOP_LEVEL = (
    "parse", "admit", "queue_wait", "handoff", "coalesce", "pad",
    "device_execute", "postprocess", "resolve", "encode",
)


def read(obs):
    spans = obs.get("spans") or {}
    roots = spans.get("anomaly")
    staged = [sum(spans[name]) for name in TOP_LEVEL if spans.get(name)]
    if not roots or not staged:
        return None
    return 100.0 * sum(staged) / sum(roots)
