"""99th percentile of the window's client-side latencies, the sample
``score_p50_ms`` and ``score_p95_ms`` are taken from. Not an end-to-end
metric: with 40-50 requests beyond it, one or two 0.1 s stalls in a window
move it by half (PERF.md section 6)."""

import numpy as np


def read(obs):
    latency = obs.get("latency_ms")
    if latency is None or not len(latency):
        return None
    return float(np.percentile(latency, 99))
