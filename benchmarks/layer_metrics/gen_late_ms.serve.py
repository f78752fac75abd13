"""How late the load generator really sent requests (99th percentile of
sent - due): a starved generator must not read as a fast server."""

import numpy as np


def read(obs):
    late = obs.get("late_ms")
    if late is None or not len(late):
        return None
    return float(np.percentile(late, 99))
