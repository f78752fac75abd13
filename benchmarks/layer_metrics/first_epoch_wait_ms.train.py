"""What a fit's first epoch dispatch waits for beyond an epoch: the gang's
rows still crossing to the device when it is dispatched (and the trace of
the program, where a fit re-traces it). Per fit, ``epoch_seconds[0]`` less
the median of its later epochs; median over the window's fits. ``None``
where no fit has two epochs."""

import statistics


def read(obs):
    waits = [
        f["epoch_seconds"][0] - statistics.median(f["epoch_seconds"][1:])
        for f in obs.get("fits") or ()
        if len(f.get("epoch_seconds") or ()) >= 2
    ]
    return 1e3 * statistics.median(waits) if waits else None
