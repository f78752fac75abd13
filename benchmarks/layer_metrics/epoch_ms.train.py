"""Median host-clock time of one epoch dispatch (blocks on the losses),
over every epoch of every fit in the window. The first two epochs of a fit
re-trace and load their program, so they sit above the median."""

import statistics


def read(obs):
    fits = obs.get("fits")
    if not fits:
        return None
    return 1e3 * statistics.median(s for f in fits for s in f["epoch_seconds"])
