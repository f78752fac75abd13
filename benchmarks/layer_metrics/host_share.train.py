"""Share of a fit's wall time outside the epoch dispatches: scaler fits,
host-to-device copies, error scalers, unstacking. Source: the trainer's own
``fleet_stats.epoch_seconds`` against the benchmark's span around ``fit``,
over every fit of the window."""


def read(obs):
    fits = obs.get("fits")
    if not fits:
        return None
    wall = sum(f["wall_s"] for f in fits)
    epochs = sum(sum(f["epoch_seconds"]) for f in fits)
    return 100.0 * (1.0 - epochs / wall)
