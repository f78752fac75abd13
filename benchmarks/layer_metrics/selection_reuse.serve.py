"""Layers that attended under a selection over layers that made one: the
bank's counters ``selection_uses`` over ``selection_layers`` (``/stats``
``bank_shared``). 2.5 for the benchmark's cut of 2 ``full`` layers in 5
(3.7 for the published 21 in 78), 1 where every layer selects for itself:
what it falls to if sharing ever stops. ``None`` where the program keeps no
such counters or no layer selected."""


def read(obs):
    shared = obs.get("shared") or {}
    if not shared.get("selection_layers") or "selection_uses" not in shared:
        return None
    return shared["selection_uses"] / shared["selection_layers"]
