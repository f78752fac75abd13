"""Share (%) of the routed (row, expert) pairs that fell on an expert this
chip holds: the bank's counters ``held_pairs`` over ``routed_pairs``
(``/stats`` ``bank_shared``). 100 x held / published experts (6.25 at 12 of
192) is an even load; ``None`` where the program keeps no such counters."""


def read(obs):
    shared = obs.get("shared") or {}
    if not shared.get("routed_pairs"):
        return None
    return 100.0 * shared.get("held_pairs", 0) / shared["routed_pairs"]
