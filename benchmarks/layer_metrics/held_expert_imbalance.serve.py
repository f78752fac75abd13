"""Pairs routed to a routed layer's busiest held expert over the mean
pairs a held expert, summed over layers and dispatches: the bank's counters
``held_tokens_busiest`` x held experts / ``held_pairs`` (``/stats``
``bank_shared``). 1 is an even load among the experts this chip holds, the
number held (12) every held pair on one expert: what bounds the longest
group of the grouped matmul, which ``held_pair_share.serve`` cannot see.
``None`` where no pair was held or the program keeps no such counters."""


def read(obs):
    shared = obs.get("shared") or {}
    if not shared.get("held_pairs"):
        return None
    first, end = obs["config"]["expert_shard"]["held"]
    return shared.get("held_tokens_busiest", 0) * (int(end) - int(first)) / shared["held_pairs"]
