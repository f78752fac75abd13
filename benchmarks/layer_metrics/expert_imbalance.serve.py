"""Tokens routed to a layer's busiest expert over the mean tokens an
expert, summed over layers and dispatches: from the counters the bank
keeps from arrays the bucket program returns (``/stats`` ``bank_shared``:
``expert_tokens_busiest``, ``expert_tokens``). 1 is an even load."""


def read(obs):
    shared = obs.get("shared")
    if not shared or not shared.get("expert_tokens"):
        return None
    experts = int(obs["config"]["num_experts"])
    return shared["expert_tokens_busiest"] * experts / shared["expert_tokens"]
