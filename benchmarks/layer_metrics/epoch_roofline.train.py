"""The epoch program's share of its roofline: the benchmark's count for one
epoch of the gang at the chip's peaks over that program's device time per
call in the trace. Memory-bound by count (Adam state per 100-row step)."""

from harness import counts

MODULE_PREFIXES = ("jit_masked_epoch", "jit_masked_gang")


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    names = [n for n in trace["module_seconds"] if n.startswith(MODULE_PREFIXES)]
    calls = sum(trace["module_calls"][n] for n in names)
    if not calls:
        return None
    seconds = sum(trace["module_seconds"][n] for n in names) / calls
    config = obs["config"]
    share, _bound = counts.roofline(
        counts.train_epoch_flops(config, obs["gang_members"], obs["rows"]),
        counts.train_epoch_bytes(config, obs["gang_members"], obs["rows"], obs["padded_rows"]),
        seconds, obs["peaks"],
    )
    return share
