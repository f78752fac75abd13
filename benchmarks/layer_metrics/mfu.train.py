"""Whole-step share of the chip's peak: training FLOPs of the members
finished in the window (3 x forward per row per epoch) over the window over
the peak bf16 rate."""

from harness import counts


def read(obs):
    if not obs.get("peaks") or not obs.get("fits"):
        return None
    config = obs["config"]
    flops = counts.train_epoch_flops(config, obs["members_done"], obs["rows"]) * int(config["epochs"])
    return 100.0 * flops / obs["window_s"] / obs["peaks"]["flops_bf16"]
