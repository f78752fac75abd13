"""Whole-step share of the chip's peak: forward FLOPs of the rows the
window completed over the window over the peak bf16 rate."""

from harness import counts


def read(obs):
    if not obs.get("peaks") or not obs.get("requests_completed"):
        return None
    config = obs["config"]
    flops = (
        counts.forward_flops_per_row(config)
        * counts.windows_per_request(config, obs["request_rows"])
        * obs["requests_completed"]
    )
    return 100.0 * flops / obs["window_s"] / obs["peaks"]["flops_bf16"]
