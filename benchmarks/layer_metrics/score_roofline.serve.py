"""The bucket program's share of its roofline: the benchmark's count for
the requests the traced stretch scored (forward FLOPs per row x rows;
bytes: each request's member weights, inputs and outputs once) at the
chip's peaks, over the bucket program's device time in the trace. The
requests of the stretch are taken as the window's rate times its length."""

from harness import counts

MODULE_PREFIXES = ("jit_score",)


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs.get("requests_completed"):
        return None
    seconds = sum(
        s for name, s in trace["module_seconds"].items() if name.startswith(MODULE_PREFIXES)
    )
    if not seconds:
        return None
    config, rows = obs["config"], obs["request_rows"]
    requests = obs["requests_completed"] * obs["traced_window_s"] / obs["window_s"]
    flops = counts.forward_flops_per_row(config) * counts.windows_per_request(config, rows)
    share, _bound = counts.roofline(
        requests * flops, requests * counts.score_request_bytes(config, rows),
        seconds, obs["peaks"],
    )
    return share
