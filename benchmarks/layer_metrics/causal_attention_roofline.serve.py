"""The attention kernel's share of its roofline where it runs with no mask
(every causal key, causality from the tiles' own indices): the family's
count for the traced stretch (operations: every query head's scores and
values over every causal pair of every request's valid rows in every
attention layer; bytes: every query head's query and the key-value heads'
keys and values in, in bfloat16, every query head's output out, in
float32) at the chip's peaks, over the device seconds of the ops under the
scope ``trunk/attention`` (the kernel alone). The stretch's requests are
the closing bucket program's calls in the trace times the window's
requests a dispatch. ``None``, never 0, without a trace and where the
program has no such scope."""

import families
from harness import counts


def read(obs):
    trace, scopes, engine = obs.get("trace"), obs.get("scopes"), obs.get("engine") or {}
    if not trace or not scopes or not engine.get("batches"):
        return None
    seconds = scopes.get("trunk/attention", 0.0)
    dispatches = trace["module_calls"].get("jit_score")
    layout = families.load(obs["config"]["family"], "layout")
    if not seconds or not dispatches or not hasattr(layout, "causal_attention_flops"):
        return None
    config, rows = obs["config"], obs["request_rows"]
    requests = dispatches * engine["requests"] / engine["batches"]
    layers = layout.count(config, "*")
    share, _bound = counts.roofline(
        requests * layers * layout.causal_attention_flops(config, rows),
        layers * layout.causal_attention_bytes(config, requests * rows), seconds, obs["peaks"],
    )
    return share
