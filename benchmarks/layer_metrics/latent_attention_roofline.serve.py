"""The latent attention kernel's share of its roofline: the family's count
for the traced stretch (operations: every head's scores, nope + rope wide,
and values over the causal pairs of every request and layer, the expanded
form's, which is the fewest the equations need, so a form that does more
cannot read better for it; bytes: the least any form must move, every
head's query and the row's 576-wide latent in, every head's output out, in
bfloat16) at the chip's peaks, over the device seconds of the ops under
the scope ``trunk/attend`` (the kernel alone). The stretch's requests are
the closing bucket program's calls in the trace times the window's
requests a dispatch. ``None`` where the program has no such scope."""

import families
from harness import counts


def read(obs):
    trace, scopes = obs.get("trace"), obs.get("scopes")
    engine = obs.get("engine") or {}
    if not trace or not scopes or not engine.get("batches"):
        return None
    seconds = scopes.get("trunk/attend", 0.0)
    dispatches = trace["module_calls"].get("jit_score")
    layout = families.load(obs["config"]["family"], "layout")
    if not seconds or not dispatches or not hasattr(layout, "latent_attention_flops"):
        return None
    config, rows = obs["config"], obs["request_rows"]
    requests = dispatches * engine["requests"] / engine["batches"]
    layers = int(config["num_hidden_layers"])
    share, _bound = counts.roofline(
        requests * layers * layout.latent_attention_flops(config, rows),
        layers * layout.latent_attention_bytes(config, requests * rows), seconds, obs["peaks"],
    )
    return share
