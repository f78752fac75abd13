"""The latent attention kernel's share of its roofline where it attends
under a selection: the family's count for the traced stretch (operations:
every head's scores, nope + rope wide, and values over the SELECTED pairs
of every request and every layer that attends under a selection, which is
what the equations need, whatever the kernel computes: a kernel that works
through every causal tile does 2.7 times as many and reads low for it;
bytes: the least any form must move, every head's query and the row's
576-wide latent in, every head's output out, in bfloat16, and the
selection's bits) at the chip's peaks, over the device seconds of the ops
under the scope ``trunk/attend`` (the kernel alone). The stretch's requests
are the closing bucket program's calls in the trace times the window's
requests a dispatch. ``None``, never 0, without a trace, and where the
program keeps no count of layers that attended under a selection."""

import families
from harness import counts


def read(obs):
    trace, scopes, shared = obs.get("trace"), obs.get("scopes"), obs.get("shared") or {}
    engine = obs.get("engine") or {}
    if not trace or not scopes or not engine.get("batches"):
        return None
    if not shared.get("selection_uses") or not shared.get("dispatches"):
        return None
    seconds = scopes.get("trunk/attend", 0.0)
    dispatches = trace["module_calls"].get("jit_score")
    layout = families.load(obs["config"]["family"], "layout")
    if not seconds or not dispatches or not hasattr(layout, "selected_attention_flops"):
        return None
    config, rows = obs["config"], obs["request_rows"]
    requests = dispatches * engine["requests"] / engine["batches"]
    layers = shared["selection_uses"] / shared["dispatches"]  # layers that attended under one, a dispatch
    share, _bound = counts.roofline(
        requests * layers * layout.selected_attention_flops(config, rows),
        layers * layout.selected_attention_bytes(config, requests * rows, rows), seconds, obs["peaks"],
    )
    return share
