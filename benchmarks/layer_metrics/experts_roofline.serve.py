"""The routed expert layers' share of their roofline: the family's count
for the traced stretch (operations: each completed row's ``k`` experts in
every layer; bytes: every layer's experts read once a dispatch, each row's
state in and out once a layer) at the chip's peaks, over the device
seconds of the ops under the scopes ``trunk/route``, ``trunk/experts`` and
``trunk/combine``. Dispatches are the closing bucket program's calls in
the trace, and the stretch's rows those dispatches times the window's
requests a dispatch (at one request a second a 10 s stretch holds 6 to 14
of them: the window's rate would misread it)."""

import families
from harness import counts

SCOPES = ("trunk/route", "trunk/experts", "trunk/combine")


def read(obs):
    trace, scopes = obs.get("trace"), obs.get("scopes")
    engine = obs.get("engine") or {}
    if not trace or not scopes or not engine.get("batches"):
        return None
    seconds = sum(scopes.get(s, 0.0) for s in SCOPES)
    dispatches = trace["module_calls"].get("jit_score")
    if not seconds or not dispatches:
        return None
    config = obs["config"]
    layout = families.load(config["family"], "layout")
    rows = dispatches * engine["requests"] / engine["batches"] * obs["request_rows"]
    flops = rows * int(config["num_hidden_layers"]) * layout.experts_flops_per_row(config)
    share, _bound = counts.roofline(
        flops, layout.experts_bytes(config, dispatches, rows), seconds, obs["peaks"]
    )
    return share
