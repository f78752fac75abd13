"""The held experts' share of their roofline, in the routed layers of a
trunk that holds a range of each layer's experts: operations of the (row,
expert) pairs that fell on a held expert (the bank's counter ``held_pairs``
a dispatch, times the stretch's dispatches: gate, up and down of each
pair), bytes every routed layer's held experts read once a dispatch and
each row's state in and out once a routed layer, at the chip's peaks, over
the device seconds of the ops under the scopes ``trunk/route``,
``trunk/experts`` and ``trunk/combine``. ``None``, never 0, where no pair
was held or the program keeps no such counter."""

import families
from harness import counts

SCOPES = ("trunk/route", "trunk/experts", "trunk/combine")


def read(obs):
    trace, scopes, shared = obs.get("trace"), obs.get("scopes"), obs.get("shared") or {}
    engine = obs.get("engine") or {}
    if not trace or not scopes or not engine.get("batches"):
        return None
    if not shared.get("held_pairs") or not shared.get("dispatches"):
        return None
    seconds = sum(scopes.get(s, 0.0) for s in SCOPES)
    dispatches = trace["module_calls"].get("jit_score")
    if not seconds or not dispatches:
        return None
    config = obs["config"]
    layout = families.load(config["family"], "layout")
    rows = dispatches * engine["requests"] / engine["batches"] * obs["request_rows"]
    held_pairs = dispatches * shared["held_pairs"] / shared["dispatches"]
    share, _bound = counts.roofline(
        layout.held_experts_flops(config, held_pairs),
        layout.held_experts_bytes(config, dispatches, rows), seconds, obs["peaks"],
    )
    return share
