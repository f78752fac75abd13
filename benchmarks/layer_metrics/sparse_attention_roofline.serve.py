"""The indexer-selected attention's share of its roofline: the family's
count for the traced stretch (operations of the selected keys and of the
causal indexer scores only; bytes: queries, keys, values, indexer queries
and keys once a layer) at the chip's peaks, over the device seconds of the
ops under the scopes ``trunk/indexer``, ``trunk/select`` and
``trunk/attend``. A program that computes dense causal tiles under a mask
does more operations than are counted here, and reads lower for it. The
stretch's requests are the closing bucket program's calls in the trace
times the window's requests a dispatch."""

import families
from harness import counts

SCOPES = ("trunk/indexer", "trunk/select", "trunk/attend")


def read(obs):
    trace, scopes = obs.get("trace"), obs.get("scopes")
    engine = obs.get("engine") or {}
    if not trace or not scopes or not engine.get("batches"):
        return None
    seconds = sum(scopes.get(s, 0.0) for s in SCOPES)
    dispatches = trace["module_calls"].get("jit_score")
    if not seconds or not dispatches:
        return None
    config, rows = obs["config"], obs["request_rows"]
    layout = families.load(config["family"], "layout")
    requests = dispatches * engine["requests"] / engine["batches"]
    layers = int(config["num_hidden_layers"])
    share, _bound = counts.roofline(
        requests * layers * layout.sparse_attention_flops(config, rows),
        layout.sparse_attention_bytes(config, requests * rows), seconds, obs["peaks"],
    )
    return share
