"""Device milliseconds a dispatch that go to MAKING the selections: the
device seconds of the ops under the scopes ``trunk/indexer`` (the indexer's
dots and scores, every causal pair of the layers that select) and
``trunk/select`` (the k-th largest score a query, the mask, the count and
the witness), over the calls of the closing bucket program. What sharing a
selection between layers saves is this, times the layers that share.
``None`` without a trace and where the program has no such scopes."""

SCOPES = ("trunk/indexer", "trunk/select")


def read(obs):
    trace, scopes = obs.get("trace"), obs.get("scopes")
    if not trace or not scopes:
        return None
    seconds = sum(scopes.get(s, 0.0) for s in SCOPES)
    dispatches = trace["module_calls"].get("jit_score")
    if not seconds or not dispatches:
        return None
    return 1e3 * seconds / dispatches
