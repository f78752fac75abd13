"""Device milliseconds of one dispatch of a shared-trunk bucket: the
device seconds of its programs in the trace (XLA modules ``jit_score*``:
``score_enter``, ``score_layer`` once a layer, ``score``) over the calls
of the closing one."""

MODULE_PREFIXES = ("jit_score",)


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs.get("scopes"):
        return None
    dispatches = trace["module_calls"].get("jit_score")
    seconds = sum(
        s for name, s in trace["module_seconds"].items() if name.startswith(MODULE_PREFIXES)
    )
    if not dispatches or not seconds:
        return None
    return 1e3 * seconds / dispatches
