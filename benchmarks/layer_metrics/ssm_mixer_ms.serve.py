"""Device milliseconds a dispatch of the state-space mixer layers: the
device seconds of the ops under the scopes ``trunk/mamba/in_proj``,
``trunk/mamba/conv``, ``trunk/mamba/scan``, ``trunk/mamba/norm`` and
``trunk/mamba/out_proj``, every mixer layer's, over the calls of the
closing bucket program. ``None`` without a trace and where the program has
no such scopes."""

SCOPES = ("trunk/mamba/in_proj", "trunk/mamba/conv", "trunk/mamba/scan", "trunk/mamba/norm",
          "trunk/mamba/out_proj")


def read(obs):
    trace, scopes = obs.get("trace"), obs.get("scopes")
    if not trace or not scopes:
        return None
    seconds = sum(scopes.get(s, 0.0) for s in SCOPES)
    dispatches = trace["module_calls"].get("jit_score")
    if not seconds or not dispatches:
        return None
    return 1e3 * seconds / dispatches
