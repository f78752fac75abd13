"""The state-space scan kernel's share of its roofline: the family's count
for the traced stretch (operations: the scan in whole chunks of the
configuration's ``chunk_size``, a chunk's ``C B^T`` a group, its decayed
matrix times x, the carried state's term and the state's update a head;
bytes: x, B and C in, the time step and its decays' running sums in, y
out, the state staying on chip), over the chunks the bank's counter
``ssm_chunks`` says the mixer layers scanned a dispatch (valid rows'
chunks, summed over the layers) times the stretch's dispatches, at the
chip's peaks, over the device seconds of the ops under the scope
``trunk/mamba/scan`` (softplus, the decays' running sums and the kernel).
``None``, never 0, where nothing was scanned or the program keeps no such
counter."""

import families
from harness import counts


def read(obs):
    trace, scopes, shared = obs.get("trace"), obs.get("scopes"), obs.get("shared") or {}
    if not trace or not scopes:
        return None
    if not shared.get("ssm_chunks") or not shared.get("dispatches"):
        return None
    seconds = scopes.get("trunk/mamba/scan", 0.0)
    dispatches = trace["module_calls"].get("jit_score")
    layout = families.load(obs["config"]["family"], "layout")
    if not seconds or not dispatches or not hasattr(layout, "scan_flops"):
        return None
    config = obs["config"]
    rows = dispatches * shared["ssm_chunks"] / shared["dispatches"] * int(config["chunk_size"])
    share, _bound = counts.roofline(
        layout.scan_flops(config, rows), layout.scan_bytes(config, rows), seconds, obs["peaks"])
    return share
