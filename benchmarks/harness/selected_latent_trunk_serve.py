"""Cells whose bank is a latent-attention trunk that attends under an
indexer's selection shared between layers, of which the chip holds a share
of the routed experts (configuration ``glm52_trunk300``), scoring requests
over HTTP.

The third trunk driver, and nearly all of it is the first's.
``trunk_serve.py`` stages, serves, runs, compares both kinds of selection
and reads the control, but its reference call reads ``config["sa_config"]``
and its scopes are one kind's; ``latent_trunk_serve.py`` has no key
selections. ``trunk_serve``'s functions find their scopes and their
reference by its module's names (``SCOPES``, ``_sample_reference``), so
``run`` and ``control_readings`` here call ``trunk_serve``'s with this
driver's two in their place (the seam ``tools/trunk_sweep.py`` uses on
``serve.start_server``); everything else (``stage_trunk`` through
``start_server``, ``_serve``, the window, ``compare_answers``,
``selection_gaps``, ``as_answer``, the result line) is ``trunk_serve``'s as
it stands.

After the window a seeded sample of the window's own answers is compared
with the family's plain reference, which is given the same share of the
experts: the six arrays, which 8 of the 256 experts each row was routed to
in every routed layer, and which keys every 64th query attended to in
EVERY layer: under the selection the layer made (``full``) or was handed
(``shared``), so that a layer that attends under another one than it should
shows. A traced run adds the device
seconds by ``jax.named_scope`` and the bank's counters for buckets with
shared leaves (``key_selections``, ``selection_layers``, ``selection_uses``
beside the held experts'). The merge of the three drivers is a
``benchmark`` issue's (PERF.md section 7.3b).
"""

from typing import Dict, List, Optional
from unittest import mock

import numpy as np

import families
from harness import spec, trunk_serve, weights

SCOPES = (
    "trunk/project", "trunk/indexer", "trunk/select", "trunk/attend", "trunk/dense_mlp",
    "trunk/shared_expert", "trunk/route", "trunk/experts", "trunk/combine",
    "member/in_proj", "member/head",
)
_control_readings = trunk_serve.control_readings  # bound now: a tool may point ``trunk_serve``'s at ours


def reference_answer(config: dict, seed: int, w: Dict[str, np.ndarray], X: np.ndarray,
                     **how) -> Dict[str, np.ndarray]:
    """The six arrays and both kinds of selection for one request, by the
    family's plain reference: input scaling, the trunk's forecast, absolute
    error in model space against the NEXT row, error scaling, row norms."""
    import jax.numpy as jnp

    layout = families.load(config["family"], "layout")
    forward = families.load(config["family"], "forward")
    xs = (np.asarray(X, np.float32) - w["in_shift"]) * w["in_scale"]
    stride = layout.witness_stride(config)
    got = forward.forecast(
        config, lambda l: layout.trunk_layer(config, seed, l),
        {k: jnp.asarray(v) for k, v in w.items()}, xs, np.arange(stride - 1, len(xs), stride), **how,
    )
    recon = np.asarray(got["out"])[:-1]
    diff = np.abs(xs[1:] - recon)
    scaled = (diff - w["err_shift"]) * w["err_scale"]
    return {
        "model-input": np.asarray(X[1:], np.float32), "model-output": recon,
        "tag-anomaly-unscaled": diff, "tag-anomaly-scaled": scaled,
        "total-anomaly-unscaled": np.sqrt(np.sum(diff * diff, axis=-1)),
        "total-anomaly-scaled": np.sqrt(np.sum(scaled * scaled, axis=-1)),
        "experts": np.asarray(got["experts"]),  # (routed layers, T, E) bool
        "keys": np.asarray(got["keys"]),  # (layers, sampled, T) bool: what each attended under
    }


def _sample_reference(config: dict, seed: int, rows: int, meta: dict, **how):
    return reference_answer(
        config, seed, weights.member_weights(config, seed, meta["member"]),
        weights.request_body(config, seed, meta["body"], rows), **how,
    )


def _as_trunk_serve():
    """``trunk_serve``'s names this driver stands in for, for one call."""
    return mock.patch.multiple(trunk_serve, SCOPES=SCOPES, _sample_reference=_sample_reference)


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, t_start: float,
        on_tpu: bool = True) -> dict:
    with _as_trunk_serve():
        return trunk_serve.run(cell, seed, seconds, traced, t_start, on_tpu)


def control_readings(cell: spec.Cell, seeds, requests: Optional[int] = None) -> List[dict]:
    """``trunk_serve.control_readings`` over this family's reference: the
    stated arithmetic, the float8 control and each of the family's planted
    faults, each read against the reference, on the chip with no server."""
    with _as_trunk_serve():
        return _control_readings(cell, seeds, requests)
