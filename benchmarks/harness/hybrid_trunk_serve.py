"""Cells whose bank is a hybrid trunk of one-mixer layers (Mamba-2
state-space mixers, routed squared-ReLU experts of which the chip holds a
share, attention over every causal key; configuration
``nemotron3_trunk300``), scoring requests over HTTP.

The fourth trunk driver, and all of it but its scopes is
``latent_trunk_serve.py``'s: staging and serving are ``trunk_serve``'s as
they stand (one trunk artifact beside the members' stubs, ``serve.py``'s
``Served``, child load generator and window), and its comparison is the
one this kind needs, the six arrays and which experts each row was routed
to, with no key selections (``trunk_serve``'s comparison reads a
``key-selection`` frame this kind's answer does not have). Its reference
call loads the configuration's family, so the family's plain reference
(``families/nemotron3_trunk/forward.py``, the mixer as the sequential
recurrence) is what the answers are compared with, given the same share of
the experts. ``latent_trunk_serve``'s functions find their scopes by its
module's name ``SCOPES``, so ``run`` and ``control_readings`` here call
``latent_trunk_serve``'s with this driver's in its place (the seam
``selected_latent_trunk_serve.py`` uses on ``trunk_serve``).

After the window a seeded sample of the window's own answers is compared
with the reference: the six arrays, and which 6 of the 128 experts each
row was routed to in every routed layer. A traced run adds the device
seconds by ``jax.named_scope`` (the mixer's five scopes among them) and
the bank's counters for buckets with shared leaves (``ssm_layers``,
``ssm_chunks`` beside the held experts').
"""

from typing import List, Optional
from unittest import mock

from harness import latent_trunk_serve, spec

SCOPES = (
    "trunk/mamba/in_proj", "trunk/mamba/conv", "trunk/mamba/scan", "trunk/mamba/norm",
    "trunk/mamba/out_proj", "trunk/project", "trunk/attention", "trunk/shared_expert",
    "trunk/route", "trunk/experts", "trunk/combine", "member/in_proj", "member/head",
)
_control_readings = latent_trunk_serve.control_readings  # bound now: a tool may point ``latent_trunk_serve``'s at ours


def _as_latent_trunk_serve():
    """``latent_trunk_serve``'s name this driver stands in for, for one call."""
    return mock.patch.object(latent_trunk_serve, "SCOPES", SCOPES)


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, t_start: float,
        on_tpu: bool = True) -> dict:
    with _as_latent_trunk_serve():
        return latent_trunk_serve.run(cell, seed, seconds, traced, t_start, on_tpu)


def control_readings(cell: spec.Cell, seeds, requests: Optional[int] = None,
                     only: Optional[List[str]] = None) -> List[dict]:
    """``latent_trunk_serve.control_readings`` over this family's
    reference: the stated arithmetic, the float8 control and each of the
    family's planted faults (``only``: those of these labels), each read
    against the reference, on the chip with no server."""
    with _as_latent_trunk_serve():
        return _control_readings(cell, seeds, requests, only)
