"""What belongs to one model family, found by the ``family`` a
configuration's file names: ``families/<family>/<part>.py``.

- ``layout``: the weight leaves in the reference's naming
  (``layer_shapes``), the same weights under the program's parameter names
  and back (``to_program``, ``from_program``), and the forward FLOPs of one
  scored row (``forward_flops_per_row``).
- ``forward``: the plain reference's forward pass (``reconstruct``).
- ``refit``: the plain reference of one gang member's whole fit
  (``refit_sample``, ``error_pass_sample``); only families with a refit
  cell have it.

A new family, or a new part of one, is a new file here; the harness edits
nothing to find it. ``forward`` and ``refit`` import nothing of the program.
"""

import importlib


def load(family: str, part: str):
    name = f"families.{family}.{part}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as exc:
        if exc.name not in (name, f"families.{family}"):
            raise
        raise SystemExit(
            f"family {family!r} has no {part!r}: add benchmarks/families/{family}/{part}.py"
        ) from None
