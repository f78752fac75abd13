"""Operations and bytes each program has to move, from shapes alone (the
forward closed forms are each family's, copied from
``observability/cost.py``; listed in PERF.md for a later PR to delete
there). They depend on the configuration only, never on which kernel ran.
"""

from typing import Dict

import families
from harness.weights import n_params


def forward_flops_per_row(config: dict) -> float:
    """Forward FLOPs for one scored row (one window for a sequence
    family), from the family's ``layout.py``."""
    return families.load(config["family"], "layout").forward_flops_per_row(config)


def windows_per_request(config: dict, request_rows: int) -> int:
    return request_rows - int(config.get("lookback_window", 1)) + 1


def score_request_bytes(config: dict, request_rows: int) -> float:
    """HBM bytes one scored request cannot avoid: its member's weights and
    scalers read once, its rows read once, five output arrays written."""
    F = int(config["tags_per_machine"])
    out_rows = windows_per_request(config, request_rows)
    return 4.0 * (n_params(config) + 4 * F + request_rows * F + out_rows * (3 * F + 2))


def train_epoch_flops(config: dict, members: int, rows: int) -> float:
    """Forward + backward of every real row once: 3 x forward."""
    return 3.0 * forward_flops_per_row(config) * rows * members


def train_epoch_bytes(config: dict, members: int, rows: int, padded_rows: int) -> float:
    """Per member: the padded data block read once, and per optimizer step
    the parameters and both Adam moments read and written (6 x P x 4 B).
    Activations and gradients are taken to stay on chip."""
    F = int(config["tags_per_machine"])
    steps = -(-rows // int(config["batch_size"]))
    return members * 4.0 * (padded_rows * F + steps * 6 * n_params(config))


def roofline(flops: float, nbytes: float, seconds: float, peaks: Dict[str, float]):
    """``(share in %, which bound)``: the least time the chip could take,
    the larger of operations over peak FLOP/s and bytes over peak bytes/s,
    over the time it took."""
    t_flops = flops / peaks["flops_bf16"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
