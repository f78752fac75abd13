"""The stacked-LSTM hourglass autoencoder (upstream ``lstm_hourglass``):
one LSTM layer per hourglass width, then a linear head back to the tags.
Gate order i, f, g, o along the last axis of every gate kernel."""

from typing import Dict, List, Tuple

import numpy as np

from harness.weights import hourglass_dims

GATES = "ifgo"


def _units(config: dict) -> Tuple[int, ...]:
    F = int(config["tags_per_machine"])
    return hourglass_dims(F, config["encoding_layers"], config["compression_factor"])


def layer_shapes(config: dict) -> List[Tuple[str, Tuple[int, ...], float]]:
    """``(name, shape, limit)`` of every weight leaf, in the order the flat
    draw is cut (see the dense family's)."""
    F = int(config["tags_per_machine"])
    out: List[Tuple[str, Tuple[int, ...], float]] = []
    fan_in = F
    for k, H in enumerate(_units(config)):
        out.append((f"wi{k}", (fan_in, 4 * H), (3.0 / fan_in) ** 0.5))
        out.append((f"wh{k}", (H, 4 * H), (3.0 / H) ** 0.5))
        out.append((f"b{k}", (4 * H,), 0.1))
        fan_in = H
    out.append(("wd", (fan_in, F), (3.0 / fan_in) ** 0.5))
    out.append(("bd", (F,), 0.1))
    return out


def to_program(config: dict, w: Dict[str, np.ndarray]) -> dict:
    """The benchmark's weights under the program's parameter names (one
    kernel per gate; the hidden kernels carry the bias)."""
    params = {"Dense_0": {"kernel": w["wd"], "bias": w["bd"]}}
    for k in range(len(_units(config))):
        H = w[f"wh{k}"].shape[0]
        cell = {}
        for g, gate in enumerate(GATES):
            sl = slice(g * H, (g + 1) * H)
            cell["i" + gate] = {"kernel": w[f"wi{k}"][:, sl]}
            cell["h" + gate] = {"kernel": w[f"wh{k}"][:, sl], "bias": w[f"b{k}"][sl]}
        params[f"OptimizedLSTMCell_{k}"] = cell
    return params


def from_program(params: dict) -> Dict[str, np.ndarray]:
    """A fitted member's parameters in the reference's naming."""
    out = {
        "wd": np.asarray(params["Dense_0"]["kernel"]),
        "bd": np.asarray(params["Dense_0"]["bias"]),
    }
    for k in range(len(params) - 1):
        cell = params[f"OptimizedLSTMCell_{k}"]
        cat = lambda side, leaf: np.concatenate(
            [np.asarray(cell[side + gate][leaf]) for gate in GATES], axis=-1
        )
        out[f"wi{k}"], out[f"wh{k}"], out[f"b{k}"] = cat("i", "kernel"), cat("h", "kernel"), cat("h", "bias")
    return out


def forward_flops_per_row(config: dict) -> float:
    """One window of ``lookback_window`` steps through every layer, then
    the head; gate nonlinearities are left out (under 1%). Closed form
    copied from ``observability/cost.py`` ``lstm_stack_flops``."""
    F = int(config["tags_per_machine"])
    w = (F,) + _units(config)
    per_step = sum(2 * 4 * H * (fan_in + H) for fan_in, H in zip(w[:-1], w[1:]))
    return float(int(config["lookback_window"]) * per_step + 2 * w[-1] * F)
