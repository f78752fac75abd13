"""The dense hourglass autoencoder (upstream ``feedforward_hourglass``):
``tags -> hourglass widths -> tags``, one kernel and one bias a layer."""

from typing import Dict, List, Tuple

import numpy as np

from harness.weights import hourglass_dims


def _chain(config: dict) -> Tuple[int, ...]:
    F = int(config["tags_per_machine"])
    return (F,) + hourglass_dims(F, config["encoding_layers"], config["compression_factor"]) + (F,)


def layer_shapes(config: dict) -> List[Tuple[str, Tuple[int, ...], float]]:
    """``(name, shape, limit)`` of every weight leaf, in the order the flat
    draw is cut. ``limit`` is the half-width of the uniform a served
    member's leaf is drawn from (variance 1/fan_in for kernels, so
    activations stay O(1) and tanh stays off its rails)."""
    chain = _chain(config)
    out: List[Tuple[str, Tuple[int, ...], float]] = []
    for k, (fan_in, width) in enumerate(zip(chain[:-1], chain[1:])):
        out.append((f"w{k}", (fan_in, width), (3.0 / fan_in) ** 0.5))
        out.append((f"b{k}", (width,), 0.1))
    return out


def to_program(config: dict, w: Dict[str, np.ndarray]) -> dict:
    """The benchmark's weights under the program's parameter names."""
    n = len(_chain(config)) - 1
    return {f"Dense_{k}": {"kernel": w[f"w{k}"], "bias": w[f"b{k}"]} for k in range(n)}


def from_program(params: dict) -> Dict[str, np.ndarray]:
    """A fitted member's parameters in the reference's naming."""
    out = {}
    for k in range(len(params)):
        out[f"w{k}"] = np.asarray(params[f"Dense_{k}"]["kernel"])
        out[f"b{k}"] = np.asarray(params[f"Dense_{k}"]["bias"])
    return out


def forward_flops_per_row(config: dict) -> float:
    """2 per multiply-add of every matmul; the scoring epilogue is left
    out (under 1%). Closed form copied from ``observability/cost.py``
    ``dense_chain_flops``."""
    chain = _chain(config)
    return float(sum(2 * a * b for a, b in zip(chain[:-1], chain[1:])))
