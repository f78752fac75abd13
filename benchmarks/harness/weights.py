"""Everything a run draws from ``--seed``, in numpy alone: the members'
weights and scalers (serve cells), the members' training data (refit
cells) and the request bodies. The harness builds the program's objects
from these and the plain reference rebuilds the same arrays for the members
it samples, so neither takes anything the other made.

Streams of one seed never overlap: every draw is keyed by
``(seed, stream, index)``.
"""

from typing import Dict, List, Tuple

import numpy as np

WEIGHTS, TRAIN_DATA, BODIES, SAMPLE, ARRIVALS = range(5)


def rng_for(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream), int(index)])


def hourglass_dims(n_features: int, encoding_layers: int, compression_factor: float) -> Tuple[int, ...]:
    """The published hourglass rule (upstream ``hourglass_calc_dims``):
    encoder widths interpolate linearly from ``n_features`` down to
    ``n_features * compression_factor``; the decoder mirrors them."""
    smallest = max(1, round(n_features * compression_factor))
    enc = [
        max(1, round(n_features - (n_features - smallest) * (i / encoding_layers)))
        for i in range(1, encoding_layers + 1)
    ]
    return tuple(enc) + tuple(reversed(enc))


def layer_shapes(config: dict) -> List[Tuple[str, Tuple[int, ...], float]]:
    """``(name, shape, limit)`` of every weight leaf in the reference's own
    naming, in the order the flat draw is cut: the family's
    ``families/<family>/layout.py`` says which."""
    import families

    return families.load(config["family"], "layout").layer_shapes(config)


def n_params(config: dict) -> int:
    return sum(int(np.prod(s)) for _, s, _ in layer_shapes(config))


def member_weights(config: dict, seed: int, index: int) -> Dict[str, np.ndarray]:
    """One served member: weights, input scaler and error scaler."""
    shapes = layer_shapes(config)
    F = int(config["tags_per_machine"])
    rng = rng_for(seed, WEIGHTS, index)
    flat = rng.random(sum(int(np.prod(s)) for _, s, _ in shapes) + 4 * F, dtype=np.float32)
    out, pos = {}, 0
    for name, shape, limit in shapes:
        n = int(np.prod(shape))
        out[name] = ((flat[pos : pos + n] * 2.0 - 1.0) * np.float32(limit)).reshape(shape)
        pos += n
    u = flat[pos:].reshape(4, F)
    out["in_shift"] = (u[0] - 0.5) * np.float32(0.4)
    out["in_scale"] = np.float32(0.8) + u[1] * np.float32(0.45)
    out["err_shift"] = u[2] * np.float32(0.02)
    out["err_scale"] = np.float32(2.0) + u[3] * np.float32(6.0)
    return out


def member_train_data(config: dict, seed: int, index: int, rows: int) -> np.ndarray:
    """One member's ``(rows, tags)`` training block: every tag has its own
    offset and span, so the fitted min-max scalers are not the identity."""
    F = int(config["tags_per_machine"])
    rng = rng_for(seed, TRAIN_DATA, index)
    lo = rng.random(F, dtype=np.float32) * 2.0 - 1.0
    span = np.float32(0.5) + rng.random(F, dtype=np.float32) * np.float32(1.5)
    return lo + span * rng.random((rows, F), dtype=np.float32)


def request_body(config: dict, seed: int, index: int, rows: int) -> np.ndarray:
    F = int(config["tags_per_machine"])
    return rng_for(seed, BODIES, index).random((rows, F), dtype=np.float32)
