"""Plain reference forward of the dense hourglass: tanh hidden layers,
linear output."""

from typing import Dict

import jax.numpy as jnp

F32 = jnp.float32


def dense_forward(w: Dict[str, jnp.ndarray], x: jnp.ndarray, dtype=F32) -> jnp.ndarray:
    n_hidden = sum(1 for k in w if k[0] == "w" and k[1:].isdigit()) - 1
    h = x.astype(dtype)
    for k in range(n_hidden + 1):
        h = h @ w[f"w{k}"].astype(dtype) + w[f"b{k}"].astype(dtype)
        if k < n_hidden:
            h = jnp.tanh(h)
    return h.astype(F32)


def reconstruct(w: Dict[str, jnp.ndarray], xs: jnp.ndarray, lookback: int, dtype=F32) -> jnp.ndarray:
    """The model's output for scaled rows ``xs``: one row out per row in."""
    if lookback != 1:
        raise ValueError("the dense family looks back one row")
    return dense_forward(w, xs, dtype)
