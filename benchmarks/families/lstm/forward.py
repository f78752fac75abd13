"""Plain reference forward of the stacked-LSTM hourglass: every layer's
whole output sequence, through tanh, feeds the next; the last layer's final
step goes through a linear head. Gates i, f, g, o; ``c' = f*c + i*g``;
``h' = o*tanh(c')``."""

from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def lstm_forward(w: Dict[str, jnp.ndarray], windows: jnp.ndarray, dtype=F32) -> jnp.ndarray:
    """``(batch, lookback, F)`` windows to ``(batch, F)`` outputs."""
    n_layers = sum(1 for k in w if k.startswith("wh"))
    seq = windows.astype(dtype)
    for k in range(n_layers):
        wi, wh, b = (w[f"{n}{k}"].astype(dtype) for n in ("wi", "wh", "b"))
        H = wh.shape[0]
        B = seq.shape[0]

        def step(carry, x_t, wi=wi, wh=wh, b=b, H=H):
            h, c = carry
            z = x_t @ wi + h @ wh + b
            i, f = jax.nn.sigmoid(z[:, :H]), jax.nn.sigmoid(z[:, H : 2 * H])
            g, o = jnp.tanh(z[:, 2 * H : 3 * H]), jax.nn.sigmoid(z[:, 3 * H :])
            c = f * c + i * g
            h = o * jnp.tanh(c)
            return (h, c), h

        zeros = jnp.zeros((B, H), dtype)
        _, hs = jax.lax.scan(step, (zeros, zeros), jnp.swapaxes(seq, 0, 1))
        seq = jnp.tanh(jnp.swapaxes(hs, 0, 1))
    out = seq[:, -1, :] @ w["wd"].astype(dtype) + w["bd"].astype(dtype)
    return out.astype(F32)


def reconstruct(w: Dict[str, jnp.ndarray], xs: jnp.ndarray, lookback: int, dtype=F32) -> jnp.ndarray:
    """The model's output for scaled rows ``xs``: one row out for every
    window of ``lookback`` rows, so ``len(xs) - lookback + 1`` rows."""
    n = xs.shape[0] - lookback + 1
    idx = jnp.arange(n)[:, None] + jnp.arange(lookback)[None, :]
    return lstm_forward(w, xs[idx], dtype)
