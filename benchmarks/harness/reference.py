"""The plain reference: straightforward ``jax.numpy`` float32. Here is
what every family shares: the anomaly scores around a family's forward
pass, and the small pieces a family's refit is written from. The forward
pass and the whole fit of one family are ``families/<family>/forward.py``
and ``refit.py``, found by the configuration's ``family``. None of it
imports anything of the program.

Precision. The configurations state float32 parameters and activations at
the platform's *default* matmul precision (what the product ships with; on
a TPU that rounds matmul operands to bf16 and accumulates in float32).
The reference computes the same: plain float32 ``@``. ``precision=
"highest"`` is there to measure how far that default is from exact
float32; ``dtype="bfloat16"`` is the control (the nearest precision below:
operands, activations and matmul outputs in bf16), which ``correct`` has to
reject.
"""

import contextlib
import functools
import hashlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

import families

F32 = jnp.float32


def precision_scope(precision: Optional[str]):
    return jax.default_matmul_precision(precision) if precision else contextlib.nullcontext()


@functools.lru_cache(maxsize=None)
def _anomaly_program(family: str, lookback: int, dtype: str):
    """One jitted program per family, lookback and dtype, so a run's
    sampled answers trace and compile it once."""
    dt = jnp.dtype(dtype)
    reconstruct = families.load(family, "forward").reconstruct

    def run(w, X):
        xs = (X - w["in_shift"]) * w["in_scale"]
        recon = reconstruct(w, xs, lookback, dt)
        diff = jnp.abs(xs[lookback - 1 :] - recon)
        scaled = (diff - w["err_shift"]) * w["err_scale"]
        return (
            recon, diff, scaled,
            jnp.sqrt(jnp.sum(diff * diff, axis=-1)),
            jnp.sqrt(jnp.sum(scaled * scaled, axis=-1)),
        )

    return jax.jit(run)


def anomaly(
    config: dict, w: Dict[str, np.ndarray], X: np.ndarray,
    dtype: str = "float32", precision: Optional[str] = None,
) -> Dict[str, np.ndarray]:
    """The six arrays of ``POST .../anomaly/prediction`` for one request:
    input scaling, forward (windowed for a sequence family), absolute
    reconstruction error in model space, error scaling, row norms."""
    lookback = int(config.get("lookback_window", 1))
    program = _anomaly_program(config["family"], lookback, dtype)
    with precision_scope(precision):
        recon, diff, scaled, tot_u, tot_s = program(
            {k: jnp.asarray(v) for k, v in w.items()}, jnp.asarray(X, F32)
        )
    return {
        "model-input": np.asarray(X[lookback - 1 :], np.float32),
        "model-output": np.asarray(recon),
        "tag-anomaly-unscaled": np.asarray(diff),
        "tag-anomaly-scaled": np.asarray(scaled),
        "total-anomaly-unscaled": np.asarray(tot_u),
        "total-anomaly-scaled": np.asarray(tot_s),
    }


# ------------------------------------------- pieces a family's refit uses


def fold_in_path(rng, scope: str, nth_param: int):
    """How the trainer's seed reaches one parameter: the module library
    derives a leaf's key by folding into the init key a hash of the
    layer's scope name and the leaf's ordinal within it (flax ``LazyRng``:
    sha1 over the name's bytes and the counter's, first four bytes)."""
    m = hashlib.sha1()
    m.update(scope.encode("utf-8"))
    m.update(nth_param.to_bytes(1, "big"))
    return jax.random.fold_in(rng, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def masked_mse(pred, target, mask):
    err = (pred - target) ** 2
    return jnp.sum(err * mask[:, None]) / (jnp.maximum(jnp.sum(mask), 1.0) * err.shape[1])


def minmax(X):
    """Min-max scaler of the anomaly contract: ``(shift, scale)`` per column."""
    lo, hi = jnp.nanmin(X, axis=0), jnp.nanmax(X, axis=0)
    span = jnp.where(jnp.abs(hi - lo) < 1e-12, 1.0, hi - lo)
    return lo, 1.0 / span
