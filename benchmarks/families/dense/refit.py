"""Plain reference of one gang member's whole fit, dense family, as the
configuration states it: the trainer's initial weights remade from its
seed, min-max input scaling, ``epochs`` passes of shuffled ``batch_size``
batches under Adam, then the error scaler and thresholds of the anomaly
contract. It imports nothing of the program."""

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from families.dense.forward import dense_forward
from harness import reference
from harness.weights import hourglass_dims

F32 = jnp.float32


def dense_init(init_rng, widths: Sequence[int]) -> Dict[str, jnp.ndarray]:
    """The default detector's initial weights from the trainer's seed:
    kernels LeCun-normal, biases zero, layer ``k`` keyed by
    ``("Dense_k", 1)`` (the kernel is the scope's first parameter)."""
    lecun = jax.nn.initializers.lecun_normal()
    w = {}
    for k, (fan_in, width) in enumerate(zip(widths[:-1], widths[1:])):
        w[f"w{k}"] = lecun(reference.fold_in_path(init_rng, f"Dense_{k}", 1), (fan_in, width), F32)
        w[f"b{k}"] = jnp.zeros((width,), F32)
    return w


def refit_member(
    config: dict, member_rng, X: jnp.ndarray, padded_rows: int,
    dtype=F32, fault: Optional[str] = None, state_dtype=F32,
):
    """One gang member's whole fit, as the configuration states it:
    min-max input scaling, ``epochs`` passes of shuffled ``batch_size``
    batches under Adam, then the error scaler and thresholds of the
    anomaly contract. ``member_rng`` is the member's key (row ``i`` of
    ``split(PRNGKey(seed), gang)``). Returns the per-epoch losses, the
    initial and final weights, and the scalers.

    ``dtype`` is the precision of the forward and backward pass and
    ``state_dtype`` the one the parameters and both Adam moments are kept
    and updated in. The configuration states float32 for both; the control
    takes bfloat16 for both (no float32 master copy: the step that halves
    the epoch program's bytes).

    ``fault`` plants what ``correct`` has to catch: ``"half_batch"``
    leaves the second half of every batch out of the loss (mean over the
    rest); ``"no_update"`` returns the state unchanged."""
    F = int(config["tags_per_machine"])
    widths = (F,) + hourglass_dims(F, config["encoding_layers"], config["compression_factor"]) + (F,)
    bs, epochs = int(config["batch_size"]), int(config["epochs"])
    lr = 0.0 if fault == "no_update" else float(config["learning_rate"])
    b1, b2, eps = 0.9, 0.999, 1e-8
    rows = X.shape[0]
    n_batches = padded_rows // bs

    init_rng, state_rng = jax.random.split(member_rng)
    w0 = dense_init(init_rng, widths)
    lo, scale = reference.minmax(X)
    Xs = jnp.zeros((padded_rows, F), F32).at[:rows].set((X - lo) * scale)
    mask = (jnp.arange(padded_rows) < rows).astype(F32)
    batch_mask = jnp.ones((bs,), F32)
    if fault == "half_batch":
        batch_mask = (jnp.arange(bs) < bs // 2).astype(F32)

    def loss_fn(w, xb, mb):
        return reference.masked_mse(dense_forward(w, xb, dtype), xb, mb)

    def epoch(carry, _):
        w, m, v, count, rng = carry
        rng, perm_rng, _batch_base = jax.random.split(rng, 3)
        keys = jax.random.uniform(perm_rng, (padded_rows,))
        perm = jnp.argsort(jnp.where(mask > 0, keys, 2.0))
        Xb = Xs[perm].reshape((n_batches, bs, F))
        Mb = mask[perm].reshape((n_batches, bs)) * batch_mask

        def step(c, batch):
            w, m, v, count = c
            xb, mb = batch
            loss, g = jax.value_and_grad(loss_fn)(w, xb, mb)
            t = count + 1
            m2 = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
            v2 = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
            tf = t.astype(F32)
            w2 = jax.tree.map(
                lambda p, a, b: (
                    p - lr * (a / (1 - b1**tf)) / (jnp.sqrt(b / (1 - b2**tf)) + eps)
                ).astype(p.dtype),
                w, m2, v2,
            )
            real = jnp.sum(mb) > 0  # an all-padding batch is an exact no-op
            keep = lambda new, old: jax.tree.map(lambda n, o: jnp.where(real, n, o), new, old)
            return (keep(w2, w), keep(m2, m), keep(v2, v), jnp.where(real, t, count)), (loss, jnp.sum(mb))

        (w, m, v, count), (losses, counts) = jax.lax.scan(step, (w, m, v, count), (Xb, Mb))
        mean = jnp.sum(losses * counts) / jnp.maximum(jnp.sum(counts), 1.0)
        return (w, m, v, count, rng), mean

    start = jax.tree.map(lambda a: a.astype(state_dtype), w0)
    zeros = jax.tree.map(jnp.zeros_like, start)
    (w, _, _, _, _), losses = jax.lax.scan(
        epoch, (start, zeros, zeros, jnp.zeros((), jnp.int32), state_rng), None, length=epochs
    )
    w = jax.tree.map(lambda a: a.astype(F32), w)
    out = {"losses": losses, "w0": w0, "w": w}
    out.update(error_pass(config, w, X, dtype))
    return out


def error_pass(config: dict, w: Dict[str, jnp.ndarray], X: jnp.ndarray, dtype=F32) -> dict:
    """The fit's last stage, from a member's weights and its training
    rows: the min-max input scaler, then the error scaler (min-max of the
    absolute reconstruction error per tag) and the thresholds of the
    anomaly contract (largest scaled error per tag and largest row norm)."""
    lo, scale = reference.minmax(X)
    Xs = (X - lo) * scale
    diff = jnp.abs(Xs - dense_forward(w, Xs, dtype))
    e_lo, e_scale = reference.minmax(diff)
    scaled = (diff - e_lo) * e_scale
    return {
        "in_shift": lo, "in_scale": scale, "err_shift": e_lo, "err_scale": e_scale,
        "feature_thresholds": jnp.max(scaled, axis=0),
        "total_threshold": jnp.max(jnp.sqrt(jnp.sum(scaled * scaled, axis=-1))),
    }


def error_pass_sample(config: dict, ws: Dict[str, np.ndarray], X: np.ndarray,
                      dtype: str = "float32", precision: Optional[str] = None) -> dict:
    """``error_pass`` over stacked members' weights ``ws`` and rows ``X``."""
    fn = jax.jit(jax.vmap(lambda w, x: error_pass(config, w, x, jnp.dtype(dtype))))
    with reference.precision_scope(precision):
        out = fn({k: jnp.asarray(v) for k, v in ws.items()}, jnp.asarray(X, F32))
    return jax.tree.map(np.asarray, out)


def refit_sample(
    config: dict, trainer_seed: int, gang_size: int, indices: Sequence[int],
    X: np.ndarray, padded_rows: int, dtype: str = "float32",
    precision: Optional[str] = None, fault: Optional[str] = None,
    state_dtype: str = "float32",
):
    """``refit_member`` over the sampled members ``indices`` of a gang of
    ``gang_size`` (their data stacked in ``X``), one compiled call."""
    rngs = jax.random.split(jax.random.PRNGKey(trainer_seed), gang_size)[jnp.asarray(indices)]
    fn = jax.jit(
        jax.vmap(lambda r, x: refit_member(
            config, r, x, padded_rows, jnp.dtype(dtype), fault, jnp.dtype(state_dtype)))
    )
    with reference.precision_scope(precision):
        out = fn(rngs, jnp.asarray(X, F32))
    return jax.tree.map(np.asarray, out)
