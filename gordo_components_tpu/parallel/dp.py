"""Data-parallel training (single model, batch rows sharded over ICI).

The north star names pmap-style DP over ICI for per-model batches
(BASELINE.json). The modern JAX idiom is ``shard_map`` over a mesh ``data``
axis: params replicated, each device computes gradients on its slice of
every batch, and a weighted ``psum`` reconstructs the exact global-batch
gradient — XLA lowers it to an ICI all-reduce. Used when one machine's
dataset is large enough to warrant intra-model parallelism (the fleet
engine's model-axis sharding covers the many-model case).

Two granularities:

- :func:`make_dp_train_step` — one sharded optimizer step per call (the
  building block the multichip dryrun exercises);
- :func:`make_dp_epoch_fn` — a full DP epoch program mirroring
  ``train_core.epoch_fn`` (on-device shuffle + ``lax.scan`` over batches)
  with each batch's ROWS split across devices. Inputs are replicated —
  every device holds the full (padded) dataset and runs the identical
  shuffle, so batch composition, rng consumption, and results match the
  single-device program exactly; only the per-row gradient work is
  partitioned. Replication costs HBM (fine for per-machine sensor
  datasets, the reference's scale) in exchange for a shuffle with zero
  resharding traffic: the only collective in the program is the gradient
  all-reduce.
"""

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gordo_components_tpu.ops.losses import mse_loss

DATA_AXIS = "data"


def dp_device_count(batch_size: int, available: int) -> int:
    """Largest device count <= ``available`` that divides ``batch_size``.

    DP splits each batch's rows evenly; running on a divisor of the batch
    size keeps the split exact so DP results match single-device results
    instead of silently changing the effective batch composition.
    """
    n = max(1, min(int(available), int(batch_size)))
    while batch_size % n:
        n -= 1
    return n


def data_mesh(n_devices=None, devices=None) -> Mesh:
    import numpy as np

    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def make_dp_train_step(
    module, optimizer: optax.GradientTransformation, mesh: Mesh,
    check_vma: bool = True,
) -> Callable:
    """Returns jit'd ``step(params, opt_state, xb, yb) ->
    (params, opt_state, loss)`` with the batch dimension sharded over the
    mesh ``data`` axis and gradients all-reduced (psum/pmean over ICI).
    ``check_vma=False`` for recurrent modules (see make_dp_epoch_fn)."""

    def loss_fn(params, xb, yb):
        pred = module.apply(params, xb)
        return mse_loss(pred, yb)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=check_vma,
    )
    def sharded_step(params, opt_state, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(params, xb, yb)
        grads = jax.lax.pmean(grads, DATA_AXIS)
        loss = jax.lax.pmean(loss, DATA_AXIS)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(sharded_step, donate_argnums=(0, 1))


def make_dp_epoch_fn(
    module,
    optimizer: optax.GradientTransformation,
    batch_size: int,
    mesh: Mesh,
    loss: str = "mse",
    kl_weight: float = 1.0,
    check_vma: bool = True,
) -> Callable:
    """DP mirror of ``train_core.epoch_fn``: same shuffle, same rng stream,
    same batch composition — but each batch's rows are split over the mesh
    ``data`` axis and the global-batch gradient is reconstructed with a
    count-weighted ``psum`` (exact: the single-device gradient of a
    masked-mean loss is the count-weighted mean of the shard gradients).

    Requires ``batch_size % mesh.shape[DATA_AXIS] == 0`` (see
    :func:`dp_device_count`). Deterministic losses (mse) match the
    single-device program to float tolerance; sampling losses (vae) use
    device-decorrelated rngs and match statistically, not bitwise.
    """
    from gordo_components_tpu.models.train_core import TrainState, make_loss_fn

    n_dev = int(mesh.shape[DATA_AXIS])
    if batch_size % n_dev:
        raise ValueError(
            f"batch_size {batch_size} not divisible by mesh size {n_dev}"
        )
    sub = batch_size // n_dev
    loss_fn = make_loss_fn(module, loss=loss, kl_weight=kl_weight)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P(), P(), P(), P()), out_specs=(P(), P()),
        # the static varying-manual-axes analysis rejects recurrent modules
        # whose scan carry initializes unvarying (flax nn.RNN zeros) while
        # inputs vary over 'data' — numerically fine (all cross-device
        # reductions here are explicit psums). Callers disable the check
        # ONLY for recurrent estimators (models.py `_dp_check_vma`) so the
        # static replication proof still guards every other fit.
        check_vma=check_vma,
    )
    def epoch(state, X, Y, mask):
        n_pad = X.shape[0]
        n_batches = n_pad // batch_size
        # identical to train_core.epoch_fn: rng use independent of batch
        # count; real rows shuffled densely into leading batches, padding
        # sorted (stably) to the end
        rng, perm_rng, batch_base = jax.random.split(state.rng, 3)
        rngs = jax.vmap(lambda i: jax.random.fold_in(batch_base, i))(
            jnp.arange(n_batches)
        )
        keys = jax.random.uniform(perm_rng, (n_pad,))
        perm = jnp.argsort(jnp.where(mask > 0, keys, 2.0))
        idx = jax.lax.axis_index(DATA_AXIS)
        # this device's row slice of every batch: (n_batches, sub, ...)
        take = lambda A: jax.lax.dynamic_slice_in_dim(
            A[perm].reshape((n_batches, batch_size) + A.shape[1:]),
            idx * sub, sub, axis=1,
        )
        Xs, Ys, Ms = take(X), take(Y), take(mask)

        def step(carry, batch):
            params, opt_state = carry
            xb, yb, mb, brng = batch
            # decorrelate sampling losses across devices; mse ignores brng
            brng = jax.random.fold_in(brng, idx)
            local_loss, local_grads = jax.value_and_grad(loss_fn)(
                params, brng, xb, yb, mb
            )
            # local values are masked MEANS over this shard's real rows:
            # weight by the shard's real-row count and renormalize to get
            # the exact global-batch mean/gradient
            cnt = jnp.sum(mb)
            total = jax.lax.psum(cnt, DATA_AXIS)
            denom = jnp.maximum(total, 1.0)
            grads = jax.tree.map(
                lambda g: jax.lax.psum(g * cnt, DATA_AXIS) / denom, local_grads
            )
            loss_val = jax.lax.psum(local_loss * cnt, DATA_AXIS) / denom
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            # all-pad batches are exact no-ops, as in train_core.epoch_fn
            has_real = total > 0
            keep = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(has_real, n, o), new, old
            )
            return (keep(new_params, params), keep(new_opt_state, opt_state)), (
                loss_val,
                total,
            )

        (params, opt_state), (losses, counts) = jax.lax.scan(
            step, (state.params, state.opt_state), (Xs, Ys, Ms, rngs)
        )
        mean_loss = jnp.sum(losses * counts) / jnp.maximum(jnp.sum(counts), 1.0)
        return TrainState(params=params, opt_state=opt_state, rng=rng), mean_loss

    return jax.jit(epoch, donate_argnums=(0,))
