"""Functional training core: pure init/epoch/predict functions over explicit
state pytrees.

This replaces the reference's ``keras.Model.fit`` inner loop
(gordo_components/model/models.py, unverified; SURVEY.md §3.1 "the COMPUTE
HOT LOOP") with a TPU-idiomatic design:

- one jit'd **epoch** program: on-device shuffle (``jax.random.permutation``)
  + ``lax.scan`` over fixed-size batches — a single XLA computation per
  epoch, no per-batch host round-trips, static shapes throughout;
- ragged data handled by **padding + masks**, never dynamic shapes;
- everything is written to be ``vmap``-ed over a leading model axis: the
  fleet engine (parallel/fleet.py) maps these exact functions over stacked
  params to train thousands of models in one program.
"""

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from gordo_components_tpu.ops.losses import mse_loss


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    rng: jax.Array


def make_optimizer(
    name: str = "adam",
    learning_rate: float = 1e-3,
    inject: bool = False,
    **kwargs,
) -> optax.GradientTransformation:
    """Resolve an optax optimizer by name (reference models compile with
    Keras optimizer names; same strings work here).

    ``inject=True`` wraps the optimizer in ``optax.inject_hyperparams`` so
    ``learning_rate`` lives in the opt STATE instead of being baked into
    the transform — under ``vmap`` that state leaf is a stacked (M,)
    vector, which is how the fleet engine trains members with per-member
    learning rates in ONE program (numerics identical when every member
    shares the base value)."""
    name = name.lower()
    table = {
        "adam": optax.adam,
        "adamw": optax.adamw,
        "sgd": optax.sgd,
        "rmsprop": optax.rmsprop,
        "adagrad": optax.adagrad,
    }
    try:
        factory = table[name]
    except KeyError:
        raise ValueError(f"Unknown optimizer {name!r}; known: {sorted(table)}")
    if inject:
        return optax.inject_hyperparams(factory)(
            learning_rate=learning_rate, **kwargs
        )
    return factory(learning_rate, **kwargs)


def pad_to_batches(
    X: np.ndarray, Y: np.ndarray, batch_size: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Pad (X, Y) with zero rows to a multiple of ``batch_size``.

    Returns (X_pad, Y_pad, mask, n_batches); mask is 1.0 for real rows.
    Padding keeps every batch the same shape so the epoch program compiles
    once regardless of dataset length.
    """
    n = X.shape[0]
    if n == 0:
        raise ValueError("Cannot train on an empty dataset")
    n_batches = max(1, -(-n // batch_size))
    n_pad = n_batches * batch_size
    mask = np.zeros((n_pad,), dtype=np.float32)
    mask[:n] = 1.0
    X_pad = np.zeros((n_pad,) + X.shape[1:], dtype=np.float32)
    X_pad[:n] = X
    Y_pad = np.zeros((n_pad,) + Y.shape[1:], dtype=np.float32)
    Y_pad[:n] = Y
    return X_pad, Y_pad, mask, n_batches


def make_loss_fn(module, loss: str = "mse", kl_weight: float = 1.0) -> Callable:
    """Build ``loss_fn(params, rng, xb, yb, maskb) -> scalar``.

    ``loss='mse'`` covers the reference's autoencoder losses; ``loss='vae'``
    calls the module's ``elbo_terms`` (variational zoo) adding the KL term.
    """
    if loss == "mse":

        def loss_fn(params, rng, xb, yb, maskb):
            pred = module.apply(params, xb)
            return mse_loss(pred, yb, maskb)

    elif loss == "vae":

        def loss_fn(params, rng, xb, yb, maskb):
            recon, kl = module.apply(
                params, xb, method="elbo_terms", rngs={"sample": rng}
            )
            rec = mse_loss(recon, yb, maskb)
            klm = jnp.sum(kl * maskb) / jnp.maximum(jnp.sum(maskb), 1.0)
            return rec + kl_weight * klm

    else:
        raise ValueError(f"Unknown loss {loss!r} (known: mse, vae)")
    return loss_fn


def make_train_fns(
    module,
    optimizer: optax.GradientTransformation,
    batch_size: int,
    loss: str = "mse",
    kl_weight: float = 1.0,
):
    """Returns ``(init_fn, epoch_fn)``.

    - ``init_fn(rng, sample_x) -> TrainState`` (sample_x: one batch-shaped
      row, used only for shape inference)
    - ``epoch_fn(state, X, Y, mask) -> (state, mean_loss)`` where X/Y/mask
      are padded to ``n_batches * batch_size`` rows (see ``pad_to_batches``).
      Performs an on-device shuffle then ``lax.scan`` over batches.

    Both are pure and vmap-able over a leading model axis.
    """
    loss_fn = make_loss_fn(module, loss=loss, kl_weight=kl_weight)

    def init_fn(rng: jax.Array, sample_x: jnp.ndarray) -> TrainState:
        init_rng, state_rng = jax.random.split(rng)
        params = module.init(init_rng, sample_x[None, ...])
        opt_state = optimizer.init(params)
        return TrainState(params=params, opt_state=opt_state, rng=state_rng)

    def epoch_fn(state: TrainState, X, Y, mask):
        n_pad = X.shape[0]
        n_batches = n_pad // batch_size
        # rng consumption is deliberately INDEPENDENT of n_batches (three
        # splits + fold_in per batch index): training a dataset padded to a
        # larger row bucket consumes the same random stream, which is what
        # makes the fleet engine's row-count quantization a true no-op
        rng, perm_rng, batch_base = jax.random.split(state.rng, 3)
        rngs = jax.vmap(lambda i: jax.random.fold_in(batch_base, i))(
            jnp.arange(n_batches)
        )
        # shuffle real rows among themselves and sort padding to the END
        # (stable argsort of prefix-stable uniform keys): real rows stay
        # densely packed in the leading batches — the effective batch size
        # is preserved no matter how much row padding the bucket adds, and
        # any fully-padded trailing batch is skipped as a no-op below.
        keys = jax.random.uniform(perm_rng, (n_pad,))
        perm = jnp.argsort(jnp.where(mask > 0, keys, 2.0))
        Xs = X[perm].reshape((n_batches, batch_size) + X.shape[1:])
        Ys = Y[perm].reshape((n_batches, batch_size) + Y.shape[1:])
        Ms = mask[perm].reshape((n_batches, batch_size))

        def step(carry, batch):
            params, opt_state = carry
            xb, yb, mb, brng = batch
            loss_val, grads = jax.value_and_grad(loss_fn)(params, brng, xb, yb, mb)
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            # all-padding batches must be EXACT no-ops: even zero gradients
            # advance adam's bias-correction count and decay its momentum,
            # which would silently change training dynamics with row padding
            has_real = jnp.sum(mb) > 0
            keep = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(has_real, n, o), new, old
            )
            # weight the batch loss by its real-row count for a correct
            # dataset-mean when the last batch is partly padding
            return (keep(new_params, params), keep(new_opt_state, opt_state)), (
                loss_val,
                jnp.sum(mb),
            )

        (params, opt_state), (losses, counts) = jax.lax.scan(
            step, (state.params, state.opt_state), (Xs, Ys, Ms, rngs)
        )
        mean_loss = jnp.sum(losses * counts) / jnp.maximum(jnp.sum(counts), 1.0)
        return TrainState(params=params, opt_state=opt_state, rng=rng), mean_loss

    return init_fn, epoch_fn


def gather_window_batch(X, item_idx, lookback: int, target_offset: int):
    """(xb, yb) for a batch of window-start items over raw rows ``X``:
    ``xb`` gathers rows ``[i, i+lookback)``, ``yb`` the target row
    ``i + lookback - 1 + target_offset``. Indices CLIP into range, so
    out-of-range (padded) items gather garbage the caller's item mask must
    zero out — the one shared definition of the windows-as-views index
    arithmetic (train, eval, and fleet error-scaler programs all use it)."""
    rows = X.shape[0]
    widx = jnp.clip(
        item_idx[:, None] + jnp.arange(lookback)[None, :], 0, rows - 1
    )
    yb = X[jnp.clip(item_idx + lookback - 1 + target_offset, 0, rows - 1)]
    return X[widx], yb


def make_seq_train_fns(
    module,
    optimizer: optax.GradientTransformation,
    batch_size: int,
    lookback: int,
    target_offset: int = 0,
    loss: str = "mse",
    kl_weight: float = 1.0,
):
    """Sequence-model variant of :func:`make_train_fns` where windows are
    GATHERED per batch instead of materialized.

    The single-model path materializes ``(n_windows, lookback, f)`` host-side
    and feeds :func:`make_train_fns`; at fleet scale that costs ``lookback``x
    the HBM of the raw rows. Here the epoch program keeps only the raw
    ``(rows, f)`` member block on device and the scan body gathers each
    batch's windows (``X[i : i+lookback]``) on the fly — numerically
    identical (window *i* holds the same rows either way, the shuffle/rng
    scheme is byte-for-byte the one in ``make_train_fns``), but HBM stays
    O(rows) per member.

    - ``init_fn(rng, sample_w) -> TrainState`` (sample_w: one (lookback, f)
      window for shape inference)
    - ``epoch_fn(state, X, Y, mask) -> (state, mean_loss)``: X is the raw
      padded ``(rows_pad, f)`` block; Y is IGNORED (targets derive from X:
      item *i* trains window ``[i, i+lookback)`` against row
      ``i + lookback - 1 + target_offset``); mask is the (items_pad,) item
      validity mask, items_pad a multiple of ``batch_size``.
    """
    loss_fn = make_loss_fn(module, loss=loss, kl_weight=kl_weight)

    def init_fn(rng: jax.Array, sample_w: jnp.ndarray) -> TrainState:
        init_rng, state_rng = jax.random.split(rng)
        params = module.init(init_rng, sample_w[None, ...])
        opt_state = optimizer.init(params)
        return TrainState(params=params, opt_state=opt_state, rng=state_rng)

    def epoch_fn(state: TrainState, X, Y, mask):
        del Y  # targets are rows of X (reconstruction/forecast)
        n_pad = mask.shape[0]
        n_batches = n_pad // batch_size
        rng, perm_rng, batch_base = jax.random.split(state.rng, 3)
        rngs = jax.vmap(lambda i: jax.random.fold_in(batch_base, i))(
            jnp.arange(n_batches)
        )
        keys = jax.random.uniform(perm_rng, (n_pad,))
        perm = jnp.argsort(jnp.where(mask > 0, keys, 2.0))
        idxs = perm.reshape((n_batches, batch_size))
        Ms = mask[perm].reshape((n_batches, batch_size))

        def step(carry, batch):
            params, opt_state = carry
            ib, mb, brng = batch
            # padded items gather clipped garbage; their mask zeroes them out
            xb, yb = gather_window_batch(X, ib, lookback, target_offset)
            loss_val, grads = jax.value_and_grad(loss_fn)(params, brng, xb, yb, mb)
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            has_real = jnp.sum(mb) > 0
            keep = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(has_real, n, o), new, old
            )
            return (keep(new_params, params), keep(new_opt_state, opt_state)), (
                loss_val,
                jnp.sum(mb),
            )

        (params, opt_state), (losses, counts) = jax.lax.scan(
            step, (state.params, state.opt_state), (idxs, Ms, rngs)
        )
        mean_loss = jnp.sum(losses * counts) / jnp.maximum(jnp.sum(counts), 1.0)
        return TrainState(params=params, opt_state=opt_state, rng=rng), mean_loss

    return init_fn, epoch_fn


def make_seq_gang_epoch(
    module,
    optimizer: optax.GradientTransformation,
    batch_size: int,
    lookback: int,
    target_offset: int = 0,
):
    """Time-major GANG epoch: the whole member axis trains in one
    non-vmapped program whose recurrent scan keeps members innermost
    (ops/seq_scan.py) — ``vmap(epoch_fn)``'s fast-path replacement for
    LSTM buckets.

    ``epoch_fn(states, X, mask) -> (states, (M,) losses)`` over STACKED
    state (leading member axis), X: (M, rows_pad, f), mask: (M,
    items_pad). Per-member semantics are the legacy path's exactly:

    - the shuffle/rng plan is ``make_seq_train_fns``'s byte-for-byte
      (same three splits + fold_in per batch, vmapped per member), so
      every member sees the identical batch sequence;
    - the loss is the per-member masked mean; gradients come from the
      SUM of member losses, which decouples exactly (each member's loss
      depends only on its own parameter rows);
    - the optimizer update and the all-padding-batch no-op guard are
      vmapped per member — elementwise work, not the hot loop.

    The one intentional difference is the forward: the time-major scan
    re-associates the gate matmuls, so parity with the legacy layout is
    fp32-rounding-level, not bitwise (band pinned by
    tests/test_seq_fastpath.py). MSE only — the gang loss needs the
    member-explicit forward, which the variational heads don't have.
    """
    from gordo_components_tpu.ops.seq_scan import lstm_time_major_forward

    def epoch_fn(states: TrainState, X, mask):
        M, n_pad = mask.shape
        n_batches = n_pad // batch_size

        def plan(rng, m):
            rng2, perm_rng, batch_base = jax.random.split(rng, 3)
            rngs = jax.vmap(lambda i: jax.random.fold_in(batch_base, i))(
                jnp.arange(n_batches)
            )
            keys = jax.random.uniform(perm_rng, (n_pad,))
            perm = jnp.argsort(jnp.where(m > 0, keys, 2.0))
            return rng2, perm, rngs

        rng2, perms, rngss = jax.vmap(plan)(states.rng, mask)
        # batch-major so the scan slices one (M, batch) block per step
        idxs = perms.reshape((M, n_batches, batch_size)).transpose(1, 0, 2)
        Ms = (
            jnp.take_along_axis(mask, perms, axis=1)
            .reshape((M, n_batches, batch_size))
            .transpose(1, 0, 2)
        )

        def step(carry, batch):
            params, opt_state = carry
            ib, mb = batch
            xb, yb = jax.vmap(
                gather_window_batch, in_axes=(0, 0, None, None)
            )(X, ib, lookback, target_offset)

            def gang_loss(p):
                preds = lstm_time_major_forward(module, p, xb, kernel="jnp")
                losses = jax.vmap(mse_loss)(preds, yb, mb)
                return jnp.sum(losses), losses

            grads, losses = jax.grad(gang_loss, has_aux=True)(params)
            updates, new_opt = jax.vmap(optimizer.update)(
                grads, opt_state, params
            )
            new_params = optax.apply_updates(params, updates)
            has_real = jnp.sum(mb, axis=1) > 0  # (M,)

            def keep(n, o):
                hr = has_real.reshape((M,) + (1,) * (n.ndim - 1))
                return jnp.where(hr, n, o)

            return (
                jax.tree.map(keep, new_params, params),
                jax.tree.map(keep, new_opt, opt_state),
            ), (losses, jnp.sum(mb, axis=1))

        (params, opt_state), (losses, counts) = jax.lax.scan(
            step, (states.params, states.opt_state), (idxs, Ms)
        )
        mean_loss = jnp.sum(losses * counts, axis=0) / jnp.maximum(
            jnp.sum(counts, axis=0), 1.0
        )
        return (
            TrainState(params=params, opt_state=opt_state, rng=rng2),
            mean_loss,
        )

    return epoch_fn


def make_dense_gang_epoch(step_fns: Tuple[Callable, Callable, Callable], batch_size: int):
    """GANG epoch of a dense bucket whose step is ONE program over the
    member axis (``ops/dense_step.make_step``): ``vmap(epoch_fn)``'s
    replacement for the buckets that kernel takes.

    ``epoch_fn(states, X, mask, active) -> (states, (M,) losses)`` over
    STACKED state, X: (M, rows_pad, f), mask: (M, rows_pad) of 0/1,
    ``active``: (M,) > 0 for members still training. Per member it is
    ``make_train_fns``'s epoch under the bucket's ``active`` rule: the
    same three splits of its rng, the same shuffle with padding sorted to
    the end, the same scan over batches and the same row-weighted loss
    mean; a frozen member keeps its whole state, rng included, and
    reports NaN. Only the step differs: where the vmapped body calls
    ``value_and_grad`` + ``optimizer.update`` + ``apply_updates`` on one
    member, this body hands the gang's state to ``step``, which picks
    batch ``t`` out of the shuffled rows itself (no slice is copied) and
    leaves a member without real rows in that batch, or frozen, untouched.
    The scan carries the state as the step takes it (``enter``, ``leave``).
    """
    enter, step_fn, leave = step_fns

    def epoch_fn(states: TrainState, X, mask, active):
        n_pad = mask.shape[1]
        n_batches = n_pad // batch_size

        def plan(rng, x, m):
            rng2, perm_rng, _ = jax.random.split(rng, 3)
            keys = jax.random.uniform(perm_rng, (n_pad,))
            perm = jnp.argsort(jnp.where(m > 0, keys, 2.0))
            # the sort packs the real rows first: batch t holds the next
            # ``batch_size`` of them, as many as are left (no gather of m)
            left = jnp.sum(m > 0) - batch_size * jnp.arange(n_batches)
            counts = jnp.clip(left, 0, batch_size).astype(jnp.float32)
            # a batch fills whole 8-row tiles (the extra rows lie beyond the
            # count, like padding): the batches are then a view of the
            # gathered rows, where 100-row batches are laid out a second time
            batches = jnp.pad(
                perm.reshape((n_batches, batch_size)), ((0, 0), (0, -batch_size % 8))
            )
            return rng2, x[batches], counts

        rng2, Xs, counts = jax.vmap(plan)(states.rng, X, mask)

        hyperparams = states.opt_state.hyperparams

        def step(carry, batch):
            t, n_real = batch
            return step_fn(carry, hyperparams, Xs, t, n_real, active)

        carry, losses = jax.lax.scan(
            step, enter(states.params, states.opt_state),
            (jnp.arange(n_batches), counts.T),
        )
        params, opt_state = leave(carry, states.opt_state)
        mean_loss = jnp.sum(losses * counts.T, axis=0) / jnp.maximum(
            jnp.sum(counts, axis=1), 1.0
        )
        act = active > 0
        return (
            TrainState(
                params=params, opt_state=opt_state,
                rng=jnp.where(
                    act.reshape(act.shape + (1,) * (rng2.ndim - 1)), rng2, states.rng
                ),
            ),
            jnp.where(act, mean_loss, jnp.nan),
        )

    return epoch_fn


def make_seq_eval_fn(
    module,
    batch_size: int,
    lookback: int,
    target_offset: int = 0,
    loss: str = "mse",
    kl_weight: float = 1.0,
):
    """``eval_fn(params, X, item_mask) -> mean_loss`` over gathered windows
    (validation loss for sequence fleet members), scan-chunked so HBM never
    holds more than one batch of materialized windows. Uses the SAME loss
    family as training (fixed eval rng, like :func:`make_eval_fn`)."""
    loss_fn = make_loss_fn(module, loss=loss, kl_weight=kl_weight)

    def eval_fn(params, X, mask):
        n_pad = mask.shape[0]
        n_batches = n_pad // batch_size
        idxs = jnp.arange(n_pad).reshape((n_batches, batch_size))
        Ms = mask.reshape((n_batches, batch_size))
        rng = jax.random.PRNGKey(0)

        def step(_, batch):
            ib, mb = batch
            xb, yb = gather_window_batch(X, ib, lookback, target_offset)
            lv = loss_fn(params, rng, xb, yb, mb)
            return None, (lv * jnp.sum(mb), jnp.sum(mb))

        _, (sums, counts) = jax.lax.scan(step, None, (idxs, Ms))
        return jnp.sum(sums) / jnp.maximum(jnp.sum(counts), 1.0)

    return eval_fn


def make_eval_fn(module, batch_size: int, loss: str = "mse", kl_weight: float = 1.0):
    """``eval_fn(state, X, Y, mask) -> mean_loss`` over padded data, no
    parameter update (validation loss / early stopping)."""
    loss_fn = make_loss_fn(module, loss=loss, kl_weight=kl_weight)

    def eval_fn(state: TrainState, X, Y, mask):
        n_batches = X.shape[0] // batch_size
        Xs = X.reshape((n_batches, batch_size) + X.shape[1:])
        Ys = Y.reshape((n_batches, batch_size) + Y.shape[1:])
        Ms = mask.reshape((n_batches, batch_size))
        rng = jax.random.PRNGKey(0)

        def step(_, batch):
            xb, yb, mb = batch
            return None, (loss_fn(state.params, rng, xb, yb, mb), jnp.sum(mb))

        _, (losses, counts) = jax.lax.scan(step, None, (Xs, Ys, Ms))
        return jnp.sum(losses * counts) / jnp.maximum(jnp.sum(counts), 1.0)

    return eval_fn


def batched_apply(
    module, params, X: np.ndarray, batch_size: int = 4096
) -> np.ndarray:
    """Run ``module.apply`` over X in fixed-size chunks.

    Pads to a multiple of ``batch_size`` and scans, so inference compiles
    once per (batch_size, feature-shape) regardless of request length —
    essential for the server, where request sizes vary per call.
    """
    n = X.shape[0]
    if n == 0:
        raise ValueError("empty input")
    eff_bs = min(batch_size, _next_pow2(n))
    n_batches = -(-n // eff_bs)
    n_pad = n_batches * eff_bs
    X_pad = np.zeros((n_pad,) + X.shape[1:], dtype=np.float32)
    X_pad[:n] = X
    out = _scan_apply(module, params, jnp.asarray(X_pad), eff_bs)
    return np.asarray(out)[:n]


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# jit cache for batched_apply: a fresh @jax.jit closure per call would
# recompile on EVERY predict (the server's per-model hot path). Keyed by
# module identity (modules are rebuilt once per estimator and reused) and
# batch size; the module object is pinned in the value so its id can't be
# recycled while the entry lives.
_apply_cache: dict = {}


def _scan_apply(module, params, X_pad, batch_size):
    key = (id(module), batch_size)
    entry = _apply_cache.get(key)
    if entry is None or entry[0] is not module:

        @jax.jit
        def run(params, X_pad):
            n_batches = X_pad.shape[0] // batch_size
            Xs = X_pad.reshape((n_batches, batch_size) + X_pad.shape[1:])

            def step(_, xb):
                return None, module.apply(params, xb)

            _, out = jax.lax.scan(step, None, Xs)
            return out.reshape((n_batches * batch_size,) + out.shape[2:])

        if len(_apply_cache) >= 512:  # bound memory on pathological churn
            _apply_cache.clear()
        entry = (module, run)
        _apply_cache[key] = entry
    return entry[1](params, X_pad)
