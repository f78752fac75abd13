"""sklearn-compatible JAX/Flax autoencoder estimators.

Reference parity: gordo_components/model/models.py (unverified; SURVEY.md §2
"model.models") — ``KerasBaseEstimator`` / ``KerasAutoEncoder`` /
``KerasLSTMAutoEncoder`` / ``KerasLSTMForecast``. Same estimator semantics
(fit reconstructs X; LSTM variants window the series with
``lookback_window`` and reconstruct the current step or forecast t+1; score
is explained variance; per-epoch history recorded into metadata), but the
engine is the functional train core (train_core.py): one jit'd epoch
program, on-device shuffling, static shapes, bfloat16-capable.

These classes drop into ``sklearn.pipeline.Pipeline`` and pickle cleanly
(params are converted to numpy pytrees), which is what the serializer and
server rely on.
"""

import functools
import logging
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gordo_components_tpu.models.base import GordoBase
from gordo_components_tpu.models.register import lookup_factory
from gordo_components_tpu.models import factories  # noqa: F401 — registers factories
from gordo_components_tpu.models import train_core
from gordo_components_tpu.ops.losses import explained_variance, regression_metrics
from gordo_components_tpu.utils import capture_args

logger = logging.getLogger(__name__)


def _as_float32(X) -> np.ndarray:
    """DataFrame/array -> float32 ndarray (reference accepts both)."""
    if hasattr(X, "values"):
        X = X.values
    return np.asarray(X, dtype=np.float32)




class BaseEstimator(GordoBase):
    """Shared engine for all autoencoder estimators.

    ``kind`` selects a registered factory for this estimator's type (class
    name), exactly like the reference's ``KerasBaseEstimator``; remaining
    ``**kwargs`` flow to the factory.
    """

    # registry type; subclasses override (class name by default)
    @property
    def _registry_type(self) -> str:
        return type(self).__name__

    # DP shard_map's varying-manual-axes proof stays ON except for
    # recurrent modules, whose flax scan carries initialize unvarying and
    # trip the static analysis despite exact numerics (parallel/dp.py)
    _dp_check_vma = True

    @capture_args
    def __init__(
        self,
        kind: str = "feedforward_hourglass",
        batch_size: int = 100,
        epochs: int = 10,
        learning_rate: float = 1e-3,
        optimizer: str = "adam",
        loss: str = "auto",
        kl_weight: float = 1.0,
        validation_split: float = 0.0,
        early_stopping_patience: Optional[int] = None,
        early_stopping_min_delta: float = 0.0,
        seed: int = 0,
        compute_dtype: str = "float32",
        data_parallel: bool = False,
        **factory_kwargs,
    ):
        self.kind = kind
        self.batch_size = int(batch_size)
        self.epochs = int(epochs)
        self.learning_rate = float(learning_rate)
        self.optimizer = optimizer
        self.loss = loss
        self.kl_weight = float(kl_weight)
        self.validation_split = float(validation_split)
        self.early_stopping_patience = early_stopping_patience
        self.early_stopping_min_delta = float(early_stopping_min_delta)
        self.seed = int(seed)
        self.compute_dtype = compute_dtype
        # train with batch rows sharded over all devices (ICI DP) when more
        # than one device is visible; see fit() for the sharding design
        self.data_parallel = bool(data_parallel)
        self.factory_kwargs = factory_kwargs
        # fitted state
        self.params_ = None
        self.n_features_ = None
        self.history: Dict[str, list] = {}
        self._module = None
        # validate the kind eagerly for fail-fast config errors
        lookup_factory(self._registry_type, kind)

    # ------------------------------------------------------------------ #
    # module/data plumbing — subclasses specialize windowing semantics
    # ------------------------------------------------------------------ #

    def _build_module(self, n_features: int):
        factory = lookup_factory(self._registry_type, self.kind)
        return factory(
            n_features, compute_dtype=self.compute_dtype, **self.factory_kwargs
        )

    def _make_xy(self, X: np.ndarray, y: Optional[np.ndarray]):
        """(train_inputs, train_targets) — AE default: reconstruct X."""
        return X, X if y is None else _as_float32(y)

    def _resolved_loss(self) -> str:
        if self.loss != "auto":
            return self.loss
        # variational modules train with the ELBO; everything else MSE
        return "vae" if hasattr(self._module, "elbo_terms") else "mse"

    @property
    def module(self):
        if self._module is None:
            if self.n_features_ is None:
                raise RuntimeError("Model is not fitted; no module to build")
            self._module = self._build_module(self.n_features_)
        return self._module

    # ------------------------------------------------------------------ #
    # sklearn-style API
    # ------------------------------------------------------------------ #

    def fit(self, X, y=None, **kwargs):
        X = _as_float32(X)
        if X.ndim == 1:
            X = X[:, None]
        Xin, Yin = self._make_xy(X, y)
        self.n_features_ = int(X.shape[-1])
        self._module = None  # rebuild for (possibly) new n_features
        module = self.module

        n = Xin.shape[0]
        if n == 0:
            raise ValueError("Cannot fit on empty data")
        bs = min(self.batch_size, n)

        # host-side split, device-side everything else
        n_val = int(n * self.validation_split)
        if n_val > 0:
            Xtr, Ytr = Xin[:-n_val], Yin[:-n_val]
            Xva, Yva = Xin[-n_val:], Yin[-n_val:]
        else:
            Xtr, Ytr, Xva, Yva = Xin, Yin, None, None

        opt = train_core.make_optimizer(self.optimizer, self.learning_rate)
        loss = self._resolved_loss()
        init_fn, epoch_fn = train_core.make_train_fns(
            module, opt, bs, loss=loss, kl_weight=self.kl_weight
        )
        epoch_fn = jax.jit(epoch_fn, donate_argnums=(0,))

        # ---- data parallelism (BASELINE.json north star: DP over ICI) ----
        # Swap in the shard_map DP epoch: each batch's ROWS split across
        # the data mesh, gradients reconstructed with a count-weighted
        # psum (parallel/dp.py). Same shuffle, same rng stream -> same
        # model as the single-device fit; only the per-row gradient work
        # is partitioned. Runs on the largest device count dividing the
        # batch size so the split is exact.
        if self.data_parallel:
            from gordo_components_tpu.parallel.dp import (
                data_mesh,
                dp_device_count,
                make_dp_epoch_fn,
            )

            n_dp = dp_device_count(bs, len(jax.devices()))
            if n_dp > 1:
                dp_mesh = data_mesh(n_dp)
                epoch_fn = make_dp_epoch_fn(
                    module, opt, bs, dp_mesh, loss=loss,
                    kl_weight=self.kl_weight,
                    check_vma=self._dp_check_vma,
                )
                logger.info(
                    "Data-parallel fit: batch %d split over %d devices", bs, n_dp
                )
            else:
                logger.info(
                    "data_parallel requested but unusable (1 usable device "
                    "for batch_size=%d); single-device fit", bs,
                )

        Xp, Yp, mask, _ = train_core.pad_to_batches(Xtr, Ytr, bs)
        Xp, Yp, mask = jnp.asarray(Xp), jnp.asarray(Yp), jnp.asarray(mask)
        state = init_fn(jax.random.PRNGKey(self.seed), Xp[0])

        eval_fn = None
        if Xva is not None:
            eval_fn = jax.jit(
                train_core.make_eval_fn(module, bs, loss=loss, kl_weight=self.kl_weight)
            )
            Xvp, Yvp, vmask, _ = train_core.pad_to_batches(Xva, Yva, bs)
            Xvp, Yvp, vmask = jnp.asarray(Xvp), jnp.asarray(Yvp), jnp.asarray(vmask)

        self.history = {"loss": []}
        if eval_fn is not None:
            self.history["val_loss"] = []
        best, patience_left = np.inf, self.early_stopping_patience
        best_params = None
        for epoch in range(self.epochs):
            state, loss_val = epoch_fn(state, Xp, Yp, mask)
            loss_f = float(loss_val)
            self.history["loss"].append(loss_f)
            monitored = loss_f
            if eval_fn is not None:
                val = float(eval_fn(state, Xvp, Yvp, vmask))
                self.history["val_loss"].append(val)
                monitored = val
            if self.early_stopping_patience is not None:
                if monitored < best - self.early_stopping_min_delta:
                    best, patience_left = monitored, self.early_stopping_patience
                    best_params = jax.tree.map(np.asarray, state.params)
                else:
                    patience_left -= 1
                    if patience_left <= 0:
                        logger.info("Early stopping at epoch %d", epoch + 1)
                        break

        final = best_params if best_params is not None else state.params
        self.params_ = jax.tree.map(np.asarray, final)
        return self

    def _check_fitted(self):
        if self.params_ is None:
            raise RuntimeError(f"{type(self).__name__} has not been fitted")

    def predict(self, X) -> np.ndarray:
        """Reconstruction of X (reference: ``KerasAutoEncoder.transform``
        returns the autoencoder output)."""
        self._check_fitted()
        X = _as_float32(X)
        if X.ndim == 1:
            X = X[:, None]
        return train_core.batched_apply(self.module, self.params_, X)

    # sklearn Pipeline compatibility: AE estimators act as transformers too
    def transform(self, X) -> np.ndarray:
        return self.predict(X)

    def _scoring_pair(self, X, y):
        """(aligned target, prediction) — the single definition of scoring
        alignment, shared by ``score`` and ``score_metrics`` (sequence
        estimators override to drop the lookback warm-up rows)."""
        X = _as_float32(X)
        target = X if y is None else _as_float32(y)
        return target, self.predict(X)

    def score(self, X, y=None) -> float:
        """Explained variance of the reconstruction (reference semantics)."""
        self._check_fitted()
        target, pred = self._scoring_pair(X, y)
        return float(explained_variance(jnp.asarray(target), jnp.asarray(pred)))

    def score_metrics(self, X, y=None) -> Dict[str, float]:
        """The reference's full evaluation metric set (explained variance,
        r2, MSE, MAE) with ``score``'s exact target alignment — one
        prediction pass feeds all four."""
        self._check_fitted()
        target, pred = self._scoring_pair(X, y)
        return regression_metrics(jnp.asarray(target), jnp.asarray(pred))

    def get_metadata(self) -> Dict[str, Any]:
        md: Dict[str, Any] = {
            "type": type(self).__name__,
            "kind": self.kind,
            "params": _jsonable(self.get_params()),
        }
        if self.params_ is not None:
            md["n_features"] = self.n_features_
            md["history"] = self.history
            md["parameter_count"] = int(
                sum(int(np.size(p)) for p in jax.tree.leaves(self.params_))
            )
        return md

    # ------------------------------------------------------------------ #
    # pickling (serializer dump/load; reference made Keras picklable via
    # HDF5 bytes — here params are already a numpy pytree, so default
    # pickling works once the unpicklable Flax module is dropped)
    # ------------------------------------------------------------------ #

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_module"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)


class AutoEncoder(BaseEstimator):
    """Feedforward autoencoder over flat feature vectors
    (reference: ``KerasAutoEncoder``)."""


class SequenceBaseEstimator(BaseEstimator):
    """Shared windowing logic for sequence estimators: X is windowed into
    (n_windows, lookback_window, n_features) on device."""

    @capture_args
    def __init__(self, kind: str = "lstm_hourglass", lookback_window: int = 10, **kwargs):
        self.lookback_window = int(lookback_window)
        super().__init__(kind=kind, **kwargs)
        # capture_args on both ctors: merge so lookback_window is retained
        self._params = {"kind": kind, "lookback_window": lookback_window, **kwargs}

    # offset: prediction i corresponds to input row i + offset
    _target_offset = 0  # 0 => reconstruct window's last step

    def _window_inputs(self, X: np.ndarray) -> np.ndarray:
        lb = self.lookback_window
        if X.shape[0] < lb + self._target_offset:
            raise ValueError(
                f"Need at least lookback_window+{self._target_offset}="
                f"{lb + self._target_offset} rows, got {X.shape[0]}"
            )
        # host-side windowing: native multithreaded copy when available
        # (gordo_components_tpu/native); ops/windows.sliding_windows is the
        # in-graph equivalent used inside jit'd programs
        from gordo_components_tpu.native import sliding_windows_host

        W = sliding_windows_host(X, lb)
        if self._target_offset:
            W = W[: -self._target_offset]
        return W

    def _make_xy(self, X: np.ndarray, y=None):
        base = X if y is None else _as_float32(y)
        W = self._window_inputs(X)
        targets = base[self.lookback_window - 1 + self._target_offset :]
        return W, targets

    def predict(self, X) -> np.ndarray:
        """Output row i is the model value for input row
        ``i + lookback_window - 1 + offset`` (reference LSTM semantics:
        output is shorter than input by the warm-up window)."""
        self._check_fitted()
        X = _as_float32(X)
        if X.ndim == 1:
            X = X[:, None]
        W = self._window_inputs(X)
        return train_core.batched_apply(self.module, self.params_, W)

    def _scoring_pair(self, X, y):
        X = _as_float32(X)
        base = X if y is None else _as_float32(y)
        target = base[self.lookback_window - 1 + self._target_offset :]
        return target, self.predict(X)


class LSTMAutoEncoder(SequenceBaseEstimator):
    """Windowed sequence autoencoder reconstructing the current step
    (reference: ``KerasLSTMAutoEncoder``)."""

    _target_offset = 0
    _dp_check_vma = False  # recurrent: see BaseEstimator._dp_check_vma


class LSTMForecast(SequenceBaseEstimator):
    """Windowed sequence model forecasting t+1
    (reference: ``KerasLSTMForecast``)."""

    _target_offset = 1
    _dp_check_vma = False  # recurrent: see BaseEstimator._dp_check_vma


class ConvAutoEncoder(SequenceBaseEstimator):
    """Conv1D window autoencoder (extended zoo, BASELINE.json config 4).
    ``lookback_window`` must be divisible by ``2**len(channels)``."""

    @capture_args
    def __init__(self, kind: str = "conv1d_autoencoder", lookback_window: int = 16, **kwargs):
        # pin the conv implementation explicitly at build time: the
        # factory default changed once (lax -> matmul, 2026-07-31) and a
        # trained artifact must reload with the impl its thresholds were
        # calibrated under, not whatever the default is at load time
        kwargs.setdefault("conv_impl", "matmul")
        super().__init__(kind=kind, lookback_window=lookback_window, **kwargs)
        self._params = {"kind": kind, "lookback_window": lookback_window, **kwargs}

    def __setstate__(self, state):
        super().__setstate__(state)
        # artifacts pickled before the impl was pinned were built under
        # the then-default "lax"; resolve them to it so reload never
        # flips numerics under a trained model's thresholds. (Unpinned
        # pickles from the ~1h window where the default was already
        # matmul but the pin hadn't landed are indistinguishable and
        # resolve to lax too — a deliberate tie-break toward the years of
        # pre-flip artifacts; both impls agree within f32 1e-5 anyway.)
        self.factory_kwargs.setdefault("conv_impl", "lax")
        if hasattr(self, "_params"):
            self._params.setdefault("conv_impl", "lax")

    _target_offset = 0


class TrunkForecast(BaseEstimator):
    """One machine's view of a shared decoder trunk: a causal-sequence
    forecaster (output row i forecasts input row i + 1 from rows 0..i,
    the whole call being one sequence) whose parameters split in two.

    - **Shared**: the trunk (``models/factories/trunk.py``), a *trunk
      artifact* named by ``trunk`` (a directory; a relative path means
      "beside this member's artifact"). Every member that names it holds
      the same object, and a bank places it on the device once.
    - **Per machine** (``params_``): the input projection ``tags ->
      hidden`` and the head ``hidden -> tags``.

    ``fit`` never touches the trunk. It draws the input projection from
    ``seed`` and solves the head by ridge regression (``ridge``) of the
    next row on the trunk's state, over the training rows cut into
    sequences of ``sequence_rows``. A trunk artifact that does not exist
    yet is made from ``seed`` with random weights and written for the
    members to come; training a trunk, and fitting members as a fleet,
    are not implemented (``build-fleet`` refuses this estimator).
    ``**factory_kwargs`` are the trunk kind's sizes."""

    lookback_window = 1
    _target_offset = 1  # prediction i corresponds to input row i + 1

    @capture_args
    def __init__(
        self,
        kind: str = "sparse_moe_decoder",
        trunk: str = "trunk",
        sequence_rows: int = 512,
        ridge: float = 1e-3,
        seed: int = 0,
        **factory_kwargs,
    ):
        super().__init__(kind=kind, seed=seed, **factory_kwargs)
        self.trunk = str(trunk)
        self.sequence_rows = int(sequence_rows)
        self.ridge = float(ridge)
        self.artifact_root_: Optional[str] = None
        self._forward = None
        # capture_args on both ctors: keep this one's view
        self._params = {
            "kind": kind, "trunk": trunk, "sequence_rows": sequence_rows,
            "ridge": ridge, "seed": seed, **factory_kwargs,
        }

    def bind_artifact_root(self, root: str) -> None:
        """``serializer.load``: the directory this member's artifact lies in."""
        self.artifact_root_ = root

    @property
    def trunk_path(self) -> str:
        if os.path.isabs(self.trunk) or self.artifact_root_ is None:
            return os.path.abspath(self.trunk)
        return os.path.join(self.artifact_root_, self.trunk)

    @property
    def trunk_params(self):
        """The shared tree (``serializer.load_trunk`` caches it: one
        object per process and artifact version)."""
        from gordo_components_tpu import serializer

        return serializer.load_trunk(self.trunk_path)

    def _ensure_trunk(self):
        from gordo_components_tpu import serializer

        if not os.path.exists(os.path.join(self.trunk_path, "trunk.pkl")):
            logger.warning(
                "No trunk artifact at %s: writing a random one from seed %d",
                self.trunk_path, self.seed,
            )
            trunk = self.module.init_trunk(jax.random.PRNGKey(self.seed))
            serializer.dump_trunk(jax.tree.map(np.asarray, trunk), self.trunk_path)
        return self.trunk_params

    def _run(self, member, X: np.ndarray, n_valid) -> np.ndarray:
        """``module.apply`` over (B, rows, F) sequences padded to whole
        chunks, jitted once per shape; the kernel choice is the bank's. A
        ``member`` without a head gets the state the heads read."""
        from gordo_components_tpu.ops.pallas_score import resolve_bank_kernel_mode

        module = self.module
        if self._forward is None:
            interpret = resolve_bank_kernel_mode() != "pallas"
            self._forward = jax.jit(functools.partial(module.apply, interpret=interpret))
        T = module.padded_rows(X.shape[1])
        Xp = np.zeros((X.shape[0], T, X.shape[2]), np.float32)
        Xp[:, : X.shape[1]] = X
        out, _observed = self._forward(
            self.trunk_params, member, jnp.asarray(Xp), jnp.asarray(n_valid, jnp.int32)
        )
        return np.asarray(out)[:, : X.shape[1]]

    def fit(self, X, y=None, **kwargs):
        X = _as_float32(X)
        if X.ndim == 1:
            X = X[:, None]
        if y is not None:
            raise ValueError("TrunkForecast forecasts its own input; y is not taken")
        if X.shape[0] < 2:
            raise ValueError("Need at least 2 rows to fit a forecast")
        self.n_features_ = int(X.shape[-1])
        self._module = self._forward = None
        module = self.module
        self._ensure_trunk()
        member = jax.tree.map(np.asarray, module.init_member(jax.random.PRNGKey(self.seed)))
        # whole sequences of ``sequence_rows``; the remainder is its own, shorter one
        L = min(self.sequence_rows, X.shape[0])
        starts = list(range(0, X.shape[0] - 1, L))
        seqs = np.zeros((len(starts), L, X.shape[1]), np.float32)
        lengths = np.asarray([min(L, X.shape[0] - s) for s in starts], np.int32)
        for i, (s, n) in enumerate(zip(starts, lengths)):
            seqs[i, :n] = X[s : s + n]
        in_proj = jax.tree.map(lambda a: a[None].repeat(len(starts), 0), member["in_proj"])
        state = self._run({"in_proj": in_proj}, seqs, lengths).astype(np.float64)
        keep = np.arange(L - 1)[None, :] < (lengths - 1)[:, None]  # rows with a next row
        H = np.concatenate([state[:, :-1][keep], np.ones((int(keep.sum()), 1))], axis=1)
        Y = seqs[:, 1:][keep].astype(np.float64)
        gram = H.T @ H + self.ridge * len(H) * np.eye(H.shape[1])
        W = np.linalg.solve(gram, H.T @ Y)
        member["head"] = {"kernel": W[:-1].astype(np.float32), "bias": W[-1].astype(np.float32)}
        self.params_ = {"params": member}
        resid = H @ W - Y
        self.history = {"loss": [float(np.mean(resid * resid))]}
        return self

    def predict(self, X) -> np.ndarray:
        """Output row i is the forecast of input row i + 1: one row
        shorter than the input, the input being one sequence."""
        self._check_fitted()
        X = _as_float32(X)
        if X.ndim == 1:
            X = X[:, None]
        if X.shape[0] < 2:
            raise ValueError(f"Need at least 2 rows (one to forecast), got {X.shape[0]}")
        member = jax.tree.map(lambda a: np.asarray(a)[None], self.params_["params"])
        return self._run(member, X[None], [X.shape[0]])[0, :-1]

    def _scoring_pair(self, X, y):
        X = _as_float32(X)
        base = X if y is None else _as_float32(y)
        return base[1:], self.predict(X)

    def __getstate__(self):
        state = super().__getstate__()
        state["_forward"] = None
        return state


def _jsonable(obj):
    """Best-effort conversion of captured params to JSON-safe values."""
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)
