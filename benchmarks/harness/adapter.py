"""The one module that touches the program under test: it builds the
trainer the way ``build-fleet`` builds it, turns the benchmark's seeded
weights into the artifacts ``run-server`` loads, starts the server, and
asserts the device decisions a TPU makes. Nothing here measures or judges.
"""

import copy
import os
import pickle
from typing import Dict, List, Optional, Tuple

import numpy as np

import families
from harness import weights


def tpu_decisions(config: dict, side: str) -> Dict[str, str]:
    """The device decisions a TPU backend makes for this configuration with
    default settings (``chip_smoke.py`` ``TPU_EXPECT``), as the
    configuration's file states them under ``tpu_decisions``; ``side`` is
    ``serve`` or ``train``. A run on the chip fails on any other."""
    return dict(config["tpu_decisions"][side])


def compile_cache_dir() -> str:
    """The program's own resolver: ``JAX_COMPILATION_CACHE_DIR`` if set
    (nothing is configured then), else the fixed ``<checkout>/.jax_cache``."""
    from gordo_components_tpu.utils import resolve_compile_cache

    return resolve_compile_cache()


def estimator_entry(model: dict) -> Tuple[str, dict]:
    """``(class path, kwargs)`` of the estimator inside the canonical
    pipeline definition."""
    (_, det), = model.items()
    (_, pipe), = det["base_estimator"].items()
    (path, kwargs), = pipe["steps"][-1].items()
    return path, kwargs


def _estimator_kwargs(model: dict) -> dict:
    return estimator_entry(model)[1]


def model_definition(config: dict, trainer_seed: int = 0) -> dict:
    model = copy.deepcopy(config["model"])
    _estimator_kwargs(model)["seed"] = int(trainer_seed)
    return model


def gang_trainer(config: dict, trainer_seed: int, names: List[str]):
    """``(trainer, member_hparams)`` exactly as
    ``builder/fleet_build.py::_build_fleet_group`` makes them from the
    machines' model definition: through ``extract_fleetable`` and the
    ``_TRAINER_KEYS`` split, so every default is the product's."""
    from gordo_components_tpu.builder.fleet_build import (
        _TRAINER_KEYS,
        _member_hparams_of,
        extract_fleetable,
    )
    from gordo_components_tpu.parallel.fleet import FleetTrainer

    ae_kwargs = extract_fleetable(model_definition(config, trainer_seed))
    if ae_kwargs is None:
        raise RuntimeError("the configuration's model is not fleetable")
    member_hparams = {name: _member_hparams_of(ae_kwargs) for name in names}
    trainer_kwargs = {k: ae_kwargs.pop(k) for k in _TRAINER_KEYS if k in ae_kwargs}
    trainer = FleetTrainer(
        checkpoint_dir=None, checkpoint_every=1, mesh=None, epoch_callback=None,
        **trainer_kwargs, **ae_kwargs,
    )
    return trainer, member_hparams


def member_arrays(config: dict, member) -> Dict[str, np.ndarray]:
    """A fitted ``FleetMemberModel`` in the reference's naming."""
    layout = families.load(config["family"], "layout")
    return {
        "w": layout.from_program(member.params["params"]),
        "losses": np.asarray(member.history["loss"], np.float64),
        "in_shift": np.asarray(member.scaler.shift),
        "in_scale": np.asarray(member.scaler.scale),
        "err_shift": np.asarray(member.error_scaler.shift),
        "err_scale": np.asarray(member.error_scaler.scale),
        "feature_thresholds": np.asarray(member.feature_thresholds),
        "total_threshold": np.asarray(member.total_threshold),
    }


# ------------------------------------------------------------ serve side


def _flax_params(config: dict, w: Dict[str, np.ndarray]) -> dict:
    """The benchmark's weights under the program's parameter names."""
    return {"params": families.load(config["family"], "layout").to_program(config, w)}


def make_member(config: dict, seed: int, index: int):
    """The fitted detector ``serializer.load`` would return for served
    member ``index``: the configuration's pipeline with the benchmark's
    seeded weights and scalers in place of trained ones."""
    from gordo_components_tpu import serializer
    from gordo_components_tpu.ops.scaler import ScalerParams

    F = int(config["tags_per_machine"])
    w = weights.member_weights(config, seed, index)
    det = serializer.from_definition(model_definition(config))
    pipe = det.base_estimator
    scaler, est = pipe.steps[0][1], pipe.steps[-1][1]
    scaler = _jax_minmax(ScalerParams(w["in_shift"], w["in_scale"]), F)
    pipe.steps[0] = (pipe.steps[0][0], scaler)
    est.params_ = _flax_params(config, w)
    est.n_features_ = F
    est.history = {"loss": []}
    det.error_scaler_ = ScalerParams(w["err_shift"], w["err_scale"])
    det.tags_ = [f"tag-{j}" for j in range(F)]
    det.feature_thresholds_ = np.ones((F,), np.float32)
    det.total_threshold_ = float(F) ** 0.5
    det.threshold_method_ = "exact"
    return det


def _jax_minmax(params, n_features: int):
    from gordo_components_tpu.models.transformers import JaxMinMaxScaler

    scaler = JaxMinMaxScaler()
    scaler.set_fitted(params, n_features)
    return scaler


class _MemberStub:
    """What a served member's ``model.pkl`` holds: not 1.3-8.4 MB of
    weights but the call that remakes them from the seed when the server
    unpickles the artifact. A bank of 4096 members would otherwise write
    ~11 GB per run (``serializer.dump`` writes the weights twice), and the
    machine's host keeps every block ever written."""

    def __init__(self, config: dict, seed: int, index: int):
        self.args = (config, seed, index)

    def __reduce__(self):
        return make_member, self.args


def member_name(index: int) -> str:
    return f"m-{index:05d}"


def write_artifacts(config: dict, seed: int, n_members: int, model_dir: str) -> List[str]:
    """The artifact tree ``build_app`` scans: one directory per member
    with a ``model.pkl`` (see ``_MemberStub``)."""
    names = []
    for i in range(n_members):
        name = member_name(i)
        path = os.path.join(model_dir, name)
        os.makedirs(path)
        with open(os.path.join(path, "model.pkl"), "wb") as fh:
            pickle.dump(_MemberStub(config, seed, i), fh)
        names.append(name)
    return names


def build_server_app(model_dir: str):
    from gordo_components_tpu.server import build_app

    return build_app(model_dir)


def check_serving_decisions(app, n_members: int, expect: Optional[dict]) -> dict:
    """Every member banked, nothing on a fall-back path, and the kernels a
    TPU resolves to (``chip_smoke.py::_check_serving_state``, read from the
    bank directly). ``expect=None`` (CPU rehearsal) checks coverage only."""
    bank = app["bank"]
    cov = bank.coverage()
    if cov["banked"] != n_members or cov["fallback"]:
        raise RuntimeError(
            f"banked {cov['banked']}/{n_members}, fallback {cov['fallback']}"
        )
    rows = list(bank.flops_stats().values())
    if len(rows) != 1:
        raise RuntimeError(f"expected one bucket, the bank has {len(rows)}")
    got = dict(kernel=cov["kernel"], seq_layout=rows[0].get("seq_layout"),
               seq_kernel=rows[0].get("seq_kernel"))
    bad = {k: (got.get(k), v) for k, v in (expect or {}).items() if got.get(k) != v}
    if bad:
        raise RuntimeError(f"device decisions (got, expected): {bad}")
    return got


def check_training_decisions(stats: dict, expect: Optional[dict]) -> dict:
    if len(stats["buckets"]) != 1:
        raise RuntimeError(f"the gang split into {len(stats['buckets'])} buckets")
    bucket = stats["buckets"][0]
    got = dict(layout=bucket["layout"], device=bucket["device"])
    bad = {k: (got.get(k), v) for k, v in (expect or {}).items() if got.get(k) != v}
    if bad:
        raise RuntimeError(f"device decisions (got, expected): {bad}")
    return got
