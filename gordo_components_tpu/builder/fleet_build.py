"""Fleet builder: train a gang of machines in one process.

This is the builder-pod entrypoint for gang-scheduled TPU jobs
(workflow/scheduler.py): where the reference runs ``build_model`` once per
pod, a gang job loads every member's dataset host-side, then trains all
*fleetable* members in one vmap/shard_map program (parallel/fleet.py) and
falls back to the per-machine ``provide_saved_model`` path for bespoke
model configs — so arbitrary reference-style configs still work inside a
gang.
"""

import copy
import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from gordo_components_tpu import serializer
from gordo_components_tpu.builder.build_model import (
    _mirror_artifact,
    _normalize_evaluation,
    _wants_cv,
    cached_cv_satisfied,
    calculate_model_key,
    provide_saved_model,
)
from gordo_components_tpu.parallel.fleet import (
    DEFAULT_LEARNING_RATE,
    FleetTrainer,
    _family_defaults,
    _target_offset_for,
)
from gordo_components_tpu.observability import get_registry
from gordo_components_tpu.observability.tracing import (
    chrome_trace,
    get_tracer,
    use_trace,
)
from gordo_components_tpu.resilience.faults import faultpoint
from gordo_components_tpu.utils import metadata_timestamp
from gordo_components_tpu.utils.staging import stage_members
from gordo_components_tpu.workflow.config import Machine

logger = logging.getLogger(__name__)

# chaos site (tests/test_chaos.py): one poisoned hparam group's training
# must degrade to a partial manifest, never abort the whole gang
_FP_GROUP = faultpoint("fleet_build.group")

# cross-arch gang scheduling (ISSUE 20): groups at or below this member
# count are "small" — their wall time is dominated by host-side work
# (tracing, compile, stack/unstack), so overlapping them pays; larger
# groups saturate the device alone and stay serial
GANG_SMALL_MAX = 32
GANG_WIDTH_ENV = "GORDO_GANG_WIDTH"


def resolve_gang_width(n_groups: int) -> int:
    """Worker-thread count for the small-group gang scheduler. Env
    ``GORDO_GANG_WIDTH``: an integer pins it; ``auto``/unset picks
    min(4, n_groups) when more than one accelerator device is present
    (overlap is free there) and 1 on a single-device host — the CPU test
    rigs keep today's strictly serial, deterministic schedule unless a
    test opts in explicitly."""
    raw = (os.environ.get(GANG_WIDTH_ENV) or "auto").strip().lower()
    if raw not in ("", "auto"):
        width = int(raw)
        if width < 1:
            raise ValueError(f"{GANG_WIDTH_ENV} must be >= 1, got {width}")
        return min(width, max(1, n_groups))
    import jax

    if jax.device_count() > 1 or jax.default_backend() in ("tpu", "gpu"):
        return min(4, max(1, n_groups))
    return 1


class _LockedHeartbeat:
    """Serializes heartbeat writes when gang worker threads report
    concurrently — the state file update is read-modify-write."""

    def __init__(self, hb):
        self._hb = hb
        self._lock = threading.Lock()

    def update(self, **kw):
        with self._lock:
            self._hb.update(**kw)

    def finish(self, *a, **kw):
        with self._lock:
            self._hb.finish(*a, **kw)


class FleetBuildReport(Dict[str, str]):
    """``build_fleet``'s return value: name -> artifact dir, exactly the
    mapping callers have always received, PLUS the partial-build record —
    ``failed`` maps members whose group (or bespoke build) exhausted its
    retries to the error string, and ``group_retries`` counts retry
    attempts that eventually succeeded. ``manifest()`` renders the
    partial-manifest schema the CLI ships."""

    SCHEMA = "gordo.fleet-build.manifest/v1"

    def __init__(self, *args: Any, **kwargs: Any):
        super().__init__(*args, **kwargs)
        self.failed: Dict[str, str] = {}
        self.group_retries: int = 0
        self.gang_width: int = 1  # small-group scheduler width used
        # one row per trained fleet bucket: what the trainer resolved and
        # where it ran (model family, member count, sequence layout,
        # device block) — provenance a parent process that never touches
        # JAX can read back from the manifest
        self.buckets: List[Dict[str, Any]] = []

    @property
    def device(self) -> Optional[Dict[str, Any]]:
        """``{"platform", "kind", "count"}`` the fleet buckets trained on
        (the widest bucket's block; None when nothing fleet-trained)."""
        blocks = [b["device"] for b in self.buckets if b.get("device")]
        return max(blocks, key=lambda d: d["count"]) if blocks else None

    def manifest(self) -> Dict[str, Any]:
        return {
            "schema": self.SCHEMA,
            "built": dict(self),
            "failed": dict(self.failed),
            "n_built": len(self),
            "n_failed": len(self.failed),
            "group_retries": self.group_retries,
            "gang_width": self.gang_width,
            "buckets": list(self.buckets),
            "device": self.device,
        }


def _finish_build_trace(trace, output_dir: str, **attrs: Any) -> None:
    """Close the build trace and persist it as Chrome trace-event JSON
    next to the build manifest — best-effort (the trace is diagnostics,
    never worth failing a build over), and written on the crash path too:
    a flight recorder is most valuable for the build that died."""
    if trace is None:
        return
    trace.finish(**attrs)
    try:
        os.makedirs(output_dir, exist_ok=True)
        path = os.path.join(output_dir, "build_trace.json")
        with open(path, "w") as f:
            json.dump(chrome_trace([trace]), f)
        logger.info(
            "build trace (fit/compile/checkpoint spans per bucket) -> %s "
            "(trace_id=%s; open in chrome://tracing or Perfetto)",
            path, trace.trace_id,
        )
    except Exception:
        logger.warning("failed to write build trace", exc_info=True)


def _build_counters():
    """Builder-process metrics (observability/): how many models were
    built, how, and how many the cache spared — progress a restarted gang
    pod's registry snapshot makes visible next to its heartbeats."""
    reg = get_registry()
    return {
        "built": reg.counter(
            "gordo_build_models_built_total",
            "Models built (artifact written)", ("path",),
        ),
        "cache_hits": reg.counter(
            "gordo_build_cache_hits_total",
            "Builds skipped because the register cache satisfied them",
        ),
    }

_AE_PATHS = (
    "gordo_components_tpu.models.AutoEncoder",
    "gordo_components_tpu.models.models.AutoEncoder",
    "gordo_components.model.models.KerasAutoEncoder",
)
# sequence families the fleet engine also gang-trains (gather-windowed
# programs, parallel/fleet.py); reference-era aliases included
_SEQ_PATHS = {
    "LSTMAutoEncoder": (
        "gordo_components_tpu.models.LSTMAutoEncoder",
        "gordo_components_tpu.models.models.LSTMAutoEncoder",
        "gordo_components.model.models.KerasLSTMAutoEncoder",
    ),
    "LSTMForecast": (
        "gordo_components_tpu.models.LSTMForecast",
        "gordo_components_tpu.models.models.LSTMForecast",
        "gordo_components.model.models.KerasLSTMForecast",
    ),
    "ConvAutoEncoder": (
        "gordo_components_tpu.models.ConvAutoEncoder",
        "gordo_components_tpu.models.models.ConvAutoEncoder",
    ),
}
_DET_PATHS = (
    "gordo_components_tpu.models.DiffBasedAnomalyDetector",
    "gordo_components_tpu.models.anomaly.DiffBasedAnomalyDetector",
    "gordo_components.model.anomaly.DiffBasedAnomalyDetector",
)
_SCALER_PATHS = (
    "sklearn.preprocessing.MinMaxScaler",
    "gordo_components_tpu.models.transformers.JaxMinMaxScaler",
)
_STANDARD_SCALER_PATHS = (
    "sklearn.preprocessing.StandardScaler",
    "gordo_components_tpu.models.transformers.JaxStandardScaler",
)

# Estimator kwargs the fleet path honors with semantics identical to the
# single-build path: FleetTrainer's own training knobs (including
# validation_split, whose val-loss drives the per-member ES mask, and
# loss/kl_weight, resolved per module exactly like BaseEstimator) plus the
# factory surfaces. Anything else (e.g. data_parallel) must take the
# single-build path rather than be silently dropped.
_TRAINER_KEYS = frozenset(
    {
        "kind", "epochs", "batch_size", "learning_rate", "optimizer",
        "early_stopping_patience", "early_stopping_min_delta",
        "validation_split", "seed", "compute_dtype", "quantize_rows",
        "loss", "kl_weight",
    }
)
# NOTE: "input_scaler" is deliberately NOT in _TRAINER_KEYS: it is injected
# by extract_fleetable from the pipeline's scaler STEP, never accepted as a
# user-supplied AutoEncoder kwarg (which must fail the fleetable check and
# then fail loudly on the single-build path).
_FACTORY_KEYS = frozenset(
    {
        "encoding_dim", "decoding_dim", "encoding_func", "decoding_func",
        "out_func", "dims", "funcs", "encoding_layers", "compression_factor",
        "func", "channels", "kernel_size", "latent_dim", "conv_impl",
    }
)


def extract_fleetable(model_config: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """If ``model_config`` is EXACTLY the canonical anomaly pipeline —
    ``DiffBasedAnomalyDetector(base_estimator=Pipeline(scaler,
    estimator))`` with a default-kwargs MinMax/Standard scaler step — return
    the estimator kwargs for FleetTrainer, augmented with the honored
    routing kwargs (``input_scaler`` for the z-score scaler, ``model_type``
    for sequence families, ``threshold_quantile``/``require_thresholds``
    detector knobs — quantile thresholds are exact for the dense family
    and histogram-approximate (one-bin-width tolerance) for sequence
    families); else None (single-build path).

    The check is deliberately strict: the fleet engine fits exactly the
    default min-max or z-score affine, so any config that deviates (unknown
    detector or estimator kwargs, scaler kwargs, no scaler step, bare base
    estimator) must take the single-build path to keep identical semantics.
    """
    if not isinstance(model_config, dict) or len(model_config) != 1:
        return None
    (path, kwargs), = model_config.items()
    kwargs = kwargs or {}
    if path not in _DET_PATHS:
        return None
    det_kwargs = {k: v for k, v in kwargs.items() if k != "base_estimator"}
    if set(det_kwargs) - {"threshold_quantile", "require_thresholds"}:
        return None  # detector overrides the fleet can't honor
    base = kwargs.get("base_estimator")
    if not (isinstance(base, dict) and len(base) == 1):
        return None
    (bpath, bkwargs), = base.items()
    if bpath != "sklearn.pipeline.Pipeline":
        return None
    steps = (bkwargs or {}).get("steps", [])
    inner = []
    for s in steps:
        if isinstance(s, (list, tuple)) and len(s) == 2:
            s = s[1]
        inner.append(s)
    scaler_kind = None
    if len(inner) == 2 and _is_path(inner[0], _SCALER_PATHS):
        scaler_kind = "minmax"
    elif len(inner) == 2 and _is_path(inner[0], _STANDARD_SCALER_PATHS):
        scaler_kind = "standard"
    if scaler_kind is not None:
        est = _estimator_kwargs(inner[1])
        if est is None:
            return None
        model_type, ae = est
        honored = _TRAINER_KEYS | _FACTORY_KEYS
        if model_type != "AutoEncoder":
            honored = honored | {"lookback_window"}
        if set(ae) - honored:
            return None  # kwargs the trainer can't honor identically
        if scaler_kind != "minmax":
            ae = dict(ae, input_scaler=scaler_kind)
        if model_type != "AutoEncoder":
            ae = dict(ae, model_type=model_type)
        if det_kwargs:
            ae = dict(ae, **det_kwargs)
        return ae
    return None


def _is_path(defn, paths) -> bool:
    """True iff ``defn`` names one of ``paths`` with NO constructor kwargs —
    a scaler with e.g. a custom feature_range must not take the fleet path
    (which always fits the default (0, 1) min-max)."""
    if isinstance(defn, str):
        return defn in paths
    if isinstance(defn, dict) and len(defn) == 1:
        (path, kwargs), = defn.items()
        return path in paths and not kwargs
    return False


def _estimator_kwargs(defn) -> Optional[Tuple[str, Dict[str, Any]]]:
    """(model_type, kwargs) for a recognized estimator definition, else
    None. model_type is the registry namespace FleetTrainer trains."""
    if isinstance(defn, str):
        path, kwargs = defn, {}
    elif isinstance(defn, dict) and len(defn) == 1:
        (path, kwargs), = defn.items()
        kwargs = dict(kwargs or {})
    else:
        return None
    if path in _AE_PATHS:
        return "AutoEncoder", kwargs
    for model_type, paths in _SEQ_PATHS.items():
        if path in paths:
            return model_type, kwargs
    return None


def _group_key(ae_kwargs: Dict[str, Any]) -> Tuple:
    """Gang membership key. ``learning_rate`` and (the VALUE of)
    ``early_stopping_patience`` are excluded: FleetTrainer stacks them as
    per-member (M,) vectors inside one program (VERDICT r3 next #7 /
    SURVEY §7 hard part 4), so machines differing only in those knobs
    must share a gang instead of shrinking vmap width. ES *presence*
    still splits — ES-on and ES-off members run different programs."""
    items = []
    for k, v in sorted(ae_kwargs.items()):
        if k == "learning_rate":
            continue
        if k == "early_stopping_patience":
            if v is not None:  # explicit None == omitted == ES off
                items.append((k, True))
            continue
        items.append((k, repr(v)))
    return tuple(items)


def _member_hparams_of(ae_kwargs: Dict[str, Any]) -> Dict[str, Any]:
    """The per-member vector knobs, with omissions normalized to the
    ENGINE defaults — a machine that omitted learning_rate must train at
    the default, not at whichever rate the group's first machine chose."""
    hp = {
        "learning_rate": float(
            ae_kwargs.get("learning_rate", DEFAULT_LEARNING_RATE)
        )
    }
    if ae_kwargs.get("early_stopping_patience") is not None:
        hp["early_stopping_patience"] = int(ae_kwargs["early_stopping_patience"])
    return hp


# CV fold members ride the SAME stacked member axis as real members — the
# separator cannot occur in machine names (NUL is not config-expressible)
_CV_SEP = "\x00cv\x00"


def _cv_key(name: str, fold: int) -> str:
    return f"{_CV_SEP}{fold}{_CV_SEP}{name}"


def _cache_satisfies_cv(cached: str, machine: Machine) -> bool:
    return cached_cv_satisfied(
        cached, _normalize_evaluation(machine.evaluation or None)
    )


def _plan_cv_folds(
    pending: List[Machine],
    member_data: Dict[str, Any],
    ae_kwargs: Dict[str, Any],
) -> Tuple[Dict[str, Tuple[List, np.ndarray]], Dict[str, np.ndarray], List[Machine]]:
    """TimeSeriesSplit fold plan for every CV-requesting member of a gang.

    Returns ``(plan_by_name, fold_member_data, infeasible)`` where the plan
    maps name -> (splits, float32 member array — reused by the scoring
    pass so the full history matrix converts once): fold
    training slices become extra stacked members (the TPU-first answer to
    per-machine ``evaluation`` blocks — folds vmap along the member axis,
    so k-fold CV widens the gang program instead of multiplying builds;
    VERDICT r3 next #2). A machine whose folds are too short for this
    family (sequence warmup) is returned as infeasible and must take the
    single-build path, which raises the same errors a reference-style
    single build would.
    """
    from sklearn.model_selection import TimeSeriesSplit

    model_type = ae_kwargs.get("model_type", "AutoEncoder")
    t_offset = _target_offset_for(model_type)
    if t_offset is None:
        min_rows = 1
    else:
        lb = ae_kwargs.get("lookback_window")
        if lb is None:
            _, lb = _family_defaults(model_type)
        min_rows = int(lb) + t_offset  # shortest slice fit/score accepts

    plan_by_name: Dict[str, Tuple[List, np.ndarray]] = {}
    fold_data: Dict[str, np.ndarray] = {}
    infeasible: List[Machine] = []
    for machine in pending:
        ev = _normalize_evaluation(machine.evaluation or None)
        if not _wants_cv(ev):
            continue
        X = member_data[machine.name]
        Xv = np.asarray(X.values if hasattr(X, "values") else X, np.float32)
        n_splits = int(ev.get("n_splits", 3))
        try:
            splits = list(TimeSeriesSplit(n_splits=n_splits).split(Xv))
        except ValueError:
            splits = None
        if splits is None or any(
            len(tr) < min_rows or len(te) < min_rows for tr, te in splits
        ):
            infeasible.append(machine)
            continue
        plan_by_name[machine.name] = (splits, Xv)
        for fold, (tr, _te) in enumerate(splits):
            fold_data[_cv_key(machine.name, fold)] = Xv[tr]
    return plan_by_name, fold_data, infeasible


def _score_cv_folds(
    plan_by_name: Dict[str, Tuple[List, np.ndarray]],
    fleet_models: Dict[str, Any],
) -> Dict[str, Dict[str, Any]]:
    """The reference's full metric set per fold (explained variance, r2,
    MSE, MAE), scored with each fold member converted to the SAME
    detector pipeline the single-build CV scores — metadata keys
    identical to build_model._cross_validate."""
    from gordo_components_tpu.builder.build_model import summarize_cv_folds

    out: Dict[str, Dict[str, Any]] = {}
    for name, (splits, Xv) in plan_by_name.items():
        t0 = time.time()
        folds = []
        for fold, (_tr, te) in enumerate(splits):
            det = fleet_models[_cv_key(name, fold)].to_estimator()
            folds.append(det.score_metrics(Xv[te]))
        out[name] = {
            "cv_duration_sec": time.time() - t0,
            # fold training amortized inside the gang program; this wall
            # time covers only the scoring pass
            "fleet_cv": True,
            **summarize_cv_folds(folds),
        }
    return out


def build_fleet(
    machines: List[Machine],
    output_dir: str,
    model_register_dir: Optional[str] = None,
    replace_cache: bool = False,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    distributed: bool = False,
    state_dir: Optional[str] = None,
    gang_id: Optional[str] = None,
    group_retries: Optional[int] = None,
) -> "FleetBuildReport":
    """Build every machine; returns a :class:`FleetBuildReport` —
    name -> artifact dir (a plain dict to existing callers) with the
    partial-build record on ``.failed``.

    Fleetable machines with identical AutoEncoder kwargs train together in
    one FleetTrainer program; everything else falls back to the single-model
    builder. Cache semantics (config-hash keyed) apply to both paths.
    ``checkpoint_dir`` enables mid-training preemption recovery for the
    fleet groups (parallel/checkpoint.py): a restarted gang resumes its
    interrupted epoch loop instead of retraining from scratch.
    ``state_dir`` enables gang heartbeats (workflow/gang_state.py): phase
    and per-epoch progress on a shared volume for watchman to aggregate.

    Failure isolation: a bespoke machine whose single build fails, or an
    hparam group whose gang training fails ``group_retries + 1`` times
    (default 1 retry; env ``GORDO_BUILD_GROUP_RETRIES``), records its
    member(s) under ``.failed`` and every OTHER machine/group still
    ships — one poisoned config must not abort a 10k-member gang. The
    heartbeat ends in phase ``done`` (nothing failed), ``partial`` (some
    members failed), or ``failed`` (nothing built).
    """
    from gordo_components_tpu.resilience import configure_from_env

    configure_from_env()  # GORDO_FAULTS: chaos runs drive the build path too
    if group_retries is None:
        group_retries = int(os.environ.get("GORDO_BUILD_GROUP_RETRIES", "1"))
    # one build trace per build_fleet run (observability/tracing.py):
    # fleet groups record per-bucket fit/compile/checkpoint spans into it
    # and the Chrome trace-event export lands next to the build manifest.
    # force=True: a build is one trace, not head-sampled traffic —
    # GORDO_TRACE_SAMPLE=0 still disables tracing entirely
    tracer = get_tracer()
    trace = tracer.start_trace("fleet_build", force=True)
    results = FleetBuildReport()
    fleet_groups: Dict[Tuple, List[Tuple[Machine, Dict[str, Any]]]] = {}
    trainer_mesh = None
    dist_ok = False
    counters = _build_counters()  # once: the families are process-wide

    if distributed:
        # pod-scale gang: every host runs this same function; each owns a
        # deterministic member slice and trains it independently — zero DCN
        # traffic during training (parallel/distributed.py)
        from gordo_components_tpu.parallel.distributed import (
            initialize_distributed,
            partition_members,
        )

        dist_ok = initialize_distributed()
        if dist_ok:
            # members are partitioned per host, so each host's member stack
            # is host-local and differently shaped: the trainer mesh must
            # span only THIS host's devices. A global mesh (jax.devices()
            # spans the whole pod under jax.distributed) would device_put
            # host-local data onto a non-addressable sharding and trace
            # per-host-different programs — an SPMD violation. The global
            # runtime is kept only for the rendezvous/partition step.
            import jax

            from gordo_components_tpu.parallel.mesh import fleet_mesh

            trainer_mesh = fleet_mesh(devices=jax.local_devices())
        else:
            # misconfigured rendezvous silently degrading would make EVERY
            # worker own the full fleet: duplicated training + racing
            # artifact writes. Be loud; proceed only because a genuine
            # single-host launch with --distributed is legitimate.
            logger.warning(
                "--distributed requested but running single-process "
                "(no coordinator found / rendezvous not configured): this "
                "process will build ALL %d members. If other workers were "
                "launched the same way they are duplicating this work.",
                len(machines),
            )
        owned = set(partition_members([m.name for m in machines]))
        skipped = [m.name for m in machines if m.name not in owned]
        if skipped:
            logger.info(
                "Distributed gang: this host owns %d/%d members",
                len(owned), len(machines),
            )
        machines = [m for m in machines if m.name in owned]

    heartbeat = None
    if state_dir:
        from gordo_components_tpu.workflow.gang_state import GangHeartbeat

        # created AFTER member partitioning: n_machines reflects this
        # host's slice, and in a multi-host gang the template-pinned
        # GANG_ID is suffixed per host so peers don't clobber each other's
        # heartbeat (one host finishing must not mask the rest)
        if gang_id and dist_ok:
            import jax

            gang_id = f"{gang_id}-host{jax.process_index()}"
        heartbeat = _LockedHeartbeat(GangHeartbeat(state_dir, gang_id))
        heartbeat.update(
            phase="starting", n_machines=len(machines), built=0,
            distributed=bool(distributed),
        )

    try:
        for machine in machines:
            if "TrunkForecast" in json.dumps(machine.model, default=str):
                # its parameters split into a trunk many members share and
                # per-machine leaves: the gang trainer stacks EVERY leaf per
                # member and has no optimizer for the split. Building such
                # members one by one under a fleet build would only look
                # like one.
                raise ValueError(
                    f"Machine {machine.name}: a TrunkForecast member cannot be "
                    "fleet-built (shared trunk leaves are not implemented in "
                    "FleetTrainer); build it with `build` (build-model), one "
                    "machine at a time, against its trunk artifact"
                )
            ae_kwargs = extract_fleetable(machine.model)
            # the fleet engine trains X -> X (reconstruction); a dataset
            # declaring target tags supervises X -> y, so it must take the
            # single-build path (which honors y) rather than silently
            # training the wrong objective
            if ae_kwargs is not None and (machine.dataset or {}).get(
                "target_tag_list"
            ):
                ae_kwargs = None
            # cross_val_only's contract is an evaluation-only (untrained)
            # artifact — the single-build path owns that; fleet groups
            # handle the full_build+cross_validation case by vmapping folds
            if (
                ae_kwargs is not None
                and _normalize_evaluation(machine.evaluation or None)["cv_mode"]
                == "cross_val_only"
            ):
                ae_kwargs = None
            if ae_kwargs is None:
                logger.info(
                    "Machine %s: bespoke config, single-build path", machine.name
                )
                try:
                    results[machine.name] = provide_saved_model(
                        machine.name,
                        machine.model,
                        machine.dataset,
                        machine.metadata,
                        output_dir=os.path.join(output_dir, machine.name),
                        model_register_dir=model_register_dir,
                        replace_cache=replace_cache,
                        evaluation_config=machine.evaluation or None,
                    )
                except Exception as exc:
                    # per-machine isolation on the bespoke path: record and
                    # keep building the rest of the gang
                    results.failed[machine.name] = f"{type(exc).__name__}: {exc}"
                    logger.error(
                        "Machine %s: single build FAILED (%s); remaining "
                        "machines continue", machine.name, exc, exc_info=True,
                    )
                else:
                    counters["built"].labels("single").inc()
                if heartbeat is not None:
                    heartbeat.update(
                        phase="building", built=len(results),
                        failed_members=len(results.failed),
                    )
            else:
                fleet_groups.setdefault(_group_key(ae_kwargs), []).append(
                    (machine, ae_kwargs)
                )

        def train_group(group):
            # per-group isolation with bounded retry: a poisoned hparam
            # group (bad LR diverging the whole stack, an injected fault,
            # an OOM at this bucket's batch shape) exhausts its retries,
            # records its members as failed, and the remaining groups
            # still ship their artifacts
            for attempt in range(group_retries + 1):
                try:
                    # use_trace: the fleet trainer's bucket loop reads the
                    # current trace from the contextvar (parallel/fleet.py)
                    # instead of threading a parameter six layers down
                    with use_trace(trace):
                        _build_fleet_group(
                            group, output_dir, model_register_dir,
                            replace_cache, results,
                            checkpoint_dir=checkpoint_dir,
                            checkpoint_every=checkpoint_every,
                            mesh=trainer_mesh,
                            heartbeat=heartbeat, counters=counters,
                        )
                    return
                except Exception as exc:
                    if attempt < group_retries:
                        results.group_retries += 1
                        logger.warning(
                            "Fleet group of %d member(s) failed (attempt "
                            "%d/%d): %s; retrying",
                            len(group), attempt + 1, group_retries + 1, exc,
                        )
                        continue
                    error = f"{type(exc).__name__}: {exc}"
                    for m, _kw in group:
                        # members already shipped (cache hits, a pre-crash
                        # infeasible-CV single build) are built, not failed
                        if m.name not in results:
                            results.failed[m.name] = error
                    logger.error(
                        "Fleet group of %d member(s) FAILED after %d "
                        "attempt(s); members recorded in the partial "
                        "manifest; remaining groups continue: %s",
                        len(group), group_retries + 1, error, exc_info=True,
                    )

        # cross-arch gang scheduling: LARGE groups saturate the device on
        # their own and train one at a time, but a tail of SMALL
        # heterogeneous groups (different archs -> different compiled
        # programs, no shared vmap possible) would otherwise issue one
        # tiny dispatch each with the device idle during every group's
        # host-side work (tracing, XLA compile, stacking, unstacking).
        # GORDO_GANG_WIDTH worker threads drive those groups concurrently:
        # JAX dispatch is thread-safe, device work interleaves in the
        # queue, and group A's compile overlaps group B's compute. Results
        # are per-member (distinct keys per group), heartbeat writes are
        # serialized below, and the fleet program cache takes its own lock.
        gang_width = resolve_gang_width(len(fleet_groups))
        serial = [
            g for g in fleet_groups.values() if len(g) > GANG_SMALL_MAX
        ]
        small = [
            g for g in fleet_groups.values() if len(g) <= GANG_SMALL_MAX
        ]
        results.gang_width = gang_width
        for group in serial:
            train_group(group)
        if gang_width > 1 and len(small) > 1:
            import concurrent.futures as _futures

            with _futures.ThreadPoolExecutor(
                max_workers=gang_width, thread_name_prefix="gordo-gang"
            ) as pool:
                for f in [pool.submit(train_group, g) for g in small]:
                    f.result()  # train_group never raises; surface bugs
        else:
            for group in small:
                train_group(group)
    except BaseException as exc:
        # only non-build failures (preemption signals, a broken state
        # volume, bugs outside the isolated paths) land here now
        if heartbeat is not None:
            heartbeat.finish(
                "failed", built=len(results), error=f"{type(exc).__name__}: {exc}"
            )
        _finish_build_trace(trace, output_dir, error=True)
        raise
    _finish_build_trace(
        trace, output_dir,
        n_built=len(results), n_failed=len(results.failed),
    )
    if heartbeat is not None:
        if not results.failed:
            heartbeat.finish("done", built=len(results))
        elif results:
            heartbeat.finish(
                "partial", built=len(results),
                failed_members=len(results.failed),
                error=next(iter(results.failed.values())),
            )
        else:
            heartbeat.finish(
                "failed", built=0, failed_members=len(results.failed),
                error=next(iter(results.failed.values())),
            )
    return results


def _build_fleet_group(
    group: List[Tuple[Machine, Dict[str, Any]]],
    output_dir: str,
    model_register_dir: Optional[str],
    replace_cache: bool,
    results: FleetBuildReport,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    mesh=None,
    heartbeat=None,
    counters=None,
) -> None:
    _FP_GROUP.fire()
    ae_kwargs = copy.deepcopy(group[0][1])
    if counters is None:  # direct callers (tests) outside build_fleet
        counters = _build_counters()

    # cache check per machine first — reruns skip already-built members
    # (a CV-requesting machine only hits if the artifact records matching
    # per-fold scores, mirroring provide_saved_model)
    pending: List[Machine] = []
    pending_kwargs: Dict[str, Dict[str, Any]] = {}
    for machine, kw in group:
        key = calculate_model_key(machine.name, machine.model, machine.dataset, machine.metadata)
        if model_register_dir and not replace_cache:
            cached = os.path.join(model_register_dir, key)
            if (
                os.path.isdir(cached)
                and os.path.exists(os.path.join(cached, "model.pkl"))
                and _cache_satisfies_cv(cached, machine)
            ):
                logger.info("Machine %s: cache hit", machine.name)
                _mirror_artifact(cached, os.path.join(output_dir, machine.name))
                results[machine.name] = cached
                counters["cache_hits"].inc()
                continue
        pending.append(machine)
        pending_kwargs[machine.name] = kw
    if not pending:
        return

    # per-member vector knobs (LR/ES patience) for FleetTrainer.fit —
    # PENDING machines only: cache-hit members never reach the trainer,
    # and fit() rejects hparams for members it wasn't given
    member_hparams = {
        m.name: _member_hparams_of(pending_kwargs[m.name]) for m in pending
    }

    # host-side data loading (the IO hot loop, SURVEY.md §3.1). One process
    # feeds the whole gang here (SURVEY.md §7 hard part 2); stage_members
    # owns worker count and thread-vs-process engine selection
    # (utils/staging.py).
    if heartbeat is not None:
        heartbeat.update(phase="loading", group_members=len(pending))
    t0 = time.time()
    loaded = stage_members([dict(m.dataset) for m in pending])
    member_data: Dict[str, np.ndarray] = {}
    datasets_meta: Dict[str, Dict] = {}
    for machine, (X, meta) in zip(pending, loaded):
        member_data[machine.name] = X  # DataFrame: trainer keeps tag names
        datasets_meta[machine.name] = meta
    load_elapsed = time.time() - t0

    # CV fold plan (VERDICT r3 next #2): fold training slices join the gang
    # as extra stacked members — one wider vmap program instead of
    # n_splits extra builds per machine. Machines whose folds are
    # infeasible for this family fall back to the single-build path (their
    # staged data is dropped; the single path re-loads, a rare edge).
    cv_plan, fold_data, infeasible = _plan_cv_folds(
        pending, member_data, ae_kwargs
    )
    for machine in infeasible:
        logger.info(
            "Machine %s: CV folds infeasible for the gang, single-build path",
            machine.name,
        )
        pending = [m for m in pending if m.name != machine.name]
        member_data.pop(machine.name, None)
        datasets_meta.pop(machine.name, None)
        member_hparams.pop(machine.name, None)
        results[machine.name] = provide_saved_model(
            machine.name,
            machine.model,
            machine.dataset,
            machine.metadata,
            output_dir=os.path.join(output_dir, machine.name),
            model_register_dir=model_register_dir,
            replace_cache=replace_cache,
            evaluation_config=machine.evaluation or None,
        )
        counters["built"].labels("single").inc()
    if not pending:
        return

    trainer_kwargs = {
        k: ae_kwargs.pop(k) for k in _TRAINER_KEYS if k in ae_kwargs
    }
    epoch_cb = None
    if heartbeat is not None:

        def epoch_cb(info):
            heartbeat.update(
                phase="training",
                bucket=[int(info["n_features"]), int(info["padded_rows"])],
                epoch=int(info["epoch"]),
                n_active=int(info["n_active"]),
            )

    trainer = FleetTrainer(
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        mesh=mesh, epoch_callback=epoch_cb, **trainer_kwargs, **ae_kwargs,
    )
    t1 = time.time()
    from gordo_components_tpu.utils.profiling import device_memory_stats, maybe_profile

    # CV fold members train with their machine's own hyperparameters
    for name, (splits, _Xv) in cv_plan.items():
        for fold in range(len(splits)):
            member_hparams[_cv_key(name, fold)] = member_hparams[name]

    with maybe_profile(f"fleet-gang-{len(pending)}m"):
        fleet_models = trainer.fit(
            {**member_data, **fold_data}, member_hparams=member_hparams
        )
    train_elapsed = time.time() - t1
    trainer.last_stats["device_memory"] = device_memory_stats()
    results.buckets.extend(
        {
            "model_type": trainer.model_type,
            "kind": trainer.kind,
            "n_features": b["n_features"],
            "n_members": b["n_members"],
            "layout": b["layout"],
            "device": b["device"],
        }
        for b in trainer.last_stats["buckets"]
    )
    if fold_data:
        trainer.last_stats["cv_fold_members"] = len(fold_data)

    cv_meta_by_name = _score_cv_folds(cv_plan, fleet_models)

    by_name = {m.name: m for m in pending}
    for name, fm in fleet_models.items():
        if _CV_SEP in name:
            continue  # fold members exist only to produce CV scores
        machine = by_name[name]
        det = fm.to_estimator()
        key = calculate_model_key(machine.name, machine.model, machine.dataset, machine.metadata)
        metadata = {
            "name": name,
            "checked_at": metadata_timestamp(),
            "dataset": datasets_meta[name],
            "model": {
                "model_config": machine.model,
                "fleet_trained": True,
                "fleet_stats": trainer.last_stats,
                "data_query_duration_sec": load_elapsed / max(1, len(pending)),
                "model_training_duration_sec": train_elapsed / max(1, len(pending)),
                "history": fm.history,
                "model_builder_cache_key": key,
                "trained": True,
                # detector metadata (thresholds + their provenance —
                # "exact" vs the fleet's "histogram-8192" streaming
                # quantiles), same placement as the single-build path
                # (build_model.py)
                **det.get_metadata(),
            },
            "user-defined": machine.metadata,
        }
        if name in cv_meta_by_name:
            metadata["model"]["cross-validation"] = cv_meta_by_name[name]
        dest = (
            os.path.join(model_register_dir, key)
            if model_register_dir
            else os.path.join(output_dir, name)
        )
        serializer.dump(det, dest, metadata=metadata)
        mirror = os.path.join(output_dir, name)
        if os.path.abspath(mirror) != os.path.abspath(dest):
            serializer.dump(det, mirror, metadata=metadata)
        results[name] = dest
        counters["built"].labels("fleet").inc()
        logger.info("Machine %s: fleet-built -> %s", name, dest)
    if heartbeat is not None:
        heartbeat.update(phase="building", built=len(results))
