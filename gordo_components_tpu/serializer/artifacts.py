"""Artifact persistence: model directory trees.

Reference parity: serializer ``dump``/``load``/``load_metadata``
(gordo_components/serializer/, unverified; SURVEY.md §2) — the reference
persists a pipeline as a directory of pickled steps + Keras HDF5 +
``metadata.json``. Here the artifact directory is:

- ``model.pkl``      — the full (sklearn-compatible) object; our estimators
                       carry numpy param pytrees so plain pickle is exact
- ``params.npz``     — flax params flattened to ``a/b/c`` keys, saved
                       language-neutrally for non-Python consumers
- ``metadata.json``  — the build-metadata contract

A *trunk* artifact is a directory of its own holding ``trunk.pkl`` (no
``model.pkl``, so no server lists it as a model): the parameters that
many members share (``models/factories/trunk.py``). Members name it;
``load_trunk`` reads it once per process however many members do.

The unit of persistence is the *finished model artifact* exactly as in the
reference (SURVEY.md §5 "Checkpoint/resume"); mid-training checkpointing of
fleet state lives in parallel/ (orbax), not here.
"""

import json
import os
import pickle
from typing import Any, Dict, Optional, Tuple

import numpy as np

_MODEL_FILE = "model.pkl"
_PARAMS_FILE = "params.npz"
_METADATA_FILE = "metadata.json"


def _flatten_params(tree: Any, prefix: str = "") -> Dict[str, np.ndarray]:
    out: Dict[str, np.ndarray] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten_params(v, f"{prefix}{k}/"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_flatten_params(v, f"{prefix}{i}/"))
    else:
        out[prefix.rstrip("/")] = np.asarray(tree)
    return out


def dump(obj: Any, dest_dir: str, metadata: Optional[Dict] = None) -> None:
    """Persist a model (pipeline/estimator/detector) into ``dest_dir``."""
    os.makedirs(dest_dir, exist_ok=True)
    with open(os.path.join(dest_dir, _MODEL_FILE), "wb") as f:
        pickle.dump(obj, f)

    params = _extract_params(obj)
    if params:
        np.savez(os.path.join(dest_dir, _PARAMS_FILE), **params)

    if metadata is not None:
        with open(os.path.join(dest_dir, _METADATA_FILE), "w") as f:
            json.dump(metadata, f, default=str, indent=2)


def _extract_params(obj: Any) -> Dict[str, np.ndarray]:
    """Find flax param pytrees on the object (estimator, pipeline step, or
    anomaly wrapper) for the language-neutral npz."""
    if getattr(obj, "params_", None) is not None:
        return _flatten_params(obj.params_)
    if hasattr(obj, "base_estimator"):
        return _extract_params(obj.base_estimator)
    if hasattr(obj, "steps"):
        return _extract_params(obj.steps[-1][1])
    return {}


def dumps(obj: Any) -> bytes:
    return pickle.dumps(obj)


def loads(data: bytes) -> Any:
    return pickle.loads(data)


def _final_estimator(obj: Any) -> Any:
    if hasattr(obj, "base_estimator"):
        return _final_estimator(obj.base_estimator)
    if hasattr(obj, "steps"):
        return _final_estimator(obj.steps[-1][1])
    return obj


def load(source_dir: str) -> Any:
    with open(os.path.join(source_dir, _MODEL_FILE), "rb") as f:
        obj = pickle.load(f)
    # a member that names a trunk by a relative path means: beside me
    bind = getattr(_final_estimator(obj), "bind_artifact_root", None)
    if bind is not None:
        bind(os.path.dirname(os.path.abspath(source_dir)))
    return obj


_TRUNK_FILE = "trunk.pkl"
_TRUNKS: Dict[str, Tuple[float, Any]] = {}  # real path -> (mtime, params)


def dump_trunk(params: Any, dest_dir: str) -> None:
    """Write a trunk artifact; the rename makes it appear whole."""
    os.makedirs(dest_dir, exist_ok=True)
    path = os.path.join(dest_dir, _TRUNK_FILE)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        pickle.dump(params, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)


def load_trunk(source_dir: str) -> Any:
    """The trunk's parameter tree, read once per process and artifact
    version: every member of a bank gets the SAME object, which is how
    the bank knows to place it once."""
    path = os.path.realpath(os.path.join(source_dir, _TRUNK_FILE))
    mtime = os.path.getmtime(path)
    cached = _TRUNKS.get(path)
    if cached is None or cached[0] != mtime:
        with open(path, "rb") as f:
            cached = _TRUNKS[path] = (mtime, pickle.load(f))
    return cached[1]


def load_metadata(source_dir: str) -> Dict:
    path = os.path.join(source_dir, _METADATA_FILE)
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return json.load(f)


def release_trunks() -> None:
    """Forget every trunk read so far (a server that dropped its bank and
    wants the memory back; the next ``load_trunk`` reads the file again)."""
    _TRUNKS.clear()
