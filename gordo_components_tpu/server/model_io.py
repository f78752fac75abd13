"""Model loading + model-boundary wire I/O for the server.

Reference parity: gordo_components/server/model_io.py (unverified; SURVEY.md
§2 "server") — the reference loads ONE artifact per server process (env
``MODEL_LOCATION``). The TPU-native server instead serves a *collection*:
a directory of per-machine artifact dirs loaded into one process so a whole
fleet shares a chip's HBM (BASELINE.json config 5); a single artifact dir
still works and behaves like the reference.

Also the binary scoring data plane's server half (PR 10): decode a
``application/x-gordo-tensor`` request body straight into the float32
arrays the bank scores (``np.frombuffer`` view, no DataFrame), and frame
score arrays for the response (utils/wire.py): as the list of buffers the
HTTP connection writes, or joined for a transport that needs one body.
"""

import io
import json
import logging
import os
import tarfile
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from gordo_components_tpu import serializer
from gordo_components_tpu.resilience.faults import faultpoint
from gordo_components_tpu.utils.wire import (
    ANOMALY_FRAME_NAMES,
    WireFormatError,
    pack_frames,
    rows_as_f32,
    unpack_frames,
)

logger = logging.getLogger(__name__)


def decode_tensor_request(
    raw: bytes,
) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Tensor request body -> ``(X, y)`` float32 arrays.

    The body must carry an ``X`` frame (rows x features); ``y`` is
    optional (supervised targets). Native little-endian float32 payloads
    come back as zero-copy read-only views of ``raw`` — the bank's
    coalescing stage copies rows into arena staging buffers anyway, so
    nothing downstream needs writability. Raises
    :class:`~gordo_components_tpu.utils.wire.WireFormatError` (-> 400
    with the reason) on malformed bodies.
    """
    X, y, _ = decode_tensor_request_ex(raw)
    return X, y


def decode_tensor_request_ex(
    raw: bytes,
) -> Tuple[np.ndarray, Optional[np.ndarray], Optional[Dict[str, Any]]]:
    """:func:`decode_tensor_request` plus the request's ``__meta__``
    sidecar (or None): the binary path's carrier for non-tensor request
    facts — today the QoS identity (``{"tenant", "priority"}``,
    qos/classify.py), which must survive transports that have no
    headers (the shm envelope) or proxies that strip custom ones. A
    malformed sidecar is ignored, not a 400: QoS tagging is best-effort
    metadata, never a reason to refuse a well-formed tensor body."""
    frames = unpack_frames(raw)
    if "X" not in frames:
        raise WireFormatError(
            f"tensor body must carry an 'X' frame (got {sorted(frames)})"
        )
    X = rows_as_f32(frames["X"], "X")
    y = rows_as_f32(frames["y"], "y") if "y" in frames else None
    if y is not None and len(y) != len(X):
        raise WireFormatError(
            f"y has {len(y)} rows but X has {len(X)}"
        )
    meta: Optional[Dict[str, Any]] = None
    if "__meta__" in frames:
        try:
            doc = json.loads(np.asarray(frames["__meta__"], np.uint8).tobytes())
            if isinstance(doc, dict):
                meta = doc
        except (ValueError, TypeError):
            pass
    return X, y, meta


def _meta_frame(meta: Dict[str, Any]) -> Tuple[str, np.ndarray]:
    """Small JSON sidecar riding as a u1 frame: offsets/tags — the few
    non-tensor facts a client needs to reassemble an indexed frame."""
    return "__meta__", np.frombuffer(json.dumps(meta).encode("utf-8"), np.uint8)


def prediction_frames(
    output: np.ndarray, n_input_rows: int
) -> List[Tuple[str, np.ndarray]]:
    """``POST /prediction`` tensor response: a ``data`` frame plus the
    sequence-warmup ``offset`` (output row i is input row i + offset) in
    ``__meta__`` — the client trims its own index by it, replacing the
    JSON body's stringified index round-trip."""
    output = np.asarray(output)
    return [
        _meta_frame({"offset": int(n_input_rows - len(output))}),
        ("data", output),
    ]


def anomaly_frames(
    tags, arrays: Dict[str, np.ndarray], offset: int
) -> List[Tuple[str, np.ndarray]]:
    """``POST /anomaly/prediction`` tensor response: ``__meta__`` and the
    six score arrays (``ScoreResult.to_arrays`` order) as they are — no
    DataFrame assembly, no per-column ``tolist``. Whatever else
    ``arrays`` holds (a shared-trunk member's selections) follows them."""
    meta = _meta_frame({"offset": int(offset), "tags": [str(t) for t in tags]})
    more = [name for name in arrays if name not in ANOMALY_FRAME_NAMES]
    return [meta] + [(name, arrays[name]) for name in (*ANOMALY_FRAME_NAMES, *more)]


def encode_prediction_response(output: np.ndarray, n_input_rows: int) -> bytes:
    """:func:`prediction_frames` as one body, for a transport that needs
    one piece of bytes (the shm ring's envelope); the HTTP view writes
    the same frames' segments to its connection."""
    return pack_frames(prediction_frames(output, n_input_rows))


def encode_anomaly_response(
    tags, arrays: Dict[str, np.ndarray], offset: int
) -> bytes:
    """:func:`anomaly_frames` as one body (see
    :func:`encode_prediction_response`)."""
    return pack_frames(anomaly_frames(tags, arrays, offset))


def anomaly_frame_arrays(frame) -> Dict[str, np.ndarray]:
    """The wire arrays from an assembled anomaly DataFrame — the
    per-model fallback path scores through ``model.anomaly`` (which
    builds the frame); the banked path never builds one
    (``ScoreResult.to_arrays``)."""
    return {
        "model-input": frame["model-input"].to_numpy(),
        "model-output": frame["model-output"].to_numpy(),
        "tag-anomaly-unscaled": frame["tag-anomaly-unscaled"].to_numpy(),
        "tag-anomaly-scaled": frame["tag-anomaly-scaled"].to_numpy(),
        "total-anomaly-unscaled": frame[("total-anomaly-unscaled", "")].to_numpy(),
        "total-anomaly-scaled": frame[("total-anomaly-scaled", "")].to_numpy(),
    }

# chaos site: artifact deserialization (tests/test_chaos.py drives it);
# firing inside _load_one lands the failure in refresh()'s per-entry
# isolation, exactly where a truly corrupt artifact would surface
_FP_LOAD = faultpoint("model_io.load")


def pack_artifact_dir(path: str) -> bytes:
    """One member's artifact dir as a gzipped tar (the cross-replica
    shipping format for mesh migrations). Paths inside the archive are
    relative to the dir, so the receiver lands them under its own root
    regardless of the sender's layout."""
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w:gz") as tar:
        for entry in sorted(os.listdir(path)):
            tar.add(os.path.join(path, entry), arcname=entry)
    return buf.getvalue()


def unpack_artifact_dir(raw: bytes, dest: str) -> None:
    """Extract a shipped artifact archive under ``dest``, validating
    every member name first — the archive crosses a network boundary, so
    absolute paths, ``..`` traversal, links, and devices are rejected
    outright (a hostile or corrupted archive must not write outside the
    member's own dir)."""
    os.makedirs(dest, exist_ok=True)
    with tarfile.open(fileobj=io.BytesIO(raw), mode="r:gz") as tar:
        for member in tar.getmembers():
            name = member.name
            if (
                os.path.isabs(name)
                or ".." in name.split("/")
                or not (member.isfile() or member.isdir())
            ):
                raise ValueError(
                    f"refusing artifact archive member {name!r} "
                    "(unsafe path or non-file entry)"
                )
        for member in tar.getmembers():
            tar.extract(member, dest, set_attrs=False)


def scan_artifacts(root: str, target_name: Optional[str] = None) -> Dict[str, str]:
    """name -> artifact dir for the on-disk state under ``root`` (a
    single artifact dir, or a dir of artifact subdirs). Module-level so
    the mesh bootstrap can compute the FULL fleet roster — every replica
    must partition the same global member list — before the collection
    filters down to this replica's slice."""
    if os.path.exists(os.path.join(root, "model.pkl")):
        name = target_name or os.path.basename(os.path.normpath(root))
        return {name: root}
    out = {}
    try:
        entries = sorted(os.listdir(root))
    except FileNotFoundError:
        return {}
    for entry in entries:
        path = os.path.join(root, entry)
        if os.path.isdir(path) and os.path.exists(os.path.join(path, "model.pkl")):
            out[entry] = path
    return out


class ModelCollection:
    """name -> (model, metadata) for every artifact under ``root``.

    ``root`` may be a single artifact dir (containing ``model.pkl``) —
    loaded under the name ``target_name or basename(root)`` — or a dir of
    artifact subdirs, each loaded under its subdir name.

    :meth:`refresh` rescans the root and incrementally loads new or
    changed artifacts (by ``model.pkl`` mtime) and drops removed ones, so
    a running server can pick up freshly built fleet artifacts without a
    restart (the reference redeployed a pod per model instead).

    ``owned`` (multi-host serving mesh): an explicit member-ownership
    set — the collection loads and serves ONLY these names even when the
    artifact dir holds the whole fleet (a shared volume is the common
    deploy). ``None`` (the default) means unpartitioned: own everything
    on disk, exactly the old behavior. An owned-but-empty partition is
    legal (a small fleet over many replicas, or a source replica that
    migrated everything away) and does NOT raise at startup the way an
    empty unpartitioned dir does — the mesh routing plane, not this
    process, decides whether zero members here is a problem.
    """

    def __init__(
        self,
        root: str,
        target_name: Optional[str] = None,
        owned=None,
    ):
        self.root = root
        self.target_name = target_name
        self.owned = None if owned is None else set(owned)
        # (models, metadata) published together as ONE tuple: refresh()
        # builds fresh dicts off to the side and swaps them in with a
        # single (GIL-atomic) assignment, so readers on other threads
        # never see a half-mutated collection — published dicts are never
        # mutated afterwards. Read both sides through snapshot() when
        # cross-dict consistency matters.
        self._state: tuple = ({}, {})
        self._mtimes: Dict[str, float] = {}
        # operator-visible corrupt-artifact accounting: the healthy-subset
        # fallback below must not be invisible. ``load_failures`` is the
        # CURRENT failed set (latest scan); ``load_failed_total`` counts
        # every failed load attempt monotonically (each retrying refresh
        # increments it again — that is what a Prometheus counter wants,
        # rate() > 0 means "still failing")
        self.load_failures: Dict[str, str] = {}
        self.load_failed_total: int = 0
        changes = self.refresh()
        if not self.models and self.owned is None:
            detail = (
                f"; all artifact loads failed: {changes['failed']}"
                if changes["failed"]
                else ""
            )
            raise FileNotFoundError(
                f"No model artifacts found under {root!r}{detail}"
            )
        if changes["failed"]:
            # serve the healthy subset (one corrupt artifact must not
            # crashloop serving for the whole fleet) — but loudly: a
            # partial startup is an operator problem, not business as usual
            logger.error(
                "Startup loaded %d models but %d artifacts FAILED: %s",
                len(self.models), len(changes["failed"]), changes["failed"],
            )

    @property
    def models(self) -> Dict[str, Any]:
        return self._state[0]

    @property
    def metadata(self) -> Dict[str, Dict]:
        return self._state[1]

    def snapshot(self) -> tuple:
        """One consistent (models, metadata) pair."""
        return self._state

    def entry(self, name: str):
        """(model, metadata) read from ONE state snapshot — the two-dict
        lookup a concurrent refresh could otherwise straddle."""
        models, metadata = self._state
        return models[name], metadata.get(name, {})

    def _scan(self) -> Dict[str, str]:
        """name -> artifact dir for the current on-disk state, filtered
        to this collection's ownership set when one is active (the mesh
        partition: the shared volume holds everyone's artifacts, this
        replica loads only its own)."""
        on_disk = scan_artifacts(self.root, self.target_name)
        if self.owned is None:
            return on_disk
        return {n: p for n, p in on_disk.items() if n in self.owned}

    # ------------------------------------------------------------------ #
    # mesh ownership (multi-host serving): acquire/release move a member
    # between replicas; the bank rebuild + zero-downtime swap happens in
    # the caller (server/views.py mesh endpoints, under the reload lock)
    # ------------------------------------------------------------------ #

    def acquire(self, name: str) -> Dict[str, Any]:
        """Take ownership of ``name`` (its artifact must already be under
        ``root`` — the mesh acquire endpoint ships it first) and load it.
        Idempotent; on an unpartitioned collection ownership is implicit
        and this is just a refresh. Raises ``FileNotFoundError`` when the
        artifact is not on disk — taking ownership of nothing would
        blackhole the member's traffic behind a routing entry."""
        if self.owned is not None:
            self.owned.add(name)
        changes = self.refresh()
        if name not in self.models:
            if self.owned is not None:
                self.owned.discard(name)
            reason = changes["failed"].get(name, "artifact not found on disk")
            raise FileNotFoundError(
                f"cannot acquire {name!r} under {self.root!r}: {reason}"
            )
        return changes

    def release(self, name: str) -> Dict[str, Any]:
        """Drop ownership of ``name`` (the migration source's half of a
        cross-replica move): the member stops loading/serving here; its
        artifact stays on disk (cheap, and a failed migration can
        re-acquire without re-shipping). On an unpartitioned collection
        the current roster is materialized as the ownership set first —
        release must work on a replica that booted owning everything."""
        if name not in self.models:
            raise KeyError(f"cannot release unknown member {name!r}")
        if self.owned is None:
            self.owned = set(self.models)
        self.owned.discard(name)
        return self.refresh()

    def refresh(self) -> Dict[str, Any]:
        """Incremental rescan. Returns {"added": [...], "updated": [...],
        "removed": [...], "failed": {name: error}} by model name. Changes
        are staged on copies and published atomically (see ``_state``).

        Per-entry load isolation: a corrupt or mid-write artifact (a
        builder racing the reload is normal in a live fleet) must not
        block reloading everything else — the failing name is skipped
        (its previously loaded version, if any, keeps serving), reported
        under ``failed``, and its mtime stays unrecorded so the next
        refresh retries it."""
        on_disk = self._scan()
        models, metadata = dict(self.models), dict(self.metadata)
        # mtimes stage on a copy too: recording them eagerly would let a
        # load failure mark an ALREADY-RELOADED name as current while its
        # new model was discarded — serving the stale model forever after
        mtimes = dict(self._mtimes)
        added, updated, removed = [], [], []
        failed: Dict[str, str] = {}
        for name in list(models):
            if name not in on_disk:
                removed.append(name)
                del models[name]
                metadata.pop(name, None)
                mtimes.pop(name, None)
        for name, path in on_disk.items():
            try:
                mtime = os.path.getmtime(os.path.join(path, "model.pkl"))
            except OSError as exc:
                # deleted between _scan() and here (builder rewriting):
                # report it — a name silently in no bucket would hide a
                # stale-serving model from callers watching ``failed``
                failed[name] = f"{type(exc).__name__}: {exc}"
                continue
            is_new = name not in models
            if not is_new and mtime == mtimes.get(name):
                continue
            try:
                self._load_one(models, metadata, name, path)
            except Exception as exc:
                logger.warning("Failed to load %r from %s: %s", name, path, exc)
                failed[name] = f"{type(exc).__name__}: {exc}"
                continue
            mtimes[name] = mtime
            (added if is_new else updated).append(name)
        self._state = (models, metadata)  # atomic publish
        self._mtimes = mtimes
        self.load_failures = dict(failed)
        self.load_failed_total += len(failed)
        if added or updated or removed or failed:
            logger.info(
                "Collection refresh: +%d ~%d -%d !%d (now %d models)",
                len(added), len(updated), len(removed), len(failed), len(models),
            )
        return {
            "added": added, "updated": updated, "removed": removed,
            "failed": failed,
        }

    def publish(
        self, updates: Dict[str, Any], note: Optional[Dict[str, Any]] = None
    ) -> None:
        """Atomically publish in-memory model replacements (the streaming
        adaptation plane's recalibration/refit path — no artifact write).

        Only names already in the collection may be replaced: new members
        arrive via artifacts + :meth:`refresh`. The replacement persists
        across refreshes until the on-disk artifact's mtime changes (a
        rebuilt artifact is newer truth and wins). ``note`` (optional) is
        merged into each replaced member's metadata under
        ``online-adaptation`` so ``/metadata`` shows that — and when —
        the serving calibration diverged from the artifact."""
        unknown = [n for n in updates if n not in self.models]
        if unknown:
            raise KeyError(f"cannot publish unknown members: {sorted(unknown)}")
        models, metadata = dict(self.models), dict(self.metadata)
        for name, model in updates.items():
            models[name] = model
            if note is not None:
                meta = dict(metadata.get(name, {}))
                meta["online-adaptation"] = {
                    **note,
                    "total-anomaly-threshold": getattr(
                        model, "total_threshold_", None
                    ),
                    "threshold-method": getattr(model, "threshold_method_", None),
                }
                metadata[name] = meta
        self._state = (models, metadata)  # atomic publish

    def restore(self, state: tuple) -> None:
        """Roll back to a snapshot taken before :meth:`publish` (the
        adaptation plane's failed-swap path). The tuple is published
        as-is — snapshots are immutable by the ``_state`` contract."""
        self._state = state

    @staticmethod
    def _load_one(models: Dict, metadata: Dict, name: str, path: str) -> None:
        logger.info("Loading model %r from %s", name, path)
        _FP_LOAD.fire()
        # assign only after BOTH loads succeed: a metadata failure must
        # not leave a model without its metadata in the staged dicts
        model = serializer.load(path)
        meta = serializer.load_metadata(path)
        # serve the artifact's recorded name if present
        meta.setdefault("name", name)
        models[name] = model
        metadata[name] = meta

    def __contains__(self, name: str) -> bool:
        return name in self.models

    def __getitem__(self, name: str):
        return self.models[name]

    def names(self):
        return sorted(self.models)
