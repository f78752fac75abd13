"""Fleet vmap-width autotuning (``GORDO_FLEET_WIDTH``).

The one width sweep that ran on a chip (a pre-round record since deleted,
not a ledger number) stopped at 4096 members per dispatch with the rate
still rising: narrower gangs underfill the device, while wider ones inflate
the epoch program's working set (and the quantile histogram transient,
which scales with the vmap width — parallel/fleet.py
``run_error_scalers``). Default member widths are whatever the caller's
bucketing produced.

``GORDO_FLEET_WIDTH`` caps the member width of every training dispatch:

- unset / ``off`` — no cap (today's behavior);
- an integer — explicit cap, e.g. ``GORDO_FLEET_WIDTH=4096``;
- ``auto`` — a cheap calibration sweep picks the cap ONCE per
  (arch, device kind) and persists it, so the sweep never reruns on a
  machine that has already measured this architecture. The sweep times a
  proxy of the epoch's inner op (a member-batched matmul) at a ladder of
  widths and takes the SMALLEST width within 10% of peak per-member
  throughput, breaking flat ties toward 4096 (the widest width measured) —
  under-capping costs real throughput, over-capping only transient memory.

Persistence is a tiny JSON table keyed ``{arch}|{device_kind}`` at
``GORDO_FLEET_WIDTH_CACHE`` (default ``~/.cache/gordo/fleet_width.json``).
Corrupt or unwritable cache files degrade to an in-process table — the
sweep result still applies for the life of the process.
"""

import json
import logging
import os
import time
from typing import Callable, Optional

logger = logging.getLogger(__name__)

FLEET_WIDTH_ENV = "GORDO_FLEET_WIDTH"
FLEET_WIDTH_CACHE_ENV = "GORDO_FLEET_WIDTH_CACHE"

# candidate member widths for the calibration sweep; KNEE is the real-TPU
# measurement the flat-curve tiebreak defaults toward
SWEEP_WIDTHS = (512, 1024, 2048, 4096, 8192)
KNEE_DEFAULT = 4096
# sweep proxy shapes: one member-batched (B, H) x (H, H) matmul per width
_PROXY_B = 8
_PROXY_H = 64

# sweep results already resolved this process (also the degraded path
# when the cache file is unwritable)
_process_cache: dict = {}


def cache_path() -> str:
    p = os.environ.get(FLEET_WIDTH_CACHE_ENV)
    if p:
        return p
    return os.path.join(
        os.path.expanduser("~"), ".cache", "gordo", "fleet_width.json"
    )


def _load_table() -> dict:
    try:
        with open(cache_path()) as f:
            tab = json.load(f)
        return tab if isinstance(tab, dict) else {}
    except (OSError, ValueError):
        return {}


def _store(key: str, width: int, measured: dict) -> None:
    path = cache_path()
    tab = _load_table()
    tab[key] = {"width": int(width), "measured": measured}
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(tab, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        logger.warning(
            "Fleet width cache %s unwritable; autotuned width %d for %s "
            "applies in-process only", path, width, key,
        )


def _device_kind() -> str:
    import jax

    try:
        return str(jax.devices()[0].device_kind).replace(" ", "_")
    except Exception:
        return "unknown"


def calibrate_width(arch: str) -> "tuple[int, dict]":
    """Time the member-batched matmul proxy across SWEEP_WIDTHS and pick
    the smallest width within 10% of peak per-member throughput (flat
    ties break toward KNEE_DEFAULT). Cheap by construction — a handful
    of jit calls on tiny per-member shapes."""
    import jax
    import jax.numpy as jnp

    eff = {}

    @jax.jit
    def proxy(x, w):
        return jnp.einsum("mbh,mhg->mbg", x, w)

    for width in SWEEP_WIDTHS:
        x = jnp.ones((width, _PROXY_B, _PROXY_H), jnp.float32)
        w = jnp.ones((width, _PROXY_H, _PROXY_H), jnp.float32)
        jax.block_until_ready(proxy(x, w))  # compile outside the clock
        t0 = time.perf_counter()
        for _ in range(3):
            out = proxy(x, w)
        jax.block_until_ready(out)
        eff[width] = width / max(time.perf_counter() - t0, 1e-9)
    peak = max(eff.values())
    good = [w for w in SWEEP_WIDTHS if eff[w] >= 0.9 * peak]
    # smallest width at ~peak efficiency; a flat curve (everything within
    # band) is no evidence against the measured knee, so default there
    width = KNEE_DEFAULT if set(good) >= set(SWEEP_WIDTHS) else min(good)
    return width, {str(w): round(e, 1) for w, e in eff.items()}


def resolve_fleet_width(
    arch: str, sweep: Optional[Callable] = None
) -> Optional[int]:
    """The member-width cap for training dispatches, or None for no cap.

    ``arch`` keys the persisted sweep result (e.g. ``"LSTMAutoEncoder:
    lstm_symmetric"``); ``sweep`` overrides :func:`calibrate_width`
    (tests inject a deterministic one). Resolution order: env off →
    None; explicit int → that; ``auto`` → process cache → persisted
    table → run the sweep once and persist."""
    raw = (os.environ.get(FLEET_WIDTH_ENV) or "").strip().lower()
    if not raw or raw == "off":
        return None
    if raw != "auto":
        try:
            width = int(raw)
        except ValueError:
            raise ValueError(
                f"{FLEET_WIDTH_ENV} must be an integer, 'auto', or 'off'; "
                f"got {raw!r}"
            )
        if width < 1:
            raise ValueError(f"{FLEET_WIDTH_ENV} must be >= 1, got {width}")
        return width
    key = f"{arch}|{_device_kind()}"
    if key in _process_cache:
        return _process_cache[key]
    row = _load_table().get(key)
    if isinstance(row, dict) and int(row.get("width", 0)) >= 1:
        width = int(row["width"])
    else:
        width, measured = (sweep or calibrate_width)(arch)
        width = int(width)
        _store(key, width, measured)
        logger.info(
            "Autotuned fleet width for %s: %d (persisted to %s)",
            key, width, cache_path(),
        )
    _process_cache[key] = width
    return width
