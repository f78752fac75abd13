"""On-demand profiling and device observability.

The reference's only timing artifact is the per-epoch Keras history
captured into build metadata (SURVEY.md §5 "Tracing / profiling"). The
TPU-native rebuild keeps that metadata-as-contract design and adds what a
compiled-accelerator stack actually needs:

- :func:`maybe_profile` — a ``jax.profiler`` trace (viewable in
  TensorBoard / Perfetto) around any block, activated by passing a
  directory or exporting ``GORDO_PROFILE_DIR``; zero overhead when off.
- :func:`device_memory_stats` — per-device HBM usage snapshot, recorded
  into build metadata so fleet sizing (models per chip) is observable from
  the artifact, not just from a live process.
"""

import contextlib
import logging
import os
import re
from typing import Any, Dict, Optional

logger = logging.getLogger(__name__)


@contextlib.contextmanager
def maybe_profile(name: str, profile_dir: Optional[str] = None):
    """Trace the enclosed block when profiling is enabled.

    ``profile_dir`` falls back to env ``GORDO_PROFILE_DIR``; when neither
    is set the context is free. Traces land under
    ``<profile_dir>/<name>/`` (name is sanitized for the filesystem).
    """
    profile_dir = profile_dir or os.environ.get("GORDO_PROFILE_DIR")
    if not profile_dir:
        yield
        return
    import jax

    safe = re.sub(r"[^A-Za-z0-9_.-]+", "-", name) or "trace"
    out = os.path.join(profile_dir, safe)
    os.makedirs(out, exist_ok=True)
    logger.info("Profiling %r -> %s", name, out)
    with jax.profiler.trace(out):
        yield


def device_memory_stats() -> Dict[str, Any]:
    """Per-device memory snapshot: ``{device: {bytes_in_use, bytes_limit,
    peak_bytes_in_use}}`` for devices that report stats (TPU does; CPU
    returns an empty dict)."""
    import jax

    out: Dict[str, Any] = {}
    try:
        devices = jax.devices()
    except RuntimeError:
        return out
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            continue
        if not stats:
            continue
        out[str(d)] = {
            k: int(stats[k])
            for k in ("bytes_in_use", "bytes_limit", "peak_bytes_in_use")
            if k in stats
        }
    return out


def enable_compile_cache(cache_dir: str, min_compile_seconds: float = 1.0) -> str:
    """Enable JAX's persistent (on-disk) XLA compilation cache.

    The fleet engine already collapses gang shapes onto quantized ladders
    (parallel/fleet.py), but each PROCESS still compiles every shape once
    — and builder pods are routinely preempted and restarted (the
    checkpoint-resume path), while rolling server deploys re-warm every
    bucket. Pointing this at a shared volume makes those recompiles disk
    reads. Programs cheaper than ``min_compile_seconds`` stay uncached —
    writing them costs more than recompiling. Returns the directory
    (created if absent).
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_seconds)
    )
    # jax memoizes "is the cache used" at the FIRST compile of the
    # process: any jit before this call would freeze the verdict at "no"
    # and ignore the config above for the process lifetime. Reset the memo
    # so enabling the cache mid-process (a /reload-created bank, the
    # rebalance swap's rebuild, tests) takes effect.
    compilation_cache.reset_cache()
    logger.info("persistent XLA compilation cache at %s", cache_dir)
    return cache_dir


JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
COMPILE_CACHE_ENV = "GORDO_COMPILE_CACHE_DIR"
# the path is part of the cache's key, so the default never moves: no
# tempfile, pid or timestamp — <checkout>/.jax_cache (git-ignored)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def resolve_compile_cache(knob: Optional[str] = None) -> str:
    """Place the persistent compilation cache; every entry point (CLI
    group, ``build_app``, ``chip_smoke.py``) calls this once.

    1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX already uses it. Nothing is
       configured here and ``knob`` / ``GORDO_COMPILE_CACHE_DIR`` are
       ignored — where the machine names a cache, what the program
       caches there is found again by its next run.
    2. else ``knob`` (``--compile-cache-dir``) or
       ``GORDO_COMPILE_CACHE_DIR``.
    3. else the fixed :data:`DEFAULT_COMPILE_CACHE_DIR`.

    Returns the directory in use."""
    knob = knob or os.environ.get(COMPILE_CACHE_ENV)
    jax_dir = os.environ.get(JAX_CACHE_ENV)
    if jax_dir:
        if knob and knob != jax_dir:
            logger.info(
                "%s=%s is set: ignoring the compile-cache knob (%s)",
                JAX_CACHE_ENV, jax_dir, knob,
            )
        return jax_dir
    return enable_compile_cache(knob or DEFAULT_COMPILE_CACHE_DIR)
