"""Cross-cutting utilities.

Reference parity: ``gordo_components``'s ``capture_args`` decorator
(gordo_components/dataset/data_provider/base.py, unverified — see
SURVEY.md §2 "util"), which records constructor kwargs so that objects can
be round-tripped through metadata / config definitions.
"""

from gordo_components_tpu.utils.capture import capture_args
from gordo_components_tpu.utils.encoding import parquet_engine_available
from gordo_components_tpu.utils.metadata import metadata_timestamp, package_version
from gordo_components_tpu.utils.profiling import (
    device_memory_stats,
    enable_compile_cache,
    maybe_profile,
    resolve_compile_cache,
)

__all__ = [
    "capture_args",
    "env_num",
    "metadata_timestamp",
    "package_version",
    "device_memory_stats",
    "maybe_profile",
]


def env_num(name: str, default, cast):
    """Numeric env knob with an actionable error: these deploy to every
    replica, and a bare ``int()``/``float()`` traceback would crashloop
    the fleet with no hint which knob is malformed. Empty/unset keeps
    the default. (Several older modules carry a private copy of this
    predating the shared helper; new code should use this one.)"""
    import os

    raw = os.environ.get(name)
    if not raw:
        return default
    try:
        return cast(raw)
    except ValueError:
        raise ValueError(f"{name} must be a number, got {raw!r}") from None
