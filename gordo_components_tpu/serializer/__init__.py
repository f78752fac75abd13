"""Serializer: bidirectional config ⇄ object, and artifact dump/load.

Reference parity: gordo_components/serializer/ (unverified; SURVEY.md §2
"serializer") — the pipeline-definition language (dotted import paths with
nested kwargs) is user-facing API in the reference and preserved here
verbatim, including reference-era ``gordo_components.*`` paths, which are
transparently aliased onto this package so existing fleet configs load
unchanged.
"""

from gordo_components_tpu.serializer.definitions import (
    from_definition,
    into_definition,
    pipeline_from_definition,
    pipeline_into_definition,
)
from gordo_components_tpu.serializer.artifacts import (
    dump,
    dump_trunk,
    dumps,
    load,
    loads,
    load_metadata,
    load_trunk,
    release_trunks,
)

__all__ = [
    "from_definition",
    "into_definition",
    "pipeline_from_definition",
    "pipeline_into_definition",
    "dump",
    "dumps",
    "load",
    "loads",
    "load_metadata",
    "dump_trunk",
    "load_trunk",
    "release_trunks",
]
