"""Wire-encoding capability probes shared by the server and bulk client.

The scoring POST bodies can ride parquet instead of JSON float lists
(SURVEY.md §2 "server"/"client": the reference supported both and its bulk
client used parquet because JSON encode/decode dominates at backfill
scale). pandas needs a parquet engine for that; this probe is how the
server decides what to advertise and the client decides what to send.
"""

import functools
from typing import Optional


@functools.cache
def parquet_engine() -> Optional[str]:
    """The pandas parquet engine name ("pyarrow"/"fastparquet") or None.

    Resolved ONCE and passed explicitly to every per-chunk
    ``to_parquet``/``read_parquet`` call, skipping pandas' per-call
    ``engine="auto"`` resolution (measured as a first-chunks cold-start
    cost on a cold process, noise once warm). Why parquet request
    bodies never beat JSON is another matter, the RESPONSE side staying
    JSON in both modes: docs/architecture.md "Wire protocol"."""
    try:
        import pyarrow  # noqa: F401

        return "pyarrow"
    except ImportError:
        try:
            import fastparquet  # noqa: F401

            return "fastparquet"
        except ImportError:
            return None


def parquet_engine_available() -> bool:
    """True iff pandas can (de)serialize parquet here (pyarrow or
    fastparquet importable)."""
    return parquet_engine() is not None
