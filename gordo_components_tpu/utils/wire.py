"""Framed binary tensor wire format (``application/x-gordo-tensor``).

The scoring data plane's zero-copy encoding: between the bank's scoring
and what a client sees over the wire lies pandas/JSON (de)serialization
(and parquet's per-file metadata costs more than JSON at bulk-chunk
shapes; see docs/architecture.md "Wire protocol"). A float row is already
bytes; this module just frames those bytes so both ends can exchange
ndarrays with one header parse and zero value-level churn:

- server parse is ``np.frombuffer`` over the request body (a view, no copy,
  no per-value float boxing);
- an HTTP response is never assembled: the connection writes the frame
  headers and then each score array from its own memory
  (:func:`frame_segments`; no joined body, no DataFrame, no ``tolist``,
  no float64 shadow copies);
- whoever needs one piece of bytes (the client's request body, the shm
  ring's envelope) joins the same segments once (:func:`pack_frames`).

Body layout (all integers little-endian)::

    MAGIC(4)=b"GTNS" | VERSION(u8)=1 | NFRAMES(u8) | frame*NFRAMES

    frame := NAMELEN(u8) | NAME(utf-8)
           | DTYPELEN(u8) | DTYPE(ascii, numpy str e.g. "<f4")
           | NDIM(u8) | DIM(u64-le) * NDIM
           | NBYTES(u64-le) | PAYLOAD(C-order bytes)

``NBYTES`` is redundant with ``prod(shape) * itemsize`` by construction and
is VERIFIED on parse — the cheap integrity check that turns a truncated or
padded body into a named 400 instead of a silently wrong score. Multi-frame
bodies carry a request's ``X``/``y`` (or a response's anomaly arrays plus a
``__meta__`` JSON frame) in one POST.

Versioning policy (docs/architecture.md): the magic+version pair is the
negotiation unit. Parsers MUST reject an unknown version (no best-effort
decoding of future layouts); any layout change bumps ``WIRE_VERSION`` and a
new server keeps accepting every version it ever shipped. Fields are only
ever APPENDED to the frame header within a version — never reordered.
"""

import struct
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

__all__ = [
    "ANOMALY_FRAME_NAMES",
    "TENSOR_CONTENT_TYPE",
    "WIRE_MAGIC",
    "WIRE_VERSION",
    "WireFormatError",
    "encoding_of",
    "frame_segments",
    "pack_frames",
    "unpack_frames",
    "rows_as_f32",
]

TENSOR_CONTENT_TYPE = "application/x-gordo-tensor"
WIRE_MAGIC = b"GTNS"
WIRE_VERSION = 1

# anomaly-response frame names, in wire order — part of the format
# contract (both ends must agree): the same top-level column names the
# JSON body's ``data`` dict uses, so a client reconstructs an identical
# frame from either encoding
ANOMALY_FRAME_NAMES = (
    "model-input",
    "model-output",
    "tag-anomaly-unscaled",
    "tag-anomaly-scaled",
    "total-anomaly-unscaled",
    "total-anomaly-scaled",
)


def encoding_of(content_type: Optional[str]) -> str:
    """Classify a request body's wire encoding from its content type —
    THE opt-in rule, defined once so the HTTP handlers and the
    per-encoding metrics can never drift: ``tensor`` | ``parquet`` |
    ``json`` (the default; a JSON request must flow byte-identical
    through the pre-tensor code)."""
    content_type = content_type or ""
    if TENSOR_CONTENT_TYPE in content_type:
        return "tensor"
    if "parquet" in content_type:
        return "parquet"
    return "json"

# parse-side resource bounds: a hostile header must not make the server
# allocate absurd shape tuples or loop forever (payload size itself is
# already bounded by aiohttp's client_max_size before parse runs)
_MAX_FRAMES = 64
_MAX_NDIM = 8

# fixed-width numeric kinds only: float/int/uint/bool. Anything else
# ("O" object, "U"/"S" strings, "V" void) either cannot be viewed with
# frombuffer or would let a request body smuggle non-numeric payloads
# into the scoring path.
_ALLOWED_KINDS = frozenset("fiub")
_MAX_ITEMSIZE = 8

_U64 = struct.Struct("<Q")

# a payload under this many bytes is copied into the header bytes beside
# it: a write of its own (a ``send`` call, a buffer on the transport's
# queue) costs more than copying it
INLINE_PAYLOAD_BYTES = 64 * 1024


class WireFormatError(ValueError):
    """A tensor body that violates the frame layout. The HTTP layer maps
    this to a 400 whose body carries the reason verbatim."""


def _check_dtype(dtype_str: str) -> np.dtype:
    try:
        dtype = np.dtype(dtype_str)
    except TypeError as exc:
        raise WireFormatError(f"undecodable dtype {dtype_str!r}: {exc}") from None
    if dtype.kind not in _ALLOWED_KINDS or dtype.itemsize > _MAX_ITEMSIZE:
        raise WireFormatError(
            f"dtype {dtype_str!r} not allowed on the wire "
            f"(numeric kinds {sorted(_ALLOWED_KINDS)}, itemsize <= {_MAX_ITEMSIZE})"
        )
    return dtype


def frame_segments(
    frames: Sequence[Tuple[str, np.ndarray]]
) -> List[Union[bytes, memoryview]]:
    """The buffers of a tensor body, in wire order: what a connection
    writes one after another, and what :func:`pack_frames` joins.

    A payload of :data:`INLINE_PAYLOAD_BYTES` or more is NOT copied: its
    segment is a flat ``memoryview`` of the array's own memory (of its
    C-order copy where the input was not contiguous), which keeps the
    array alive for as long as the segment is held. Everything between
    two such payloads (the body's head, frame headers, and the payloads
    too small to be worth a write of their own) is one ``bytes``.
    """
    if not frames:
        raise WireFormatError("a tensor body must carry at least one frame")
    if len(frames) > _MAX_FRAMES:
        raise WireFormatError(
            f"{len(frames)} frames exceeds the {_MAX_FRAMES}-frame bound"
        )
    segments: List[Union[bytes, memoryview]] = []
    head = bytearray(WIRE_MAGIC)
    head += bytes((WIRE_VERSION, len(frames)))
    for name, arr in frames:
        arr = np.ascontiguousarray(arr)
        _check_dtype(arr.dtype.str)
        name_b = name.encode("utf-8")
        dtype_b = arr.dtype.str.encode("ascii")
        if not 0 < len(name_b) < 256:
            raise WireFormatError(f"frame name {name!r} must be 1..255 bytes")
        if arr.ndim > _MAX_NDIM:
            raise WireFormatError(
                f"frame {name!r} has {arr.ndim} dims (bound {_MAX_NDIM})"
            )
        head.append(len(name_b))
        head += name_b
        head.append(len(dtype_b))
        head += dtype_b
        head.append(arr.ndim)
        for dim in arr.shape:
            head += _U64.pack(dim)
        head += _U64.pack(arr.nbytes)
        if not arr.nbytes:
            continue
        payload = memoryview(arr).cast("B")
        if arr.nbytes < INLINE_PAYLOAD_BYTES:
            head += payload
        else:
            segments.append(bytes(head))
            segments.append(payload)
            head = bytearray()
    if head:
        segments.append(bytes(head))
    return segments


def pack_frames(frames: Sequence[Tuple[str, np.ndarray]]) -> bytes:
    """Serialize named arrays into one tensor body: the join of
    :func:`frame_segments`, one allocation and one copy of each array's
    bytes (the C-order normalization of a non-contiguous input is the
    only other copy this path can make). For callers that need one piece
    of bytes: request bodies, the shm ring's envelope."""
    return b"".join(frame_segments(frames))


def unpack_frames(data: bytes) -> "Dict[str, np.ndarray]":
    """Parse a tensor body into ``{name: ndarray}`` (insertion-ordered).

    Zero-copy: every returned array is a read-only ``np.frombuffer`` view
    into ``data``. Raises :class:`WireFormatError` naming the violation
    for malformed magic, unknown version, disallowed dtypes, shape/payload
    size mismatches, truncation, and trailing bytes.
    """
    n = len(data)
    if n < len(WIRE_MAGIC) + 2:
        raise WireFormatError(f"body of {n} bytes is shorter than the header")
    if bytes(data[: len(WIRE_MAGIC)]) != WIRE_MAGIC:
        raise WireFormatError(
            f"bad magic {bytes(data[:len(WIRE_MAGIC)])!r} "
            f"(expected {WIRE_MAGIC!r})"
        )
    version = data[len(WIRE_MAGIC)]
    if version != WIRE_VERSION:
        raise WireFormatError(
            f"unsupported wire version {version} (this parser speaks "
            f"{WIRE_VERSION})"
        )
    n_frames = data[len(WIRE_MAGIC) + 1]
    if not 0 < n_frames <= _MAX_FRAMES:
        raise WireFormatError(
            f"frame count {n_frames} outside 1..{_MAX_FRAMES}"
        )
    mv = memoryview(data)
    pos = len(WIRE_MAGIC) + 2
    out: Dict[str, np.ndarray] = {}

    def take(count: int, what: str) -> int:
        nonlocal pos
        if pos + count > n:
            raise WireFormatError(
                f"truncated body: {what} needs {count} bytes at offset "
                f"{pos} but only {n - pos} remain"
            )
        start = pos
        pos += count
        return start

    for fi in range(n_frames):
        name_len = data[take(1, "frame name length")]
        start = take(name_len, "frame name")
        try:
            name = bytes(mv[start : start + name_len]).decode("utf-8")
        except UnicodeDecodeError as exc:
            raise WireFormatError(f"frame {fi} name is not utf-8: {exc}") from None
        dtype_len = data[take(1, "dtype length")]
        start = take(dtype_len, "dtype")
        dtype = _check_dtype(bytes(mv[start : start + dtype_len]).decode("ascii", "replace"))
        ndim = data[take(1, "ndim")]
        if ndim > _MAX_NDIM:
            raise WireFormatError(
                f"frame {name!r} declares {ndim} dims (bound {_MAX_NDIM})"
            )
        shape = tuple(
            _U64.unpack_from(mv, take(8, "shape dim"))[0] for _ in range(ndim)
        )
        nbytes = _U64.unpack_from(mv, take(8, "payload size"))[0]
        expected = int(np.prod(shape, dtype=object)) * dtype.itemsize if ndim else dtype.itemsize
        if nbytes != expected:
            raise WireFormatError(
                f"frame {name!r} payload size {nbytes} does not match "
                f"shape {shape} x {dtype.str} = {expected} bytes"
            )
        start = take(nbytes, f"frame {name!r} payload")
        arr = np.frombuffer(mv[start : start + nbytes], dtype=dtype)
        out[name] = arr.reshape(shape) if ndim else arr[0]
    if pos != n:
        raise WireFormatError(
            f"{n - pos} trailing bytes after the last frame (oversized body)"
        )
    return out


def rows_as_f32(arr: np.ndarray, name: str = "X") -> np.ndarray:
    """A wire frame as the (rows, features) float32 C-order array the
    scoring path wants, copying ONLY when the wire dtype/byte order
    actually differs (the native little-endian float32 fast path is the
    frombuffer view itself — zero copies between socket and scorer)."""
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    if arr.ndim != 2:
        raise WireFormatError(
            f"frame {name!r} must be 1-D or 2-D (rows x features), got "
            f"shape {arr.shape}"
        )
    if arr.dtype == np.float32 and arr.dtype.isnative:
        return arr
    # big-endian / wider floats / ints: one conversion copy, still vectorized
    return arr.astype(np.float32, order="C")
