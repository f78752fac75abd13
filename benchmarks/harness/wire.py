"""The client side of the product's framed tensor body
(``application/x-gordo-tensor``), written from the format's description
(``utils/wire.py`` docstring) so the load generator imports nothing of the
program (and no JAX)::

    b"GTNS" | version u8 = 1 | nframes u8 | frame * nframes
    frame := namelen u8 | name | dtypelen u8 | dtype | ndim u8
           | dim u64le * ndim | nbytes u64le | payload
"""

import struct
from typing import Dict, Sequence, Tuple

import numpy as np

CONTENT_TYPE = "application/x-gordo-tensor"
MAGIC, VERSION = b"GTNS", 1
ANOMALY_FRAMES = (
    "model-input", "model-output", "tag-anomaly-unscaled", "tag-anomaly-scaled",
    "total-anomaly-unscaled", "total-anomaly-scaled",
)


def pack(frames: Sequence[Tuple[str, np.ndarray]]) -> bytes:
    parts = [MAGIC, bytes([VERSION, len(frames)])]
    for name, arr in frames:
        arr = np.ascontiguousarray(arr)
        name_b, dtype_b = name.encode("utf-8"), arr.dtype.str.encode("ascii")
        parts += [
            bytes([len(name_b)]), name_b, bytes([len(dtype_b)]), dtype_b,
            bytes([arr.ndim]), struct.pack(f"<{arr.ndim}Q", *arr.shape),
            struct.pack("<Q", arr.nbytes), arr.tobytes(),
        ]
    return b"".join(parts)


def unpack(data: bytes) -> Dict[str, np.ndarray]:
    """``{name: array}``; arrays are views into ``data``. Raises
    ``ValueError`` on anything but a whole, well-formed body."""
    if data[:4] != MAGIC or data[4] != VERSION:
        raise ValueError(f"not a version-{VERSION} tensor body: {bytes(data[:5])!r}")
    pos, out = 6, {}
    for _ in range(data[5]):
        n = data[pos]
        name = bytes(data[pos + 1 : pos + 1 + n]).decode("utf-8")
        pos += 1 + n
        n = data[pos]
        dtype = np.dtype(bytes(data[pos + 1 : pos + 1 + n]).decode("ascii"))
        pos += 1 + n
        ndim = data[pos]
        shape = struct.unpack_from(f"<{ndim}Q", data, pos + 1)
        pos += 1 + 8 * ndim
        (nbytes,) = struct.unpack_from("<Q", data, pos)
        pos += 8
        count = int(np.prod(shape, dtype=np.int64)) if ndim else 1
        if nbytes != count * dtype.itemsize or pos + nbytes > len(data):
            raise ValueError(f"frame {name!r}: {nbytes} bytes for shape {shape} {dtype}")
        out[name] = np.frombuffer(data, dtype, count, pos).reshape(shape)
        pos += nbytes
    if pos != len(data):
        raise ValueError(f"{len(data) - pos} trailing bytes")
    return out
