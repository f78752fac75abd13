"""Device seconds by ``jax.named_scope``, from the same ``.xplane.pb`` that
``trace.py`` reduces. ``jax.profiler.ProfileData`` shows an XLA op's event
under its HLO line and keeps back the event *metadata*, which is where the
profiler writes the op's framework name (``tf_op``:
``jit(score_layer)/trunk/experts/...``, the ``jax.named_scope`` path of the
line that made it). So this reads the file's protobuf wire format itself:
the handful of XSpace fields it needs, nothing else, no generated code.

    XSpace.planes=1 -> XPlane{name=2, lines=3, event_metadata=4 (map),
                              stat_metadata=5 (map)}
    XLine{name=2, events=4}   XEvent{metadata_id=1, duration_ps=3}
    XEventMetadata{id=1, name=2, stats=5}   XStatMetadata{id=1, name=2}
    XStat{metadata_id=1, str_value=5, ref_value=7}
"""

from typing import Dict, Iterator, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message; a length-
    delimited value is a ``memoryview`` slice, no copy."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = buf[pos : pos + size]
            pos += size
        elif wire == 1:
            value, pos = buf[pos : pos + 8], pos + 8
        elif wire == 5:
            value, pos = buf[pos : pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield number, wire, value


def _map_entry(buf) -> Tuple[int, bytes]:
    key, value = 0, b""
    for number, _wire, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, Optional[str]]:
    name, value = "", None
    for number, _wire, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v, "")
        elif number == 5:
            value = _text(v)
        elif number == 7:  # a reference into the stat metadata's names
            value = stat_names.get(v)
    return name, value


def op_seconds_by_framework_name(data: bytes) -> Dict[str, float]:
    """Device seconds of every XLA op of every TPU plane, keyed by the
    op's framework name (``tf_op``), averaged over the planes. A ``while``
    op's own event spans its body's ops, which are listed themselves: it
    is left out."""
    view = memoryview(data)
    totals: Dict[str, float] = {}
    planes = 0
    for number, _wire, plane in _fields(view):
        if number != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for n, _w, v in _fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 3:
                lines.append(v)
            elif n == 4:
                key, value = _map_entry(v)
                event_meta[key] = value
            elif n == 5:
                key, value = _map_entry(v)
                stat_names[key] = next(
                    (_text(x) for m, _w2, x in _fields(value) if m == 2), ""
                )
        if not name.startswith(DEVICE_PLANE_PREFIX):
            continue
        planes += 1
        op_names: Dict[int, Optional[str]] = {}
        for key, meta in event_meta.items():
            hlo, framework = "", None
            for n, _w, v in _fields(meta):
                if n == 2:
                    hlo = _text(v)
                elif n == 5:
                    stat, value = _stat(v, stat_names)
                    if stat == "tf_op":
                        framework = value
            op_names[key] = None if " while(" in hlo or hlo.startswith("%while") else framework
        for line in lines:
            fields = list(_fields(line))
            if not any(n == 2 and _text(v) == OPS_LINE for n, _w, v in fields):
                continue
            for n, _w, event in fields:
                if n != 4:
                    continue
                meta_id = duration_ps = 0
                for m, _w2, v in _fields(event):
                    if m == 1:
                        meta_id = v
                    elif m == 3:
                        duration_ps = v
                framework = op_names.get(meta_id)
                if framework:
                    totals[framework] = totals.get(framework, 0.0) + duration_ps / 1e12
    return {k: v / planes for k, v in totals.items()} if planes else {}


def seconds_by_scope(by_name: Dict[str, float], scopes: Sequence[str]) -> Dict[str, float]:
    """Sum ``op_seconds_by_framework_name`` by the scopes' names: an op
    belongs to the LAST of ``scopes`` its framework name holds as a path
    component (``.../trunk/experts/...``: ``trunk/experts``), so a scope
    opened inside another keeps its own time."""
    out = {scope: 0.0 for scope in scopes}
    for name, seconds in by_name.items():
        path = "/" + name + "/"
        found = [(path.rfind("/" + scope + "/"), scope) for scope in scopes]
        at, scope = max(found)
        if at >= 0:
            out[scope] += seconds
    return out


def reduce_file(path: str, scopes: Sequence[str]) -> Dict[str, float]:
    with open(path, "rb") as fh:
        return seconds_by_scope(op_seconds_by_framework_name(fh.read()), scopes)
