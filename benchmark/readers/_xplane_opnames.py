"""HLO ``op_name`` of the device operations in an xplane file.

On the v5e under jax 0.9.0 the ``op_name`` of an operation (the path of
jit and ``jax.named_scope`` names, ``jit(_run)/generate.sample/sort``)
is in the trace, as the stat ``tf_op`` — but on the event's METADATA
(``XEventMetadata.stats``), which ``jax.profiler.ProfileData`` does not
hand out: its events list only their own stats (``device_offset_ps``,
``device_duration_ps``).  So this reads the few fields needed straight
from the protobuf wire format (``tsl/profiler/protobuf/xplane.proto``):
per device plane, the stat-name table and the event-metadata table; the
lines, which are the bulk of the file, are skipped by their length.

    XSpace         planes=1
    XPlane         name=2  lines=3  event_metadata=4  stat_metadata=5
    map entry      key=1  value=2
    XEventMetadata name=2  stats=5
    XStatMetadata  name=2
    XStat          metadata_id=1  str_value=5  ref_value=7
"""
from __future__ import annotations

OP_NAME_STAT = "tf_op"


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width
    fields are stepped over."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield field, wire, value


def _entry(buf):
    """A map entry -> (key, value bytes)."""
    key, value = 0, b""
    for field, wire, v in _fields(buf):
        if field == 1 and wire == 0:
            key = v
        elif field == 2 and wire == 2:
            value = v
    return key, value


def _name(buf) -> str:
    for field, wire, v in _fields(buf):
        if field == 2 and wire == 2:
            return bytes(v).decode("utf-8", "replace")
    return ""


def op_names(path: str, plane_name: str = "/device:TPU:0") -> dict:
    """{event name (the instruction's text): op_name} of one device
    plane; {} where the plane or the stat is not there."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, wire, plane in _fields(space):
        if field != 1 or wire != 2:
            continue
        name, events, stats = "", [], {}
        for f2, w2, v in _fields(plane):
            if w2 != 2:
                continue
            if f2 == 2:
                name = bytes(v).decode("utf-8", "replace")
            elif f2 == 4:
                events.append(_entry(v)[1])
            elif f2 == 5:
                key, meta = _entry(v)
                stats[key] = _name(meta)
        if name != plane_name:
            continue
        wanted = {k for k, n in stats.items() if n == OP_NAME_STAT}
        out = {}
        for meta in events:
            ev_name, op = "", ""
            for f3, w3, v in _fields(meta):
                if f3 == 2 and w3 == 2:
                    ev_name = bytes(v).decode("utf-8", "replace")
                elif f3 == 5 and w3 == 2:
                    sid, text = None, ""
                    for f4, w4, x in _fields(v):
                        if f4 == 1 and w4 == 0:
                            sid = x
                        elif f4 == 5 and w4 == 2:
                            text = bytes(x).decode("utf-8", "replace")
                        elif f4 == 7 and w4 == 0:
                            text = stats.get(x, "")
                    if sid in wanted:
                        op = text
            if op:
                out[ev_name] = op
        return out
    return {}
