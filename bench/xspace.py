"""The ``tf_op`` of each device operation, read from the raw profile.

``jax.profiler.ProfileData`` gives each ``XLA Ops`` event its HLO text
and its times, but not the stats of the event's metadata, where the
profiler keeps the operation's ``tf_op``: the HLO ``op_name``, which
carries the program's ``jax.named_scope`` path
(``jit(call)/while/body/vertex_step/edge_gather/gather``).  This module
reads them from the ``.xplane.pb`` file with a minimal decoder of the
published XSpace schema (``tsl/profiler/protobuf/xplane.proto``)::

    XSpace          planes = 1
    XPlane          name = 2, event_metadata = 4, stat_metadata = 5
    XEventMetadata  name = 2, stats = 5
    XStatMetadata   name = 2
    XStat           metadata_id = 1, str_value = 5, ref_value = 7

each map being repeated entries of key = 1 and value = 2.  The planes'
``lines``, which hold the events themselves, are skipped by length.
"""
from __future__ import annotations

from pathlib import Path

from bench.trace import DEVICE_PLANE_PREFIX

TF_OP = "tf_op"

# wire types of the protobuf encoding
_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf, lo: int, hi: int):
    """``(field number, value)`` of each field in ``buf[lo:hi]``: a
    varint's integer, a length-delimited field's ``(start, end)``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
        elif wire == _BYTES:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (_FIXED64, _FIXED32):
            n = 8 if wire == _FIXED64 else 4
            value, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield number, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _entry(buf, span):
    """A map entry's ``(key, value)``."""
    key = value = None
    for number, v in _fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _name(buf, span):
    """Field 2, the name, of an ``XEventMetadata`` or ``XStatMetadata``."""
    for number, v in _fields(buf, *span):
        if number == 2:
            return _text(buf, v)
    return None


def _plane_tf_ops(buf, events, stats) -> dict:
    stat_names = {}
    for entry in stats:
        key, value = _entry(buf, entry)
        if value is not None:
            stat_names[key] = _name(buf, value)
    tf_ids = {k for k, v in stat_names.items() if v == TF_OP}
    out = {}
    for entry in events:
        _, value = _entry(buf, entry)
        if value is None:
            continue
        name, tf_op = None, ""
        for number, v in _fields(buf, *value):
            if number == 2:
                name = _text(buf, v)
            elif number == 5:
                stat = dict(_fields(buf, *v))
                if stat.get(1) not in tf_ids:
                    continue
                if 5 in stat:
                    tf_op = _text(buf, stat[5])
                elif 7 in stat:
                    tf_op = stat_names.get(stat[7]) or ""
        if name is None:
            continue
        # one name, two operations with different scopes: neither is known
        out[name] = tf_op if out.get(name, tf_op) == tf_op else None
    return {k: v for k, v in out.items() if v}


def tf_ops(path) -> dict:
    """``{device plane name: {event name: tf_op}}``.  An event name is the
    HLO text that ``ProfileData`` gives as ``ev.name``.  Names without a
    ``tf_op``, or whose events carry different ones, are left out."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, events, stats = None, [], []
        for field, v in _fields(buf, *plane):
            if field == 2:
                name = _text(buf, v)
            elif field == 4:
                events.append(v)
            elif field == 5:
                stats.append(v)
        if name is not None and name.startswith(DEVICE_PLANE_PREFIX):
            out[name] = _plane_tf_ops(buf, events, stats)
    return out
