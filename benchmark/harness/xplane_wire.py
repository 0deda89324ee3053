"""A reader of the profiler's ``.xplane.pb`` from the protobuf wire format,
with nothing but the standard library.

``jax.profiler.ProfileData`` shows an event's name, times and its OWN stats.
What says which part of the program a device operation belongs to is on the
event's METADATA: the ``tf_op`` stat holds the operation's ``op_name`` path
(``jit(step)/grow/partition/sort``: the ``jax.named_scope`` names are
components of it), beside ``hlo_category``, ``bytes_accessed`` and ``flops``.
This module reads enough of ``XSpace`` (tsl/profiler/protobuf/xplane.proto)
to give both, resolving ``ref_value`` stats through the plane's stat table.

    for plane in read(path):
        plane["name"]; plane["lines"][i]["name"]
        ev = plane["lines"][i]["events"][j]
        ev["name"], ev["start_ns"], ev["duration_ns"]
        ev["stats"]         # the event's own stats, {name: value}
        ev["meta_stats"]    # its metadata's stats (shared dict: do not edit)

Field numbers (proto3; a map entry is key = 1, value = 2):
XSpace.planes 1 | XPlane.name 2, lines 3, event_metadata 4, stat_metadata 5 |
XLine.name 2, timestamp_ns 3, events 4 | XEvent.metadata_id 1, offset_ps 2,
duration_ps 3, stats 4 | XStat.metadata_id 1, double 2, uint64 3, int64 4,
str 5, bytes 6, ref 7 | XEventMetadata.id 1, name 2, stats 5 |
XStatMetadata.id 1, name 2.
"""

import struct

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, pos):
    val = buf[pos]
    pos += 1
    if val < 0x80:
        return val, pos
    val &= 0x7F
    shift = 7
    while True:
        b = buf[pos]
        pos += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, pos
        shift += 7


def _fields(buf, pos, end):
    """(field number, wire type, value) of one message; a length-delimited
    value is its (start, end) in ``buf``."""
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wt = key >> 3, key & 7
        if wt == _VARINT:
            val, pos = _varint(buf, pos)
            yield num, wt, val
        elif wt == _BYTES:
            n, pos = _varint(buf, pos)
            yield num, wt, (pos, pos + n)
            pos += n
        elif wt == _FIXED64:
            yield num, wt, buf[pos:pos + 8]
            pos += 8
        elif wt == _FIXED32:
            yield num, wt, buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wt} at byte {pos}: not an XSpace")


def _signed(v):
    """An int64 field's varint, as the signed number it encodes."""
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span):
    """(stat metadata id, value, value is a reference into the stat table)."""
    mid, val, ref = 0, None, False
    for num, wt, v in _fields(buf, *span):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num == 5:
            val = _text(buf, v)
        elif num == 6:
            val = bytes(buf[v[0]:v[1]])
        elif num == 7:
            val, ref = v, True
    return mid, val, ref


def _named_stats(raw, stat_names):
    out = {}
    for mid, val, ref in raw:
        out[stat_names.get(mid, str(mid))] = (
            stat_names.get(val, str(val)) if ref else val)
    return out


def _map_value(buf, span):
    for num, wt, v in _fields(buf, *span):
        if num == 2:
            return v
    return None


def _event_metadata(buf, span):
    mid, name, stats = 0, "", []
    for num, wt, v in _fields(buf, *span):
        if num == 1:
            mid = v
        elif num == 2:
            name = _text(buf, v)
        elif num == 5:
            stats.append(_stat(buf, v))
    return mid, name, stats


def _stat_metadata(buf, span):
    mid, name = 0, ""
    for num, wt, v in _fields(buf, *span):
        if num == 1:
            mid = v
        elif num == 2:
            name = _text(buf, v)
    return mid, name


def _line(buf, span, meta, stat_names, want_event):
    name, t0_ns, raw_events = "", 0, []
    for num, wt, v in _fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            t0_ns = _signed(v)
        elif num == 4:
            raw_events.append(v)
    events = []
    for ev_span in raw_events:
        mid, offset_ps, dur_ps, stats = 0, 0, 0, []
        for num, wt, v in _fields(buf, *ev_span):
            if num == 1:
                mid = v
            elif num == 2:
                offset_ps = _signed(v)
            elif num == 3:
                dur_ps = _signed(v)
            elif num == 4:
                stats.append(v)
        ev_name, meta_stats = meta.get(mid, (str(mid), {}))
        if want_event is not None and not want_event(ev_name):
            continue
        events.append({
            "name": ev_name,
            # as ProfileData gives them: picoseconds cut to whole nanoseconds
            "start_ns": float(offset_ps // 1000 + t0_ns),
            "duration_ns": float(dur_ps // 1000),
            "stats": _named_stats([_stat(buf, s) for s in stats],
                                  stat_names) if stats else {},
            "meta_stats": meta_stats,
        })
    return {"name": name, "timestamp_ns": t0_ns, "events": events}


def _plane(buf, span, want_plane, want_line, want_event):
    name, lines, metas, stat_names = "", [], [], {}
    for num, wt, v in _fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            metas.append(v)
        elif num == 5:
            value = _map_value(buf, v)
            if value is not None:
                mid, sname = _stat_metadata(buf, value)
                stat_names[mid] = sname
    if want_plane is not None and not want_plane(name):
        return None
    meta = {}
    for entry in metas:
        value = _map_value(buf, entry)
        if value is not None:
            mid, ev_name, raw = _event_metadata(buf, value)
            meta[mid] = (ev_name, _named_stats(raw, stat_names))
    out = []
    for line_span in lines:
        if want_line is not None:
            lname = next((_text(buf, v) for num, wt, v
                          in _fields(buf, *line_span) if num == 2), "")
            if not want_line(name, lname):
                continue
        out.append(_line(buf, line_span, meta, stat_names,
                         want_event and (lambda n, p=name: want_event(p, n))))
    return {"name": name, "lines": out}


def read(path, want_plane=None, want_line=None, want_event=None):
    """The planes of one ``.xplane.pb``.  ``want_plane(plane name)``,
    ``want_line(plane name, line name)`` and ``want_event(plane name, event
    name)`` leave out what the caller does not read, before it is decoded (a
    host plane can hold a million Python events)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for num, wt, v in _fields(buf, 0, len(buf)):
        if num == 1 and wt == _BYTES:
            plane = _plane(buf, v, want_plane, want_line, want_event)
            if plane is not None:
                planes.append(plane)
    return planes


def without_planes(raw, drop):
    """The bytes of an ``XSpace`` without the planes whose name is in
    ``drop`` (the HLO protos under ``/host:metadata`` are nine tenths of a
    trace and no reduction reads them).  Every top-level field is
    length-delimited, so an entry runs from the end of the one before it to
    the end of its payload."""
    buf, out, at = memoryview(raw), [], 0
    for num, _, (a, b) in _fields(buf, 0, len(buf)):
        name = next((_text(buf, v) for n, _, v in _fields(buf, a, b)
                     if n == 2), "")
        if not (num == 1 and name in drop):
            out.append(bytes(buf[at:b]))
        at = b
    return b"".join(out)
