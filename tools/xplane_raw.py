"""Read a profiler's ``.xplane.pb`` (an ``XSpace`` protocol buffer) with
nothing but the standard library.

``jax.profiler.ProfileData`` gives an event its own stats only. What the
compiler knew about a device operation — the ``tf_op`` stat, which holds
the operation's ``op_name`` and so its ``jax.named_scope`` path, and
``source`` (file:line) — sits on the event's *metadata*, which
``ProfileData`` leaves out (read by hand on a v5e trace, PERF.md section
5). So this module decodes the wire format itself; the field numbers are
those of ``tsl/profiler/protobuf/xplane.proto``.

    for plane in read(path):
        plane["name"], plane["lines"] -> [{"name", "events": [
            (name, start_ns, duration_ns, {stat: value})]}]

An event's stats are its metadata's stats overlaid by its own; a stat
that refers to another (``ref_value``) is resolved to that name.
"""
from __future__ import annotations

import struct
from typing import Any, Dict, Iterator, List, Tuple


def _varint(buf: memoryview, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def fields(buf: memoryview) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview, every other an int (fixed64 raw bits)."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif wire == 1:
            val, pos = int.from_bytes(buf[pos:pos + 8], "little"), pos + 8
        elif wire == 5:
            val, pos = int.from_bytes(buf[pos:pos + 4], "little"), pos + 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield num, wire, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: memoryview) -> Tuple[int, Any, bool]:
    """(stat metadata id, value, whether the value is a reference)."""
    sid, val, ref = 0, None, False
    for num, _w, v in fields(buf):
        if num == 1:
            sid = v
        elif num == 2:
            val = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif num == 6:
            val = bytes(v)
        elif num == 7:
            val, ref = v, True
    return sid, val, ref


def _map_entry(buf: memoryview) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for num, _w, v in fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane(buf: memoryview) -> Dict[str, Any]:
    name, lines, event_md, stat_names = "", [], {}, {}
    for num, _w, v in fields(buf):
        if num == 2:
            name = bytes(v).decode()
        elif num == 3:
            lines.append(v)
        elif num == 4:
            key, md = _map_entry(v)
            event_md[key] = md
        elif num == 5:
            key, md = _map_entry(v)
            stat_names[key] = next(
                (bytes(x).decode() for n, _w2, x in fields(md) if n == 2), "")

    def stats_of(raw: List[memoryview]) -> Dict[str, Any]:
        out = {}
        for sid, val, ref in map(_stat, raw):
            out[stat_names.get(sid, str(sid))] = \
                stat_names.get(val, val) if ref else val
        return out

    metadata: Dict[int, Tuple[str, Dict[str, Any]]] = {}
    for key, md in event_md.items():
        md_name, md_stats = "", []
        for num, _w, v in fields(md):
            if num == 2:
                md_name = bytes(v).decode("utf-8", "replace")
            elif num == 5:
                md_stats.append(v)
        metadata[key] = (md_name, stats_of(md_stats))

    out_lines = []
    for line in lines:
        line_name, t0_ns, events = "", 0, []
        for num, _w, v in fields(line):
            if num == 2:
                line_name = bytes(v).decode()
            elif num == 3:
                t0_ns = v
            elif num == 4:
                events.append(v)
        decoded = []
        for ev in events:
            mid = offset_ps = dur_ps = 0
            own = []
            for num, _w, v in fields(ev):
                if num == 1:
                    mid = v
                elif num == 2:
                    offset_ps = v
                elif num == 3:
                    dur_ps = v
                elif num == 4:
                    own.append(v)
            md_name, md_stats = metadata.get(mid, ("", {}))
            decoded.append((md_name, t0_ns + offset_ps / 1e3, dur_ps / 1e3,
                            {**md_stats, **stats_of(own)}))
        out_lines.append({"name": line_name, "events": decoded})
    return {"name": name, "lines": out_lines}


def read(path: str) -> List[Dict[str, Any]]:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    return [_plane(v) for num, _w, v in fields(space) if num == 1]
