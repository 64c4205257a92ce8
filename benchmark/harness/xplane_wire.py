"""Read an ``.xplane.pb`` with the stats ``jax.profiler.ProfileData`` leaves
out.

``ProfileData`` gives an event its own stats only. What names a device
operation's place in the model — the HLO ``op_name`` with the
``jax.named_scope`` path, stat ``tf_op`` on a v5e (looked at by hand in PR 25,
PERF.md §3) — is a stat of the event's *metadata* (one per HLO instruction,
shared by its executions), which ``ProfileData`` does not expose. The file is
a plain protobuf (``tsl/profiler/protobuf/xplane.proto``), so this module
decodes the wire format itself: varints and length-delimited fields, the
handful of messages below, nothing to install and no TensorFlow import in
the process that holds the chip.

    XSpace         1 planes
    XPlane         2 name, 3 lines, 4 event_metadata<id, XEventMetadata>,
                   5 stat_metadata<id, XStatMetadata>
    XLine          2 name, 3 timestamp_ns, 4 events
    XEvent         1 metadata_id, 2 offset_ps, 3 duration_ps, 4 stats
    XEventMetadata 1 id, 2 name, 5 stats
    XStatMetadata  1 id, 2 name
    XStat          1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str,
                   6 bytes, 7 ref (the id of a stat metadata whose name is
                   the value)
"""

from __future__ import annotations

import struct
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from .reduce_trace import Event, Planes


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, int, Any]]:
    """``(field number, wire type, value)`` of one message: an int for a
    varint or a fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, wire, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, wire, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield field, wire, int.from_bytes(buf[i:i + size], "little")
            i += size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v: memoryview) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf: memoryview, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    name, value = "", None
    for f, _, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = _text(v)
        elif f == 6:
            value = bytes(v)
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf: memoryview) -> Tuple[int, Optional[memoryview]]:
    key, value = 0, None
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def read_planes(path: str, want_plane: Callable[[str], bool],
                want_line: Callable[[str, str], bool],
                keep_stats: Tuple[str, ...] = (),
                short_name: Optional[Callable[[str, str], str]] = None
                ) -> Planes:
    """``{plane: {line: [Event]}}`` of the planes and lines asked for. An
    event's ``stats`` are those named in ``keep_stats``, its own over its
    metadata's; times are seconds on the file's one clock (a line's
    ``timestamp_ns`` plus the event's offset). ``short_name(line, name)``
    may shorten event names (the ops lines print whole instructions)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Planes = {}
    for f0, _, plane in _fields(space):
        if f0 != 1:
            continue
        name, lines, meta_bufs, stat_names = "", [], [], {}
        for f, _, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                meta_bufs.append(v)
            elif f == 5:
                _, sm = _map_entry(v)
                sid, sname = 0, ""
                for g, _, w in _fields(sm):
                    if g == 1:
                        sid = w
                    elif g == 2:
                        sname = _text(w)
                stat_names[sid] = sname
        if not want_plane(name):
            continue
        meta: Dict[int, Tuple[str, Dict[str, Any]]] = {}
        for mb in meta_bufs:
            mid, md = _map_entry(mb)
            mname, mstats = "", {}
            for g, _, w in _fields(md):
                if g == 2:
                    mname = _text(w)
                elif g == 5 and keep_stats:
                    k, val = _stat(w, stat_names)
                    if k in keep_stats:
                        mstats[k] = val
            meta[mid] = (mname, mstats)
        plane_out = out.setdefault(name, {})
        for lb in lines:
            lname, t0_ns, ev_bufs = "", 0, []
            for f, _, v in _fields(lb):
                if f == 2:
                    lname = _text(v)
                elif f == 3:
                    t0_ns = _signed(v)
                elif f == 4:
                    ev_bufs.append(v)
            if not want_line(name, lname):
                continue
            evs: List[Event] = plane_out.setdefault(lname, [])
            for eb in ev_bufs:
                mid = off_ps = dur_ps = 0
                own: Dict[str, Any] = {}
                for f, _, v in _fields(eb):
                    if f == 1:
                        mid = v
                    elif f == 2:
                        off_ps = _signed(v)
                    elif f == 3:
                        dur_ps = _signed(v)
                    elif f == 4 and keep_stats:
                        k, val = _stat(v, stat_names)
                        if k in keep_stats:
                            own[k] = val
                mname, mstats = meta.get(mid, ("", {}))
                if short_name is not None:
                    mname = short_name(lname, mname)
                evs.append(Event(mname, t0_ns * 1e-9 + off_ps * 1e-12,
                                 dur_ps * 1e-12, {**mstats, **own}))
    return out
