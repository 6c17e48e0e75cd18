"""A minimal reader of the profiler's ``.xplane.pb`` (XSpace protobuf).

JAX's ``ProfileData`` exposes events and their own stats, but not the stats
of an event's *metadata*, which is where a TPU trace keeps what an op came
from (``source``: the file and line of the call that built it, ``tf_op``:
its JAX name stack, ``hlo_category``).  Those are what tell one Pallas
kernel from another, so this reads the wire format directly.  Field
numbers follow ``tsl/profiler/protobuf/xplane.proto``:

    XSpace  { repeated XPlane planes = 1; }
    XPlane  { int64 id = 1; string name = 2; repeated XLine lines = 3;
              map<int64, XEventMetadata> event_metadata = 4;
              map<int64, XStatMetadata> stat_metadata = 5; }
    XLine   { string name = 2; int64 timestamp_ns = 3;
              repeated XEvent events = 4; }
    XEvent  { int64 metadata_id = 1; int64 offset_ps = 2;
              int64 duration_ps = 3; }
    XEventMetadata { int64 id = 1; string name = 2; string display_name = 4;
                     repeated XStat stats = 5; }
    XStatMetadata  { int64 id = 1; string name = 2; }
    XStat   { int64 metadata_id = 1; double double_value = 2;
              uint64 uint64_value = 3; int64 int64_value = 4;
              string str_value = 5; uint64 ref_value = 7; }
"""

from __future__ import annotations

import dataclasses
import struct
from typing import Dict, Iterator, List, Tuple


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: int = -1) -> Iterator[tuple]:
    """(field number, value) pairs of one message; length-delimited values
    are ``(start, stop)`` offsets into ``buf``."""
    end = len(buf) if end < 0 else end
    while i < end:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
            if v >= 1 << 63:
                v -= 1 << 64
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        elif wt == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield num, v


def _str(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


@dataclasses.dataclass
class EventMeta:
    name: str = ""
    display_name: str = ""
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Line:
    name: str
    events: List[Tuple[int, float, float]]   # (metadata id, start ns, end ns)


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]
    event_metadata: Dict[int, EventMeta]


def _stat(buf, span, stat_names) -> Tuple[str, object]:
    mid, val = 0, None
    for num, v in _fields(buf, *span):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num in (3, 4):
            val = v
        elif num == 5:
            val = _str(buf, v)
        elif num == 7:
            val = ("ref", v)
    if isinstance(val, tuple):
        val = stat_names.get(val[1], "")
    return stat_names.get(mid, str(mid)), val


def _plane(buf, span) -> Plane:
    name, lines_raw, ev_md_raw, stat_names = "", [], [], {}
    for num, v in _fields(buf, *span):
        if num == 2:
            name = _str(buf, v)
        elif num == 3:
            lines_raw.append(v)
        elif num == 4:
            ev_md_raw.append(v)
        elif num == 5:                              # map entry: key, value
            for n2, v2 in _fields(buf, *v):
                if n2 == 2:
                    sid, sname = 0, ""
                    for n3, v3 in _fields(buf, *v2):
                        if n3 == 1:
                            sid = v3
                        elif n3 == 2:
                            sname = _str(buf, v3)
                    stat_names[sid] = sname
    metas: Dict[int, EventMeta] = {}
    for entry in ev_md_raw:
        for n2, v2 in _fields(buf, *entry):
            if n2 != 2:
                continue
            mid, md = 0, EventMeta()
            for n3, v3 in _fields(buf, *v2):
                if n3 == 1:
                    mid = v3
                elif n3 == 2:
                    md.name = _str(buf, v3)
                elif n3 == 4:
                    md.display_name = _str(buf, v3)
                elif n3 == 5:
                    k, val = _stat(buf, v3, stat_names)
                    md.stats[k] = val
            metas[mid] = md
    lines = []
    for span_l in lines_raw:
        lname, ts, evs_raw = "", 0, []
        for n2, v2 in _fields(buf, *span_l):
            if n2 == 2:
                lname = _str(buf, v2)
            elif n2 == 3:
                ts = v2
            elif n2 == 4:
                evs_raw.append(v2)
        evs = []
        for e in evs_raw:
            mid = off = dur = 0
            for n3, v3 in _fields(buf, *e):
                if n3 == 1:
                    mid = v3
                elif n3 == 2:
                    off = v3
                elif n3 == 3:
                    dur = v3
            start = ts + off * 1e-3
            evs.append((mid, start, start + dur * 1e-3))
        lines.append(Line(lname, evs))
    return Plane(name, lines, metas)


def read(path: str) -> List[Plane]:
    with open(path, "rb") as f:
        buf = f.read()
    return [_plane(buf, v) for num, v in _fields(buf) if num == 1]
