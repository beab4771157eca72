"""A reader of the profiler's `*.xplane.pb` (an `XSpace` protobuf) that
keeps what `jax.profiler.ProfileData` leaves out: each event's
`tf_op` stat, the JAX name stack of the operation an XLA op event ran
(`jit(_allocate_impl)/bcd/while/body/sp2/...`).

It decodes the protobuf wire format itself, with the field numbers of
XLA's `xplane.proto`:

    XSpace          planes=1
    XPlane          name=2 lines=3 event_metadata=4 stat_metadata=5
                    (both maps: key=1, value=2)
    XLine           name=2 timestamp_ns=3 events=4
    XEvent          metadata_id=1 offset_ps=2 duration_ps=3 stats=4
    XEventMetadata  id=1 name=2 stats=5
    XStat           metadata_id=1 str_value=5 ref_value=7
    XStatMetadata   id=1 name=2

`tf_op` is a `str_value`, or a `ref_value` naming a stat metadata whose
`name` is the string. An event starts at its line's `timestamp_ns` plus
`offset_ps` and lasts `duration_ps`, both cut to whole nanoseconds, as
`ProfileData` gives them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

TF_OP = "tf_op"


@dataclasses.dataclass
class Line:
    name: str
    # (name, start_ns, duration_ns, tf_op or "")
    events: List[Tuple[str, int, int, str]]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    out, shift = b & 0x7F, 7
    while True:
        i += 1
        b = buf[i]
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i + 1
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of each field in buf[i:end]: an int for a
    varint, a (start, end) slice for a length-delimited field."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, span):
    """The value slice of one map<int64, message> entry."""
    for f, v in _fields(buf, *span):
        if f == 2:
            return v
    return (span[1], span[1])


def _plane(buf: bytes, i: int, end: int) -> Plane:
    name, lines, ev_meta, stat_names = "", [], [], {}
    for f, v in _fields(buf, i, end):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_meta.append(_map_values(buf, v))
        elif f == 5:
            sid, sname = 0, ""
            for g, w in _fields(buf, *_map_values(buf, v)):
                if g == 1:
                    sid = w
                elif g == 2:
                    sname = _text(buf, w)
            stat_names[sid] = sname
    tf_op_ids = {k for k, v in stat_names.items() if v == TF_OP}
    meta: Dict[int, Tuple[str, str]] = {}
    for span in ev_meta:
        mid, mname, op = 0, "", ""
        for g, w in _fields(buf, *span):
            if g == 1:
                mid = w
            elif g == 2:
                mname = _text(buf, w)
            elif g == 5:
                sid, sval = 0, ""
                for h, x in _fields(buf, *w):
                    if h == 1:
                        sid = x
                    elif h == 5:
                        sval = _text(buf, x)
                    elif h == 7:
                        sval = stat_names.get(x, "")
                if sid in tf_op_ids:
                    op = sval
        meta[mid] = (mname, op)
    return Plane(name, [_line(buf, s, meta) for s in lines])


def _line(buf: bytes, span, meta: Dict[int, Tuple[str, str]]) -> Line:
    name, ts, events = "", 0, []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            ts = v
        elif f == 4:
            events.append(_event(buf, *v))
    none = ("", "")
    out = []
    for mid, off, dur in events:
        mname, op = meta.get(mid, none)
        out.append((mname, ts + off // 1000, dur // 1000, op))
    return Line(name, out)


def _event(buf: bytes, i: int, end: int) -> Tuple[int, int, int]:
    """(metadata_id, offset_ps, duration_ps) of one XEvent: the reader's
    hot loop, so its varints are decoded in place and its stats skipped."""
    got = [0, 0, 0, 0]
    while i < end:
        key = buf[i]
        i += 1
        if key & 7 == 0:
            v = buf[i]
            i += 1
            if v >= 0x80:
                v &= 0x7F
                shift = 7
                while True:
                    b = buf[i]
                    i += 1
                    v |= (b & 0x7F) << shift
                    if b < 0x80:
                        break
                    shift += 7
            if key < 0x20:
                got[key >> 3] = v
        elif key & 7 == 2:
            n, i = _varint(buf, i)
            i += n
        else:
            i += 8 if key & 7 == 1 else 4
    return got[1], got[2], got[3]


def read(path: str) -> List[Plane]:
    """Every plane of the `*.xplane.pb` at `path`."""
    with open(path, "rb") as fh:
        buf = fh.read()
    return [_plane(buf, *v) for f, v in _fields(buf, 0, len(buf)) if f == 1]
