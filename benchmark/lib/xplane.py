"""The profiler's `.xplane.pb`, read by field number with nothing but the
standard library.

`jax.profiler.ProfileData` lists an event's own stats and not those of its
METADATA, where the scope path of a device op is kept (stat `tf_op`, e.g.
`jit(step_fn)/optimizer/add:`). TensorFlow's generated module would read it,
and is not imported into a measured process beside JAX. The messages and
their field numbers, from `tsl/profiler/protobuf/xplane.proto`:

    XSpace          1 planes
    XPlane          1 id, 2 name, 3 lines, 4 event_metadata (map id -> XEventMetadata),
                    5 stat_metadata (map id -> XStatMetadata), 6 stats
    XLine           1 id, 2 name, 3 timestamp_ns, 4 events, 9 duration_ps, 10, 11
    XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps, 4 stats, 5 num_occurrences
    XEventMetadata  1 id, 2 name, 3 metadata, 4 display_name, 5 stats, 6 child_id
    XStatMetadata   1 id, 2 name, 3 description
    XStat           1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str, 6 bytes,
                    7 ref (the id of a stat metadata whose name is the value)

A map entry is a message of its own: 1 key, 2 value.
"""
from __future__ import annotations

import struct
from dataclasses import dataclass, field
from pathlib import Path

VARINT, FIXED64, LEN, FIXED32 = 0, 1, 2, 5


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    value, shift = b & 0x7F, 7
    while True:
        i += 1
        b = buf[i]
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i + 1
        shift += 7


def fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """(field number, wire type, value) of each field of the message in
    `buf[lo:hi]`: an int for a varint or a fixed field, (start, end) into
    `buf` for a length-delimited one."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == VARINT:
            value, i = _varint(buf, i)
        elif wire == LEN:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == FIXED64:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == FIXED32:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


@dataclass
class EventMetadata:
    name: str = ""
    #: stat metadata id -> value: str, int, float, bytes, or ("ref", id)
    stats: dict = field(default_factory=dict)


@dataclass
class Line:
    name: str = ""
    timestamp_ns: int = 0
    #: (metadata id, offset_ps, duration_ps), in the order of the file
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str = ""
    lines: list = field(default_factory=list)
    event_metadata: dict = field(default_factory=dict)      # id -> EventMetadata
    stat_names: dict = field(default_factory=dict)          # id -> name

    def stat(self, meta: EventMetadata, name: str):
        """The value of `meta`'s stat called `name`, a reference resolved to
        the name it points at; None where it has none."""
        for sid, value in meta.stats.items():
            if self.stat_names.get(sid) == name:
                if isinstance(value, tuple):
                    return self.stat_names.get(value[1], "")
                return value
        return None


def _stat(buf: bytes, span) -> tuple[int, object]:
    sid, value = 0, None
    for num, _, v in fields(buf, *span):
        if num == 1:
            sid = v
        elif num == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif num in (3, 4):
            value = _signed(v) if num == 4 else v
        elif num == 5:
            value = _text(buf, v)
        elif num == 6:
            value = buf[v[0]:v[1]]
        elif num == 7:
            value = ("ref", v)
    return sid, value


def _event_metadata(buf: bytes, span) -> EventMetadata:
    meta = EventMetadata()
    for num, _, v in fields(buf, *span):
        if num == 2:
            meta.name = _text(buf, v)
        elif num == 5:
            sid, value = _stat(buf, v)
            meta.stats[sid] = value
    return meta


def _map_entry(buf: bytes, span) -> tuple[int, tuple[int, int] | None]:
    key, value = 0, None
    for num, _, v in fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _line(buf: bytes, span) -> Line:
    line = Line()
    for num, _, v in fields(buf, *span):
        if num == 2:
            line.name = _text(buf, v)
        elif num == 3:
            line.timestamp_ns = _signed(v)
        elif num == 4:
            mid = offset = duration = 0
            for n, _, x in fields(buf, *v):
                if n == 1:
                    mid = x
                elif n == 2:
                    offset = _signed(x)
                elif n == 3:
                    duration = _signed(x)
            line.events.append((mid, offset, duration))
    return line


def planes(buf: bytes, want=lambda name: True) -> list[Plane]:
    """The planes of a serialized XSpace whose name `want` accepts, with their
    lines, events, event metadata and stat names; the others are skipped
    unread."""
    out = []
    for num, _, span in fields(buf):
        if num != 1:
            continue
        parts = list(fields(buf, *span))
        name = next((_text(buf, v) for n, _, v in parts if n == 2), "")
        if not want(name):
            continue
        plane = Plane(name=name)
        for n, _, v in parts:
            if n == 3:
                plane.lines.append(_line(buf, v))
            elif n == 4:
                key, value = _map_entry(buf, v)
                if value is not None:
                    plane.event_metadata[key] = _event_metadata(buf, value)
            elif n == 5:
                key, value = _map_entry(buf, v)
                if value is not None:
                    plane.stat_names[key] = next(
                        (_text(buf, x) for m, _, x in fields(buf, *value)
                         if m == 2), "")
        out.append(plane)
    return out


def read(path: str | Path, want=lambda name: True) -> list[Plane]:
    return planes(Path(path).read_bytes(), want)
