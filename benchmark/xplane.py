"""A reader (and a writer, for test fixtures) of the profiler's
`.xplane.pb`, in plain Python: the protobuf wire format of
tsl/profiler/protobuf/xplane.proto, as far as the reduction needs it.
No JAX, so the harness's parent process can read a trace itself.

    XSpace { planes = 1 }
    XPlane { id = 1, name = 2, lines = 3, event_metadata = 4 (map) }
    XLine  { id = 1, name = 2, timestamp_ns = 3, events = 4,
             duration_ps = 9, display_name = 11 }
    XEvent { metadata_id = 1, offset_ps = 2, duration_ps = 3 }
    XEventMetadata { id = 1, name = 2, display_name = 4 }

An event's start is line.timestamp_ns * 1000 + offset_ps, in
picoseconds. Stats are skipped.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Event:
    name: str
    start_ps: int
    duration_ps: int

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.duration_ps


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def _varint(buf: bytes, pos: int) -> tuple:
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return result, pos
        shift += 7


def _fields(buf: bytes):
    """Yield (field number, wire type, value) over one message."""
    pos, end = 0, len(buf)
    while pos < end:
        tag, pos = _varint(buf, pos)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = buf[pos: pos + size]
            pos += size
        elif wire == 1:
            value, pos = buf[pos: pos + 8], pos + 8
        elif wire == 5:
            value, pos = buf[pos: pos + 4], pos + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield number, wire, value


def _metadata(buf: bytes) -> tuple:
    """One event_metadata map entry -> (id, name)."""
    key, name, display = 0, "", ""
    for number, _, value in _fields(buf):
        if number == 1:
            key = value
        elif number == 2:  # the XEventMetadata
            for n2, _, v2 in _fields(value):
                if n2 == 1:
                    key = v2
                elif n2 == 2:
                    name = v2.decode("utf-8", "replace")
                elif n2 == 4:
                    display = v2.decode("utf-8", "replace")
    return key, name or display


def _line(buf: bytes, names: dict) -> Line:
    name, display, t0_ns, raw = "", "", 0, []
    for number, _, value in _fields(buf):
        if number == 2:
            name = value.decode("utf-8", "replace")
        elif number == 11:
            display = value.decode("utf-8", "replace")
        elif number == 3:
            t0_ns = value
        elif number == 4:
            raw.append(value)
    events = []
    for ev in raw:
        meta = offset = duration = 0
        for number, _, value in _fields(ev):
            if number == 1:
                meta = value
            elif number == 2:
                offset = value
            elif number == 3:
                duration = value
        events.append(
            Event(names.get(meta, f"#{meta}"), t0_ns * 1000 + offset, duration)
        )
    return Line(name or display, events)


def parse(data: bytes) -> list:
    """All planes of an XSpace."""
    planes = []
    for number, _, value in _fields(data):
        if number != 1:
            continue
        name, raw_lines, names = "", [], {}
        for n2, _, v2 in _fields(value):
            if n2 == 2:
                name = v2.decode("utf-8", "replace")
            elif n2 == 3:
                raw_lines.append(v2)
            elif n2 == 4:
                key, ev_name = _metadata(v2)
                names[key] = ev_name
        planes.append(Plane(name, [_line(ln, names) for ln in raw_lines]))
    return planes


def load(path: str) -> list:
    with open(path, "rb") as f:
        return parse(f.read())


# -- writer: only what a fixture needs ---------------------------------------


def _enc_varint(value: int) -> bytes:
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _enc(number: int, value) -> bytes:
    if isinstance(value, int):
        return _enc_varint(number << 3) + _enc_varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _enc_varint(number << 3 | 2) + _enc_varint(len(value)) + value


def dump(planes: list) -> bytes:
    """Serialise planes; every line's timestamp is its first event's
    start, rounded down to a nanosecond."""
    out = b""
    for p_id, plane in enumerate(planes):
        ids: dict = {}
        body = _enc(1, p_id) + _enc(2, plane.name)
        for l_id, line in enumerate(plane.lines):
            t0_ns = min((e.start_ps for e in line.events), default=0) // 1000
            lb = _enc(1, l_id) + _enc(2, line.name) + _enc(3, t0_ns)
            for e in line.events:
                meta = ids.setdefault(e.name, len(ids) + 1)
                lb += _enc(4, _enc(1, meta)
                           + _enc(2, e.start_ps - t0_ns * 1000)
                           + _enc(3, e.duration_ps))
            body += _enc(3, lb)
        for name, meta in ids.items():
            body += _enc(4, _enc(1, meta) + _enc(2, _enc(1, meta) + _enc(2, name)))
        out += _enc(1, body)
    return out
