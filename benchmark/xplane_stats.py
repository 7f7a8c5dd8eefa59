"""An xplane file's device events WITH their statistics.

`benchmark/xplane.py` reads a trace through `jax.profiler.ProfileData`,
which hands out an event's name, start, duration and its OWN statistics.
What says where an operation came from is not among them: the profiler
writes the `op_name` of the HLO instruction (the JAX name stack with the
program's named scopes: `tf_op` on this libtpu), its category and its
program once per distinct operation, as statistics of the event's METADATA
(`XEventMetadata.stats`), which that API does not reach. So this module
reads the file itself: the protobuf wire format of `XSpace` (tsl/profiler/
protobuf/xplane.proto), the few messages and fields named below and
nothing else. A plane that is not asked for is skipped by its length
without being decoded, which keeps a run's second look at its trace to a
second or two.

    planes = read_device_planes(path)           # {chip: Plane}
    for ev in planes[0].lines["XLA Ops"]:
        ev.name, ev.start_ns, ev.duration_ns, ev.stats["tf_op"]
"""

from __future__ import annotations

import dataclasses
import re
import struct
from typing import Dict, Iterator, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class StatEvent:
    name: str                  # the metadata's name: the whole HLO line
    start_ns: float
    duration_ns: float
    stats: Dict[str, object]   # the metadata's statistics, then the event's


@dataclasses.dataclass
class Plane:
    name: str
    lines: Dict[str, List[StatEvent]]


# -- the wire format ---------------------------------------------------------

def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: a varint's value,
    the 8 or 4 raw bytes of a fixed field, or a length-delimited field's
    bytes as a memoryview slice of `buf`."""
    pos, end = 0, len(buf)
    while pos < end:
        tag, pos = _varint(buf, pos)
        num, wire = tag >> 3, tag & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 1:
            value, pos = buf[pos:pos + 8], pos + 8
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + n], pos + n
        elif wire == 5:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {pos}")
        yield num, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, stat_names: Dict[int, str]) -> Tuple[str, object]:
    """XStat: metadata_id 1, double 2, uint64 3, int64 4, str 5, bytes 6,
    ref 7 (the id of a stat metadata whose NAME is the value)."""
    name, value = "", None
    for num, _wire, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif num == 6:
            value = bytes(v)
        elif num == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for num, _wire, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane_name(buf: bytes) -> str:
    for num, wire, v in _fields(buf):
        if num == 2 and wire == 2:
            return bytes(v).decode("utf-8", "replace")
    return ""


def _plane(buf: bytes, name: str, want_lines) -> Plane:
    """XPlane: name 2, lines 3, event_metadata 4 (map), stat_metadata 5
    (map). The two maps may follow the lines in the file, so the lines
    are kept as bytes until both are read."""
    raw_lines, raw_events, stat_names = [], {}, {}
    for num, wire, v in _fields(buf):
        if wire != 2:
            continue
        if num == 3:
            raw_lines.append(v)
        elif num == 4:
            key, value = _map_entry(v)
            raw_events[key] = value
        elif num == 5:
            key, value = _map_entry(v)
            # XStatMetadata: id 1, name 2
            for n2, w2, v2 in _fields(value):
                if n2 == 2 and w2 == 2:
                    stat_names[key] = bytes(v2).decode("utf-8", "replace")
    events_meta: Dict[int, Tuple[str, Dict[str, object]]] = {}

    def meta(mid: int) -> Tuple[str, Dict[str, object]]:
        # XEventMetadata: id 1, name 2, stats 5
        if mid not in events_meta:
            ename, stats = "", {}
            for n2, w2, v2 in _fields(raw_events.get(mid, b"")):
                if n2 == 2 and w2 == 2:
                    ename = bytes(v2).decode("utf-8", "replace")
                elif n2 == 5 and w2 == 2:
                    k, val = _stat(v2, stat_names)
                    stats[k] = val
            events_meta[mid] = (ename, stats)
        return events_meta[mid]

    lines: Dict[str, List[StatEvent]] = {}
    for raw in raw_lines:
        # XLine: name 2, timestamp_ns 3, events 4
        lname, t0_ns, raw_evs = "", 0, []
        for n2, w2, v2 in _fields(raw):
            if n2 == 2 and w2 == 2:
                lname = bytes(v2).decode("utf-8", "replace")
            elif n2 == 3 and w2 == 0:
                t0_ns = v2
            elif n2 == 4 and w2 == 2:
                raw_evs.append(v2)
        if want_lines is not None and lname not in want_lines:
            continue
        out = lines.setdefault(lname, [])
        for rev in raw_evs:
            # XEvent: metadata_id 1, offset_ps 2, duration_ps 3, stats 4
            mid = off_ps = dur_ps = 0
            own = None
            for n3, w3, v3 in _fields(rev):
                if n3 == 1 and w3 == 0:
                    mid = v3
                elif n3 == 2 and w3 == 0:
                    off_ps = v3
                elif n3 == 3 and w3 == 0:
                    dur_ps = v3
                elif n3 == 4 and w3 == 2:
                    k, val = _stat(v3, stat_names)
                    own = own if own is not None else {}
                    own[k] = val
            ename, stats = meta(mid)
            if own:
                stats = {**stats, **own}
            out.append(StatEvent(ename, t0_ns + off_ps / 1e3, dur_ps / 1e3,
                                 stats))
    return Plane(name, lines)


def read_device_planes(path: str, lines: Optional[Tuple[str, ...]] = None,
                       chips: Optional[Tuple[int, ...]] = None
                       ) -> Dict[int, Plane]:
    """{chip: Plane} of the `/device:TPU:<n>` planes of an xplane file,
    only the lines named (all by default) of only the chips named."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[int, Plane] = {}
    # XSpace: planes 1
    for num, wire, v in _fields(buf):
        if num != 1 or wire != 2:
            continue
        name = _plane_name(v)
        m = DEVICE_PLANE.match(name)
        if not m or (chips is not None and int(m.group(1)) not in chips):
            continue
        out[int(m.group(1))] = _plane(v, name, lines)
    return out
