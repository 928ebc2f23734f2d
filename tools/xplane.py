"""Minimal xplane.pb reader (no tensorflow/protobuf dependency).

jax.profiler.trace writes an XSpace protobuf
(tensorflow/core/profiler/protobuf/xplane.proto).  This module decodes just
enough of the wire format to aggregate per-op device time: planes ->
lines -> events, with event names resolved through each plane's
event_metadata map.  Used by tools/tpu_profile.py; kept separate so tests
can exercise the parser against a synthetic buffer.

Wire format: each field is (field_number << 3 | wire_type) varint, then a
varint (type 0) or length-delimited bytes (type 2).  Fixed64/fixed32 are
skipped.  Field numbers used (stable across TF/JAX releases):
  XSpace.planes=1; XPlane.name=2 .lines=3 .event_metadata=4;
  XLine.name=2 .events=4; XEvent.metadata_id=1 .duration_ps=3;
  XEventMetadata map entry: key=1, value=2; XEventMetadata.id=1 .name=2
  .display_name=4.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

__all__ = ["parse_xspace", "device_op_times"]


def _decode_varint(buf: bytes, pos: int) -> Tuple[int, int]:
    result = 0
    shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """Yield (field_number, wire_type, value) over a message buffer."""
    pos = 0
    n = len(buf)
    while pos < n:
        tag, pos = _decode_varint(buf, pos)
        field, wt = tag >> 3, tag & 7
        if wt == 0:  # varint
            val, pos = _decode_varint(buf, pos)
        elif wt == 2:  # length-delimited
            ln, pos = _decode_varint(buf, pos)
            val = buf[pos:pos + ln]
            pos += ln
        elif wt == 5:  # fixed32
            val = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        elif wt == 1:  # fixed64
            val = int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        else:
            raise ValueError(f"unsupported wire type {wt} at {pos}")
        yield field, wt, val


def _parse_event(buf: bytes) -> Tuple[int, int]:
    meta_id = dur_ps = 0
    for f, _, v in _fields(buf):
        if f == 1:
            meta_id = v
        elif f == 3:
            dur_ps = v
    return meta_id, dur_ps


def _parse_line(buf: bytes) -> Tuple[str, List[Tuple[int, int]]]:
    name = ""
    events: List[Tuple[int, int]] = []
    for f, _, v in _fields(buf):
        if f == 2:
            name = v.decode("utf-8", "replace")
        elif f == 4:
            events.append(_parse_event(v))
    return name, events


def _parse_event_metadata(buf: bytes) -> Tuple[int, str]:
    mid = 0
    name = disp = ""
    for f, _, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            name = v.decode("utf-8", "replace")
        elif f == 4:
            disp = v.decode("utf-8", "replace")
    return mid, disp or name


def _parse_plane(buf: bytes) -> dict:
    name = ""
    lines = []
    meta: Dict[int, str] = {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = v.decode("utf-8", "replace")
        elif f == 3:
            lines.append(_parse_line(v))
        elif f == 4:  # map<int64, XEventMetadata> entry
            key = 0
            val = b""
            for ef, _, ev in _fields(v):
                if ef == 1:
                    key = ev
                elif ef == 2:
                    val = ev
            mid, mname = _parse_event_metadata(val)
            meta[mid or key] = mname
    return {"name": name, "lines": lines, "event_metadata": meta}


def parse_xspace(data: bytes) -> List[dict]:
    """XSpace bytes -> list of plane dicts."""
    return [_parse_plane(v) for f, _, v in _fields(data) if f == 1]


def device_op_times(
    data: bytes,
    device_tokens: Tuple[str, ...] = ("tpu", "/device", "gpu"),
    line_name: str = "XLA Ops",
    strict_line: bool = False,
) -> Dict[str, float]:
    """Sum event durations (microseconds) per op name over device planes.

    Only the per-op line (default 'XLA Ops') is aggregated — the 'Steps'
    line counts wall-clock between dispatches and 'XLA Modules' double-counts
    whole executables.  When the named line is absent a plane falls back to
    all of its lines UNLESS strict_line is set (callers asking for a
    specific line, e.g. 'Async XLA Ops', must get {} rather than a
    fabricated total).  Falls back to all planes when no device plane
    matches (pure CPU traces name their plane '/host:CPU')."""
    planes = parse_xspace(data)
    chosen = [
        p for p in planes
        if p["lines"] and any(t in p["name"].lower() for t in device_tokens)
    ]
    if not chosen:
        chosen = [p for p in planes if p["lines"]]
    totals: Dict[str, float] = {}
    for plane in chosen:
        meta = plane["event_metadata"]
        lines = [le for le in plane["lines"] if le[0] == line_name]
        if not lines:
            if strict_line:
                continue
            lines = plane["lines"]
        for _, events in lines:
            for mid, dur_ps in events:
                name = meta.get(mid, f"#{mid}")
                totals[name] = totals.get(name, 0.0) + dur_ps / 1e6
    return totals
