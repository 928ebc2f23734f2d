"""Device time under one of the program's name scopes (`fluid.name_scope`,
which core/compiler.py::lower_op turns into XLA metadata scopes).

The profiler keeps each operation's scope path (its `op_name`:
`jit(fn)/transpose(jvp(/loop.body/recurrence))/while/body/...`) as a stat
of the operation's *metadata*, which jax.profiler.ProfileData does not hand
out; so the metadata is read from the .xplane.pb itself, with the few lines
of protobuf wire format that takes, and the events (names and times) from
ProfileData as everywhere else.  The time under a scope is the union of the
intervals of the first device's operations, inside `bench.window`, whose
scope path contains the scope's name: an operation that holds others (the
`while` of a scan and the operations of its body) counts once.

A fusion has the scope of its root operation, so a fusion across two
scopes goes to one of them whole.  A trace of a program without the scope
gives None: the readers then report nothing.
"""

from __future__ import annotations

import os

if __package__ in (None, ""):  # run as a file: find the sibling modules
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.harness import trace
else:
    from . import trace

# the stat of an operation's metadata that holds its scope path
SCOPE_STATS = ("tf_op", "hlo_op_name", "op_name")


def _varint(buf: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of one protobuf message: an int for a varint,
    bytes for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield num, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")


def _map_entry(buf: bytes) -> tuple:
    key = val = None
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def op_scopes(xspace: bytes) -> dict:
    """{device index: {operation's name as the trace has it: scope path}}
    from the event metadata of each /device:TPU:<n> plane (XSpace.planes =
    1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7; XStatMetadata.name = 2)."""
    out = {}
    for num, plane in _fields(xspace):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode("utf-8", "replace")
            elif f == 4:
                events.append(_map_entry(v)[1])
            elif f == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (x.decode("utf-8", "replace")
                     for g, x in _fields(meta or b"") if g == 2), "")
        m = trace.DEVICE_PLANE.match(name)
        if not m:
            continue
        scopes = {}
        for meta in events:
            op, path = None, None
            for f, v in _fields(meta or b""):
                if f == 2:
                    op = trace.op_name(v.decode("utf-8", "replace"))
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) in SCOPE_STATS:
                        ref = stat.get(7)
                        path = (stat[5].decode("utf-8", "replace")
                                if 5 in stat else stat_names.get(ref, ""))
            if op is not None and path:
                scopes[op] = path
        out[int(m.group(1))] = scopes
    return out


def scope_ms(profile, scopes: dict, scope: str):
    """Device ms inside `bench.window` in the first device's operations
    whose scope path contains `scope`, or None where no operation has it."""
    win = trace.window(profile)
    if win is None or not scopes:
        return None
    t0, t1 = win
    paths = scopes[min(scopes)]
    ops = [(s, e) for n, s, e in trace.first_device_ops(profile, t0, t1)
           if scope in paths.get(n, "")]
    if not ops:
        return None
    return sum(e - s for s, e in trace.union(ops)) / 1e6


def newest(root: str | None = None):
    """(profile, scopes) of the newest trace under bench_out/trace."""
    found = trace.newest_parsed(root)
    if found is None:
        return None
    return found.profile, found.once("op_scopes", lambda p: op_scopes(p.raw))


def per_step_ms(obs, scope: str):
    """Device ms a traced step under `scope` (kind train), or None."""
    if obs.get("kind") != "train" or not obs.get("trace_steps") \
            or obs.get("trace") is None:
        return None
    found = newest()
    if found is None:
        return None
    ms = scope_ms(found[0], found[1], scope)
    return None if ms is None else ms / obs["trace_steps"]


if __name__ == "__main__":
    import json
    import sys

    prof, scopes = newest(sys.argv[1] if len(sys.argv) > 1 else None)
    print(json.dumps({s: scope_ms(prof, scopes, s)
                      for s in sys.argv[2:] or ["loop.body", "loop.heads"]}))
