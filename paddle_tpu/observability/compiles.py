"""The set-up log: what a process paid before its first steady step.

Set-up is over before any reader runs, and the benchmark runs with
FLAGS_observability off, so this log is ALWAYS on (one of two: the step
log, stepstats.py, is the other).  It costs a steady step nothing: jax fires its compile events only when something is traced,
lowered, built or loaded, and the executor reads a clock only on a run that
missed its in-memory table (core/executor.py::cached_entry).

Two kinds of entry, both on `time.perf_counter()`:

- a **record** an executable, assembled from the order in which jax fires
  its own monitoring events: `jaxpr_trace_duration` (inner jitted functions
  first, the outermost last), `jaxpr_to_mlir_module_duration`, then
  `compile_requests_use_cache`, then `cache_hits` +
  `cache_retrieval_time_sec` or, at the write, `cache_misses`, closed by
  `backend_compile_duration`, which in this jax WRAPS the retrieval: for a
  loaded executable `backend_s` >= `retrieval_s`.  Fields: `fun` (jax's
  name, `jit(fn)`), `trace_s` (of the function that is lowered: nested
  `pjit` traces lie inside it and are not added again, and an executable
  made inside a trace or a lowering, a constant computed eagerly, is taken
  out of it: it has a record of its own), `lower_s`, `backend_s`, `cache`
  (`hit` | `miss`: asked and not found | `off`: no request went to the
  persistent cache), `retrieval_s`, `t_end`, `run` (the index of the first
  run it fell inside, None outside any), `entry_bytes`, `evicted_bytes`
  (from the cache directory, below).
- a **first run** a miss of an executor's in-memory table (where it keeps
  one: with `use_cache=False` every step is a miss and none is logged, so a
  long process cannot push its start-up out of the ring): `index`, `kind`
  (`serial` | `spmd`), `program` (the fingerprint's 12 hex digits), `t0`
  (before the block is built), `t1` (after `executor.fetch`), `n_feed`,
  `n_fetch`, `n_state`.  The start-up program's is the first that takes no
  feed and fetches nothing, the step program's the first that fetches.

The cache directory beside it: one `os.scandir` of jax's cache directory at
the first request that goes to it and after every write (only a write
evicts).  The difference gives a written record its `entry_bytes` (the
`<module>-<key>-cache` file that appeared) and `evicted_bytes` (the files
that went).  A hit's `entry_bytes` is filled when a
snapshot is taken, from the entries whose `-atime` stamp is newer than the
log's start (jax's LRUCache writes one at every `get` where
JAX_COMPILATION_CACHE_MAX_SIZE is set), matched to records by module name
and order; None where the cache keeps no stamps.

Under FLAGS_observability a record also goes to the tracer's ring as
`compile.trace`, `compile.lower`, `compile.backend` (counts `fun`, `cache`,
`entry_bytes`): `export_run`'s `trace.json` shows them under the step that
paid.  Every field above is read by a `setup_*.train` reader of the
benchmark (benchmark/harness/setup_log.py) or printed by tools/obsdump.py.
"""

from __future__ import annotations

import collections
import os
import re
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.monitoring

from .. import flags as _flags

__all__ = ["CompileLog", "default_compile_log"]

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
WRITE = "/jax/compilation_cache/cache_misses"  # fired at the cache's put
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_TIMED = (TRACE, LOWER, BACKEND)
_WRAPPED = re.compile(r"\w+\((.*)\)$")
_ENTRY = "-cache"
_STAMP = "-atime"


def _module_name(fun: str) -> str:
    """jax's module name for `fun` (`jit(step_fn)` -> `jit_step_fn`), the
    head of its cache entries' file names (interpreters/mlir.py)."""
    return re.sub(r"[^\w.-]", "_", fun).rstrip("_")


def _entry_module(file_name: str) -> str:
    return file_name[:-len(_ENTRY)].rsplit("-", 1)[0]


def cache_dir() -> Optional[str]:
    """The directory jax's persistent cache is kept in, None where it is
    off."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir or None


def list_entries(path: Optional[str]) -> Optional[Dict[str, int]]:
    """{file name: bytes} of the cache's entries ({} before jax has made
    the directory), None where the cache is off."""
    if not path:
        return None
    out = {}
    try:
        with os.scandir(path) as it:
            for e in it:
                if e.name.endswith(_ENTRY):
                    try:
                        out[e.name] = e.stat().st_size
                    except OSError:  # evicted by another process meanwhile
                        pass
    except OSError:
        pass
    return out


class _Pending(threading.local):
    """What jax has fired on this thread since the last closing event."""

    def __init__(self):
        # the traces, lowerings and builds that have begun and not ended,
        # outermost first: [event, the seconds of whole records inside it]
        self.open: List[list] = []
        # depth -> (name, seconds, end) of the trace that last ended there:
        # a lowering at a depth follows the trace at that depth
        self.traced: Dict[int, tuple] = {}
        self.reset()

    def reset(self):
        self.fun = None
        self.trace = self.lower = None      # (seconds, end)
        self.requested = self.hit = self.written = False
        self.retrieval_s = None


class CompileLog:
    """Bounded like Tracer: the newest `capacity` records and first runs,
    `dropped` counts the records that went."""

    def __init__(self, capacity: int = 4096):
        self._lock = threading.Lock()
        self._records = collections.deque(maxlen=int(capacity))
        self._runs = collections.deque(maxlen=int(capacity))
        self._pending = _Pending()
        self._open = threading.local()
        self._listening = False
        self.clear()

    def clear(self) -> None:
        with self._lock:
            self._records.clear()
            self._runs.clear()
            self.count = 0          # records ever appended: what a step reads
            self.dropped = 0
            self._n_runs = 0
            self._listing = None    # the cache directory as last listed
            self._claimed = set()   # entries a record has: written, or hit
            self._hits_filled = 0   # `count` when hits were last matched
            self.imported_at = None
            self.started_ns = time.time_ns()

    def listen(self) -> None:
        """Register with jax.monitoring, once a process."""
        if self._listening:
            return
        self._listening = True
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_scalar_listener(self._on_begin)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def note_imported(self) -> None:
        """`paddle_tpu/__init__.py`'s last line."""
        self.imported_at = time.perf_counter()

    # -- jax's events -------------------------------------------------------

    def _on_event(self, event: str, **kw) -> None:
        p = self._pending
        if event == REQUEST:
            p.requested = True
            if self._listing is None:
                now = list_entries(cache_dir())
                with self._lock:
                    if self._listing is None:
                        self._listing = now
        elif event == HIT:
            p.hit = True
        elif event == WRITE:
            p.written = True

    def _on_begin(self, event: str, value, **kw) -> None:
        # jax's scalar at the START of a trace, a lowering, a build
        if event in _TIMED:
            self._pending.open.append([event, 0.0])

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        p = self._pending
        if event in _TIMED:
            # an executable made INSIDE this one's trace or lowering (a
            # constant computed eagerly) has its own record: not twice
            if p.open and p.open[-1][0] == event:
                seconds = max(0.0, seconds - p.open.pop()[1])
            now = time.perf_counter()
            if event == TRACE:
                p.traced[len(p.open)] = (kw.get("fun_name"), seconds, now)
            elif event == LOWER:
                p.fun = kw.get("fun_name")
                p.lower = (seconds, now)
                # `jit(fn)` is lowered from the trace of `fn`, the last one
                # that ended at this depth: nested `pjit` traces end deeper
                name, *trace = p.traced.pop(len(p.open), (None,))
                m = _WRAPPED.match(p.fun or "")
                if m and m.group(1) in (name, "<unknown>"):
                    p.trace = tuple(trace)
            else:
                self._close(kw.get("fun_name"), seconds, now)
        elif event == RETRIEVAL:
            p.retrieval_s = seconds

    def _close(self, fun, backend_s: float, t_end: float) -> None:
        p = self._pending
        same = p.fun == fun
        trace = p.trace if same else None
        lower = p.lower if same else None
        rec = {
            "fun": fun,
            "trace_s": trace[0] if trace else 0.0,
            "lower_s": lower[0] if lower else 0.0,
            "backend_s": backend_s,
            "cache": "hit" if p.hit else "miss" if p.requested else "off",
            "retrieval_s": p.retrieval_s, "t_end": t_end, "run": None,
            "entry_bytes": None, "evicted_bytes": None,
        }
        written = p.written
        p.reset()
        whole = rec["trace_s"] + rec["lower_s"] + backend_s
        for frame in p.open:
            frame[1] += whole
        if written:
            self._after_write(rec)
        with self._lock:
            if len(self._records) == self._records.maxlen:
                self.dropped += 1
            self._records.append(rec)
            self.count += 1
        if _flags._VALUES["FLAGS_observability"]:
            self._emit(rec, trace, lower)

    def _after_write(self, rec: dict) -> None:
        now = list_entries(cache_dir())
        if now is None:
            return
        with self._lock:  # listeners run on whichever thread compiled
            before, self._listing = self._listing, now
            if before is None:
                return
            new = [n for n in now if n not in before]
            mine = [n for n in new
                    if _entry_module(n) == _module_name(rec["fun"] or "")]
            if len(mine) == 1 or len(new) == 1:
                name = (mine or new)[0]
                rec["entry_bytes"] = now[name]
                self._claimed.add(name)
            rec["evicted_bytes"] = sum(
                size for n, size in before.items() if n not in now)

    def _emit(self, rec: dict, trace, lower) -> None:
        from . import default_tracer

        counts = {"fun": rec["fun"], "cache": rec["cache"]}
        if rec["entry_bytes"] is not None:
            counts["entry_bytes"] = rec["entry_bytes"]
        tracer = default_tracer()
        for name, part in (("compile.trace", trace), ("compile.lower", lower),
                           ("compile.backend",
                            (rec["backend_s"], rec["t_end"]))):
            if part:
                tracer.record(name, part[1] - part[0], part[1], **counts)

    # -- the executor's first runs ------------------------------------------

    def open_run(self, program: str) -> None:
        """A miss of an executor's in-memory table, before the block is
        built.  A run that never closes (capture_program builds and runs
        nothing) is forgotten at the next one."""
        o = self._open
        o.program = program
        with self._lock:
            o.index = self._n_runs
            self._n_runs += 1
        o.t0 = time.perf_counter()

    def close_run(self, kind: str, n_feed: int, n_fetch: int,
                  n_state: int) -> None:
        """That run's fetch is on the host."""
        o = self._open
        index = getattr(o, "index", None)
        if index is None:
            return
        run = {"index": index, "kind": kind, "program": o.program,
               "t0": o.t0, "t1": time.perf_counter(), "n_feed": n_feed,
               "n_fetch": n_fetch, "n_state": n_state}
        o.index = None
        with self._lock:
            self._runs.append(run)
            for r in reversed(self._records):
                if r["t_end"] < run["t0"]:
                    break
                if r["run"] is None:
                    r["run"] = index

    # -- readers ------------------------------------------------------------

    def since(self, count: int) -> dict:
        """What `executor.dispatch` sets on its span where the log grew
        under it: the records appended after `count`."""
        with self._lock:
            recs = list(self._records)[-(self.count - count):]
        return {
            "executables": len(recs),
            "cache_misses": sum(r["cache"] == "miss" for r in recs),
            "compile_s": sum(r["trace_s"] + r["lower_s"] + r["backend_s"]
                             for r in recs),
        }

    def _fill_hits(self) -> None:
        """`entry_bytes` of the hits that lack it, from the stamps the
        cache wrote at their `get`.  Under `_lock`: a reader's listing, after
        set-up."""
        if self._hits_filled == self.count:
            return
        self._hits_filled = self.count
        hits = [r for r in self._records
                if r["cache"] == "hit" and r["entry_bytes"] is None]
        path = cache_dir()
        entries = list_entries(path) if hits else None
        if not entries:
            return
        stamped: Dict[str, List[tuple]] = {}
        for name, size in entries.items():
            if name in self._claimed:
                continue
            try:
                with open(os.path.join(
                        path, name[:-len(_ENTRY)] + _STAMP), "rb") as f:
                    stamp = int.from_bytes(f.read(8), "little")
            except OSError:
                continue
            if stamp > self.started_ns:
                stamped.setdefault(_entry_module(name), []).append(
                    (stamp, name, size))
        for files in stamped.values():
            files.sort()
        for r in hits:
            files = stamped.get(_module_name(r["fun"] or ""))
            if files:
                _, name, r["entry_bytes"] = files.pop(0)
                self._claimed.add(name)  # one record a stamp

    def snapshot(self) -> dict:
        """The whole log as plain values (`export_run`'s `setup` section,
        the benchmark's `harness/setup_log.py`)."""
        with self._lock:
            self._fill_hits()
            return {
                "imported_at": self.imported_at,
                "cache_dir": cache_dir(),
                "dropped": self.dropped,
                "records": [dict(r) for r in self._records],
                "runs": [dict(r) for r in self._runs],
            }


_default = CompileLog()


def default_compile_log() -> CompileLog:
    return _default
