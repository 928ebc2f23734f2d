"""Executor: run a Program on a Place.

Parity target: python/paddle/fluid/executor.py:256 (Executor.run :375) and
the C++ serial interpreter it drives (paddle/fluid/framework/executor.cc:203).
The reference interprets ops one-by-one against a Scope; here Executor.run
lowers the whole main block to ONE jitted XLA computation via
core.compiler.CompiledBlock (cached per (program, feeds, fetches) signature —
mirroring the reference's program cache), feeds host arrays in, and writes
updated persistable state (params, optimizer accumulators, the PRNG stream)
back to the Scope.  Buffer donation on the state tuple gives the in-place
param-update semantics of the reference's optimizer ops without mutation.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import numpy as np

from . import amp
from .. import flags
from .. import observability as _obs
from ..observability.compiles import default_compile_log as _compile_log
from ..observability.stepstats import (
    DISPATCH as _T_DISPATCH,
    DISPATCHED as _T_DISPATCHED,
    FETCH as _T_FETCH,
    READY as _T_READY,
)
from .compiler import CompiledBlock
from .framework import Program, Variable, default_main_program
from .lod import LoDValue
from .place import CPUPlace, Place, TPUPlace, device_is_tpu
from .dtypes import checked_feed_cast
from .proto import VarType, dtype_to_numpy, dtype_to_runtime
from .scope import Scope, global_scope

__all__ = ["Executor", "RNG_STATE_VAR"]

RNG_STATE_VAR = "@rng_key@"


def _as_feed_value(value, var_desc=None):
    if hasattr(value, "_as_feed"):  # fluid.Tensor / fluid.LoDTensor shim
        value = value._as_feed()
    if isinstance(value, LoDValue):
        if var_desc is not None and isinstance(value.data, np.ndarray):
            want = dtype_to_numpy(var_desc.dtype)
            try:
                cast = checked_feed_cast(value.data, want, var_desc.name)
            except TypeError:
                cast = value.data
            if cast is not value.data:
                value = LoDValue(cast, value.lengths, value.sub_lengths)
        return value
    if isinstance(value, jax.Array):
        # already on device: pass through untouched (np.asarray would force a
        # blocking device->host copy and re-upload — the round 1 bench bug)
        return value
    arr = np.asarray(value)
    if var_desc is not None and var_desc.type == VarType.LOD_TENSOR:
        want = dtype_to_numpy(var_desc.dtype)
        try:
            # range-checked narrow of int64 feeds (OverflowError past
            # 2**31 unless x64 is on — core/dtypes.py policy)
            arr = checked_feed_cast(arr, want, var_desc.name)
        except TypeError:
            pass
    return arr


def _block_state_names(
    program: Program, block_idx: int = 0, extra: Sequence[str] = ()
) -> List[str]:
    """All persistable vars a block touches (plus explicitly fetched ones) —
    the cross-run state threaded through the jitted step."""
    block = program.desc.block(block_idx)
    names: Set[str] = set()
    referenced: Set[str] = set(extra)
    for op in block.ops:
        referenced.update(op.input_arg_names())
        referenced.update(op.output_arg_names())
    for name, var in block.vars.items():
        if var.persistable and name in referenced:
            names.add(name)
    return sorted(names)


def _read_before_write(program: Program, state_names: Sequence[str], feed_names) -> Set[str]:
    block = program.desc.block(0)
    written: Set[str] = set(feed_names)
    rbw: Set[str] = set()
    states = set(state_names)
    for op in block.ops:
        for n in op.input_arg_names():
            if n in states and n not in written:
                rbw.add(n)
        written.update(op.output_arg_names())
    return rbw


class _RunPlan:
    """Per-(program, feeds, fetches) run bookkeeping shared by the serial
    Executor and ParallelExecutor, computed once and cached beside the
    CompiledBlock: which persistable state threads through the step, and
    which of it must already exist in the scope."""

    def __init__(self, program: Program, feed_names, fetch_names):
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.state_names = _block_state_names(program, extra=fetch_names)
        self.rbw = _read_before_write(program, self.state_names, self.feed_names)
        # ParallelExecutor: the shardings its call wants on its mesh, (the
        # feeds', the state values' and the key's), built once with the
        # entry and read every step
        self.shardings = None

    def feed_values(self, feed, block0):
        return tuple(
            _as_feed_value(feed[n], block0.vars.get(n)) for n in self.feed_names
        )

    def state_values(self, scope: Scope, block0):
        vals = []
        for n in self.state_names:
            v = scope.find_var(n)
            if v is None:
                if n in self.rbw:
                    raise RuntimeError(
                        f"persistable variable '{n}' is read before it is "
                        "written but is not initialized in the scope; run the "
                        "startup program first"
                    )
                vd = block0.vars[n]
                shape = [d if d >= 0 else 1 for d in vd.shape] or [1]
                v = np.zeros(shape, dtype=dtype_to_runtime(vd.dtype))
            vals.append(v)
        return tuple(vals)

    def rng_value(self, scope: Scope, program: Program):
        rng = scope.find_var(RNG_STATE_VAR)
        if rng is None:
            # FLAGS_cpu_deterministic holds by construction: unseeded
            # programs use PRNGKey(0) and every lowering draws from the
            # counter-based stream; XLA reductions are run-to-run
            # deterministic (see flags.py)
            rng = jax.random.PRNGKey(program.random_seed or 0)
        return rng

    def write_back(self, scope: Scope, new_states, new_rng) -> None:
        for n, v in zip(self.state_names, new_states):
            if v is not None:
                scope.set_var(n, v)
        scope.set_var(RNG_STATE_VAR, new_rng)

    def convert_fetches(self, fetches, block0, return_numpy: bool):
        return [
            Executor._convert_fetch(val, block0.vars.get(name), return_numpy)
            for name, val in zip(self.fetch_names, fetches)
        ]


def _check_nan_inf(plan, fetches, new_states) -> None:
    """FLAGS_check_nan_inf: post-step scan of fetches + persistable state
    (reference: framework/operator.cc:777 checks every op output; the
    one-XLA-program design checks once per step instead, still naming the
    first offending variable)."""
    from .. import flags as _flags

    if not _flags.flag("check_nan_inf"):
        return
    import jax.numpy as jnp

    def bad_leaves(v):
        for leaf in jax.tree_util.tree_leaves(v):
            arr = jnp.asarray(leaf)
            if jnp.issubdtype(arr.dtype, jnp.floating) and not bool(
                jnp.all(jnp.isfinite(arr))
            ):
                return True
        return False

    for name, v in zip(plan.fetch_names, fetches):
        if v is not None and bad_leaves(v):
            raise RuntimeError(
                f"FLAGS_check_nan_inf: fetch '{name}' contains nan/inf "
                "after this step"
            )
    for name, v in zip(plan.state_names, new_states):
        if v is not None and bad_leaves(v):
            raise RuntimeError(
                f"FLAGS_check_nan_inf: variable '{name}' contains nan/inf "
                "after this step"
            )


def cached_entry(cache, key, fp, build, use_cache: bool = True):
    """The ONE copy of the fingerprint-validated lookup both executors'
    run makes: (entry, hit), the entry being (fp,) + build() on a miss,
    built under the `compile` span, which covers the block's construction
    (a CompiledBlock, a closure, a jax.jit object: milliseconds) and no
    compilation: jax traces, lowers and builds or loads under the first
    `executor.dispatch`.  A miss of a table that is kept, and only that,
    opens a first run in the set-up log (observability/compiles.py), which
    run_step closes once that run's fetch is on the host; with
    `use_cache=False` every step is a miss and none is a first run (its
    executables are in the log all the same).  An in-place desc mutation (another fp
    under the same key) rebuilds and replaces the stale entry.  (The
    reference keys on the Program object, executor.py _get_program_cache —
    unsound here because descs mutate in place.)"""
    entry = cache.get(key) if use_cache else None
    hit = entry is not None and entry[0] == fp
    if not hit:
        program = fp.hex()[:12]
        if use_cache:
            _compile_log().open_run(program)
        with _obs.span("compile", program=program):
            entry = (fp,) + tuple(build())
        if use_cache:
            cache[key] = entry
    if flags.flag("FLAGS_observability"):
        _obs.record_compile_cache(hit=hit)
    return entry, hit


def in_place(v, want) -> bool:
    """The staging rule's predicate: `v` is already where the call wants it,
    a jax.Array COMMITTED to the sharding `want`.  Stateless: it reads the
    value, not a memory of what the last step returned.  Not in place: a
    host value (fresh from the startup program, io.load_persistables,
    scope.set_var), an uncommitted array (jnp.zeros, a fresh PRNGKey), an
    array committed elsewhere (a scope shared by executors on two places;
    a single-device array under a mesh).  Under half a microsecond a value
    (PERF.md 3)."""
    return getattr(v, "committed", False) and v.sharding == want


def stage_values(vals, wants):
    """The ONE staging rule of Executor and ParallelExecutor:
    (values, wanted placement) -> (staged values, how many were placed).
    `wants` is one Sharding for every value or one for each.
    A value in place goes on as the very object it came as; only the rest
    go to jax.device_put, in one call.  That call is what keeps a step to
    ONE executable: committed-ness is part of jax's lowering key, so a
    host-numpy state (first step) and the committed arrays the step
    returns (every later step) must reach the jit call alike, committed.
    It is not free on values in place (this jax hands a bare Device a new
    Array for every input, 16 us a value on the chip), hence the
    predicate."""
    if isinstance(wants, jax.sharding.Sharding):
        wants = itertools.repeat(wants)
    todo = [(i, w) for i, (v, w) in enumerate(zip(vals, wants))
            if not in_place(v, w)]
    if not todo:
        return vals, 0
    placed = jax.device_put([vals[i] for i, _ in todo],
                            [w for _, w in todo])
    staged = list(vals)
    for (i, _), v in zip(todo, placed):
        staged[i] = v
    return tuple(staged), len(todo)


def staged_args(feed_vals, state_vals, rng, wants):
    """A call's three arguments through stage_values, as run_step's
    `stage` returns them: (feed_vals, state_vals, rng, moved)."""
    vals, moved = stage_values(feed_vals + state_vals + (rng,), wants)
    n = len(feed_vals)
    return vals[:n], vals[n:-1], vals[-1], moved


def cost_pending(entry) -> bool:
    """Whether FLAGS_observability_cost still wants this entry's
    once-a-program costing."""
    return (flags.flag("observability_cost") != "off"
            and not getattr(entry[1], "_obs_cost_done", False))


def abstract_args(*args):
    """A call's arguments as the once-a-program costing reads them: every
    jax.Array as its shape, dtype and sharding (what a lowering keys on),
    so that costing after the step holds no buffer the step consumed."""
    def abstract(v):
        if isinstance(v, jax.Array):
            return jax.ShapeDtypeStruct(v.shape, v.dtype,
                                        sharding=v.sharding,
                                        weak_type=v.weak_type)
        return v

    return jax.tree_util.tree_map(abstract, args)


# `seq` of `executor.step`: the process's steps by number, so that a reader
# of the spans knows step k from step k + 1 by identity and not by order
_STEP_SEQ = itertools.count()


def run_step(kind, program, scope, lookup, feeds, stage, placed, device,
             return_numpy, donated=False, sentinel=None, cost=None):
    """The ONE copy of a step's sequence, for Executor and
    ParallelExecutor: plan, stage, dispatch, commit, fetch, each a span
    under `executor.step` (observability/tracing.py: on the profiler's
    clock always, in the ring under FLAGS_observability).  Where the fetch
    converts to the host it is `executor.wait` (until every fetched value
    is ready: its end is the host's "device done" mark) and then
    `executor.copy`.  The same boundaries go to the step log, flag or no
    flag (observability/stepstats.py: one record a step under the span's
    `seq`, eight clock reads).  The frame lets go of what the step
    consumed as soon as the scope holds what the step produced: at the end of
    `executor.commit` it drops the staged state and key, the last
    references to the arrays the call took by donation, so that their
    release (a call into the runtime a shard) runs on the host while the
    device computes the step just enqueued, and not after the wait, when
    the device has nothing queued.  The callers open `executor.run` around
    all of it; what follows `executor.step` inside that span is the frames
    returning, no more.  The callers give what differs between them:

    lookup() -> ((fp, call, plan), hit); a miss nests the `compile` span
        and makes this step a first run of the set-up log, closed after
        `executor.fetch`; `executor.dispatch` says which step made
        executables (`executables`, `cache_misses`, `compile_s`, only where
        the log grew under it: two integer reads a steady step)
    feeds(plan, block0) -> the feed values as the plan phase leaves them
    stage(plan, block0, feed_vals, state_vals, rng) ->
        (feed_vals, state_vals, rng, moved): the placement the caller
        wants, asked of stage_values; `moved` is how many values went to
        jax.device_put, the other n - moved were in place
    placed: the context the call is made in (a default device, a mesh)
    sentinel(plan, fetches, new_states) -> whether to skip the write-back
    cost(entry, feed_vals, state_vals, rng): once-a-program attribution,
        made after the step so that it is in no step's time, from the
        arguments' abstract shapes (abstract_args): whether the entry
        still wants it (cost_pending) is settled before the arrays go
    """
    from ..resilience import faultinject

    skipped = False
    steps = _obs.step_stats()
    seq = next(_STEP_SEQ)
    with _obs.span("executor.step", kind=kind, seq=seq) as step:
        rec = steps.begin(seq, kind)
        with _obs.span("executor.plan") as sp:
            entry, hit = lookup()
            _, call, plan = entry
            block0 = program.desc.block(0)
            feed_vals = feeds(plan, block0)
            state_vals = plan.state_values(scope, block0)
            rng = plan.rng_value(scope, program)
            sp.set(cache="hit" if hit else "miss")
        n_given = len(plan.feed_names) + len(state_vals) + 1
        step.set(n_state=len(state_vals), n_feed=len(plan.feed_names))
        with _obs.span("executor.stage") as sp:
            feed_vals, state_vals, rng, moved = stage(
                plan, block0, feed_vals, state_vals, rng)
            sp.set(n=n_given, moved=moved)
        log = _compile_log()
        compiled = log.count
        steps.mark(rec + _T_DISPATCH)
        with _obs.span("executor.dispatch") as sp, placed:
            fetches, new_states, new_rng = call(feed_vals, state_vals, rng)
            if log.count != compiled:
                # this step made executables: `executables`,
                # `cache_misses`, `compile_s`, on the step that paid
                sp.set(**log.since(compiled))
        steps.mark(rec + _T_DISPATCHED)
        with _obs.span("executor.commit") as sp:
            fetches = faultinject.nan_fetches(plan.fetch_names, fetches)
            if sentinel is not None and sentinel(plan, fetches, new_states):
                # skip the bad step AMP-loss-scaler style: nothing is
                # written back, the previous params stay live (donation
                # is off under FLAGS_check_numerics)
                skipped = True
                sp.set(skipped=1)
            else:
                plan.write_back(scope, new_states, new_rng)
                _check_nan_inf(plan, fetches, new_states)
            cost_args = None
            if (cost is not None and not skipped and _obs.enabled()
                    and cost_pending(entry)):
                cost_args = abstract_args(feed_vals, state_vals, rng)
            # the scope holds the step's state (the new one, or on a
            # skipped step still the old): the frame's references go here,
            # under the running step, and not after the wait
            del state_vals, rng
        if return_numpy:
            steps.mark_cpu(rec + _T_FETCH)
        else:
            steps.mark(rec + _T_FETCH)
        with _obs.span("executor.fetch") as sp:
            if return_numpy:
                with _obs.span("executor.wait"):
                    # the copy is asked for first, where np.asarray asked
                    # for it when it was the one to wait: it then follows
                    # the step on the device, and does not start only once
                    # the host has heard of the step's end
                    for v in jax.tree_util.tree_leaves(fetches):
                        if isinstance(v, jax.Array):
                            v.copy_to_host_async()
                    jax.block_until_ready(fetches)
                steps.mark_cpu(rec + _T_READY)
                with _obs.span("executor.copy"):
                    out = plan.convert_fetches(fetches, block0, True)
            else:
                out = plan.convert_fetches(fetches, block0, False)
            # a step that missed the table, or under which the set-up log
            # grew, is no reference for the steps around it
            steps.end(rec, not hit or log.count != compiled)
            sp.set(n=len(out))
        if not hit:
            log.close_run(kind, len(plan.feed_names), len(out),
                          len(plan.state_names))
    if step.seconds is not None:  # FLAGS_observability
        _obs.record_executor_step(step.seconds, donated=donated,
                                  skipped=skipped)
        _obs.record_device_memory(device)
        if cost_args is not None:
            cost(entry, *cost_args)
    return out


class Executor:
    """Serial single-device executor (reference: executor.py:256)."""

    def __init__(self, place: Optional[Place] = None, donate_states: bool = True):
        # donate_states=False keeps state buffers alive across concurrent
        # runs sharing one scope (AsyncExecutor Hogwild threads)
        self.place = place if place is not None else CPUPlace()
        self.donate_states = donate_states
        self._cache: Dict[Tuple, CompiledBlock] = {}
        self._sentinel = None  # FLAGS_check_numerics NaNSentinel, lazy

    def _donate_states_now(self) -> bool:
        # FLAGS_check_numerics skips bad steps by NOT writing state back —
        # the pre-step buffers must stay alive, so donation is off while
        # the sentinel is armed (flags.trace_key() carries the flag, so
        # flipping it lands on a separate compiled entry)
        return self.donate_states and not flags.flag("check_numerics")

    def close(self) -> None:
        self._cache.clear()

    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        feed_var_name: str = "feed",
        fetch_var_name: str = "fetch",
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
    ) -> List[Any]:
        # `executor.run`: the whole call, one a step (a CompiledProgram goes
        # on to ParallelExecutor._run_in_run, which opens none).  Inside it,
        # the trace-time defaults scope: auto conv layout / auto AMP resolve
        # for the ACTUAL device this executor targets; entered around key
        # computation, compilation, and execution so cache keys and traced
        # programs always agree
        with _obs.span("executor.run"), \
                flags.tpu_trace_scope(
                    device_is_tpu(self.place.jax_device())):
            return self._run_scoped(
                program, feed, fetch_list, feed_var_name, fetch_var_name,
                scope, return_numpy, use_program_cache)

    def _run_scoped(
        self,
        program,
        feed,
        fetch_list,
        feed_var_name,
        fetch_var_name,
        scope,
        return_numpy,
        use_program_cache,
    ) -> List[Any]:
        # fluid idiom: exe.run(CompiledProgram(...).with_data_parallel(...), ...)
        if program is not None and hasattr(program, "with_data_parallel"):
            src = getattr(program, "program", None) or default_main_program()
            if feed is None and getattr(src, "_py_readers", None):
                feed = {}
                for r in src._py_readers:
                    feed.update(r._next_batch())
            pe = program._executor_for_scope(scope or global_scope())
            return pe._run_in_run(fetch_list, feed, None, return_numpy)

        program = program or default_main_program()
        if feed is None and getattr(program, "_py_readers", None):
            # feed-less run: pull the next ready batch from the program's
            # py_reader queues (reference: reader ops feeding from
            # LoDTensorBlockingQueue, operators/reader/)
            feed = {}
            for r in program._py_readers:
                feed.update(r._next_batch())
        feed = feed or {}
        fetch_list = list(fetch_list or [])
        scope = scope or global_scope()

        feed_names = sorted(feed)
        fetch_names = [v.name if isinstance(v, Variable) else str(v) for v in fetch_list]
        device = self.place.jax_device()

        # feeds, state and key alike through the one staging rule
        # (stage_values): what the last step returned is in place and goes
        # on as it is; a host batch goes to device_put, which enqueues the
        # copy and returns, so step N's compute overlaps batch N+1's
        # transfer (the reference gets this from double-buffer reader ops,
        # operators/reader/create_double_buffer_reader_op.cc)
        want = jax.sharding.SingleDeviceSharding(device)

        def stage(plan, block0, feed_vals, state_vals, rng):
            return staged_args(feed_vals, state_vals, rng, want)

        return run_step(
            "serial", program, scope,
            lambda: self._cache_entry(program, feed_names, fetch_names,
                                      use_program_cache),
            lambda plan, block0: plan.feed_values(feed, block0),
            stage, jax.default_device(device), device, return_numpy,
            donated=self._donate_states_now(),
            sentinel=self._numerics_tripped, cost=self._maybe_record_cost)

    def _numerics_tripped(self, plan, fetches, new_states) -> bool:
        """FLAGS_check_numerics: whether this step's fetches or new state
        hold a non-finite value; record_trip raises NonFiniteStepError
        after N consecutive trips."""
        if not flags.flag("check_numerics"):
            return False
        from ..resilience.sentinel import NaNSentinel

        if self._sentinel is None:
            self._sentinel = NaNSentinel()
        bad = self._sentinel.first_nonfinite(
            tuple(plan.fetch_names) + tuple(plan.state_names),
            tuple(fetches) + tuple(new_states),
        )
        if bad is None:
            self._sentinel.record_clean()
            return False
        self._sentinel.record_trip(bad)
        return True

    @staticmethod
    def _maybe_record_cost(entry, feed_vals, state_vals, rng) -> None:
        """FLAGS_observability_cost: once per fresh compiled entry (run_step
        asks cost_pending first), record the XLA cost model's bytes/flops
        per step labeled by program fingerprint, so a flag flip that
        recompiles lands on a separate series with no chip."""
        fp, compiled, _ = entry
        mode = flags.flag("observability_cost")
        compiled._obs_cost_done = True  # one attempt, even on failure
        try:
            ca = compiled.cost_analysis(
                feed_vals, state_vals, rng,
                platform="tpu" if mode == "tpu" else None)
            _obs.record_cost(ca, program=fp.hex()[:12], platform=mode)
        except Exception as e:  # costing must never fail the step
            import logging

            logging.getLogger("paddle_tpu").warning(
                "observability_cost=%s attribution failed: %s", mode, e)

    def _cache_entry(self, program, feed_names, fetch_names,
                     use_program_cache: bool = True):
        """The ONE copy of the compiled-program cache key shared by
        _run_scoped and cost_analysis: ((desc fingerprint, compiled, plan),
        hit) keyed on (program id, feeds, fetches, amp policy, trace
        flags), fingerprint-revalidated (cached_entry)."""
        key = (id(program), tuple(feed_names), tuple(fetch_names),
               amp.state_key(), flags.trace_key())

        def build():
            plan = _RunPlan(program, feed_names, fetch_names)
            return CompiledBlock(
                program, 0, plan.feed_names, plan.fetch_names,
                plan.state_names, donate_states=self._donate_states_now(),
            ), plan

        return cached_entry(self._cache, key, program.desc.fingerprint(),
                            build, use_program_cache)

    def cost_analysis(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        platform: Optional[str] = None,
    ) -> dict:
        """XLA cost accounting ({'bytes accessed', 'flops', ...}) of the
        executable this executor would run for (program, feed, fetches) —
        per single step.  Resolves the same trace-scope defaults and cache
        entry as run() (shared _cache_entry), so the analyzed module IS
        the one being timed.  The instrument for validating paper
        HBM-traffic floors (VERDICT r4: nothing had measured bytes/step).

        platform="tpu" forces the CHIP program (TPU trace scope: keep-bf16
        / NHWC auto resolution) and compiles it AOT against a chip-less
        v5e topology (core/aot_tpu.py), returning the TPU compiler's own
        bytes/step on any host — no chip needed."""
        if program is not None and hasattr(program, "with_data_parallel"):
            raise TypeError(
                "cost_analysis takes a plain Program; for a "
                "CompiledProgram pass its .program and note the analysis "
                "covers the serial executable, not the SPMD one")
        if platform not in (None, "tpu"):
            # a typo'd platform must not silently bank host-executable
            # bytes under a TPU-looking label
            raise ValueError(
                f"cost_analysis platform must be None or 'tpu', "
                f"got {platform!r}")
        want_tpu = platform == "tpu"
        with flags.tpu_trace_scope(
                True if want_tpu
                else device_is_tpu(self.place.jax_device())):
            compiled, feed_vals, state_vals, rng = self._resolve_entry(
                program, feed, fetch_list, scope)
            if want_tpu:
                # AOT path: only shapes/dtypes are consumed, no device
                # commit (there is no device)
                return compiled.cost_analysis(
                    feed_vals, state_vals, rng, platform="tpu")
            # same device commit as run(): the analyzed executable must
            # BE the one run() dispatches (an uncommitted key would
            # lower a second, never-reused variant)
            feed_vals, state_vals, rng, _ = staged_args(
                feed_vals, state_vals, rng,
                jax.sharding.SingleDeviceSharding(self.place.jax_device()))
            return compiled.cost_analysis(feed_vals, state_vals, rng)

    def _resolve_entry(
        self,
        program: Optional[Program],
        feed: Optional[Dict[str, Any]],
        fetch_list: Optional[Sequence],
        scope: Optional[Scope],
    ):
        """Resolve (program, feed, fetches) to the SAME cache entry and
        flat values run() would use — shared by cost_analysis() and
        capture_program() so their view can never drift from run()'s."""
        program = program or default_main_program()
        if feed is None and getattr(program, "_py_readers", None):
            # mirror run()'s feed-less py_reader path: pull one batch so
            # the analyzed module has the same feed signature as the one
            # being timed
            feed = {}
            for r in program._py_readers:
                feed.update(r._next_batch())
        feed = feed or {}
        fetch_list = list(fetch_list or [])
        scope = scope or global_scope()
        feed_names = sorted(feed)
        fetch_names = [
            v.name if isinstance(v, Variable) else str(v)
            for v in fetch_list
        ]
        (_, compiled, plan), _ = self._cache_entry(
            program, feed_names, fetch_names)
        block0 = program.desc.block(0)
        feed_vals = plan.feed_values(feed, block0)
        state_vals = plan.state_values(scope, block0)
        rng = plan.rng_value(scope, program)
        return compiled, feed_vals, state_vals, rng

    def capture_program(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
    ):
        """Static-analysis seam: resolve (program, feed, fetches) through
        the SAME cache entry run() would use — TPU trace scope forced, so
        the captured program is the CHIP program (keep-bf16 / NHWC auto
        resolution included) — and return (compiled: CompiledBlock,
        feed_vals, state_vals, rng) without executing anything.
        paddle_tpu.analysis.capture_executor builds its artifact bundle
        from this."""
        with flags.tpu_trace_scope(True):
            return self._resolve_entry(program, feed, fetch_list, scope)

    def tpu_lowering_check(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
    ) -> int:
        """TPU-lower the step this executor would run for (program, feed,
        fetches) on the CURRENT host — no TPU needed (see
        CompiledBlock.tpu_lowering_check) — and return the exported
        module's byte count.  The trace scope is forced to TPU so the
        checked program is the CHIP program (keep-bf16 / NHWC auto
        resolution included), whatever the host backend is."""
        with flags.tpu_trace_scope(True):
            program = program or default_main_program()
            feed = feed or {}
            fetch_list = list(fetch_list or [])
            scope = scope or global_scope()
            feed_names = sorted(feed)
            fetch_names = [
                v.name if isinstance(v, Variable) else str(v)
                for v in fetch_list
            ]
            (_, compiled, plan), _ = self._cache_entry(
                program, feed_names, fetch_names)
            block0 = program.desc.block(0)
            feed_vals = plan.feed_values(feed, block0)
            state_vals = plan.state_values(scope, block0)
            rng = plan.rng_value(scope, program)
            return compiled.tpu_lowering_check(feed_vals, state_vals, rng)

    @staticmethod
    def _restore_declared_dtype(arr: np.ndarray, var_desc) -> np.ndarray:
        """Fetches come back in the runtime width (int64 descs materialize
        as int32 under the default policy); restore the declared numpy
        dtype at the host boundary."""
        if var_desc is None:
            return arr
        want = dtype_to_numpy(var_desc.dtype)
        try:
            if np.dtype(want) != arr.dtype:
                arr = arr.astype(want)
        except TypeError:
            pass
        return arr

    @staticmethod
    def _convert_fetch(val, var_desc, return_numpy: bool):
        from .selected_rows import SelectedRowsValue

        restore = Executor._restore_declared_dtype
        if isinstance(val, SelectedRowsValue):
            return val.to_numpy() if return_numpy else val
        if isinstance(val, LoDValue):
            if return_numpy:
                return LoDValue(
                    restore(np.asarray(val.data), var_desc),
                    np.asarray(val.lengths),
                    tuple(np.asarray(sl) for sl in val.sub_lengths),
                )
            return val
        if not return_numpy:
            return val
        return restore(np.asarray(val), var_desc)


def as_numpy(value):
    """reference: executor.py:66 as_numpy — convert a fetched value (array,
    LoDTensor shim, or LoDValue) to numpy.  Values carrying LoD raise, as
    the reference does, because offsets would be lost silently."""
    if isinstance(value, (list, tuple)):
        return [as_numpy(v) for v in value]
    lod = getattr(value, "lod", None)
    if isinstance(value, LoDValue) or (callable(lod) and lod()):
        raise RuntimeError(
            "Some of your fetched tensors hold LoD information. They can "
            "not be completely cast to Python ndarray. Please set the "
            "parameter 'return_numpy' as 'False' to return LoDTensor itself "
            "directly.")
    return np.asarray(value)


def _fetch_var(name, scope=None, return_numpy=True):
    """reference: executor.py:174 _fetch_var — read one (typically
    persistable) variable's current value straight from a scope."""
    assert isinstance(name, str)
    if scope is None:
        scope = global_scope()
    val = scope.find_var(name)
    assert val is not None, (
        "Cannot find " + name + " in scope. Perhaps you need to make the"
        " variable persistable by using var.persistable = True in your"
        " program.")
    return Executor._convert_fetch(val, None, return_numpy)
