"""ParallelExecutor: SPMD training over a device mesh.

Parity target: python/paddle/fluid/parallel_executor.py:32 and the C++ engine
behind it (parallel_executor.cc:191).  The reference clones the op graph onto
every GPU, inserts NCCL allreduce op-handles at each gradient, and runs the
SSA graph with a thread pool.  Here the SAME compiled program used by the
serial Executor is jitted with `in_shardings` over a `DeviceMesh`: feeds are
sharded batch-dim over `dp`, parameters follow their logical sharding spec
(replicated by default), and XLA inserts the psum/all-gather collectives over
ICI that the reference issued through ncclAllReduce
(details/all_reduce_op_handle.cc:83).  Multi-host (the reference's "nccl2"
transpiler mode) is the same code over a process-spanning mesh after
`parallel.init_distributed()`.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import dataclasses

from .. import flags
from .. import observability as _obs
from ..core.compiler import CompiledBlock
from ..core.executor import (
    _RunPlan,
    cached_entry,
    in_place,
    run_step,
    staged_args,
)
from ..core.framework import Program, Variable, default_main_program
from ..core.scope import Scope, global_scope
from .mesh import DeviceMesh, default_mesh
from .strategy import BuildStrategy, ExecutionStrategy, ReduceStrategy, ShardingStrategy

__all__ = ["ParallelExecutor", "CompiledProgram"]


class ParallelExecutor:
    """Data-parallel (and tensor/pipeline-parallel, via sharding specs)
    executor with the reference's constructor/run surface."""

    def __init__(
        self,
        use_cuda: bool = False,
        loss_name: Optional[str] = None,
        main_program: Optional[Program] = None,
        share_vars_from: Optional["ParallelExecutor"] = None,
        exec_strategy: Optional[ExecutionStrategy] = None,
        build_strategy: Optional[BuildStrategy] = None,
        num_trainers: int = 1,
        trainer_id: int = 0,
        scope: Optional[Scope] = None,
        mesh: Optional[DeviceMesh] = None,
        sharding_strategy: Optional[ShardingStrategy] = None,
    ):
        self.program = main_program or default_main_program()
        self.loss_name = loss_name
        self.build_strategy = build_strategy or BuildStrategy()
        self.exec_strategy = exec_strategy or ExecutionStrategy()
        self.sharding_strategy = sharding_strategy or ShardingStrategy()
        if mesh is None:
            if self.sharding_strategy.mesh_axes:
                from .mesh import make_mesh

                mesh = make_mesh(self.sharding_strategy.mesh_axes)
            else:
                mesh = default_mesh()
        self.mesh = mesh
        if share_vars_from is not None:
            scope = scope or share_vars_from.scope
        self.scope = scope or global_scope()
        self._cache: Dict[Tuple, Tuple[CompiledBlock, _RunPlan]] = {}
        # Reduce strategy => shard optimizer/param state over dp (ZeRO-style
        # sibling of the reference's reduce+broadcast placement); copy the
        # strategy so a caller-shared instance isn't mutated
        if self.build_strategy.reduce_strategy == ReduceStrategy.Reduce:
            self.sharding_strategy = dataclasses.replace(
                self.sharding_strategy, shard_optimizer_state=True
            )

    @property
    def device_count(self) -> int:
        return self.mesh.num_devices

    # ------------------------------------------------------------------
    def _state_sharding(self, name: str, block0) -> Any:
        override = self.sharding_strategy.param_shardings.get(name)
        if override is not None:
            return self.mesh.sharding(override)
        vd = block0.vars.get(name)
        if vd is not None and vd.sharding:
            return self.mesh.sharding(vd.sharding)
        # ZeRO-style state sharding (Reduce strategy): split dim 0 of each
        # float state over dp when it divides evenly; XLA all-gathers on use
        if self.sharding_strategy.shard_optimizer_state and vd is not None:
            axis = self.sharding_strategy.batch_axis
            n = self.mesh.axis_size(axis)
            shape = vd.shape
            if n > 1 and shape and shape[0] > 0 and shape[0] % n == 0:
                return self.mesh.sharding([axis] + [None] * (len(shape) - 1))
        return self.mesh.replicated()

    def _feed_sharding(self, name: str, block0) -> Any:
        vd = block0.vars.get(name)
        if vd is not None and vd.sharding:
            return self.mesh.sharding(vd.sharding)
        return self.mesh.batch_sharding(self.sharding_strategy.batch_axis)

    def _compile(self, plan: _RunPlan) -> CompiledBlock:
        feed_names, fetch_names, state_names = (
            plan.feed_names, plan.fetch_names, plan.state_names,
        )
        block0 = self.program.desc.block(0)
        state_shardings = tuple(self._state_sharding(n, block0) for n in state_names)
        feed_shardings = tuple(
            self._feed_sharding(n, block0) for n in feed_names)
        replicated = self.mesh.replicated()
        plan.shardings = (feed_shardings, state_shardings + (replicated,))
        in_shardings = (feed_shardings, state_shardings, replicated)
        # pin state outputs to their input shardings so persistable state
        # round-trips across steps without resharding; fetches gather to
        # replicated (they head to host anyway)
        out_shardings = (
            tuple(self.mesh.replicated() for _ in fetch_names),
            state_shardings,
            self.mesh.replicated(),
        )
        return CompiledBlock(
            self.program,
            0,
            feed_names,
            fetch_names,
            state_names,
            donate_states=True,
            mesh=self.mesh,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
        )

    def run(
        self,
        fetch_list: Optional[Sequence] = None,
        feed: Optional[Any] = None,
        feed_dict: Optional[Dict[str, Any]] = None,
        return_numpy: bool = True,
    ) -> List[Any]:
        with _obs.span("executor.run"):
            return self._run_in_run(fetch_list, feed, feed_dict, return_numpy)

    def _run_in_run(self, fetch_list, feed, feed_dict, return_numpy):
        """run() under the caller's `executor.run`: Executor.run enters
        here with a CompiledProgram, its own span open, so a step has one."""
        # trace-time defaults scope keyed off the mesh's actual devices
        # (see core/executor.py Executor.run)
        with flags.tpu_trace_scope(self._mesh_is_tpu()):
            return self._run_scoped(fetch_list, feed, feed_dict, return_numpy)

    def _mesh_is_tpu(self) -> bool:
        from ..core.place import device_is_tpu

        return device_is_tpu(self._first_device())

    def _run_scoped(
        self,
        fetch_list=None,
        feed=None,
        feed_dict=None,
        return_numpy=True,
    ) -> List[Any]:
        feed = feed if feed is not None else feed_dict
        if isinstance(feed, (list, tuple)):
            # reference accepts one dict per device; global batch == concat.
            # Every per-device dict must feed the same vars, else batches
            # would silently mispair (reference validates the same way).
            if not feed:
                raise ValueError("feed list must contain at least one dict")
            keys = set(feed[0])
            for i, d in enumerate(feed):
                if set(d) != keys:
                    raise ValueError(
                        f"feed dict {i} keys {sorted(d)} != feed dict 0 keys "
                        f"{sorted(keys)}; all per-device feeds must match"
                    )
            feed = {
                k: np.concatenate([np.asarray(d[k]) for d in feed], axis=0)
                for k in sorted(keys)
            }
        feed = feed or {}
        fetch_list = list(fetch_list or [])

        feed_names = sorted(feed)
        fetch_names = [v.name if isinstance(v, Variable) else str(v) for v in fetch_list]

        from ..core import amp
        from .multihost import global_feed_value, is_multiprocess

        key = (tuple(feed_names), tuple(fetch_names), amp.state_key(),
               flags.trace_key())
        multiprocess = is_multiprocess(self.mesh)

        def build():
            plan = _RunPlan(self.program, feed_names, fetch_names)
            return self._compile(plan), plan

        def feeds(plan, block0):
            feed_vals = plan.feed_values(feed, block0)
            if not multiprocess:
                # multihost feeds are per-process shards assembled into
                # the global array in stage() — their local dim 0 is a
                # fraction of the dp axis, so the single-process
                # divisibility contract does not apply
                self._check_batch_divisible(
                    plan.feed_names, feed_vals, block0)
            return feed_vals

        def stage(plan, block0, feed_vals, state_vals, rng):
            feed_sh, state_sh = plan.shardings  # the key's with the state's
            if not multiprocess:
                # the one staging rule (stage_values).  What pjit returned
                # is in place.  The serial->SPMD handoff is not: the serial
                # Executor commits state and key to ITS device, and pjit
                # raises on committed single-device arguments that
                # mismatch in_shardings, so those are resharded to this
                # mesh, once
                return staged_args(feed_vals, state_vals, rng,
                                   feed_sh + state_sh)
            # each process feeds ITS batch shard; jax assembles the global
            # array (reference: per-trainer reader shards under nccl2).
            # State that is not in place is pjit's to place here
            # (device_put to a sharding over processes checks the value
            # equal on each, a collective): the same predicate, counting
            moved = len(feed_vals) + sum(
                not in_place(v, w)
                for v, w in zip(state_vals + (rng,), state_sh))
            feed_vals = tuple(
                global_feed_value(w, v) for w, v in zip(feed_sh, feed_vals))
            return feed_vals, state_vals, rng, moved

        return run_step(
            "spmd", self.program, self.scope,
            lambda: cached_entry(self._cache, key,
                                 self.program.desc.fingerprint(), build),
            feeds, stage, self.mesh.mesh, self._first_device(),
            return_numpy, donated=True)

    def _first_device(self):
        return np.asarray(self.mesh.mesh.devices).ravel()[0]

    def _check_batch_divisible(self, feed_names, feed_vals, block0) -> None:
        """A dim-0-sharded feed whose batch isn't divisible by its mesh
        axes would die inside pjit with a sharding ValueError; raise the
        framework-level message first.  Applies to ANY dim-0 sharding (dp,
        sp, or a ("dp", "sp") tuple — the divisor is the product of those
        axis sizes), not just the configured batch axis.  The reference
        redistributed uneven tail batches at run time
        (data_balance_op_handle.cc) because its per-device graphs took
        ragged sizes; XLA's static shapes make the even-batch contract
        explicit instead — pad or trim the tail batch (reader decorators
        `batch(..., drop_last=True)` do this)."""
        if self.mesh.num_devices <= 1:
            return  # no axis can shard dim 0; skip the per-feed pass
        for name, val in zip(feed_names, feed_vals):
            sh = self._feed_sharding(name, block0)
            spec = getattr(sh, "spec", None)
            if not spec or spec[0] is None:
                continue
            dim0 = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
            div = 1
            for a in dim0:
                div *= self.mesh.axis_size(a)
            if div <= 1:
                continue
            data = getattr(val, "data", val)
            n = np.shape(data)[0] if np.ndim(data) else 0
            if n % div:
                raise ValueError(
                    f"feed '{name}' batch size {n} is not divisible by its "
                    f"dim-0 mesh axes {dim0} ({div} shards); SPMD batch "
                    f"sharding needs equal per-device shards — pad or drop "
                    f"the tail batch (e.g. paddle_tpu.reader decorators "
                    f"batch(..., drop_last=True))"
                )

    def drop_local_exe_scopes(self):  # reference API; scopes are XLA-owned
        pass


class CompiledProgram:
    """fluid.compiler.CompiledProgram-style wrapper: build configuration
    fluently, execute through ParallelExecutor."""

    def __init__(self, program: Optional[Program] = None):
        self.program = program or default_main_program()
        self._pe_kwargs: Dict[str, Any] = {}
        self._pe_by_scope: Dict[int, ParallelExecutor] = {}

    def with_data_parallel(
        self,
        loss_name: Optional[str] = None,
        build_strategy: Optional[BuildStrategy] = None,
        exec_strategy: Optional[ExecutionStrategy] = None,
        share_vars_from=None,
        mesh: Optional[DeviceMesh] = None,
    ) -> "CompiledProgram":
        self._pe_kwargs.update(
            loss_name=loss_name,
            build_strategy=build_strategy,
            exec_strategy=exec_strategy,
            share_vars_from=share_vars_from,
            mesh=mesh,
        )
        self._pe_by_scope.clear()  # reconfiguration invalidates bound executors
        return self

    def executor(self, scope: Optional[Scope] = None) -> ParallelExecutor:
        return ParallelExecutor(
            main_program=self.program, scope=scope, **self._pe_kwargs
        )

    def _executor_for_scope(self, scope: Scope) -> ParallelExecutor:
        """Bound executor per scope, so Executor.run(compiled_prog) keeps its
        XLA compilation cache across steps (and across alternating scopes)."""
        pe = self._pe_by_scope.get(id(scope))
        if pe is None:
            pe = self.executor(scope=scope)
            self._pe_by_scope[id(scope)] = pe
        return pe
