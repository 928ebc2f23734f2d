"""Block -> XLA compiler.

This module replaces the reference's entire kernel-dispatch runtime — the
per-op interpreter loop (paddle/fluid/framework/executor.cc:448), kernel-map
lookup (operator.cc:729), data transforms, streams, and the ir/ fusion passes
— with a single trace: every op in a block is lowered through its registered
JAX rule into one program, jitted once, and XLA owns fusion/scheduling/memory.

Gradient ops (`<type>_grad`, produced by core.backward.append_backward) are
lowered by applying jax.vjp to the forward op's lowering at the point the
forward op runs; the vjp closure is stashed by the forward op's uid and
consumed when the grad op is reached.  This gives exact reverse-mode
gradients for every registered op with zero per-op grad code, while keeping
the reference's "gradients are ops in the program" contract.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from .enforce import op_error_context
from .framework import Block, Program
from .lod import LoDValue
from .proto import OpDesc, VarType, dtype_to_numpy
from .registry import GRAD_OP_SUFFIX, GRAD_SUFFIX, OpRegistry

__all__ = ["LoweringContext", "compile_block", "CompiledBlock"]

# ops handled by the executor itself, not lowered
_SKIP_OPS = {"feed", "fetch"}

# The one name under which a value survives the recomputation of the unit
# around it (framework.recompute_scope): see keep / rematerialised.
KEEP = "recompute.keep"
# one object for every unit: jax keys its lowering caches on a checkpoint's
# policy, and a policy made anew for each unit has every layer's functions
# lowered again (the StableHLO then differs from the bare checkpoint's)
_KEEP_POLICY = jax.checkpoint_policies.save_only_these_names(KEEP)


def keep(*values):
    """`values`, each tagged to survive the recomputation of the unit around
    it.  A kernel tags what its backward reads and its forward had in hand
    where making it again costs far more than holding it (an O(S^2) pass
    for an O(S) array); `rematerialised` saves exactly what was tagged.
    Outside a rematerialised unit a tag does nothing.  The op that lowers
    to such a kernel adds their number to `LoweringContext.kept`."""
    return tuple(checkpoint_name(v, KEEP) for v in values)


def rematerialised(fn, **checkpoint_kwargs):
    """`fn` as a unit of rematerialization, the one way a unit is made:
    the backward computes its activations again from its inputs, all but
    the values an op inside tagged with `keep`."""
    return jax.checkpoint(fn, policy=_KEEP_POLICY, **checkpoint_kwargs)


class LoweringContext:
    """Carried state while lowering one block."""

    def __init__(
        self,
        program: Program,
        block: Block,
        env: Dict[str, Any],
        key,
        mesh=None,
        is_test: bool = False,
    ):
        self.program = program
        self.block = block
        self.env = env
        self.key = key
        self.mesh = mesh
        self.is_test = is_test
        self.cur_op = None  # the OpDesc being lowered (set by the driver)
        # values this block's ops tagged with `keep` (`recurrence.lower`)
        self.kept = 0
        # uid -> (vjp_fn, primal_outs, in_slots, out_slots)
        self.vjps: Dict[int, Any] = {}
        self._fixed_key = None

    def rng(self):
        """Next PRNG key.  Random op lowerings must call this exactly once
        per random draw; the compiler threads the key through the jitted fn
        so repeated runs advance the stream like the reference's stateful
        seeds (Program.random_seed)."""
        if self._fixed_key is not None:
            k = self._fixed_key
            self._fixed_key = None
            return k
        self.key, sub = jax.random.split(self.key)
        return sub

    def lookup(self, name: str):
        if not name:
            return None
        if name not in self.env:
            raise KeyError(f"variable '{name}' used before definition during lowering")
        return self.env[name]


def _gather_inputs(ctx: LoweringContext, op: OpDesc) -> Dict[str, List[Any]]:
    return {
        slot: [ctx.lookup(n) for n in names] for slot, names in op.inputs.items()
    }


def _bind_outputs(ctx: LoweringContext, op: OpDesc, outs: Dict[str, Any]) -> None:
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        if len(vals) != len(names):
            raise ValueError(
                f"op {op.type} slot {slot}: lowering produced {len(vals)} values "
                f"for {len(names)} outputs"
            )
        for name, val in zip(names, vals):
            if name and val is not None:
                ctx.env[name] = val


def _has_inexact_leaf(v) -> bool:
    for leaf in jax.tree_util.tree_leaves(v):
        dt = getattr(leaf, "dtype", None)
        if dt is not None and jnp.issubdtype(dt, jnp.inexact):
            return True
        if isinstance(leaf, float):
            return True
    return False


class _Const:
    """Marker wrapping a non-differentiable input kept out of the vjp trace.

    Integer/bool values (loop counters, conditions, rank tables, indices)
    must stay *concrete* inside a differentiated lowering so trace-time
    control flow (while unrolling, array indexing) still sees python ints;
    lifting them into jax.vjp arguments would turn them into tracers."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v


def _flatten_ins(ins: Dict[str, List[Any]]):
    """Flatten dict-of-lists into (leaves, spec).  Differentiable (float)
    values become vjp leaves; everything else rides along as a constant."""
    spec = []
    leaves = []
    for slot in sorted(ins):
        row = []
        for v in ins[slot]:
            if v is None:
                row.append(None)
            elif _has_inexact_leaf(v):
                row.append(len(leaves))
                leaves.append(v)
            else:
                row.append(_Const(v))
        spec.append((slot, row))
    return leaves, spec


def _unflatten_ins(leaves, spec) -> Dict[str, List[Any]]:
    return {
        slot: [
            None if i is None else (i.v if isinstance(i, _Const) else leaves[i])
            for i in row
        ]
        for slot, row in spec
    }


def _flatten_outs(outs: Dict[str, Any]):
    spec = []
    leaves = []
    for slot in sorted(outs):
        vals = outs[slot]
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        row = []
        for v in vals:
            if v is None:
                row.append(None)
            else:
                row.append(len(leaves))
                leaves.append(v)
        spec.append((slot, row))
    return leaves, spec


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def _float0_zeros(p):
    return np.zeros(np.shape(p), dtype=jax.dtypes.float0)


def _leaf_cotangent(primal, g):
    """Cotangent for one array leaf: float0 for non-float primals, zeros when
    no incoming grad, else the grad cast to the primal dtype."""
    if not _is_float(primal):
        return _float0_zeros(primal)
    if g is None:
        return jnp.zeros_like(primal)
    return jnp.asarray(g, dtype=jnp.asarray(primal).dtype)


def _make_cotangent(primal, g):
    """Build a vjp cotangent matching `primal`'s pytree structure.  LoDValue
    primals take the grad on .data (the incoming grad may be a bare array or
    an LoDValue) and a float0 cotangent for the integer lengths.  Tensor
    arrays take per-step cotangents."""
    if isinstance(primal, LoDValue):
        gdata = g.data if isinstance(g, LoDValue) else g
        return LoDValue(
            _leaf_cotangent(primal.data, gdata), _float0_zeros(primal.lengths)
        )
    from .tensor_array import StackedTensorArray, TensorArrayValue

    if isinstance(primal, StackedTensorArray):
        gbuf = g.buffer if isinstance(g, StackedTensorArray) else None
        return StackedTensorArray(
            _leaf_cotangent(primal.buffer, gbuf), primal.length
        )
    if isinstance(primal, TensorArrayValue):
        gs = g.steps if isinstance(g, (TensorArrayValue, StackedTensorArray)) \
            else [None] * len(primal)
        return TensorArrayValue(
            [_make_cotangent(p, gg) for p, gg in zip(primal.steps, gs)]
        )
    return _leaf_cotangent(primal, g)


def _sanitize_input_grad(g, primal):
    """Normalize a vjp input-grad before it enters the env: float0 leaves
    become zeros, and LoDValue grads re-adopt the primal's real lengths."""
    if g is None:
        return None
    if isinstance(g, LoDValue):
        gd = g.data
        if getattr(gd, "dtype", None) == jax.dtypes.float0:
            gd = jnp.zeros_like(primal.data)
        return LoDValue(gd, primal.lengths)
    from .tensor_array import StackedTensorArray, TensorArrayValue

    if isinstance(g, StackedTensorArray):
        gb = g.buffer
        if getattr(gb, "dtype", None) == jax.dtypes.float0:
            gb = jnp.zeros_like(primal.buffer)
        return StackedTensorArray(gb, g.length)
    if isinstance(g, TensorArrayValue):
        return TensorArrayValue(
            [_sanitize_input_grad(gg, p) for gg, p in zip(g.steps, primal.steps)]
        )
    if getattr(g, "dtype", None) == jax.dtypes.float0:
        return jnp.zeros_like(primal)
    return g


def _all_concrete(ins: Dict[str, List[Any]]) -> bool:
    for leaf in jax.tree_util.tree_leaves(ins):
        if isinstance(leaf, jax.core.Tracer):
            return False
    return True


# the most elements an op's output may have and still be folded at trace
# time: counters, conditions, lengths and small tables are far below it
_FOLD_MAX_ELEMENTS = 1 << 16


def _outputs_are_small(ctx: LoweringContext, op: OpDesc) -> bool:
    """False where the descs say an output of `op` has a static shape of
    more than _FOLD_MAX_ELEMENTS elements; an unknown shape counts as
    small (it folds as it always did)."""
    for name in op.output_arg_names():
        var = ctx.block._find_var_recursive(name) if name else None
        shape = list(getattr(var, "shape", None) or [])
        if shape and all(d >= 0 for d in shape) \
                and int(np.prod(shape)) > _FOLD_MAX_ELEMENTS:
            return False
    return True


def _lower_forward_op(ctx: LoweringContext, op: OpDesc, need_vjp: bool) -> None:
    info = OpRegistry.get(op.type)
    ins = _gather_inputs(ctx, op)
    attrs = dict(op.attrs)
    ctx.cur_op = op  # lowerings with variable output arity read slot counts

    if not need_vjp or info.no_grad:
        # Constant folding: pure ops over concrete values evaluate at trace
        # time (jax.ensure_compile_time_eval), so loop counters, conditions
        # and sequence bookkeeping stay concrete and `while` ops can unroll
        # with static trip counts (the reference pins these to CPU with
        # force_cpu fill_constants; here they fold out of the program
        # entirely).
        # Only what is small is folded: a fill_constant of a table's Adam
        # moments is no loop counter, and folded it is computed eagerly,
        # embedded in the program as a constant and kept on the device
        # for as long as the executable lives (3.26 GB of zeros in the
        # start-up program of a 407 M-parameter model, PERF.md PR 27).
        if (not info.random and not info.stateful and _all_concrete(ins)
                and _outputs_are_small(ctx, op)):
            with jax.ensure_compile_time_eval():
                outs = info.lower(ctx, ins, attrs)
        else:
            outs = info.lower(ctx, ins, attrs)
        _bind_outputs(ctx, op, outs)
        return

    # pre-draw the rng key outside the vjp trace so forward and any replay
    # see identical randomness
    if info.random:
        ctx._fixed_key = ctx.rng()

    leaves, in_spec = _flatten_ins(ins)
    out_spec_holder: List[Any] = []

    def fwd(*flat):
        rebuilt = _unflatten_ins(list(flat), in_spec)
        outs = info.lower(ctx, rebuilt, attrs)
        out_leaves, out_spec = _flatten_outs(outs)
        if not out_spec_holder:
            out_spec_holder.append(out_spec)
        return tuple(out_leaves)

    if attrs.get("@recompute@") and not info.meta.get("own_recompute"):
        # rematerialization (framework.recompute_scope): backward re-runs
        # this op's lowering from its inputs instead of keeping internal
        # activations resident — jax.checkpoint drops the residuals, all
        # but what an op's kernel tagged with `keep`.  An op registered
        # own_recompute (recurrence) places the checkpoint itself, around
        # a unit smaller than the whole op
        fwd = rematerialised(fwd)
    primal_outs, vjp_fn = jax.vjp(fwd, *leaves)
    out_spec = out_spec_holder[0]
    outs = {
        slot: [None if i is None else primal_outs[i] for i in row]
        for slot, row in out_spec
    }
    _bind_outputs(ctx, op, outs)
    uid = attrs.get("__op_uid__")
    if uid is not None:
        ctx.vjps[uid] = (vjp_fn, primal_outs, in_spec, out_spec, leaves)


def _lower_grad_op(ctx: LoweringContext, op: OpDesc) -> None:
    # custom grad lowering rule wins if registered (e.g. fused ops)
    if OpRegistry.has(op.type):
        info = OpRegistry.get(op.type)
        if info.lower is not None:
            ins = _gather_inputs(ctx, op)
            ctx.cur_op = op
            _bind_outputs(ctx, op, info.lower(ctx, ins, dict(op.attrs)))
            return

    uid = op.attrs.get("__fwd_op_uid__")
    if uid is None or uid not in ctx.vjps:
        raise RuntimeError(
            f"grad op {op.type} has no recorded forward vjp (uid={uid}); "
            "was append_backward run on this program?"
        )
    vjp_fn, primal_outs, in_spec, out_spec, primal_ins = ctx.vjps[uid]

    # cotangents: one per flat forward output, read from `<slot>@GRAD` inputs
    cotangents: List[Any] = [None] * len(primal_outs)
    for slot, row in out_spec:
        gnames = op.inputs.get(slot + GRAD_SUFFIX, [])
        for pos, i in enumerate(row):
            if i is None:
                continue
            g = None
            if pos < len(gnames) and gnames[pos]:
                g = ctx.env.get(gnames[pos])
            cotangents[i] = _make_cotangent(primal_outs[i], g)
    in_grads = vjp_fn(tuple(cotangents))

    # scatter input grads to `<slot>@GRAD` output names
    for slot, row in in_spec:
        out_names = op.outputs.get(slot + GRAD_SUFFIX, [])
        for pos, i in enumerate(row):
            if i is None or pos >= len(out_names) or not out_names[pos]:
                continue
            if isinstance(i, _Const):
                # non-differentiable input: a named grad slot still gets a
                # zeros pytree so downstream accumulation stays well-formed
                ctx.env[out_names[pos]] = jax.tree_util.tree_map(
                    jnp.zeros_like, i.v
                )
                continue
            g = _sanitize_input_grad(in_grads[i], primal_ins[i])
            if g is not None:
                ctx.env[out_names[pos]] = g


def lower_op(ctx: LoweringContext, op: OpDesc, need_vjp_uids) -> None:
    if op.type in _SKIP_OPS:
        return
    is_grad = op.type.endswith(GRAD_OP_SUFFIX) and "__fwd_op_uid__" in op.attrs
    if not is_grad and not OpRegistry.has(op.type):
        # outside the context wrapper: "no lowering rule" keeps its
        # NotImplementedError contract for feature probing
        raise NotImplementedError(f"op '{op.type}' has no TPU lowering rule")
    # fluid op names (plus any fluid.name_scope annotation) become XLA
    # metadata scopes, so profiler traces map back to program ops — the
    # reference's RecordEvent-per-op/SetCurAnnotation story (profiler.h,
    # device_tracer.h) at the HLO level
    trace_name = op.attrs.get("op_namescope", "") + op.type
    with op_error_context(op), jax.named_scope(trace_name):
        if is_grad:
            _lower_grad_op(ctx, op)
            return
        uid = op.attrs.get("__op_uid__")
        _lower_forward_op(ctx, op, need_vjp=uid in need_vjp_uids)


def collect_needed_vjps(ops) -> set:
    return {
        op.attrs["__fwd_op_uid__"]
        for op in ops
        if "__fwd_op_uid__" in op.attrs
    }


_compile_cache_applied_dir: str | None = None
_compile_cache_prior: object = None  # jax config value before first apply


def _maybe_enable_compile_cache() -> None:
    """Apply FLAGS_compile_cache_dir: point jax's persistent executable
    cache at the directory so identical programs skip recompilation across
    processes.  JAX_COMPILATION_CACHE_DIR wins: where the variable is set
    jax already keeps its cache there, and this function touches nothing.
    Otherwise it tracks the APPLIED directory (not a latch) so a later
    set_flags pointing somewhere else re-applies, and clearing the flag
    restores whatever jax config the user had BEFORE the first apply."""
    global _compile_cache_applied_dir, _compile_cache_prior
    from .. import flags

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    cache_dir = flags.flag("compile_cache_dir")
    if not cache_dir:
        if _compile_cache_applied_dir is not None:
            # the flag was cleared after being applied: fall back to the
            # user's own pre-apply jax setting (often None = disabled;
            # cold-compile measurements depend on this)
            _compile_cache_applied_dir = None
            jax.config.update("jax_compilation_cache_dir",
                              _compile_cache_prior)
        return
    if str(cache_dir) == _compile_cache_applied_dir:
        return
    if _compile_cache_applied_dir is None:
        _compile_cache_prior = jax.config.jax_compilation_cache_dir
    _compile_cache_applied_dir = str(cache_dir)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))


def default_compile_cache() -> str:
    """The one place the entry points (chip_smoke.py, bench.py,
    tools/serve_bench.py, benchmark/run.py) turn the persistent cache
    on.  Returns the directory in use: JAX_COMPILATION_CACHE_DIR where it
    is set, else xla_cache/ at the root of the checkout — a fixed path,
    because the path is part of the cache key."""
    from .. import flags

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "xla_cache")
    flags.set_flags({"FLAGS_compile_cache_dir": cache_dir})
    return cache_dir


class CompiledBlock:
    """A block lowered to one jitted callable.

    fn(feed_vals: tuple, state_vals: tuple, key) ->
        (fetch_vals: tuple, new_state_vals: tuple, new_key)
    """

    def __init__(
        self,
        program: Program,
        block_idx: int,
        feed_names: Sequence[str],
        fetch_names: Sequence[str],
        state_names: Sequence[str],
        donate_states: bool = True,
        mesh=None,
        in_shardings=None,
        out_shardings=None,
    ):
        self.program = program
        self.block = program.block(block_idx)
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.state_names = list(state_names)
        self.mesh = mesh
        # recorded for the static analyzer: whether the jitted executable
        # donates the state tuple (analysis.capture re-creates the same
        # aliasing when it AOT-compiles this block for the chip)
        self.donates_states = bool(donate_states)
        _maybe_enable_compile_cache()
        block = self.block
        ops = list(block.desc.ops)
        need_vjps = collect_needed_vjps(ops)

        def fn(feed_vals, state_vals, key):
            env: Dict[str, Any] = {}
            env.update(zip(self.state_names, state_vals))
            env.update(zip(self.feed_names, feed_vals))
            ctx = LoweringContext(program, block, env, key, mesh=mesh)
            for op in ops:
                lower_op(ctx, op, need_vjps)
            fetches = tuple(ctx.lookup(n) for n in self.fetch_names)
            new_states = tuple(env.get(n) for n in self.state_names)
            return fetches, new_states, ctx.key

        # un-jitted closure, for callers that compose/jit at a higher level
        self.raw_fn = fn

        jit_kwargs: Dict[str, Any] = {}
        if donate_states:
            jit_kwargs["donate_argnums"] = (1,)
        if in_shardings is not None:
            jit_kwargs["in_shardings"] = in_shardings
        if out_shardings is not None:
            jit_kwargs["out_shardings"] = out_shardings
        self.fn = jax.jit(fn, **jit_kwargs)

    def __call__(self, feed_vals, state_vals, key):
        return self.fn(tuple(feed_vals), tuple(state_vals), key)

    def cost_analysis(self, feed_vals, state_vals, key,
                      platform: Optional[str] = None) -> dict:
        """XLA cost accounting of the COMPILED executable for these arg
        shapes: {'bytes accessed': HBM bytes per execution, 'flops': ...}.
        This is the compiled module's own traffic model — the instrument
        VERDICT r4 asked for to validate paper bytes/step floors (e.g. the
        65 GB ResNet-50 estimate).  Cheap after the first execution: the
        trace/lower/compile pipeline hits jax's compilation cache.

        platform="tpu" AOT-compiles this block against a chip-less v5e
        topology (core/aot_tpu.py) and returns the TPU compiler's own
        cost model — the compiler's bytes/step on any host, no chip."""
        if platform == "tpu":
            from .aot_tpu import tpu_cost_analysis

            return tpu_cost_analysis(
                self.raw_fn, tuple(feed_vals), tuple(state_vals), key)
        compiled = self.fn.trace(
            tuple(feed_vals), tuple(state_vals), key).lower().compile()
        ca = compiled.cost_analysis()
        return ca if isinstance(ca, dict) else (ca[0] if ca else {})

    def tpu_lowering_check(self, feed_vals, state_vals, key) -> int:
        """Lower this block's step function for the TPU platform with NO
        TPU attached (jax.export runs StableHLO + the Mosaic kernel
        lowerings client-side) and return the module byte count.

        The chip-less lowering gate: pallas kernels can pass every
        interpret-mode test and still fail the real TPU's Mosaic
        constraints (lse block tiling, strided slices) — failures that
        burn chip minutes but are fully reproducible on a CPU host via
        cross-platform export."""
        exp = jax.export.export(self.fn, platforms=["tpu"])(
            tuple(feed_vals), tuple(state_vals), key)
        return len(exp.mlir_module_serialized)


def compile_block(*args, **kwargs) -> CompiledBlock:
    return CompiledBlock(*args, **kwargs)
