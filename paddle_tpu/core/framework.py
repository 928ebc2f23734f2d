"""Graph-building front end: Program / Block / Operator / Variable.

Parity target: python/paddle/fluid/framework.py in the reference (Variable
:216, Operator :521, Block :964, Program :1466, Parameter :2060,
program_guard :2212).  Python code builds *descriptions only*; tensors
materialize when paddle_tpu.core.compiler lowers a block to one jitted XLA
computation.  Differences from the reference are deliberate TPU-first
choices:

- shape & dtype inference run eagerly at append_op time (XLA needs static
  shapes; the reference defers InferShape to kernel dispatch,
  operator.cc:706).
- variables may carry a logical sharding spec (mesh-axis names per dim) used
  by ParallelExecutor/pjit instead of the reference's SSA multi-device graph.
"""

from __future__ import annotations

import contextlib
from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .proto import (
    BlockDesc,
    DataType,
    OpDesc,
    ProgramDesc,
    VarDesc,
    VarType,
    convert_dtype,
    dtype_to_numpy,
)
from .registry import GRAD_SUFFIX, OpRegistry

__all__ = [
    "Variable",
    "Parameter",
    "Operator",
    "Block",
    "Program",
    "program_guard",
    "default_main_program",
    "default_startup_program",
    "switch_main_program",
    "switch_startup_program",
    "unique_name",
    "unique_name_guard",
    "grad_var_name",
    "recompute_scope",
    "name_scope",
]


# ---------------------------------------------------------------------------
# unique name generator (reference: python/paddle/fluid/unique_name.py)
# ---------------------------------------------------------------------------
class _UniqueNameGenerator:
    """reference: unique_name.py UniqueNameGenerator (optional prefix on
    every generated name)."""

    def __init__(self, prefix: str = ""):
        self.ids = defaultdict(int)
        self.prefix = prefix or ""

    def __call__(self, key: str) -> str:
        name = f"{self.prefix}{key}_{self.ids[key]}"
        self.ids[key] += 1
        return name


_name_generator = _UniqueNameGenerator()


def unique_name(key: str) -> str:
    return _name_generator(key)


def unique_name_switch(new_generator=None):
    """Swap the global name generator, returning the old one
    (reference: unique_name.py switch)."""
    global _name_generator
    old = _name_generator
    _name_generator = (
        new_generator if new_generator is not None else _UniqueNameGenerator()
    )
    return old


@contextlib.contextmanager
def unique_name_guard(new_generator=None):
    """Fresh name counters inside the context
    (reference: unique_name.py guard; a str argument becomes the prefix of
    every generated name) — two programs built under separate guards get
    identical auto-generated parameter names, which is what lets an
    inference program reload a training program's checkpoint."""
    if isinstance(new_generator, (str, bytes)):
        prefix = (new_generator.decode()
                  if isinstance(new_generator, bytes) else new_generator)
        new_generator = _UniqueNameGenerator(prefix)
    saved = unique_name_switch(new_generator)
    try:
        yield
    finally:
        unique_name_switch(saved)


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


class Variable:
    """Symbolic tensor in a block (reference: framework.py:216).

    Wraps a VarDesc; its value exists only at run time inside the executor's
    Scope / lowered XLA computation.
    """

    def __init__(
        self,
        block: "Block",
        name: Optional[str] = None,
        shape: Optional[Sequence[int]] = None,
        dtype: Any = None,
        lod_level: Optional[int] = None,
        persistable: Optional[bool] = None,
        stop_gradient: bool = False,
        type: VarType = VarType.LOD_TENSOR,
        sharding: Optional[Sequence[Any]] = None,
        **kwargs: Any,
    ):
        self.block = block
        if name is None:
            name = unique_name("_generated_var")
        if block.desc.has_var(name):
            # re-wrap an existing desc (mirrors reference re-entrant Variable)
            desc = block.desc.var(name)
            if shape is not None and list(shape) != list(desc.shape):
                desc.shape = list(shape)
            if dtype is not None:
                desc.dtype = convert_dtype(dtype)
        else:
            desc = VarDesc(
                name=name,
                type=type,
                shape=list(shape) if shape is not None else [],
                dtype=convert_dtype(dtype) if dtype is not None else DataType.FP32,
                lod_level=lod_level or 0,
                persistable=bool(persistable),
                stop_gradient=stop_gradient,
                sharding=list(sharding) if sharding is not None else None,
            )
            block.desc.vars[name] = desc
        self.desc = desc
        self.error_clip = kwargs.get("error_clip")
        block.vars[name] = self

    # -- desc accessors ------------------------------------------------------
    @property
    def name(self) -> str:
        return self.desc.name

    @property
    def shape(self) -> tuple:
        return tuple(self.desc.shape)

    @shape.setter
    def shape(self, value):
        self.desc.shape = list(value)

    @property
    def dtype(self) -> DataType:
        return self.desc.dtype

    @dtype.setter
    def dtype(self, value):
        self.desc.dtype = convert_dtype(value)

    @property
    def np_dtype(self):
        return dtype_to_numpy(self.desc.dtype)

    @property
    def lod_level(self) -> int:
        return self.desc.lod_level

    @property
    def persistable(self) -> bool:
        return self.desc.persistable

    @persistable.setter
    def persistable(self, value: bool):
        self.desc.persistable = bool(value)

    @property
    def stop_gradient(self) -> bool:
        return self.desc.stop_gradient

    @stop_gradient.setter
    def stop_gradient(self, value: bool):
        self.desc.stop_gradient = bool(value)

    @property
    def type(self) -> VarType:
        return self.desc.type

    @property
    def sharding(self):
        return self.desc.sharding

    @sharding.setter
    def sharding(self, spec):
        self.desc.sharding = list(spec) if spec is not None else None

    def __str__(self) -> str:
        return (
            f"var {self.name} : {VarType(self.type).name} "
            f"shape={list(self.shape)} dtype={DataType(self.dtype).name} "
            f"lod={self.lod_level}{' persistable' if self.persistable else ''}"
        )

    __repr__ = __str__

    # -- operator sugar (build graph with python operators) ------------------
    def _binary(self, other, op):
        from .. import layers

        return layers.elementwise_binary_dispatch(self, other, op)

    def __add__(self, other):
        return self._binary(other, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "elementwise_sub")

    def __mul__(self, other):
        return self._binary(other, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "elementwise_div")

    def __pow__(self, other):
        return self._binary(other, "elementwise_pow")

    def astype(self, dtype):
        from .. import layers

        return layers.cast(self, dtype)


class Parameter(Variable):
    """Trainable persistable variable (reference: framework.py:2060)."""

    def __init__(self, block, shape, dtype, **kwargs):
        kwargs.setdefault("persistable", True)
        self.trainable = kwargs.pop("trainable", True)
        self.optimize_attr = kwargs.pop("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.pop("regularizer", None)
        self.gradient_clip_attr = kwargs.pop("gradient_clip_attr", None)
        self.do_model_average = kwargs.pop("do_model_average", None)
        self.is_distributed = kwargs.pop("is_distributed", False)
        super().__init__(block, shape=shape, dtype=dtype, **kwargs)


class Operator:
    """One op in a block (reference: framework.py:521).

    Creating an Operator appends an OpDesc and runs the registered
    compile-time infer_shape to populate output VarDescs.
    """

    def __init__(
        self,
        block: "Block",
        desc: OpDesc,
        inputs: Optional[Dict[str, Any]] = None,
        outputs: Optional[Dict[str, Any]] = None,
        attrs: Optional[Dict[str, Any]] = None,
        infer: bool = True,
    ):
        self.block = block
        self.desc = desc
        if inputs:
            desc.inputs = {k: _var_name_list(v) for k, v in inputs.items() if v is not None}
        if outputs:
            desc.outputs = {k: _var_name_list(v) for k, v in outputs.items() if v is not None}
        if attrs:
            desc.attrs.update({k: v for k, v in attrs.items() if v is not None})
        if infer and OpRegistry.has(desc.type):
            info = OpRegistry.get(desc.type)
            if info.infer_shape is not None:
                info.infer_shape(desc, block)

    @property
    def type(self) -> str:
        return self.desc.type

    def input(self, slot: str) -> List[str]:
        return self.desc.input(slot)

    def output(self, slot: str) -> List[str]:
        return self.desc.output(slot)

    @property
    def input_arg_names(self):
        return self.desc.input_arg_names()

    @property
    def output_arg_names(self):
        return self.desc.output_arg_names()

    def attr(self, name: str, default=None):
        return self.desc.attr(name, default)

    def _set_attr(self, name: str, val):
        self.desc.attrs[name] = val
        # invalidate compiled-program caches keyed on the desc fingerprint
        self.block.program.desc.bump()

    def all_attrs(self):
        return dict(self.desc.attrs)

    def __str__(self):
        ins = ", ".join(f"{k}={v}" for k, v in sorted(self.desc.inputs.items()))
        outs = ", ".join(f"{k}={v}" for k, v in sorted(self.desc.outputs.items()))
        attrs = ", ".join(
            f"{k}={v!r}" for k, v in sorted(self.desc.attrs.items()) if not k.startswith("__")
        )
        return f"{{{outs}}} = {self.type}({ins}) [{attrs}]"

    __repr__ = __str__


def _var_name_list(v) -> List[str]:
    if isinstance(v, (list, tuple)):
        return [x.name if isinstance(x, Variable) else str(x) for x in v]
    return [v.name if isinstance(v, Variable) else str(v)]


class Block:
    """Ordered op list + var map (reference: framework.py:964)."""

    def __init__(self, program: "Program", idx: int):
        self.program = program
        self.desc: BlockDesc = program.desc.block(idx)
        self.vars: Dict[str, Variable] = {}
        # rebuild wrappers for descs that already carry ops (clone / prune /
        # deserialized programs) so block.ops reflects the desc — the
        # reference keeps the two in sync the same way (framework.py
        # Program._copy_: each OpDesc gets an Operator shell).  infer=False:
        # output shapes are already in the desc, and during Program.clone
        # sibling blocks aren't rebuilt yet so cross-block lookups would
        # resolve against a stale blocks list
        self.ops: List[Operator] = [
            Operator(self, d, infer=False) for d in self.desc.ops
        ]

    @property
    def idx(self) -> int:
        return self.desc.idx

    @property
    def parent_idx(self) -> int:
        return self.desc.parent_idx

    @property
    def parent_block(self) -> Optional["Block"]:
        if self.parent_idx < 0:
            return None
        return self.program.block(self.parent_idx)

    # -- vars ----------------------------------------------------------------
    def create_var(self, **kwargs) -> Variable:
        return Variable(self, **kwargs)

    def create_parameter(self, **kwargs) -> Parameter:
        shape = kwargs.pop("shape")
        dtype = kwargs.pop("dtype")
        # parameters always live in the global block (reference semantics)
        global_block = self.program.global_block()
        return Parameter(global_block, shape, dtype, **kwargs)

    def has_var(self, name: str) -> bool:
        return self.desc.has_var(name)

    def var(self, name: str) -> Variable:
        v = self._find_var_local(name)
        if v is None:
            raise ValueError(f"variable '{name}' not found in block {self.idx}")
        return v

    def _find_var_local(self, name: str) -> Optional[Variable]:
        if name in self.vars:
            return self.vars[name]
        if self.desc.has_var(name):
            return Variable(self, name=name)
        return None

    def _find_var_recursive(self, name: str) -> Optional[Variable]:
        b: Optional[Block] = self
        while b is not None:
            v = b._find_var_local(name)
            if v is not None:
                return v
            b = b.parent_block
        return None

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # -- ops -----------------------------------------------------------------
    def append_op(
        self,
        type: str,
        inputs: Optional[Dict[str, Any]] = None,
        outputs: Optional[Dict[str, Any]] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ) -> Operator:
        desc = OpDesc(type=type)
        self.desc.ops.append(desc)
        if _RECOMPUTE_DEPTH[0] > 0:
            attrs = dict(attrs or {})
            attrs["@recompute@"] = True
        scope_path = _current_name_scope()
        if scope_path:
            attrs = dict(attrs or {})
            attrs["op_namescope"] = "/" + scope_path + "/"
        op = Operator(self, desc, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.append(op)
        return op

    def _prepend_op(self, type, inputs=None, outputs=None, attrs=None) -> Operator:
        desc = OpDesc(type=type)
        self.desc.ops.insert(0, desc)
        op = Operator(self, desc, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.insert(0, op)
        return op

    def _insert_op(self, index, type, inputs=None, outputs=None, attrs=None) -> Operator:
        desc = OpDesc(type=type)
        self.desc.ops.insert(index, desc)
        op = Operator(self, desc, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.insert(index, op)
        return op

    def _remove_op(self, index: int) -> None:
        del self.desc.ops[index]
        del self.ops[index]

    def __str__(self):
        lines = [f"block {self.idx} (parent {self.parent_idx}):"]
        for name in sorted(self.desc.vars):
            lines.append("  " + str(self.var(name)))
        for op in self.ops:
            lines.append("  " + str(op))
        return "\n".join(lines)


class Program:
    """A whole computation description (reference: framework.py:1466)."""

    def __init__(self):
        self.desc = ProgramDesc()
        self.blocks: List[Block] = [Block(self, 0)]
        self.current_block_idx = 0
        self._seed = 0
        # mirrors reference Program.random_seed
        self._op_role_var: List[str] = []

    @property
    def random_seed(self) -> int:
        return self._seed

    @random_seed.setter
    def random_seed(self, seed: int):
        self._seed = seed

    def global_block(self) -> Block:
        return self.blocks[0]

    def block(self, idx: int) -> Block:
        return self.blocks[idx]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def num_blocks(self) -> int:
        return len(self.blocks)

    def _create_block(self, parent_idx: Optional[int] = None) -> Block:
        parent = self.current_block_idx if parent_idx is None else parent_idx
        self.desc.append_block(parent)
        b = Block(self, len(self.blocks))
        self.blocks.append(b)
        self.current_block_idx = b.idx
        return b

    def _rollback(self) -> None:
        self.current_block_idx = self.current_block().parent_idx

    def clone(self, for_test: bool = False) -> "Program":
        """Deep-copy the program.  for_test=True switches train-only ops
        (dropout, batch_norm) to inference behavior via their 'is_test' attr
        (reference: framework.py Program.clone)."""
        p = Program()
        p.desc = self.desc.clone()
        p.blocks = [Block(p, i) for i in range(p.desc.num_blocks())]
        p.current_block_idx = 0
        p._seed = self._seed
        if for_test:
            for block in p.blocks:
                for opdesc in block.desc.ops:
                    if "is_test" in opdesc.attrs or opdesc.type in ("dropout", "batch_norm"):
                        opdesc.attrs["is_test"] = True
            p.desc.bump()
        p._sync_params(self)
        return p

    def _sync_params(self, src: "Program") -> None:
        # re-mark Parameters in the clone so all_parameters() keeps working
        for sb, db in zip(src.blocks, self.blocks):
            for name, v in sb.vars.items():
                if isinstance(v, Parameter) and db.has_var(name):
                    p = Parameter.__new__(Parameter)
                    p.block = db
                    p.desc = db.desc.var(name)
                    p.trainable = v.trainable
                    p.optimize_attr = v.optimize_attr
                    p.regularizer = v.regularizer
                    p.gradient_clip_attr = v.gradient_clip_attr
                    p.do_model_average = v.do_model_average
                    p.is_distributed = v.is_distributed
                    p.error_clip = getattr(v, "error_clip", None)
                    db.vars[name] = p

    def all_parameters(self) -> List[Parameter]:
        return self.global_block().all_parameters()

    def list_vars(self):
        for block in self.blocks:
            for name in block.desc.vars:
                yield block.var(name)

    def to_string(self, throw_on_error: bool = False) -> str:
        return "\n".join(str(b) for b in self.blocks)

    __str__ = to_string

    def __repr__(self):
        return f"<Program blocks={self.num_blocks()} ops={len(self.global_block().ops)}>"


# ---------------------------------------------------------------------------
# default programs + guards (reference: framework.py:2162-2258)
# ---------------------------------------------------------------------------
_main_program = Program()
_startup_program = Program()


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


def switch_main_program(program: Program) -> Program:
    global _main_program
    prev, _main_program = _main_program, program
    return prev


def switch_startup_program(program: Program) -> Program:
    global _startup_program
    prev, _startup_program = _startup_program, program
    return prev


def reset_default_env() -> None:
    """Fresh default main/startup programs and a fresh global scope — the
    'start a new model from scratch in this process' idiom used by benches,
    the driver entry points, and tests."""
    from . import scope as scope_mod

    switch_main_program(Program())
    switch_startup_program(Program())
    scope_mod._current_scope = scope_mod.Scope()
    _NAME_SCOPE_COUNTS.clear()
    unique_name_switch()  # fresh name counters: fc_0, conv2d_0, ... again
    # NOTE: the AMP policy survives on purpose — enable_amp() is global
    # process policy, not program state (amp.reset_amp() returns to auto)


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Optional[Program] = None):
    prev_main = switch_main_program(main_program)
    prev_startup = None
    if startup_program is not None:
        prev_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(prev_main)
        if prev_startup is not None:
            switch_startup_program(prev_startup)


# ---------------------------------------------------------------------------
# name_scope (reference: framework.py name_scope — a debug-name hierarchy;
# ops appended inside carry the 'op_namescope' attr the reference's
# op_proto_maker attaches, consumed by the debugger/graphviz tools)
# ---------------------------------------------------------------------------
_NAME_SCOPE_STACK: List[str] = []
# per parent path: how often each child name was opened (the reference
# suffixes repeated sibling scopes: block, block_1, block_2, ...)
_NAME_SCOPE_COUNTS: Dict[tuple, Dict[str, int]] = defaultdict(
    lambda: defaultdict(int)
)


@contextlib.contextmanager
def name_scope(prefix: Optional[str] = None):
    """Annotate ops built inside with a hierarchical debug name
    (reference: framework.py name_scope; purely observational — no effect
    on execution).  Repeated sibling names auto-suffix like the
    reference's NameScope.child: block, block_1, ..."""
    prefix = prefix or ""
    parent = tuple(_NAME_SCOPE_STACK)
    if prefix:
        seen = _NAME_SCOPE_COUNTS[parent][prefix]
        _NAME_SCOPE_COUNTS[parent][prefix] += 1
        if seen:
            prefix = f"{prefix}_{seen}"
    _NAME_SCOPE_STACK.append(prefix)
    try:
        yield
    finally:
        _NAME_SCOPE_STACK.pop()


def _current_name_scope() -> str:
    return "/".join(s for s in _NAME_SCOPE_STACK if s)


# ---------------------------------------------------------------------------
# rematerialization (TPU-native; no 2018 reference analogue — later Paddle
# grew RecomputeOptimizer for the same memory/FLOPs trade)
# ---------------------------------------------------------------------------
_RECOMPUTE_DEPTH = [0]


@contextlib.contextmanager
def recompute_scope():
    """Ops appended inside this scope carry the @recompute@ attr: backward
    re-runs their forward lowering (jax.checkpoint) instead of keeping its
    residuals.  Two units, by the kind of op:

    * Any ordinary op: the remat boundary is PER OP — the compiler wraps
      that op's forward lowering.  That drops op-INTERNAL state, which is
      where the memory is for composite lowerings: fused_attention's
      [B, H, S, S] probability matrix, lstm/gru scan per-step gates, a
      `while` sub-block's carried intermediates (the whole loop is
      computed again).  Activations at op boundaries (one op's output
      feeding the next) remain resident either way, so tagging a chain of
      primitive ops (mul, softmax, add as separate ops) costs recompute
      FLOPs without saving memory.
    * A `recurrence` op (layers.Recurrence built inside the scope): the
      unit is the TRIP.  The op places the checkpoint itself, around the
      body of its lax.scan: each trip's incoming carry is kept, and the
      trip's activations — however many ops its body has — are computed
      again when the backward pass reaches that trip.  This is the unit a
      weight-tied, looped model needs (memory O(one trip) instead of
      O(trips x body)), and the one per-op tagging cannot express; the
      tags on the ops inside the body are not read.

    No 2018 reference analogue; later Paddle's RecomputeOptimizer trades
    the same way at segment granularity."""
    _RECOMPUTE_DEPTH[0] += 1
    try:
        yield
    finally:
        _RECOMPUTE_DEPTH[0] -= 1
