"""Control-flow layers (reference: python/paddle/fluid/layers/control_flow.py).

While / StaticRNN / DynamicRNN / IfElse / Switch plus the tensor-array and
rank-table helper layers.  The graph-building contract matches the reference
(sub-blocks under `while`/`conditional_block` ops, LOD_TENSOR_ARRAY vars,
lod_rank_table machinery); execution is TPU-native — static trip counts via
padded sequence shapes, trace-time unrolling, and if-conversion (see
paddle_tpu/ops/control_flow_ops.py).
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional

from ..core.framework import Variable, default_main_program, unique_name
from ..core.proto import DataType, VarType, convert_dtype
from ..layer_helper import LayerHelper
from . import tensor as tensor_layers

__all__ = [
    "equal", "not_equal", "less_than", "less_equal",
    "greater_than", "greater_equal",
    "logical_and", "logical_or", "logical_xor", "logical_not",
    "While", "StaticRNN", "Recurrence", "DynamicRNN", "IfElse", "Switch",
    "increment", "array_write", "array_read", "array_length", "create_array",
    "lod_rank_table", "max_sequence_len", "lod_tensor_to_array",
    "array_to_lod_tensor", "shrink_memory", "split_lod_tensor",
    "merge_lod_tensor", "Print", "is_empty",
    "reorder_lod_tensor_by_rank",
]


def _compare(op_type, x, y, cond=None):
    helper = LayerHelper(op_type, input=x)
    if cond is None:
        cond = helper.create_variable_for_type_inference(dtype="bool")
        cond.stop_gradient = True
    helper.append_op(
        type=op_type, inputs={"X": [x], "Y": [y]}, outputs={"Out": [cond]}
    )
    return cond


def equal(x, y, cond=None):
    return _compare("equal", x, y, cond)


def not_equal(x, y, cond=None):
    return _compare("not_equal", x, y, cond)


def less_than(x, y, cond=None, force_cpu=None):
    return _compare("less_than", x, y, cond)


def less_equal(x, y, cond=None):
    return _compare("less_equal", x, y, cond)


def greater_than(x, y, cond=None):
    return _compare("greater_than", x, y, cond)


def greater_equal(x, y, cond=None):
    return _compare("greater_equal", x, y, cond)


def logical_and(x, y, out=None, name=None):
    return _compare("logical_and", x, y, out)


def logical_or(x, y, out=None, name=None):
    return _compare("logical_or", x, y, out)


def logical_xor(x, y, out=None, name=None):
    return _compare("logical_xor", x, y, out)


def logical_not(x, out=None, name=None):
    helper = LayerHelper("logical_not", input=x)
    if out is None:
        out = helper.create_variable_for_type_inference(dtype="bool")
        out.stop_gradient = True
    helper.append_op(type="logical_not", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


increment = tensor_layers.increment


# ---------------------------------------------------------------------------
# tensor arrays
# ---------------------------------------------------------------------------
def create_array(dtype, name=None):
    """Create a LOD_TENSOR_ARRAY var with an empty runtime value
    (reference: control_flow.py create_array — var only; here an op also
    seeds the functional array value)."""
    helper = LayerHelper("create_array", name=name)
    out = helper.block.create_var(
        name=unique_name("array"),
        shape=[],
        dtype=dtype,
        type=VarType.LOD_TENSOR_ARRAY,
    )
    helper.append_op(type="create_array", inputs={}, outputs={"Out": [out]})
    return out


def array_write(x, i, array=None):
    """array[i] = x (reference: tensor_array_read_write_op.cc)."""
    helper = LayerHelper("array_write", input=x)
    if array is None:
        array = create_array(x.dtype)
    helper.append_op(
        type="write_to_array",
        inputs={"X": [x], "I": [i], "Array": [array]},
        outputs={"Out": [array]},
    )
    return array


def array_read(array, i):
    helper = LayerHelper("array_read", input=array)
    out = helper.create_variable_for_type_inference(array.dtype)
    helper.append_op(
        type="read_from_array", inputs={"X": [array], "I": [i]},
        outputs={"Out": [out]},
    )
    return out


def array_length(array):
    helper = LayerHelper("array_length", input=array)
    out = helper.create_variable_for_type_inference("int64")
    out.stop_gradient = True
    helper.append_op(
        type="lod_array_length", inputs={"X": [array]}, outputs={"Out": [out]}
    )
    return out


def is_empty(x, cond=None):
    helper = LayerHelper("is_empty", input=x)
    if cond is None:
        cond = helper.create_variable_for_type_inference(dtype="bool")
        cond.stop_gradient = True
    helper.append_op(type="is_empty", inputs={"X": [x]}, outputs={"Out": [cond]})
    return cond


# ---------------------------------------------------------------------------
# rank table machinery
# ---------------------------------------------------------------------------
def lod_rank_table(x, level=0):
    helper = LayerHelper("lod_rank_table", input=x)
    table = helper.block.create_var(
        name=unique_name("lod_rank_table"), shape=[], dtype=DataType.INT64,
        type=VarType.RAW,
    )
    helper.append_op(
        type="lod_rank_table", inputs={"X": [x]}, outputs={"Out": [table]},
        attrs={"level": level},
    )
    return table


def max_sequence_len(rank_table):
    helper = LayerHelper("max_seqence_len", input=rank_table)
    out = helper.create_variable_for_type_inference("int64")
    out.stop_gradient = True
    helper.append_op(
        type="max_sequence_len", inputs={"RankTable": [rank_table]},
        outputs={"Out": [out]},
    )
    return out


def lod_tensor_to_array(x, table):
    helper = LayerHelper("lod_tensor_to_array", input=x)
    array = helper.block.create_var(
        name=unique_name("lod_tensor_to_array"), shape=list(x.shape),
        dtype=x.dtype, type=VarType.LOD_TENSOR_ARRAY,
    )
    helper.append_op(
        type="lod_tensor_to_array", inputs={"X": [x], "RankTable": [table]},
        outputs={"Out": [array]},
    )
    return array


def array_to_lod_tensor(x, table):
    helper = LayerHelper("array_to_lod_tensor", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="array_to_lod_tensor", inputs={"X": [x], "RankTable": [table]},
        outputs={"Out": [out]},
    )
    return out


def shrink_memory(x, i, table):
    helper = LayerHelper("shrink_memory", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="shrink_rnn_memory",
        inputs={"X": [x], "I": [i], "RankTable": [table]},
        outputs={"Out": [out]},
    )
    return out


def split_lod_tensor(input, mask, level=0):
    helper = LayerHelper("split_lod_tensor", input=input)
    out_true = helper.create_variable_for_type_inference(input.dtype)
    out_false = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="split_lod_tensor",
        inputs={"X": [input], "Mask": [mask]},
        outputs={"OutTrue": [out_true], "OutFalse": [out_false]},
        attrs={"level": level},
    )
    return out_true, out_false


def merge_lod_tensor(in_true, in_false, x, mask, level=0):
    helper = LayerHelper("merge_lod_tensor", input=x)
    out = helper.create_variable_for_type_inference(in_true.dtype)
    helper.append_op(
        type="merge_lod_tensor",
        inputs={"X": [x], "Mask": [mask], "InTrue": [in_true],
                "InFalse": [in_false]},
        outputs={"Out": [out]},
        attrs={"level": level},
    )
    return out


def Print(input, first_n=-1, message=None, summarize=-1,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=True,
          print_phase="both"):
    """Debug-print a tensor in-graph (reference: operators/print_op.cc)."""
    helper = LayerHelper("print", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="print", inputs={"In": [input]}, outputs={"Out": [out]},
        attrs={
            "first_n": first_n, "message": message or "",
            "summarize": summarize, "print_tensor_name": print_tensor_name,
            "print_phase": print_phase.upper(),
        },
    )
    return out


# ---------------------------------------------------------------------------
# sub-block capture analysis
# ---------------------------------------------------------------------------
def _analyze_block_io(sub_block, include_read_outputs: bool):
    """Names a sub-block reads from / writes to enclosing scopes.

    x_names: external names read by ops in the block (in first-read order).
    out_names: external names written by ops in the block.
    include_read_outputs adds externally-existing written vars to x_names
    (conditional_block needs their prior values for if-conversion selects).
    """
    def _in_ancestors(name: str) -> bool:
        b = sub_block.parent_block
        while b is not None:
            if b.desc.has_var(name):
                return True
            b = b.parent_block
        return False

    # op-order dataflow: infer_shape may shadow parent vars into the
    # sub-block desc, so "local" means *first defined by an op here before
    # any read*, and external names must resolve in an ancestor block.
    defined: set = set()
    reads: List[str] = []
    writes: List[str] = []
    seen_r, seen_w = set(), set()
    for op in sub_block.ops:
        for n in op.input_arg_names:
            if n and n not in defined and n not in seen_r and _in_ancestors(n):
                seen_r.add(n)
                reads.append(n)
        for n in op.output_arg_names:
            if n:
                if n not in seen_w and _in_ancestors(n):
                    seen_w.add(n)
                    writes.append(n)
                defined.add(n)
    if include_read_outputs:
        for n in writes:
            if n not in seen_r:
                reads.append(n)
                seen_r.add(n)
    return reads, writes


# ---------------------------------------------------------------------------
# While
# ---------------------------------------------------------------------------
class While:
    """Run a sub-block while a bool scalar condition holds
    (reference: control_flow.py While, operators/controlflow/while_op.cc).

    with While(cond).block():
        ...ops...; update cond
    """

    def __init__(self, cond, is_test: bool = False, name: Optional[str] = None):
        self.helper = LayerHelper("while", name=name)
        if cond.dtype not in ("bool", DataType.BOOL):
            raise TypeError("While condition must be a bool Variable")
        self.cond_var = cond
        self.is_test = is_test

    @contextlib.contextmanager
    def block(self):
        program = self.helper.main_program
        parent_block = program.current_block()
        sub_block = program._create_block()
        try:
            yield
        finally:
            program._rollback()
        x_names, out_names = _analyze_block_io(
            sub_block, include_read_outputs=False
        )
        # drop reads with no runtime value yet (arrays created empty are read
        # via create_array's output, which exists; params/feeds exist)
        parent_block.append_op(
            type="while",
            inputs={"X": x_names, "Condition": [self.cond_var]},
            outputs={"Out": out_names, "StepScopes": []},
            attrs={
                "sub_block": sub_block.idx,
                "is_test": self.is_test,
                "__x_names__": x_names,
                "__out_names__": out_names,
                "__cond_name__": self.cond_var.name,
            },
        )


# ---------------------------------------------------------------------------
# StaticRNN
# ---------------------------------------------------------------------------
class StaticRNN:
    """Unrolled RNN over time-major dense inputs [T, N, ...]
    (reference: control_flow.py StaticRNN / recurrent_op.cc).

    with rnn.step():
        word = rnn.step_input(x)          # [N, ...]
        prev = rnn.memory(init=boot)      # or shape=/value=
        hidden = fc([word, prev], ...)
        rnn.update_memory(prev, hidden)
        rnn.step_output(hidden)
    out = rnn()                           # [T, N, ...]
    """

    def __init__(self, name: Optional[str] = None):
        self.helper = LayerHelper("static_rnn", name=name)
        self._parent_block = None
        self._sub_block = None
        self._counter = None
        self._cond = None
        self._seq_len_var = None
        self._seq_ref = None
        self._num_steps: Optional[int] = None
        self._outputs: List[tuple] = []  # (out_array, step_var)
        self._mem_updates: List[tuple] = []  # (mem_var, new_var)
        self._in_rnn = False

    @contextlib.contextmanager
    def step(self):
        program = self.helper.main_program
        self._parent_block = program.current_block()
        # loop counter + condition live in the parent block
        self._counter = _parent_fill_constant(
            self._parent_block, shape=[1], dtype="int64", value=0
        )
        self._cond = self._parent_block.create_var(
            name=unique_name("static_rnn_cond"), shape=[1], dtype=DataType.BOOL
        )
        self._sub_block = program._create_block()
        self._in_rnn = True
        try:
            yield
        except BaseException:
            program._rollback()
            raise
        self._in_rnn = False
        self._complete()

    def _assert_in_rnn(self):
        if not self._in_rnn:
            raise RuntimeError("StaticRNN method used outside rnn.step()")

    def step_input(self, x):
        self._assert_in_rnn()
        T = x.shape[0]
        if self._num_steps is None:
            if T is None or T < 0:
                raise ValueError(
                    "StaticRNN needs a static sequence length on axis 0"
                )
            self._num_steps = int(T)
        if self._seq_ref is None:
            self._seq_ref = x
        pb = self._parent_block
        array = pb.create_var(
            name=unique_name("static_rnn_input_array"), shape=[], dtype=x.dtype,
            type=VarType.LOD_TENSOR_ARRAY,
        )
        pb.append_op(
            type="unstack_into_array", inputs={"X": [x]},
            outputs={"Out": [array]}, attrs={"axis": 0},
        )
        step = self._sub_block.create_var(
            name=unique_name("static_rnn_step_in"),
            shape=list(x.shape[1:]), dtype=x.dtype,
        )
        self._sub_block.append_op(
            type="read_from_array", inputs={"X": [array], "I": [self._counter]},
            outputs={"Out": [step]},
        )
        return step

    def memory(self, init=None, shape=None, batch_ref=None, value=0.0,
               init_value=0.0, dtype="float32"):
        self._assert_in_rnn()
        pb = self._parent_block
        if init is None:
            if shape is None or self._seq_ref is None:
                raise ValueError(
                    "StaticRNN.memory needs init= or shape= (after step_input)"
                )
            boot = pb.create_var(
                name=unique_name("static_rnn_mem_boot"),
                shape=list(shape), dtype=dtype,
            )
            # batch dim comes from axis 1 of the time-major [T, N, ...] input
            pb.append_op(
                type="fill_constant_batch_size_like",
                inputs={"Input": [self._seq_ref]}, outputs={"Out": [boot]},
                attrs={
                    "shape": list(shape),
                    "dtype": convert_dtype(dtype),
                    "value": float(value if value else init_value),
                    "input_dim_idx": 1, "output_dim_idx": 0,
                },
            )
            init = boot
        mem = self._sub_block.create_var(
            name=unique_name("static_rnn_mem"),
            shape=list(init.shape), dtype=init.dtype,
        )
        # first iteration reads the boot value; later ones the updated value.
        # The loop-carried slot is a parent var seeded with the boot value.
        carry = pb.create_var(
            name=unique_name("static_rnn_mem_carry"),
            shape=list(init.shape), dtype=init.dtype,
        )
        pb.append_op(
            type="assign", inputs={"X": [init]}, outputs={"Out": [carry]}
        )
        self._sub_block.append_op(
            type="assign", inputs={"X": [carry]}, outputs={"Out": [mem]}
        )
        mem._carry_name = carry.name
        return mem

    def update_memory(self, mem, var):
        self._assert_in_rnn()
        carry = getattr(mem, "_carry_name", None)
        if carry is None:
            raise ValueError("update_memory target was not created by memory()")
        self._sub_block.append_op(
            type="assign", inputs={"X": [var]}, outputs={"Out": [carry]}
        )

    def step_output(self, o):
        self._assert_in_rnn()
        pb = self._parent_block
        array = pb.create_var(
            name=unique_name("static_rnn_out_array"), shape=[], dtype=o.dtype,
            type=VarType.LOD_TENSOR_ARRAY,
        )
        pb.append_op(type="create_array", inputs={}, outputs={"Out": [array]})
        self._sub_block.append_op(
            type="write_to_array",
            inputs={"X": [o], "I": [self._counter], "Array": [array]},
            outputs={"Out": [array]},
        )
        out_shape = [self._num_steps] + list(o.shape)
        out = pb.create_var(
            name=unique_name("static_rnn_out"), shape=out_shape, dtype=o.dtype
        )
        self._outputs.append((array, out))

    def output(self, *outputs):
        for o in outputs:
            self.step_output(o)

    def _complete(self):
        program = self.helper.main_program
        sub_block = self._sub_block
        pb = self._parent_block
        if self._num_steps is None:
            raise RuntimeError("StaticRNN needs at least one step_input")
        # trip bookkeeping appended at the end of the sub-block
        seq_len = _parent_fill_constant(
            pb, shape=[1], dtype="int64", value=self._num_steps
        )
        pb.append_op(
            type="less_than", inputs={"X": [self._counter], "Y": [seq_len]},
            outputs={"Out": [self._cond]},
        )
        sub_block.append_op(
            type="increment", inputs={"X": [self._counter]},
            outputs={"Out": [self._counter]}, attrs={"step": 1.0},
        )
        sub_block.append_op(
            type="less_than", inputs={"X": [self._counter], "Y": [seq_len]},
            outputs={"Out": [self._cond]},
        )
        program._rollback()
        x_names, out_names = _analyze_block_io(
            sub_block, include_read_outputs=False
        )
        pb.append_op(
            type="while",
            inputs={"X": x_names, "Condition": [self._cond]},
            outputs={"Out": out_names, "StepScopes": []},
            attrs={
                "sub_block": sub_block.idx,
                "is_test": False,
                "__x_names__": x_names,
                "__out_names__": out_names,
                "__cond_name__": self._cond.name,
            },
        )
        # stack step outputs back to [T, N, ...]
        for array, out in self._outputs:
            pb.append_op(
                type="stack_from_array", inputs={"X": [array]},
                outputs={"Out": [out]}, attrs={"axis": 0},
            )

    def __call__(self):
        outs = [out for _, out in self._outputs]
        if len(outs) == 1:
            return outs[0]
        return outs


# ---------------------------------------------------------------------------
# Recurrence
# ---------------------------------------------------------------------------
class Recurrence:
    """One body block run a static number of trips over carried values
    (TPU-native; no reference analogue): a weight-tied recurrence over
    depth, where StaticRNN is one over time.  Parameters made inside the
    body are made once and read on every trip.

    rec = Recurrence(trips=4)
    with rec.block():
        h = rec.carry(h0)                 # h0 on the first trip, then
        out = stack_of_layers(h)          # what update() handed on
        rec.update(h, out)
        rec.output(out)                   # kept from every trip
    states = rec()                        # [trips, ...]
    last = rec.final(h)                   # the carry after the last trip

    The `recurrence` op lowers its body once (lax.scan), whatever the
    number of trips; a parameter read in the body has one gradient, summed
    over the trips; built inside fluid.recompute_scope() the trip is the
    unit of recomputation (ops/control_flow_ops.py::_recurrence)."""

    def __init__(self, trips: int, name: Optional[str] = None,
                 prevent_cse: bool = False):
        if int(trips) < 1:
            raise ValueError(f"Recurrence needs at least one trip, got {trips}")
        self.helper = LayerHelper("recurrence", name=name)
        self.trips = int(trips)
        self.prevent_cse = bool(prevent_cse)
        self._parent_block = None
        self._sub_block = None
        self._carries: List[list] = []   # [init, in-block var, next, final]
        self._outputs: List[tuple] = []  # (in-block step var, stacked var)
        self._in_block = False

    @contextlib.contextmanager
    def block(self):
        program = self.helper.main_program
        self._parent_block = program.current_block()
        self._sub_block = program._create_block()
        self._in_block = True
        try:
            yield
        finally:
            self._in_block = False
            program._rollback()
        self._complete()

    def _assert_in_block(self):
        if not self._in_block:
            raise RuntimeError("Recurrence method used outside rec.block()")

    def carry(self, init):
        """The carried value as the body sees it; `init` on the first trip."""
        self._assert_in_block()
        if init.block is self._sub_block:
            raise ValueError(
                f"Recurrence.carry: the first value {init.name} is made "
                "inside the body; make it before rec.block()")
        mem = self._sub_block.create_var(
            name=unique_name("recurrence_carry"), shape=list(init.shape),
            dtype=init.dtype)
        final = self._parent_block.create_var(
            name=unique_name("recurrence_final"), shape=list(init.shape),
            dtype=init.dtype)
        self._carries.append([init, mem, None, final])
        return mem

    def _entry(self, mem, method: str) -> list:
        for c in self._carries:
            if c[1] is mem:
                return c
        raise ValueError(f"{method}() target was not created by carry()")

    def update(self, mem, var):
        """The next trip's value of a carry()."""
        self._assert_in_block()
        self._entry(mem, "update")[2] = var

    def output(self, *outputs):
        """Values of the body kept from every trip, stacked on axis 0."""
        self._assert_in_block()
        for o in outputs:
            stacked = self._parent_block.create_var(
                name=unique_name("recurrence_out"),
                shape=[self.trips] + list(o.shape), dtype=o.dtype)
            self._outputs.append((o, stacked))

    def _complete(self):
        missing = [c[1].name for c in self._carries if c[2] is None]
        if not self._carries or missing:
            raise RuntimeError(
                "Recurrence needs a carry(), and an update() for each "
                f"(none for {missing})")
        x_names, _ = _analyze_block_io(
            self._sub_block, include_read_outputs=False)
        self._parent_block.append_op(
            type="recurrence",
            inputs={"X": x_names, "Init": [c[0] for c in self._carries]},
            outputs={"Out": [s for _, s in self._outputs],
                     "Final": [c[3] for c in self._carries]},
            attrs={
                "sub_block": self._sub_block.idx,
                "trips": self.trips,
                "__x_names__": x_names,
                "__carry_names__": [c[1].name for c in self._carries],
                "__next_names__": [c[2].name for c in self._carries],
                "__step_out_names__": [o.name for o, _ in self._outputs],
                **({"prevent_cse": True} if self.prevent_cse else {}),
            },
        )

    def final(self, mem):
        """The value of a carry() after the last trip."""
        return self._entry(mem, "final")[3]

    def __call__(self):
        outs = [s for _, s in self._outputs]
        return outs[0] if len(outs) == 1 else outs


def _parent_fill_constant(block, shape, dtype, value):
    out = block.create_var(
        name=unique_name("fill_constant"), shape=list(shape),
        dtype=convert_dtype(dtype),
    )
    out.stop_gradient = True
    block.append_op(
        type="fill_constant", inputs={}, outputs={"Out": [out]},
        attrs={"shape": list(shape), "dtype": convert_dtype(dtype),
               "value": float(value), "force_cpu": False},
    )
    return out


# ---------------------------------------------------------------------------
# DynamicRNN
# ---------------------------------------------------------------------------
class DynamicRNN:
    """RNN over variable-length LoD sequences
    (reference: control_flow.py DynamicRNN).

    drnn = DynamicRNN()
    with drnn.block():
        word = drnn.step_input(sent)      # LoD input -> per-step [N, F]
        prev = drnn.memory(shape=[H], value=0.0)  # or init=
        hidden = fc([word, prev], ...)
        drnn.update_memory(prev, hidden)
        drnn.output(hidden)
    out = drnn()                          # LoD [N, T, F] result

    Design note vs the reference: the reference sorts sequences by length
    (lod_rank_table) and shrinks the batch each step so finished sequences
    drop out; that is a dynamic-shape optimization XLA cannot express.  Here
    every step runs the full padded batch and downstream ops mask by length
    — same math for row-independent cells, static shapes for the MXU.
    """

    BEFORE_RNN = 0
    IN_RNN = 1
    AFTER_RNN = 2

    def __init__(self, name: Optional[str] = None):
        self.helper = LayerHelper("dynamic_rnn", name=name)
        self.status = DynamicRNN.BEFORE_RNN
        self._parent_block = None
        self._sub_block = None
        self._counter = None
        self._cond = None
        self._rank_table = None
        self._max_len = None
        self._first_input = None
        self._outputs: List[tuple] = []
        self._mem_dict: Dict[str, str] = {}

    @contextlib.contextmanager
    def block(self):
        if self.status != DynamicRNN.BEFORE_RNN:
            raise RuntimeError("DynamicRNN.block() can only be entered once")
        program = self.helper.main_program
        self._parent_block = program.current_block()
        self._counter = _parent_fill_constant(
            self._parent_block, shape=[1], dtype="int64", value=0
        )
        self._cond = self._parent_block.create_var(
            name=unique_name("dynamic_rnn_cond"), shape=[1], dtype=DataType.BOOL
        )
        self._sub_block = program._create_block()
        self.status = DynamicRNN.IN_RNN
        try:
            yield
        except BaseException:
            program._rollback()
            raise
        self.status = DynamicRNN.AFTER_RNN
        self._complete()

    def _assert_in_rnn(self, method):
        if self.status != DynamicRNN.IN_RNN:
            raise RuntimeError(f"DynamicRNN.{method} must be called in block()")

    def step_input(self, x, level=0):
        self._assert_in_rnn("step_input")
        pb = self._parent_block
        if self._first_input is None:
            self._first_input = x
        if self._rank_table is None:
            with _block_guard(self.helper.main_program, pb):
                self._rank_table = lod_rank_table(x, level=level)
                self._max_len = max_sequence_len(self._rank_table)
                pb.append_op(
                    type="less_than",
                    inputs={"X": [self._counter], "Y": [self._max_len]},
                    outputs={"Out": [self._cond]},
                )
        with _block_guard(self.helper.main_program, pb):
            array = lod_tensor_to_array(x, self._rank_table)
        # LoD desc shapes are token-major [-1, F]; a step slice is [N, F],
        # which has the same desc shape
        step = self._sub_block.create_var(
            name=unique_name("dynamic_rnn_step_in"),
            shape=list(x.shape),
            dtype=x.dtype,
        )
        self._sub_block.append_op(
            type="read_from_array", inputs={"X": [array], "I": [self._counter]},
            outputs={"Out": [step]},
        )
        return step

    def static_input(self, x):
        """Whole-batch non-sequence input visible at every step.  The
        reference reorders rows to rank-table order; here row order is
        preserved, so this is the identity."""
        self._assert_in_rnn("static_input")
        return x

    def memory(self, init=None, shape=None, value=0.0, need_reorder=False,
               dtype="float32"):
        self._assert_in_rnn("memory")
        pb = self._parent_block
        if init is None:
            if shape is None:
                raise ValueError("DynamicRNN.memory needs init= or shape=")
            if self._rank_table is None:
                raise RuntimeError(
                    "call step_input before value-initialized memory()"
                )
            boot = pb.create_var(
                name=unique_name("dynamic_rnn_mem_boot"),
                shape=[-1] + list(shape), dtype=dtype,
            )
            pb.append_op(
                type="fill_constant_batch_size_like",
                inputs={"Input": [self._first_input]},
                outputs={"Out": [boot]},
                attrs={
                    "shape": [-1] + list(shape),
                    "dtype": convert_dtype(dtype),
                    "value": float(value),
                    "input_dim_idx": 0, "output_dim_idx": 0,
                },
            )
            init = boot
        carry = pb.create_var(
            name=unique_name("dynamic_rnn_mem_carry"),
            shape=list(init.shape), dtype=init.dtype,
        )
        pb.append_op(
            type="assign", inputs={"X": [init]}, outputs={"Out": [carry]}
        )
        mem = self._sub_block.create_var(
            name=unique_name("dynamic_rnn_mem"),
            shape=list(init.shape), dtype=init.dtype,
        )
        self._sub_block.append_op(
            type="assign", inputs={"X": [carry]}, outputs={"Out": [mem]}
        )
        self._mem_dict[mem.name] = carry.name
        return mem

    def update_memory(self, ex_mem, new_mem):
        self._assert_in_rnn("update_memory")
        carry = self._mem_dict.get(ex_mem.name)
        if carry is None:
            raise ValueError("update_memory target was not created by memory()")
        self._sub_block.append_op(
            type="assign", inputs={"X": [new_mem]}, outputs={"Out": [carry]}
        )

    def output(self, *outputs):
        self._assert_in_rnn("output")
        pb = self._parent_block
        for o in outputs:
            array = pb.create_var(
                name=unique_name("dynamic_rnn_out_array"), shape=[],
                dtype=o.dtype, type=VarType.LOD_TENSOR_ARRAY,
            )
            pb.append_op(type="create_array", inputs={}, outputs={"Out": [array]})
            self._sub_block.append_op(
                type="write_to_array",
                inputs={"X": [o], "I": [self._counter], "Array": [array]},
                outputs={"Out": [array]},
            )
            out = pb.create_var(
                name=unique_name("dynamic_rnn_out"),
                shape=[-1] + list(o.shape[1:] if len(o.shape) > 1 else []),
                dtype=o.dtype,
            )
            out.desc.lod_level = 1
            self._outputs.append((array, out))

    def _complete(self):
        if self._rank_table is None:
            raise RuntimeError("DynamicRNN needs at least one step_input")
        program = self.helper.main_program
        sub_block = self._sub_block
        pb = self._parent_block
        sub_block.append_op(
            type="increment", inputs={"X": [self._counter]},
            outputs={"Out": [self._counter]}, attrs={"step": 1.0},
        )
        sub_block.append_op(
            type="less_than",
            inputs={"X": [self._counter], "Y": [self._max_len]},
            outputs={"Out": [self._cond]},
        )
        program._rollback()
        x_names, out_names = _analyze_block_io(
            sub_block, include_read_outputs=False
        )
        pb.append_op(
            type="while",
            inputs={"X": x_names, "Condition": [self._cond]},
            outputs={"Out": out_names, "StepScopes": []},
            attrs={
                "sub_block": sub_block.idx,
                "is_test": False,
                "__x_names__": x_names,
                "__out_names__": out_names,
                "__cond_name__": self._cond.name,
            },
        )
        for array, out in self._outputs:
            pb.append_op(
                type="array_to_lod_tensor",
                inputs={"X": [array], "RankTable": [self._rank_table]},
                outputs={"Out": [out]},
            )

    def __call__(self, *args, **kwargs):
        if self.status != DynamicRNN.AFTER_RNN:
            raise RuntimeError("DynamicRNN result is only available after block()")
        outs = [out for _, out in self._outputs]
        if len(outs) == 1:
            return outs[0]
        return outs


@contextlib.contextmanager
def _block_guard(program, block):
    """Temporarily make `block` the program's current block."""
    saved = program.current_block_idx
    program.current_block_idx = block.idx
    try:
        yield
    finally:
        program.current_block_idx = saved


# ---------------------------------------------------------------------------
# IfElse
# ---------------------------------------------------------------------------
class IfElse:
    """Per-row branch on a [N, 1] bool mask
    (reference: control_flow.py IfElse via split/merge_lod_tensor).

    The reference physically routes rows into two smaller batches; here both
    branches compute on the full batch and merge_lod_tensor selects rows —
    if-conversion, the SPMD-friendly equivalent.
    """

    OUT_IF_ELSE_BLOCKS = 0
    IN_IF_ELSE_TRUE_BLOCKS = 1
    IN_IF_ELSE_FALSE_BLOCKS = 2

    def __init__(self, cond, name: Optional[str] = None):
        self.helper = LayerHelper("ifelse", name=name)
        self.cond = cond
        self.status = IfElse.OUT_IF_ELSE_BLOCKS
        # per-branch outputs, by call order
        self.output_table: List[List[Optional[Variable]]] = [[], []]
        self._inputs: Dict[str, tuple] = {}

    @contextlib.contextmanager
    def true_block(self):
        self.status = IfElse.IN_IF_ELSE_TRUE_BLOCKS
        yield
        self.status = IfElse.OUT_IF_ELSE_BLOCKS

    @contextlib.contextmanager
    def false_block(self):
        self.status = IfElse.IN_IF_ELSE_FALSE_BLOCKS
        yield
        self.status = IfElse.OUT_IF_ELSE_BLOCKS

    def input(self, x):
        if self.status == IfElse.OUT_IF_ELSE_BLOCKS:
            raise RuntimeError("IfElse.input must be called inside a branch")
        if x.name not in self._inputs:
            self._inputs[x.name] = split_lod_tensor(x, self.cond)
        out_true, out_false = self._inputs[x.name]
        return (
            out_true
            if self.status == IfElse.IN_IF_ELSE_TRUE_BLOCKS
            else out_false
        )

    def output(self, *outs):
        if self.status == IfElse.OUT_IF_ELSE_BLOCKS:
            raise RuntimeError("IfElse.output must be called inside a branch")
        branch = 0 if self.status == IfElse.IN_IF_ELSE_TRUE_BLOCKS else 1
        self.output_table[branch].extend(outs)

    def __call__(self):
        t, f = self.output_table
        if len(t) != len(f):
            raise RuntimeError(
                "IfElse branches produced different numbers of outputs"
            )
        return [
            merge_lod_tensor(ti, fi, ti, self.cond) for ti, fi in zip(t, f)
        ]


# ---------------------------------------------------------------------------
# Switch
# ---------------------------------------------------------------------------
class Switch:
    """First-matching-case scalar branch (reference: control_flow.py Switch;
    used by learning-rate schedules).  Each case body runs in a sub-block
    lowered via conditional_block if-conversion with `cond AND NOT matched`.
    """

    def __init__(self, name: Optional[str] = None):
        self.helper = LayerHelper("switch", name=name)
        self.inside_scope = False
        self._matched = None

    def __enter__(self):
        self.inside_scope = True
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.inside_scope = False
        return False

    @contextlib.contextmanager
    def case(self, condition):
        if not self.inside_scope:
            raise RuntimeError("Switch.case used outside 'with Switch()'")
        if self._matched is None:
            effective = condition
            self._matched = condition
        else:
            effective = logical_and(condition, logical_not(self._matched))
            self._matched = logical_or(self._matched, condition)
        yield from _conditional_block_ctx(self.helper, effective)

    @contextlib.contextmanager
    def default(self):
        if self._matched is None:
            raise RuntimeError("Switch.default needs at least one case first")
        effective = logical_not(self._matched)
        yield from _conditional_block_ctx(self.helper, effective)


def _conditional_block_ctx(helper, cond):
    """Shared body for Switch.case/default: build a sub-block, then append a
    conditional_block op (reference: conditional_block_op.cc)."""
    program = helper.main_program
    parent_block = program.current_block()
    sub_block = program._create_block()
    try:
        yield
    finally:
        program._rollback()
    x_names, out_names = _analyze_block_io(sub_block, include_read_outputs=True)
    parent_block.append_op(
        type="conditional_block",
        inputs={"Cond": [cond], "X": x_names},
        outputs={"Out": out_names, "Scope": []},
        attrs={
            "sub_block": sub_block.idx,
            "is_scalar_condition": True,
            "__x_names__": x_names,
            "__out_names__": out_names,
        },
    )


def reorder_lod_tensor_by_rank(x, rank_table):
    """Reorder batch rows into rank-table order (reference:
    layers/control_flow.py reorder_lod_tensor_by_rank)."""
    helper = LayerHelper("reorder_lod_tensor_by_rank")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="reorder_lod_tensor_by_rank",
        inputs={"X": [x], "RankTable": [rank_table]},
        outputs={"Out": [out]},
    )
    return out
