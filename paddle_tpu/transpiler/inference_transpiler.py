"""Inference-program rewrites (reference: python/paddle/fluid/transpiler/
inference_transpiler.py:24 InferenceTranspiler).

The reference folds a trained batch_norm into the preceding conv2d by
computing folded filter/bias host-side and writing them into NEW
``<name>_fuse_bn`` variables, renaming the op inputs (``_fuse_batch_norm``
inference_transpiler.py:300, ``_fuse_param`` :416) — the original
parameters survive untouched, so transpiling an inference clone against
the shared global scope while the training program is live is safe.  It
then flips every op into test mode (``_is_test_pass`` :78).  The MKLDNN-only passes (conv+relu, conv+eltwise,
bn+relu fusion, :108-:298) have no equivalent here: XLA fuses elementwise
epilogues into the conv at compile time, so those rewrites would change
nothing on TPU.

The batch-norm fold is NOT subsumed by XLA, though: Scale/Bias/Mean/
Variance are runtime inputs (parameters), so the compiler cannot constant-
fold them into the filter.  Folding host-side removes four [C] parameter
reads and the normalize chain from every inference step and — more
importantly for parity — produces the same "conv + elementwise_add only"
program shape the reference's deployment tooling expects.

Pattern handled (same contract as the reference):

  conv2d -> batch_norm              (conv without bias)
  conv2d -> elementwise_add -> batch_norm   (conv with bias)

with the batch_norm in test mode (global Mean/Variance).  Matching is by
def-use (the batch_norm must be the *only* consumer of the conv output),
which is stricter than the reference's adjacent-op scan and therefore safe
on branchy programs (ResNet residuals keep their unfused adds).
"""

from __future__ import annotations

import numpy as np

__all__ = ["InferenceTranspiler"]

# ops whose lowering changes behavior between train and test mode; the
# reference sets is_test on every op that *declares* the attr (it reads the
# registered proto); our descs only hold explicitly-set attrs, so the op
# set is spelled out.
_IS_TEST_OPS = ("batch_norm", "fused_bn_add_act", "dropout", "lrn",
                "fake_quantize_abs_max", "fake_quantize_range_abs_max")


def _is_foldable_bn(op):
    """batch_norm, or the fused twin WITHOUT a residual input (a Z-free
    fused_bn_add_act behind a conv is the same conv+BN shape the fold
    handles; its activation is re-emitted as a standalone relu after the
    folded add)."""
    if op.type == "batch_norm":
        return True
    return (op.type == "fused_bn_add_act"
            and not (op.desc.inputs.get("Z") or []))


class InferenceTranspiler:
    """reference: inference_transpiler.py InferenceTranspiler."""

    def transpile(self, program, place, scope=None, protected_vars=None):
        """`protected_vars`: extra variable names whose VALUES must survive
        unchanged (e.g. intermediate fetch targets of a multi-output
        inference program).  Folding rewrites the conv filter, so a conv
        output that is itself fetched would silently return BN-scaled
        activations; the desc records consumers but not run-time fetch
        lists, hence the explicit hook (the reference has the same blind
        spot — its adjacency scan folds regardless of fetch targets)."""
        from paddle_tpu.core.framework import Program
        from paddle_tpu.core.scope import global_scope

        if not isinstance(program, Program):
            raise TypeError("program should be a Program")
        if scope is None:
            scope = global_scope()
        self._fuse_batch_norm(program, scope,
                              frozenset(protected_vars or ()))
        self._is_test_pass(program)
        program.desc.bump()

    # -- passes --------------------------------------------------------------
    def _is_test_pass(self, program):
        """reference: inference_transpiler.py:78."""
        for block in program.blocks:
            for op in block.ops:
                if op.type in _IS_TEST_OPS:
                    op.desc.attrs["is_test"] = True

    def _fuse_batch_norm(self, program, scope, protected):
        """reference: inference_transpiler.py:300 (math documented there:
        W' = W * scale/std;  b' = (b - mean) * scale/std + bias)."""
        block = program.block(0)

        def all_consumers(name):
            """(block0_idx, op) pairs for block-0 consumers; ops in ANY
            other block also count (sub-block ops read parent vars through
            the scope chain) but are returned with idx None so a sub-block
            reader disqualifies the fold."""
            out = [
                (j, o) for j, o in enumerate(block.ops)
                if name in o.desc.input_arg_names()
            ]
            for blk in program.blocks:
                if blk is block:
                    continue
                for o in blk.ops:
                    if name in o.desc.input_arg_names():
                        out.append((None, o))
            return out
        # single forward pass: a fold rewrites ops at indices > i only (the
        # bn is replaced in place by / merged into an elementwise_add), so
        # the scan resumes instead of restarting — O(n^2) worst case on the
        # consumer lookups, not O(n^3)
        i = 0
        while i < len(block.ops):
            op = block.ops[i]
            i += 1
            if op.type != "conv2d":
                continue
            conv_out = op.output("Output")[0]
            if conv_out in protected:
                continue
            consumers = all_consumers(conv_out)
            if len(consumers) != 1 or consumers[0][0] is None:
                continue
            j, nxt = consumers[0]
            if _is_foldable_bn(nxt) and nxt.input("X") == [conv_out]:
                self._fold(block, scope, op, bn_idx=j, bias_op=None)
                continue
            if nxt.type == "elementwise_add" and nxt.attr("axis", -1) == 1:
                bias_name = nxt.input("Y")[0]
                if not self._is_channel_bias(block, bias_name):
                    continue
                add_out = nxt.output("Out")[0]
                if add_out in protected:
                    continue
                nxt2 = all_consumers(add_out)
                if len(nxt2) == 1 and nxt2[0][0] is not None \
                        and _is_foldable_bn(nxt2[0][1]) \
                        and nxt2[0][1].input("X") == [add_out]:
                    self._fold(block, scope, op, bn_idx=nxt2[0][0],
                               bias_op=nxt)
        self._remove_unused_vars(program)

    # -- helpers -------------------------------------------------------------
    @staticmethod
    def _is_channel_bias(block, name):
        if not block.desc.has_var(name):
            return False
        shape = block.desc.vars[name].shape
        return shape is not None and len(shape) == 1

    @staticmethod
    def _scope_array(scope, name):
        val = scope.find_var(name)
        if val is None:
            raise ValueError(
                f"InferenceTranspiler: variable '{name}' has no value in the "
                f"scope — run the startup program (and load params) first")
        return np.asarray(val)

    @staticmethod
    def _fused_copy(block, scope, src_name, value, shape):
        """Write `value` into a NEW persistable var `<src>_fuse_bn` (unique-
        suffixed if a previous fold already claimed the name, e.g. two convs
        sharing one filter) and return its name.  The reference does exactly
        this in _fuse_param (inference_transpiler.py:435 new_param_name =
        old_param_name + '_fuse_bn'): the ORIGINAL parameter survives
        untouched, so transpiling an inference clone against the shared
        global scope while the training program is live cannot corrupt
        training, and save_persistables on the training program still writes
        the true weights."""
        import dataclasses

        name = src_name + "_fuse_bn"
        n = 2
        while block.desc.has_var(name) or scope.find_var(name) is not None:
            name = f"{src_name}_fuse_bn_{n}"
            n += 1
        src_desc = block.desc.vars.get(src_name)
        if src_desc is None:
            # a runnable conv/add always carries its param descs; a missing
            # one is desc corruption — fail loudly rather than fabricate a
            # default-FP32 desc that would disagree with the scope value
            raise ValueError(
                f"InferenceTranspiler: parameter '{src_name}' has no "
                f"VarDesc in the program — cannot fold")
        desc = dataclasses.replace(
            src_desc, name=name, shape=list(shape), persistable=True)
        block.desc.vars[name] = desc
        scope.set_var(name, value)
        return name

    @staticmethod
    def _emit_act(block, idx, act, dst_name):
        """Re-emit a fused op's activation as a standalone relu at `idx`
        writing `dst_name` (the fold replaces fused_bn_add_act(act=relu)
        with add -> relu).  Returns the new pre-activation var name the
        producing add should write instead, or None when there is no
        activation."""
        import dataclasses

        if not act:
            return None
        if act != "relu":
            raise ValueError(
                f"InferenceTranspiler: cannot re-emit activation {act!r}")
        tmp = dst_name + "_prerelu"
        n = 2
        while block.desc.has_var(tmp):
            tmp = f"{dst_name}_prerelu_{n}"
            n += 1
        block.desc.vars[tmp] = dataclasses.replace(
            block.desc.vars[dst_name], name=tmp, persistable=False)
        block._insert_op(idx, type="relu", inputs={"X": [tmp]},
                         outputs={"Out": [dst_name]}, attrs={})
        return tmp

    def _fold(self, block, scope, conv_op, bn_idx, bias_op):
        bn = block.ops[bn_idx]
        act = (bn.attr("act", None)
               if bn.type == "fused_bn_add_act" else None)
        w_name = conv_op.input("Filter")[0]
        w = self._scope_array(scope, w_name)
        scale = self._scope_array(scope, bn.input("Scale")[0]).astype(np.float64)
        beta_raw = self._scope_array(scope, bn.input("Bias")[0])
        beta = beta_raw.astype(np.float64)
        mean = self._scope_array(scope, bn.input("Mean")[0]).astype(np.float64)
        var = self._scope_array(scope, bn.input("Variance")[0]).astype(np.float64)
        eps = bn.attr("epsilon", 1e-5)

        # filter is [Cout, Cin/groups, kh, kw]: channel axis 0 for any groups
        alpha = scale / np.sqrt(var + eps)
        w_new = (w.astype(np.float64) * alpha.reshape((-1,) + (1,) * (w.ndim - 1)))
        conv_op.desc.inputs["Filter"] = [self._fused_copy(
            block, scope, w_name, w_new.astype(w.dtype), w.shape)]

        bn_y = bn.output("Y")[0]
        if bias_op is not None:
            old_bias = self._scope_array(scope, bias_op.input("Y")[0])
            b_new = (old_bias.astype(np.float64) - mean) * alpha + beta
            bias_op.desc.inputs["Y"] = [self._fused_copy(
                block, scope, bias_op.input("Y")[0],
                b_new.astype(old_bias.dtype), old_bias.shape)]
            block._remove_op(bn_idx)
            # redirect the existing add's output to the bn output (or,
            # for a fused op with an activation, through a re-emitted act)
            pre = self._emit_act(block, bn_idx, act, bn_y)
            bias_op.desc.outputs["Out"] = [pre or bn_y]
        else:
            b_new = (0.0 - mean) * alpha + beta
            bias_name = self._fused_copy(
                block, scope, bn.input("Bias")[0],
                b_new.astype(beta_raw.dtype), beta.shape)
            conv_out = conv_op.output("Output")[0]
            block._remove_op(bn_idx)
            pre = self._emit_act(block, bn_idx, act, bn_y)
            block._insert_op(
                bn_idx, type="elementwise_add",
                inputs={"X": [conv_out], "Y": [bias_name]},
                outputs={"Out": [pre or bn_y]},
                attrs={"axis": 1})

    @staticmethod
    def _remove_unused_vars(program):
        """reference: inference_transpiler.py _remove_unused_var — drop desc
        vars (the stale bn Scale/Mean/Variance and intermediates) referenced
        by no op, so save_persistables after the fold skips them.  The used
        set spans EVERY block: a block-0 var consumed only inside a while/
        cond sub-block must survive (sub-block ops resolve inputs through
        the parent chain)."""
        used = set()
        for blk in program.blocks:
            for op in blk.ops:
                used.update(op.desc.input_arg_names())
                used.update(op.desc.output_arg_names())
        block = program.block(0)
        for name in list(block.desc.vars):
            if name not in used:
                del block.desc.vars[name]
                block.vars.pop(name, None)
