"""conv-epilogue fusion pass (FLAGS_fuse_conv_epilogue).

Reference counterpart: ir/conv_bn_fuse_pass + conv_elementwise_add_act_fuse
(paddle/fluid/framework/ir/), the graph passes that rewrite
conv2d -> batch_norm [-> elementwise_add] [-> relu] chains onto cuDNN's
fused conv op (operators/conv_fusion_op.cu.cc).  Here the same rewrite
targets the one-op `conv_bn_add_act` tier (ops/nn_ops.py), whose
implementation FLAGS_conv_epilogue then picks: the "reference" XLA
composition (pass-created ops store their intermediates exactly like the
unfused chain — no recompute), or the "pallas" kernel pair
(kernels/conv_epilogue.py), which accumulates BN statistics inside the
conv pass and backs it with the analytic vjp — the HBM-roofline attack
(92.5 GB/step on ResNet-50 in an earlier round's v5e run, not
re-measured).

The pass runs at COMPILE time on the op list a CompiledBlock is about to
lower (core/compiler.py); the ProgramDesc itself is never mutated, so
Program clones, serialization, transpilers and the API surface all keep
seeing the reference-shaped chain.  Gradient ops need no special casing:
the fused forward op records one jax.vjp closure under its uid, and the
four chain grad ops collapse into one `conv_bn_add_act_grad` consuming
`Y@GRAD` and scattering the boundary gradients to the exact names the
original grad ops produced (renamed-for-accumulation `@RENAME@` targets
included), so downstream `sum`/optimizer ops are untouched.

A chain is only rewritten when it is provably private: every intermediate
(conv out, bn out, add out, and their grads) is consumed exclusively
inside the chain, none is fetched, and either all four grad ops are
present or none (forward-only programs rewrite too; partial autodiff
windows do not).  Programs without a match lower byte-identically with
the flag on — the pass returns the original list untouched.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set

from .proto import OpDesc

__all__ = ["fuse_conv_epilogue_ops"]


def _one(names: Sequence[str]) -> Optional[str]:
    """The single non-empty name of a slot, or None."""
    if len(names) == 1 and names[0]:
        return names[0]
    return None


def _square(pair) -> Optional[int]:
    if isinstance(pair, (list, tuple)) and len(pair) == 2 and pair[0] == pair[1]:
        return int(pair[0])
    return None


class _Maps:
    """Consumer/producer indices over one op list."""

    def __init__(self, ops: List[OpDesc]):
        self.consumers: Dict[str, Set[int]] = {}
        self.grad_of_uid: Dict[int, int] = {}
        for i, op in enumerate(ops):
            for n in op.input_arg_names():
                if n:
                    self.consumers.setdefault(n, set()).add(i)
            uid = op.attrs.get("__fwd_op_uid__")
            if uid is not None:
                # one grad op per forward uid (append_backward contract)
                self.grad_of_uid[uid] = i

    def consumed_only_by(self, name: str, allowed: Set[int]) -> bool:
        return self.consumers.get(name, set()) <= allowed


def _match_chain(ops, maps, ci, vars_, protected, claimed=frozenset()):
    """Try to root a conv2d -> batch_norm [-> elementwise_add] [-> relu]
    chain at ops[ci].  Returns None or a dict describing the match.
    Ops in `claimed` belong to an already-matched chain: extension stops
    before them (a shortcut conv->bn whose add was taken by the main
    branch still fuses bare, with act='')."""
    conv = ops[ci]
    if conv.attrs.get("dilations", [1, 1]) != [1, 1]:
        return None
    if _square(conv.attrs.get("strides", [1, 1])) is None:
        return None
    if _square(conv.attrs.get("paddings", [0, 0])) is None:
        return None
    conv_out = _one(conv.output("Output"))
    x_in = _one(conv.input("Input"))
    filt = _one(conv.input("Filter"))
    if not (conv_out and x_in and filt) or conv_out in protected:
        return None
    fdesc = vars_.get(filt)
    if fdesc is None or len(fdesc.shape) != 4 or fdesc.shape[2] != fdesc.shape[3]:
        return None  # conv_bn_add_act needs a square filter

    def sole_fwd_consumer(name):
        idxs = [
            i for i in maps.consumers.get(name, ())
            if "__fwd_op_uid__" not in ops[i].attrs
        ]
        if len(idxs) != 1 or idxs[0] in claimed:
            return None
        return ops[idxs[0]]

    bn = sole_fwd_consumer(conv_out)
    if (
        bn is None or bn.type != "batch_norm"
        or bn.input("X") != [conv_out]
        or bn.attrs.get("is_test", False)
        or bn.attrs.get("use_global_stats", False)
        or bn.attrs.get("data_layout", "NCHW") != "NCHW"
    ):
        return None
    bn_out = _one(bn.output("Y"))
    if bn_out is None or bn_out in protected:
        return None
    for slot in ("Scale", "Bias", "Mean", "Variance"):
        if _one(bn.input(slot)) is None:
            return None

    chain = [conv, bn]
    inner = [conv_out]
    z = None
    tail_out = bn_out

    nxt = sole_fwd_consumer(bn_out)
    if nxt is not None and nxt.type == "elementwise_add" \
            and nxt.attrs.get("axis", -1) in (-1, 0):
        xs, ys = nxt.input("X"), nxt.input("Y")
        if xs == [bn_out]:
            z = _one(ys)
        elif ys == [bn_out]:
            z = _one(xs)
        add_out = _one(nxt.output("Out"))
        if z is None or add_out is None or add_out in protected or z == bn_out:
            return None
        zdesc, odesc = vars_.get(z), vars_.get(bn_out)
        if (
            zdesc is None or odesc is None
            or list(zdesc.shape) != list(odesc.shape)
            or zdesc.dtype != odesc.dtype
        ):
            return None
        chain.append(nxt)
        inner.append(bn_out)
        tail_out = add_out
        nxt = sole_fwd_consumer(add_out)

    act = ""
    if nxt is not None and nxt.type == "relu" and nxt.input("X") == [tail_out]:
        relu_out = _one(nxt.output("Out"))
        if relu_out is None:
            return None
        chain.append(nxt)
        inner.append(tail_out)
        tail_out = relu_out
        act = "relu"

    idxs = {id(op): i for i, op in enumerate(ops)}
    fwd_idx = {idxs[id(op)] for op in chain}

    # gradient window: all-or-nothing
    grad_idx: List[int] = []
    for op in chain:
        uid = op.attrs.get("__op_uid__")
        gi = maps.grad_of_uid.get(uid) if uid is not None else None
        if gi is not None and ops[gi].type == op.type + "_grad":
            grad_idx.append(gi)
    if grad_idx and len(grad_idx) != len(chain):
        return None
    removal = fwd_idx | set(grad_idx)

    # every intermediate (and its grad) must live and die inside the
    # chain: an inner grad is any output of the removed grad ops that is
    # not one of the boundary grads the fused grad op will keep producing
    grads = [ops[i] for i in sorted(grad_idx)]
    inner_grads = []
    boundary = _grad_boundary(chain, grads, z) if grads else None
    if grads:
        keep = set(boundary["outputs"].values())
        for g in grads:
            for names in g.outputs.values():
                for n in names:
                    if n and n not in keep:
                        inner_grads.append(n)
    for n in inner + inner_grads:
        if n in protected or not maps.consumed_only_by(n, removal):
            return None
        vd = vars_.get(n)
        if vd is not None and vd.persistable:
            return None

    return {
        "conv": conv, "bn": bn, "z": z, "act": act, "y": tail_out,
        "chain": chain, "grads": grads, "removal": removal,
        "fwd_pos": idxs[id(chain[-1])],
        "grad_pos": min(grad_idx) if grad_idx else None,
        "boundary": boundary,
    }


def _grad_boundary(chain, grads, z):
    """Map the original grad ops' boundary names onto the fused grad op's
    slots.  Output names are copied verbatim (they may be `@RENAME@i`
    accumulation targets)."""
    by_type = {g.type: g for g in grads}
    tail = chain[-1]
    tail_grad = by_type[tail.type + "_grad"]
    out_slot = "Y" if tail.type in ("batch_norm",) else "Out"
    y_grad = _one(tail_grad.input(out_slot + "@GRAD"))
    outputs = {}
    bn_grad = by_type["batch_norm_grad"]
    conv_grad = by_type["conv2d_grad"]
    outputs["X@GRAD"] = (conv_grad.output("Input@GRAD") or [""])[0]
    outputs["Filter@GRAD"] = (conv_grad.output("Filter@GRAD") or [""])[0]
    outputs["Scale@GRAD"] = (bn_grad.output("Scale@GRAD") or [""])[0]
    outputs["Bias@GRAD"] = (bn_grad.output("Bias@GRAD") or [""])[0]
    if z is not None:
        add = next(op for op in chain if op.type == "elementwise_add")
        add_grad = by_type["elementwise_add_grad"]
        zslot = "Y" if add.input("Y") == [z] else "X"
        outputs["Z@GRAD"] = (add_grad.output(zslot + "@GRAD") or [""])[0]
    return {"y_grad": y_grad, "outputs": outputs}


def _fused_ops(m):
    """Build the fused forward (and grad) OpDesc for one match."""
    conv, bn, z = m["conv"], m["bn"], m["z"]
    uid = conv.attrs.get("__op_uid__")
    inputs = {
        "X": list(conv.input("Input")),
        "Filter": list(conv.input("Filter")),
        "Scale": list(bn.input("Scale")),
        "Bias": list(bn.input("Bias")),
        "Mean": list(bn.input("Mean")),
        "Variance": list(bn.input("Variance")),
    }
    if z is not None:
        inputs["Z"] = [z]
    attrs = {
        "strides": list(conv.attrs.get("strides", [1, 1])),
        "paddings": list(conv.attrs.get("paddings", [0, 0])),
        "groups": int(conv.attrs.get("groups", 1) or 1),
        "momentum": bn.attrs.get("momentum", 0.9),
        "epsilon": bn.attrs.get("epsilon", 1e-5),
        "is_test": False,
        "act": m["act"],
        "__fused_from__": "conv_epilogue_pass",
    }
    scope = conv.attrs.get("op_namescope", "")
    if scope:
        attrs["op_namescope"] = scope
    if uid is not None:
        attrs["__op_uid__"] = uid
    fwd = OpDesc(
        type="conv_bn_add_act",
        inputs=inputs,
        outputs={
            "Y": [m["y"]],
            "MeanOut": list(bn.output("MeanOut")),
            "VarianceOut": list(bn.output("VarianceOut")),
            "SavedMean": list(bn.output("SavedMean")),
            "SavedVariance": list(bn.output("SavedVariance")),
        },
        attrs=attrs,
    )
    if not m["grads"]:
        return fwd, None
    b = m["boundary"]
    grad = OpDesc(
        type="conv_bn_add_act_grad",
        inputs={"Y@GRAD": [b["y_grad"] or ""]},
        outputs={slot: [name] for slot, name in b["outputs"].items()},
        attrs={"__fwd_op_uid__": uid},
    )
    return fwd, grad


def fuse_conv_epilogue_ops(
    ops: Sequence[OpDesc],
    vars_: Dict[str, object],
    protected: Sequence[str] = (),
) -> List[OpDesc]:
    """Rewrite every private conv->bn[->add][->relu] chain in `ops` into
    one conv_bn_add_act op (+ one fused grad op).  Returns the SAME list
    object when nothing matched, so callers can cheaply detect no-ops;
    the input OpDescs are never mutated either way.

    `protected` names (fetch targets) must survive the rewrite, so chains
    producing them as intermediates are skipped."""
    ops = list(ops) if not isinstance(ops, list) else ops
    protected = set(protected)
    maps = _Maps(ops)
    matches = []
    claimed: Set[int] = set()
    # reverse program order: in a residual block the MAIN branch's last
    # conv is built after the shortcut conv, and matching it first lets
    # the main chain own the elementwise_add (the shortcut then fuses as
    # a plain conv->bn); forward order would hand the add to the shortcut
    # and leave the main conv+bn unfused
    for i in reversed(range(len(ops))):
        op = ops[i]
        if op.type != "conv2d" or i in claimed:
            continue
        m = _match_chain(ops, maps, i, vars_, protected, claimed)
        if m is None or m["removal"] & claimed:
            continue
        claimed |= m["removal"]
        matches.append(m)
    if not matches:
        return ops

    fwd_at, grad_at = {}, {}
    for m in matches:
        fused_fwd, fused_grad = _fused_ops(m)
        fwd_at[m["fwd_pos"]] = fused_fwd
        if m["grad_pos"] is not None:
            grad_at[m["grad_pos"]] = fused_grad
    out: List[OpDesc] = []
    for i, op in enumerate(ops):
        if i in fwd_at:
            out.append(fwd_at[i])
        elif i in grad_at:
            out.append(grad_at[i])
        elif i not in claimed:
            out.append(op)
    return out
