"""Loss ops (reference: paddle/fluid/operators/*_loss_op.*, cross_entropy_op,
softmax_with_cross_entropy_op, sigmoid_cross_entropy_with_logits_op...)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import amp
from ..core.proto import DataType
from ..core.registry import GRAD_SUFFIX, register_op
from ..observability import span
from .common import data, in_desc, same_shape, set_output, wrap_lod


def _rowwise_loss_infer(op, block, x_slot="X"):
    x = in_desc(op, block, x_slot)
    if x is None:
        return
    set_output(block, op, "Y" if op.output("Y") else "Out", list(x.shape[:-1]) + [1], x.dtype)


def _take_label_prob(probs, label, ignore_index=-100):
    """prob of the labeled class per row; label is int [..., 1]."""
    lab = label
    if lab.ndim == probs.ndim:
        lab = jnp.squeeze(lab, axis=-1)
    picked = jnp.take_along_axis(probs, lab[..., None].astype(jnp.int32), axis=-1)
    return picked, lab


def _cross_entropy_infer(op, block):
    x = in_desc(op, block, "X")
    if x is None:
        return
    set_output(block, op, "Y", list(x.shape[:-1]) + [1], x.dtype)


@register_op("cross_entropy", infer_shape=_cross_entropy_infer, diff_inputs=["X"])
def _cross_entropy(ctx, ins, attrs):
    """-log(prob[label]) over *probabilities* (reference:
    operators/cross_entropy_op.cc; soft_label supported)."""
    x0 = data(ins["X"][0])
    # the log and its reduction run fp32 for half-width probabilities
    # (amp keep_output; eps=1e-12 is below bf16 resolution)
    x = x0.astype(amp.stats_dtype(x0))
    label = data(ins["Label"][0])
    eps = 1e-12
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        picked, lab = _take_label_prob(x, label)
        loss = -jnp.log(picked + eps)
        ignore = attrs.get("ignore_index", -100)
        mask = (lab != ignore)[..., None]
        loss = jnp.where(mask, loss, 0.0)
    return {"Y": [wrap_lod(ins["X"][0], loss.astype(x0.dtype))]}


def _swce_infer(op, block):
    x = in_desc(op, block, "Logits")
    if x is None:
        return
    set_output(block, op, "Softmax", x.shape, x.dtype)
    set_output(block, op, "Loss", list(x.shape[:-1]) + [1], x.dtype)


def _hard_ce_forward(logits, lab, smooth_eps, ignore_index):
    """One pass over the logits: (Softmax, Loss) in the logits' dtype, and
    what the backward reads: e = exp(x - max) in the logits' dtype, its
    fp32 row sum s.  The statistics run fp32 (amp.stats_dtype); the picked
    value comes from the logits themselves (x[label] - lse) and the
    smoothing's mean(logp) is mean(x) - lse, so no [rows, V] array of
    log-probabilities is written for a gather to read one value a row."""
    x = logits.astype(amp.stats_dtype(logits))
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    lse = m + jnp.log(s)
    valid = (lab != ignore_index)[..., None]
    idx = jnp.where(valid, lab[..., None], 0).astype(jnp.int32)
    picked = jnp.take_along_axis(logits, idx, axis=-1).astype(x.dtype)
    if smooth_eps:
        # folded uniform label smoothing (layers.py smooth_eps): the target
        # is (1-eps)*onehot + eps/V, so -sum(target*logp) =
        # lse - (1-eps)*x[label] - eps*mean_V(x): no [*, V] label tensor
        picked = (1.0 - smooth_eps) * picked + smooth_eps * jnp.mean(
            x, axis=-1, keepdims=True)
    loss = jnp.where(valid, lse - picked, 0.0)
    return ((e / s).astype(logits.dtype), loss.astype(logits.dtype),
            e.astype(logits.dtype), s)


def _softmax_grad_asked(ctx) -> bool:
    """Whether the program asks a gradient of this site's Softmax output:
    its grad op then names a `Softmax@GRAD`.  True where no grad op says
    (a site differentiated as part of a larger unit)."""
    uid = getattr(ctx.cur_op, "attrs", {}).get("__op_uid__")
    if uid is None:
        return True
    for op in ctx.block.desc.ops:
        if op.attrs.get("__fwd_op_uid__") == uid:
            return any(op.inputs.get("Softmax" + GRAD_SUFFIX, []))
    return True


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _hard_ce(logits, lab, smooth_eps, ignore_index, softmax_grad):
    """Hard-label softmax + cross entropy -> (Softmax, Loss) whose backward
    reads the saved exponentials instead of evaluating exp over the logits
    again.  `softmax_grad` False leaves the Softmax output's cotangent out
    of the backward: a site whose program asks no gradient of it hands the
    vjp an array of zeros, which XLA does not fold (0 * e stays a pass over
    [rows, V])."""
    return _hard_ce_forward(logits, lab, smooth_eps, ignore_index)[:2]


def _hard_ce_fwd(logits, lab, smooth_eps, ignore_index, softmax_grad):
    softmax, loss, e, s = _hard_ce_forward(logits, lab, smooth_eps,
                                           ignore_index)
    # pinned: without the barrier XLA declines the saved array and fuses
    # exp(x - max) again into each of the two gradient matmuls that read it
    return (softmax, loss), (jax.lax.optimization_barrier(e), s, lab)


def _hard_ce_bwd(smooth_eps, ignore_index, softmax_grad, res, cts):
    """dLogits = (g / s) * e - g * target: a multiply and a select a value,
    no transcendental, formed in the statistics' dtype, cast to the logits'
    dtype, left to XLA to fuse into the matmuls that consume it."""
    e, s, lab = res
    g_softmax, g_loss = cts
    e32 = e.astype(s.dtype)
    g = jnp.where((lab != ignore_index)[..., None],
                  g_loss.astype(s.dtype), 0.0)
    classes = e.shape[-1]
    hit = lab[..., None] == jnp.arange(classes, dtype=lab.dtype)
    target = jnp.where(hit, 1.0 - smooth_eps, 0.0) + smooth_eps / classes
    scale = g
    if softmax_grad:  # softmax's own vjp: p * (gp - sum(gp * p)), p = e / s
        gp = g_softmax.astype(s.dtype)
        scale = g + gp - jnp.sum(gp * e32, axis=-1, keepdims=True) / s
    dlogits = (scale / s) * e32 - g * target
    return dlogits.astype(e.dtype), None


_hard_ce.defvjp(_hard_ce_fwd, _hard_ce_bwd)


@register_op("softmax_with_cross_entropy", infer_shape=_swce_infer, diff_inputs=["Logits"])
def _softmax_with_cross_entropy(ctx, ins, attrs):
    """Fused, numerically-stable softmax+CE (reference:
    operators/softmax_with_cross_entropy_op.cc).  Hard labels (with
    smooth_eps and ignore_index) take `_hard_ce`: one exponential pass,
    saved in the logits' dtype and pinned for the backward; soft labels
    keep jax's own gradient.  `ce.lower` (a span, at lowering) says which
    path a site took and what it pinned."""
    logits = data(ins["Logits"][0])
    label = data(ins["Label"][0])
    soft = bool(attrs.get("soft_label", False))
    eps = float(attrs.get("smooth_eps", 0.0))
    classes = int(logits.shape[-1])
    rows = int(logits.size // max(classes, 1))
    with span("ce.lower", rows=rows, classes=classes,
              dtype=str(logits.dtype),
              path="autodiff" if soft else "saved_exp",
              pinned_bytes=0 if soft else logits.size * logits.dtype.itemsize,
              smooth_eps=eps, soft_label=soft):
        if soft:
            # bf16 logits (amp keep_output) reduce in fp32
            logp = jax.nn.log_softmax(
                logits.astype(amp.stats_dtype(logits)), axis=-1)
            softmax = jnp.exp(logp).astype(logits.dtype)
            loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
            loss = loss.astype(logits.dtype)
        else:
            lab = label
            if lab.ndim == logits.ndim:
                lab = jnp.squeeze(lab, axis=-1)
            softmax, loss = _hard_ce(
                logits, lab, eps, attrs.get("ignore_index", -100),
                _softmax_grad_asked(ctx))
    # outputs keep the logits' dtype (the fp32 math is internal)
    return {"Softmax": [softmax], "Loss": [loss]}


@register_op("sigmoid_cross_entropy_with_logits", infer_shape=same_shape(), diff_inputs=["X"])
def _sigmoid_ce(ctx, ins, attrs):
    x = data(ins["X"][0])
    label = data(ins["Label"][0])
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    loss = jnp.where(label == ignore, 0.0, loss)
    return {"Out": [loss]}


@register_op("bpr_loss", infer_shape=_cross_entropy_infer, diff_inputs=["X"])
def _bpr_loss(ctx, ins, attrs):
    """Bayesian personalized ranking (reference: operators/bpr_loss_op.cc)."""
    x = data(ins["X"][0])
    label = data(ins["Label"][0])
    lab = jnp.squeeze(label, axis=-1) if label.ndim == x.ndim else label
    pos = jnp.take_along_axis(x, lab[..., None].astype(jnp.int32), axis=-1)
    diff = x - pos
    loss = jnp.mean(jnp.log1p(jnp.exp(diff)), axis=-1, keepdims=True)
    return {"Y": [loss]}


@register_op("hinge_loss", infer_shape=same_shape("Logits", "Loss"), diff_inputs=["Logits"])
def _hinge_loss(ctx, ins, attrs):
    logits = data(ins["Logits"][0])
    labels = data(ins["Labels"][0])
    return {"Loss": [jnp.maximum(0.0, 1.0 - (2.0 * labels - 1.0) * logits)]}


@register_op("huber_loss", infer_shape=same_shape("X", "Out"), diff_inputs=["X", "Y"])
def _huber_loss(ctx, ins, attrs):
    x = data(ins["X"][0])
    y = data(ins["Y"][0])
    delta = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    return {"Out": [loss], "Residual": [r]}


@register_op("log_loss", infer_shape=same_shape("Predicted", "Loss"), diff_inputs=["Predicted"])
def _log_loss(ctx, ins, attrs):
    p = data(ins["Predicted"][0])
    label = data(ins["Labels"][0])
    eps = attrs.get("epsilon", 1e-4)
    loss = -label * jnp.log(p + eps) - (1.0 - label) * jnp.log(1.0 - p + eps)
    return {"Loss": [loss]}


def _rank_loss_infer(op, block):
    x = in_desc(op, block, "Left")
    if x is not None:
        set_output(block, op, "Out", x.shape, x.dtype)


@register_op("rank_loss", infer_shape=_rank_loss_infer, diff_inputs=["Left", "Right"])
def _rank_loss(ctx, ins, attrs):
    label = data(ins["Label"][0])
    left = data(ins["Left"][0])
    right = data(ins["Right"][0])
    d = left - right
    return {"Out": [jnp.log1p(jnp.exp(d)) - label * d]}


@register_op("margin_rank_loss", infer_shape=same_shape("X1", "Out"), diff_inputs=["X1", "X2"])
def _margin_rank_loss(ctx, ins, attrs):
    label = data(ins["Label"][0])
    x1 = data(ins["X1"][0])
    x2 = data(ins["X2"][0])
    margin = attrs.get("margin", 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": [out], "Activated": [(out > 0).astype(x1.dtype)]}


@register_op("smooth_l1_loss", infer_shape=lambda op, block: (set_output(block, op, "Out", list(in_desc(op, block, "X").shape[:1]) + [1], in_desc(op, block, "X").dtype), set_output(block, op, "Diff", in_desc(op, block, "X").shape, in_desc(op, block, "X").dtype)), diff_inputs=["X", "Y"])
def _smooth_l1_loss(ctx, ins, attrs):
    x = data(ins["X"][0])
    y = data(ins["Y"][0])
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    iw = ins.get("InsideWeight", [None])[0]
    ow = ins.get("OutsideWeight", [None])[0]
    if iw is not None:
        diff = diff * data(iw)
    ad = jnp.abs(diff)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * diff * diff, ad - 0.5 / s2)
    if ow is not None:
        loss = loss * data(ow)
    loss = jnp.sum(loss.reshape(loss.shape[0], -1), axis=-1, keepdims=True)
    return {"Out": [loss], "Diff": [diff]}


@register_op("squared_l2_distance", infer_shape=lambda op, block: (set_output(block, op, "Out", [in_desc(op, block, "X").shape[0], 1], in_desc(op, block, "X").dtype), set_output(block, op, "sub_result", in_desc(op, block, "X").shape, in_desc(op, block, "X").dtype)), diff_inputs=["X", "Y"])
def _squared_l2_distance(ctx, ins, attrs):
    x = data(ins["X"][0])
    y = data(ins["Y"][0])
    sub = x - y
    out = jnp.sum(sub.reshape(sub.shape[0], -1) ** 2, axis=-1, keepdims=True)
    return {"Out": [out], "sub_result": [sub]}


def _nce_infer(op, block):
    x = in_desc(op, block, "Input")
    label = in_desc(op, block, "Label")
    if x is None or label is None:
        return
    n = x.shape[0]
    num_neg = op.attr("num_neg_samples", 10)
    num_true = label.shape[1] if len(label.shape) > 1 else 1
    set_output(block, op, "Cost", [n, 1], x.dtype)
    set_output(block, op, "SampleLogits", [n, num_neg + num_true], x.dtype)
    set_output(block, op, "SampleLabels", [n, num_neg + num_true], DataType.INT64)


@register_op("nce", infer_shape=_nce_infer, diff_inputs=["Input", "Weight", "Bias"], random=True)
def _nce(ctx, ins, attrs):
    """Noise-contrastive estimation (reference: operators/nce_op.cc) with
    uniform negative sampling."""
    x = data(ins["Input"][0])          # [N, D]
    label = data(ins["Label"][0])      # [N, T]
    w = data(ins["Weight"][0])         # [V, D]
    b = ins.get("Bias", [None])[0]
    num_classes = attrs["num_total_classes"]
    num_neg = attrs.get("num_neg_samples", 10)
    n = x.shape[0]
    num_true = label.shape[1] if label.ndim > 1 else 1
    lab = label.reshape(n, num_true)
    neg = jax.random.randint(ctx.rng(), (n, num_neg), 0, num_classes)
    samples = jnp.concatenate([lab.astype(jnp.int32), neg.astype(jnp.int32)], axis=1)
    ws = jnp.take(w, samples, axis=0)               # [N, T+S, D]
    logits = jnp.einsum("nd,ntd->nt", x, ws)
    if b is not None:
        logits = logits + jnp.take(data(b).reshape(-1), samples)
    p_noise = num_neg / num_classes
    labels01 = jnp.concatenate(
        [jnp.ones((n, num_true)), jnp.zeros((n, num_neg))], axis=1
    )
    # NCE logistic loss with uniform noise: P(true|x) = s / (s + k*q)
    prob = jax.nn.sigmoid(logits - np.log(max(p_noise, 1e-12)))
    cost = -(labels01 * jnp.log(prob + 1e-12) + (1 - labels01) * jnp.log(1 - prob + 1e-12))
    return {
        "Cost": [jnp.sum(cost, axis=1, keepdims=True)],
        "SampleLogits": [logits],
        "SampleLabels": [samples.astype(jnp.int32)],
    }
