"""A sparse expert feed-forward layer on the Program path, told which experts
it holds (TPU-native; the 2018 reference has no such ops).

Three ops, one chip's share of an expert-parallel layer:

- `moe_router`: sigmoid scores over ALL the experts, the `top_k` largest of
  score + bias chosen, their weights the scores themselves (without the
  bias) divided by their sum and times a scaling factor (DeepSeek-V3's
  `noaux_tc`); or, under `scoring` "softmax", the softmax over all the
  experts in place of the sigmoid (Qwen3-MoE's rule: no bias, scaling 1).
  The bias is state with no gradient.  Scores in fp32, as the families'
  implementations compute them: a top-k over bf16 scores picks another
  expert wherever two scores lie within bf16's rounding.
- `moe_experts`: the part of sum_i g_i E_i(x) that the HELD experts
  [expert_offset, expert_offset + held) give, E_i(u) = W_down(silu(W_gate
  u) * W_up u).  Static shapes, one compilation, no capacity factor and no
  dropped token: the (token, slot) assignments are sorted by expert into a
  row buffer that holds the worst case (every assignment of every token
  held here), and a grouped matmul (on a TPU the Pallas kernel jax ships,
  megablox, whose grid covers only the row tiles the groups fill; XLA's
  ragged_dot elsewhere) runs over the groups by their sizes: its cost
  follows the rows routed here, not the buffer.  The gathers and masks
  around it do cost by the buffer's rows, so the same code is compiled
  for a ladder of buffers (row_buffers: twice what an even router sends
  here, doubled up to the worst case) and a step runs in the smallest
  that its count fits.  Forward, backward (the
  weight gradient is a grouped product too) and the forward
  computed again under recompute all take this one path.  What the absent
  experts would have added is left out; nothing stands in for the chips
  that hold them or for their exchange.
- `moe_bias_update`: after a step, bias_i += gamma * sign(mean load -
  load_i) over the step's tokens (the auxiliary-loss-free balancing of
  that family); the bias never enters the weights.

Dispatch and combine are adjoint: rows are gathered by their token
(rows <- tokens, a gather of the buffer's rows), tokens get their rows back
by a reduction over the buffer (tokens <- rows, `tokens_from_rows`: the rows
brought into token order by one gather, then a grouped product over blocks
of tokens), and each one's backward is the other's forward, so no
scatter-add runs in either direction on a TPU and nothing of width d is
indexed by the T x k assignments, 7/8 of them held on other chips in an
8-way share: only integers and scalars are (the sort's keys, `order`, `pos`,
the weights).  Name scopes `moe.dispatch` (sort, gather, combine) and
`moe.experts` (the grouped matmuls) group the device's time in a profiler
trace; `moe.lower` (a span, at lowering) says what a layer was given,
`router.lower` what a `moe_router` site was: the width of its input (the
stream's, or a router network's), its outputs, the width of the state that
network carried from the layer before (a label the model passes) and
whether its weight is trained.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import amp
from ..core.proto import DataType
from ..core.registry import register_op
from ..observability import span
from .common import data, in_desc, same_shape, set_output

__all__ = ["route", "held_experts_part", "row_buffers", "tokens_from_rows"]


SCORING = {"sigmoid": jax.nn.sigmoid,
           "softmax": functools.partial(jax.nn.softmax, axis=-1)}


def route(x, w, bias, top_k: int, scaling: float, normalize: bool,
          scoring: str = "sigmoid"):
    """(idx [T, k] int32, weight [T, k] fp32, load [E] fp32) of tokens x
    [T, d] under the router w [d, E]: s = sigmoid(x w), or under `scoring`
    "softmax" the softmax of x w over all E, in fp32; chosen the top_k of
    s + bias; weights s over the chosen (never s + bias), divided by their
    sum under `normalize`, times `scaling`; load_i the tokens that chose
    expert i.  `bias` None is no bias."""
    with jax.default_matmul_precision("highest"):
        scores = SCORING[scoring](jnp.matmul(
            x.astype(jnp.float32), w.astype(jnp.float32)))
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(choice), top_k)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    load = jnp.sum(idx.reshape(-1, 1) == jnp.arange(w.shape[1]), axis=0,
                   dtype=jnp.float32)
    return idx.astype(jnp.int32), weight * scaling, load


def row_buffers(tokens: int, top_k: int, held: int, total: int) -> tuple:
    """The rows, ascending, of the buffers the held experts' assignments
    can be sorted into.  The last holds every case: all top_k experts of
    every token held here.  What scales with the buffer (the gathers, the
    masks) is paid for its rows, not for the rows routed, so the first is
    twice what an even router sends to `held` of `total` experts, each
    further one twice the one before, and a step runs in the smallest that
    its count fits: every one is compiled once, and the count, which is
    data, picks.  So the cost around the matmuls is within a factor of two
    of what the routed rows ask for, whatever the router does.  Where
    twice the expected is the worst case (every expert held) there is one
    buffer."""
    worst = tokens * top_k
    rows = 2 * worst * held // total
    tile = 512 if rows >= 512 else 8         # the grouped matmul's row tile
    rows = -(-rows // tile) * tile
    ladder = []
    while rows < worst:
        ladder.append(rows)
        rows *= 2
    return tuple(ladder) + (worst,)


# One token block of the reduction: a token's column in its block's one-hot
# matrix, the width of the v5e's MXU.
TOKEN_BLOCK = 128


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def tokens_from_rows(values, token_of_row, filled, tokens, engine=None,
                     scale=None):
    """y [tokens, d]: y[t] = the sum of scale[r] * values[r] (`scale` [rows]
    fp32; None: of values[r]) over the rows r < `filled` with
    token_of_row[r] == t.  Product and sum in fp32, the result in the
    operands' common dtype as a matmul's is (fp32 under a scale).  A token
    with no such row reads zero; rows from `filled` on are never read into
    a sum, whatever they hold.

    Its cost goes by the rows and by `tokens`, never by the assignments:
    one gather of `rows` rows brings the rows into token order, so that the
    rows of a block of TOKEN_BLOCK tokens are contiguous, and the segmented
    sum is the grouped product megablox ships, onehot^T [TOKEN_BLOCK, rows]
    x values [rows, d] by the blocks' row counts: its grid covers the
    filled row tiles only and it zeroes the blocks no row wrote.  A one-hot
    operand is exact in the values' dtype and the MXU accumulates in fp32.
    A scale rides in the one-hot's place, in fp32: the kernel widens the
    values' tile in VMEM and the product is the MXU's fp32 one, so no
    [rows, d] fp32 value is written for it.  Off the TPU (`engine`
    "ragged_dot") the same sum is XLA's segment_sum, as ragged_dot stands
    in for gmm.  The adjoint is the gather of `rows` rows that _dispatch
    is."""
    rows, d = values.shape
    blocks = -(-tokens // TOKEN_BLOCK)
    dtype = values.dtype if scale is None else jnp.result_type(values, scale)
    # a row past the filled ones lies in no block: its key sorts last
    key = jnp.where(jnp.arange(rows) < filled, token_of_row,
                    blocks * TOKEN_BLOCK)
    if _engine(engine) == "ragged_dot":
        values = values.astype(jnp.float32)
        return jax.ops.segment_sum(
            values if scale is None else scale[:, None] * values, key,
            num_segments=tokens).astype(dtype)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    # the product [TOKEN_BLOCK, rows] x [rows, d]: rows in tiles of 128 (a
    # token block's ~128 rows straddle two at most; 256 ties, 512 loses:
    # PERF.md 6, PR 41), d whole
    tiling = (min(128, -(-rows // 8) * 8), TOKEN_BLOCK, d)
    key, by_token = jax.lax.sort_key_val(key, jnp.arange(rows))
    # the rows rounded up to whole tiles repeat row 0, outside every block
    by_token = jnp.pad(by_token, (0, -rows % tiling[0]))
    key = jnp.pad(key, (0, -rows % tiling[0]),
                  constant_values=blocks * TOKEN_BLOCK)
    onehot = key[None, :] % TOKEN_BLOCK == jnp.arange(TOKEN_BLOCK)[:, None]
    sizes = jnp.sum(key[None, :] // TOKEN_BLOCK == jnp.arange(blocks)[:, None],
                    axis=1, dtype=jnp.int32)
    onehot = (onehot.astype(values.dtype) if scale is None else
              jnp.where(onehot, jnp.take(scale, by_token)[None, :], 0.0))
    # by_token is a permutation: clipped, so that no pass over the gathered
    # rows fills in for an index out of range, as take's default mode runs
    # one before a kernel's operand.  Where an operand is fp32 the kernel's
    # product is fp32's own whatever the kernel's default precision is: a
    # scale is never rounded to bf16.
    with jax.default_matmul_precision(
            "highest" if onehot.dtype == jnp.float32 else "default"):
        y = tgmm(onehot, jnp.take(values, by_token, axis=0, mode="clip"),
                 sizes, dtype, tiling, interpret=(engine == "interpret"))
    return y.reshape(blocks * TOKEN_BLOCK, d)[:tokens]


def _tokens_from_rows_fwd(values, token_of_row, filled, tokens, engine,
                          scale):
    return (tokens_from_rows(values, token_of_row, filled, tokens, engine,
                             scale), (values, token_of_row, filled, scale))


def _tokens_from_rows_bwd(tokens, engine, res, g):
    values, token_of_row, filled, scale = res
    live = (jnp.arange(token_of_row.shape[0]) < filled)[:, None]
    g_row = jnp.take(g, token_of_row, axis=0)
    if scale is None:
        return jnp.where(live, g_row, 0).astype(values.dtype), None, None, None
    return (jnp.where(live, scale[:, None] * g_row, 0).astype(values.dtype),
            None, None, jnp.sum(jnp.where(live, g_row * values, 0), axis=-1))


tokens_from_rows.defvjp(_tokens_from_rows_fwd, _tokens_from_rows_bwd)


def feature_rows(rows: int) -> int:
    """The rows of width d that one layer's gathers and reductions index in
    a buffer of `rows` rows, forward + backward: _dispatch's gather, forward
    and recomputed; _combine's gradient by row; and the two
    tokens_from_rows (the forward's _combine, the backward's _dispatch), a
    gather into token order and a reduction each.  No term in T x k."""
    return (2 + 1 + 2 * 2) * rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, token, filled, engine):
    """xr [rows, d]: row r is token[r]'s features."""
    return jnp.take(x, token, axis=0)


def _dispatch_fwd(x, token, filled, engine):
    # (the empty slice carries the token count to the backward)
    return jnp.take(x, token, axis=0), (token, filled, x[:, :0])


def _dispatch_bwd(engine, res, g):
    # a token's gradient is the sum over its held assignments' rows
    token, filled, like = res
    return (tokens_from_rows(g, token, filled, like.shape[0], engine), None,
            None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _weight_of_row(weight, order, pos):
    """[rows] fp32: the weight of the assignment in row r, 0 where that
    assignment is not held here (the rows past the filled ones).  order
    [rows] is the assignment (t * k + j) that lies in row r, `pos` [T, k]
    where an assignment lies in the buffer (>= rows: not held here); the
    backward is the gather by `pos`, so no scatter-add runs here either.

    The forward asks `pos` too, not the filled count, which would do: then
    a layer's first forward computes `pos` (the second argsort) as its
    recomputed forward does for the backward, the two are one program op
    for op, and the compiler goes on merging them where a one-trip
    recurrence leaves no loop between them.  With `pos` dead in the first
    forward moonlight-train-ep8share ran its four expert layers'
    recomputation in full, 14.7 ms of a 186 ms step (PERF.md 6, PR 41)."""
    rows = order.shape[0]
    return jnp.where(jnp.take(pos.reshape(-1), order) < rows,
                     jnp.take(weight.reshape(-1), order), 0.0)


def _weight_of_row_fwd(weight, order, pos):
    return _weight_of_row(weight, order, pos), pos


def _weight_of_row_bwd(pos, g):
    rows = g.shape[0]
    return (jnp.where(pos < rows, jnp.take(g, jnp.minimum(pos, rows - 1)),
                      0.0).astype(g.dtype), None, None)


_weight_of_row.defvjp(_weight_of_row_fwd, _weight_of_row_bwd)


def _combine(out, weight, order, pos, filled, engine):
    """y [T, d] fp32: sum over a token's held assignments of weight x the
    row the experts gave it, product and sum in fp32."""
    T, k = pos.shape
    return tokens_from_rows(out, order // k, filled, T, engine,
                            _weight_of_row(weight, order, pos))


def _gmm_tiling(m: int, k: int, n: int) -> tuple:
    """(tm, tk, tn) of the grouped matmul [m, k] x [groups, k, n], from the
    shape: 512 rows (the largest power of two under it that divides m, for
    a small buffer), the narrower of k and n whole and the other in 512s
    (tools/moonlight_kernel_probe.py's sweep at 2048 x 1408: 45-48% of the
    v5e's peak forward, a third in the two backward products; 256 rows
    with k whole runs out of VMEM in the weight gradient)."""
    tm = next(t for t in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
              if m % t == 0)
    whole, cut = (n, min(k, 512)) if n <= k else (k, min(n, 512))
    if whole > 2048:
        whole = 512
    return (tm, cut, whole) if n <= k else (tm, whole, cut)


def _grouped_matmul(a, w, sizes, engine):
    """[m, k] x [groups, k, n] by the groups' sizes (rows past their sum
    are whatever the kernel leaves there).  On a TPU the Pallas grouped
    matmul jax ships (megablox: its grid covers only the row tiles the
    groups fill, and its custom_vjp's two backward products are grouped
    too); elsewhere XLA's ragged_dot.  On the chip the Pallas kernel takes
    0.40 ms where XLA:TPU's own ragged_dot takes 0.93 (6144 of 12288 rows,
    2048 x 1408; PERF.md PR 31), and XLA's carries no scope into the
    trace."""
    if engine == "ragged_dot":
        return jax.lax.ragged_dot(a, w, sizes)
    from jax.experimental.pallas.ops.tpu import megablox

    return megablox.gmm(
        a, w, sizes, a.dtype, _gmm_tiling(a.shape[0], a.shape[1], w.shape[2]),
        interpret=(engine == "interpret"))


# tokens_from_rows' engine beside the grouped matmul's
COMBINE = {"megablox": "tgmm", "interpret": "tgmm",
           "ragged_dot": "segment_sum"}


def _engine(engine):
    from ..kernels.engine import use_pallas

    if engine is not None:
        return engine
    return "megablox" if use_pallas("auto") else "ragged_dot"


def _experts_in_buffer(x, weight, gate_w, up_w, down_w, order, pos, sizes,
                       rows, engine):
    """The held experts' part from the sorted assignments, in a buffer of
    `rows` rows (static) of which sum(sizes) are filled."""
    k = pos.shape[1]
    order = order[:rows]
    filled = jnp.sum(sizes)
    with jax.named_scope("moe.dispatch"):
        xr = _dispatch(x, order // k, filled, engine)
    with jax.named_scope("moe.experts"):
        # rows past the groups belong to no expert: whatever the kernel
        # leaves there is cut off before silu, the product or a gradient
        # can turn it into a NaN
        live = (jnp.arange(rows) < filled)[:, None]

        def grouped(a, w):
            # the MXU accumulates in fp32; the result leaves in the compute
            # dtype, as a matmul's does under amp's keep tier
            return jnp.where(live, _grouped_matmul(
                a, w.astype(a.dtype), sizes, engine), 0)

        hidden = jax.nn.silu(grouped(xr, gate_w)) * grouped(xr, up_w)
        out = grouped(hidden, down_w)
    with jax.named_scope("moe.dispatch"):
        return _combine(out, weight, order, pos, filled, engine)


def _picked_by_the_count(sizes, buffers, make):
    """lax.switch over the buffers, the smallest that holds the step's
    count: make(rows) is a branch."""
    too_small = sum(jnp.sum(sizes) > rows for rows in buffers[:-1])
    return functools.partial(jax.lax.switch, too_small,
                             [make(rows) for rows in buffers])


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _experts_by_count(x, weight, gate_w, up_w, down_w, order, pos, sizes,
                      buffers, engine):
    """_experts_in_buffer in the smallest of `buffers` that the step's
    count fits.  Differentiated by hand, one conditional a direction:
    jax's own gradient of a conditional keeps EVERY branch's residuals from
    the forward pass, zeros for the branches not taken (2 GB more of
    temporaries a layer at the cell's size with two branches, chip-less),
    so the backward's conditional computes its branch's forward again
    instead; under a layer's recomputation that costs nothing more, the
    recomputed forward's own output being dead."""
    y = _picked_by_the_count(sizes, buffers, lambda rows: (
        lambda *ops: _experts_in_buffer(*ops, rows=rows, engine=engine)))(
            x, weight, gate_w, up_w, down_w, order, pos, sizes)
    # the branches end here.  Without the barrier XLA's conditional code
    # motion sinks the op's own reshape to [B, S, d] into them, the layer
    # behind then runs its first forward on a value the compiler cannot see
    # through while its recomputed one runs on the kept [T, d] carry, and
    # the two no longer simplify to one form: a layer's recomputed forward,
    # which the compiler merges with the first (PERF.md 6, PR 38), runs
    # (PR 41: 7 flash forward calls for 4 in PR 38's tiny step)
    return jax.lax.optimization_barrier(y)


def _experts_by_count_fwd(x, weight, gate_w, up_w, down_w, order, pos, sizes,
                          buffers, engine):
    operands = (x, weight, gate_w, up_w, down_w, order, pos, sizes)
    return _experts_by_count(*operands, buffers, engine), operands


def _experts_by_count_bwd(buffers, engine, operands, g):
    *floats, order, pos, sizes = operands

    def gradients(rows):
        def branch(g, *floats):
            return jax.vjp(lambda *f: _experts_in_buffer(
                *f, order, pos, sizes, rows=rows, engine=engine),
                *floats)[1](g)
        return branch

    return _picked_by_the_count(sizes, buffers, gradients)(
        g, *floats) + (None, None, None)


_experts_by_count.defvjp(_experts_by_count_fwd, _experts_by_count_bwd)


def held_experts_part(x, idx, weight, gate_w, up_w, down_w, expert_offset,
                      experts_total, rows=None, engine=None):
    """y [T, d] fp32 = sum over the assignments (t, j) whose expert idx[t, j]
    is one of the `held` = gate_w.shape[0] experts from `expert_offset` on
    of weight[t, j] * E(x[t]).  x [T, d] in the compute dtype, idx [T, k]
    int32 over all `experts_total` experts, weight [T, k] fp32, the weights
    [held, d, f], [held, d, f], [held, f, d].  `rows` pins one buffer and
    `engine` the grouped matmul ("megablox", "interpret", "ragged_dot") for
    a test or the probe; a model passes neither."""
    T, k = idx.shape
    held_n = gate_w.shape[0]
    with jax.named_scope("moe.dispatch"):
        local = idx - expert_offset
        here = (local >= 0) & (local < held_n)
        key = jnp.where(here, local, held_n).reshape(-1)         # [T * k]
        # assignments sorted by expert, those not held here last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sizes = jnp.sum(key[:, None] == jnp.arange(held_n), axis=0,
                        dtype=jnp.int32)
        # where each assignment lies: the inverse of the permutation.  One
        # that is not held lies past the held total; push it past every
        # buffer, so that `pos < rows` means held
        pos = jnp.argsort(order).astype(jnp.int32)
        pos = jnp.where(here.reshape(-1), pos, T * k).reshape(T, k)
    operands = (x, weight, gate_w, up_w, down_w, order, pos, sizes)
    buffers = row_buffers(T, k, held_n, experts_total)
    engine = _engine(engine)
    if rows is not None or len(buffers) == 1:
        return _experts_in_buffer(*operands, rows=rows or buffers[-1],
                                  engine=engine)
    return _experts_by_count(*operands, buffers, engine)


def _router_infer(op, block):
    x = in_desc(op, block, "X")
    w = in_desc(op, block, "Weight")
    if x is None or w is None:
        return
    k = int(op.attr("top_k", 1))
    set_output(block, op, "TopIdx", list(x.shape[:-1]) + [k], DataType.INT32)
    set_output(block, op, "TopWeight", list(x.shape[:-1]) + [k],
               DataType.FP32)
    set_output(block, op, "Load", [w.shape[1]], DataType.FP32)


@register_op("moe_router", infer_shape=_router_infer,
             diff_inputs=["X", "Weight"])
def _moe_router(ctx, ins, attrs):
    x = data(ins["X"][0])
    lead = x.shape[:-1]
    bias_in = ins.get("Bias", [None])[0]
    with span("router.lower", width=int(x.shape[-1]),
              experts=int(data(ins["Weight"][0]).shape[-1]),
              carried=int(attrs.get("carried", 0)),
              trained=int(bool(attrs.get("trained", True)))), \
            jax.named_scope("moe.router"):
        idx, weight, load = route(
            x.reshape(-1, x.shape[-1]), data(ins["Weight"][0]),
            None if bias_in is None else data(bias_in), int(attrs["top_k"]),
            float(attrs.get("scaling", 1.0)),
            bool(attrs.get("norm_topk_prob", True)),
            attrs.get("scoring", "sigmoid"))
    k = idx.shape[-1]
    return {"TopIdx": [idx.reshape(lead + (k,))],
            "TopWeight": [weight.reshape(lead + (k,))], "Load": [load]}


@register_op("moe_experts", infer_shape=same_shape("X", "Out"),
             diff_inputs=["X", "TopWeight", "GateW", "UpW", "DownW"])
def _moe_experts(ctx, ins, attrs):
    x = data(ins["X"][0])
    idx = data(ins["TopIdx"][0])
    weight = data(ins["TopWeight"][0])
    gate_w, up_w, down_w = (data(ins[s][0])
                            for s in ("GateW", "UpW", "DownW"))
    tokens, k = x.size // x.shape[-1], idx.shape[-1]
    xc, gate_c, up_c, down_c = amp.mxu_operands(x, gate_w, up_w, down_w)
    total = int(attrs["experts_total"])
    buffers = row_buffers(tokens, k, gate_w.shape[0], total)
    with span("moe.lower", experts_total=total,
              experts_held=int(gate_w.shape[0]), top_k=int(k),
              row_buffer=buffers[-1], row_buffer_usual=buffers[0],
              row_buffers=len(buffers), engine=_engine(None),
              combine=COMBINE[_engine(None)],
              feature_rows=feature_rows(buffers[0]), dropped=0,
              scoring=attrs.get("scoring", "sigmoid")):
        y = held_experts_part(
            xc.reshape(tokens, x.shape[-1]), idx.reshape(tokens, k),
            weight.reshape(tokens, k).astype(jnp.float32),
            gate_c, up_c, down_c, int(attrs.get("expert_offset", 0)), total)
    return {"Out": [y.reshape(x.shape).astype(x.dtype)]}


@register_op("moe_bias_update", infer_shape=same_shape("Bias", "BiasOut"),
             no_grad=True, stateful=True)
def _moe_bias_update(ctx, ins, attrs):
    bias = data(ins["Bias"][0])
    load = data(ins["Load"][0]).reshape(-1, bias.shape[-1]).sum(axis=0)
    step = float(attrs["gamma"]) * jnp.sign(jnp.mean(load) - load)
    return {"BiasOut": [bias + step.astype(bias.dtype)]}
