"""ops/moe_ops.py::tokens_from_rows (PR 41): tokens get their rows back by a
reduction over the row buffer, never by a gather over all T x k assignments.
The primitive against a one-hot einsum in fp32, forward and gradient, on
XLA's segment_sum and on megablox's tgmm through the Pallas interpreter;
held_experts_part against the form it had before (a plain function here:
the gather over all assignments, differentiated by jax) on the three expert
configurations' rehearsal shapes; and, chip-less at the configurations'
REAL shapes, that the op as it lowers for the TPU holds no value of T x k
rows by d and that `moe.lower`'s feature_rows is the count the lowered
program shows.

The last test (three real-size compiles of whole steps, ~7 min together) is
marked `slow` since PR 46 and is no part of tier-1: what it proves, one
forward a layer in the step the chip compiles, a cell's `correct` and rate
hold on the chip on every PR (a second forward costs
moonlight-train-ep8share 6.4%, twice its bound), and the compiled-count
bounds of tests/benchmark/test_mellum_benchmark.py and
test_zaya_benchmark.py hold it at the smallest widths the kernels lower
at.  Run it by hand, `python -m pytest tests/test_moe_tokens_from_rows.py
-m slow`, in any PR that touches ops/moe_ops.py or
models/common.py::one_trip_layer."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from paddle_tpu.ops import moe_ops  # noqa: E402

CELLS = ("moonlight-train-ep8share", "keye-train-dsa16k",
         "mellum-train-swa16k")
BLOCK = moe_ops.TOKEN_BLOCK
D = 32


def _sorted_into_rows(idx, held, rows):
    """(token_of_row [rows], filled) as held_experts_part sorts the
    assignments idx [T, k] of experts [0, held) into a buffer."""
    k = idx.shape[1]
    key = np.where(idx < held, idx, held).reshape(-1)
    order = np.argsort(key, kind="stable")
    return (order[:rows] // k).astype(np.int32), int(np.sum(idx < held))


def _case(name, rng):
    """(token_of_row, filled, T) of a named routing."""
    T, k, held, total = 3 * BLOCK, 4, 2, 16
    idx = np.stack([rng.permutation(total)[:k] for _ in range(T)])
    if name == "eighth_held":
        rows = moe_ops.row_buffers(T, k, held, total)[0]
    elif name == "every_expert_held":
        held = total
        rows = T * k
    elif name == "no_row_routed_here":
        idx = held + idx % (total - held)
        rows = moe_ops.row_buffers(T, k, held, total)[0]
    elif name == "every_assignment_held":
        # the worst-case buffer: all k experts of every token held here
        held = total = k
        idx = np.stack([rng.permutation(k) for _ in range(T)])
        rows = T * k
    elif name == "an_empty_block_between_two":
        idx[BLOCK:2 * BLOCK] = held + idx[BLOCK:2 * BLOCK] % (total - held)
        rows = moe_ops.row_buffers(T, k, held, total)[0]
    elif name == "tokens_no_block_divides":
        T = 2 * BLOCK + 40
        idx = idx[:T]
        rows = moe_ops.row_buffers(T, k, held, total)[0]
    else:
        raise KeyError(name)
    token, filled = _sorted_into_rows(idx, held, rows)
    assert filled <= rows
    return token, filled, T


CASES = ("eighth_held", "every_expert_held", "no_row_routed_here",
         "every_assignment_held", "an_empty_block_between_two",
         "tokens_no_block_divides")


def _plain(values, token, filled, T, scale=None):
    """The one-hot einsum in fp32, rows past the filled ones left out."""
    live = jnp.arange(token.shape[0]) < filled
    onehot = ((token[:, None] == jnp.arange(T)[None, :])
              & live[:, None]).astype(jnp.float32)
    values = jnp.where(live[:, None], values.astype(jnp.float32), 0)
    if scale is not None:
        values = jnp.where(live, scale, 0)[:, None] * values
    return jnp.einsum("rt,rd->td", onehot, values, precision="highest")


@pytest.mark.parametrize("engine", ["ragged_dot", "interpret"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("scaled", [False, True], ids=["sum", "weighted"])
@pytest.mark.parametrize("case", CASES)
def test_tokens_from_rows_is_the_one_hot_sum_forward_and_gradient(
        case, scaled, dtype, engine):
    """With NaN in every row (and scale) past the filled ones: they must
    not leak, into the sum or into a gradient."""
    rng = np.random.RandomState(CASES.index(case))
    token, filled, T = _case(case, rng)
    rows = token.shape[0]
    values = rng.randn(rows, D).astype(np.float32)
    scale = rng.uniform(0.2, 0.6, rows).astype(np.float32)
    values[filled:] = np.nan
    scale[filled:] = np.nan
    values = jnp.asarray(values, dtype)
    scale = jnp.asarray(scale) if scaled else None
    token = jnp.asarray(token)
    mix = jnp.asarray(rng.randn(T, D), jnp.float32)
    live = jnp.arange(rows) < filled

    def loss(fn):
        return lambda v, s: jnp.sum(jnp.sin(fn(v, s)) * mix)

    argnums = (0, 1) if scaled else (0,)
    got, got_g = jax.jit(jax.value_and_grad(loss(
        lambda v, s: moe_ops.tokens_from_rows(v, token, filled, T, engine,
                                              s)), argnums))(values, scale)
    y = moe_ops.tokens_from_rows(values, token, filled, T, engine, scale)
    want_y = _plain(values, token, filled, T, scale)
    want, want_g = jax.value_and_grad(loss(
        lambda v, s: _plain(v, token, filled, T, s)), argnums)(
            jnp.where(live[:, None], values, 0),
            jnp.where(live, scale, 0) if scaled else None)
    # the result in the operands' common dtype, as a matmul's is
    assert y.shape == (T, D)
    assert y.dtype == (jnp.float32 if scaled else values.dtype)
    # sums of at most k terms, each exact in fp32: only their order differs
    # (and, where the result leaves in bf16, its one rounding)
    eps = 1e-6 if y.dtype == jnp.float32 else 2.0 ** -8
    near = eps * (float(jnp.max(jnp.abs(want_y))) or 1.0)
    np.testing.assert_allclose(y.astype(jnp.float32), want_y, rtol=0,
                               atol=near)
    if y.dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5)
    else:  # T x D terms, each within the result's rounding
        np.testing.assert_allclose(
            got, want, rtol=0, atol=near * float(jnp.sum(jnp.abs(mix))))
    assert got_g[0].dtype == values.dtype
    for a, b in zip(got_g, want_g):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        np.testing.assert_allclose(
            a, b, rtol=0, atol=float(jnp.max(jnp.abs(b))) * (
                1e-6 if dtype == "float32" else 3e-2))
        assert not np.any(np.asarray(a[filled:]))
    held_tokens = np.zeros(T, bool)
    held_tokens[np.asarray(token[:filled])] = True
    assert not np.any(np.asarray(y.astype(jnp.float32))[~held_tokens])  # 0.
    if case == "an_empty_block_between_two":
        assert not held_tokens[BLOCK:2 * BLOCK].any()
        assert held_tokens[:BLOCK].any() and held_tokens[2 * BLOCK:].any()
    if case == "no_row_routed_here":
        assert filled == 0 and not np.any(np.asarray(y.astype(jnp.float32)))


def _cell_shape(name, rehearse=False):
    """(T, d, f, held, total, k) of an expert cell, from its files."""
    from benchmark.harness import manifest

    cell = manifest.Cell(manifest.load_manifest(), name, rehearse=rehearse)
    cfg = cell.config
    held = cfg.get("n_routed_experts", cfg.get("num_experts"))
    return (int(cell.sizing["per_chip_batch"]) * cfg["max_length"],
            cfg["hidden_size"], cfg["moe_intermediate_size"], held,
            cfg["router_experts"], cfg["num_experts_per_tok"])


def _held_part_by_gather(x, idx, weight, gate_w, up_w, down_w, total, rows,
                         engine):
    """held_experts_part as it stood before PR 41, in a buffer of `rows`
    rows: tokens gather their rows back by position over ALL T x k
    assignments into [T, k, d], those not held clamped onto the last row
    and masked.  Plain: jax differentiates it."""
    T, k = idx.shape
    held = gate_w.shape[0]
    key = jnp.where(idx < held, idx, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                    dtype=jnp.int32)
    pos = jnp.where(idx < held, jnp.argsort(order).reshape(T, k), T * k)
    live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]

    def grouped(a, w):
        return jnp.where(live, moe_ops._grouped_matmul(
            a, w.astype(a.dtype), sizes, engine), 0)

    # (masked, so that whatever a kernel's backward leaves in the rows no
    # group covers is not added to a token's gradient)
    xr = jnp.where(live, jnp.take(x, order[:rows] // k, axis=0), 0)
    out = grouped(jax.nn.silu(grouped(xr, gate_w)) * grouped(xr, up_w),
                  down_w)
    picked = jnp.take(out, jnp.minimum(pos, rows - 1), axis=0)   # [T, k, d]
    return jnp.einsum("tk,tkd->td", jnp.where(pos < rows, weight, 0.0),
                      picked.astype(jnp.float32))


def _layer_operands(cell, seed):
    T, d, f, held, total, k = _cell_shape(cell, rehearse=True)
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(T, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, total) * 0.5, jnp.float32)
    weights = [jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)
               for s in ((held, d, f), (held, d, f), (held, f, d))]
    idx, weight, _ = moe_ops.route(x, router, None, k, 1.0, True, "softmax")
    return (x, weight, *weights), idx, total


@pytest.mark.parametrize("engine", ["ragged_dot", "interpret"])
@pytest.mark.parametrize("cell", CELLS)
def test_the_layer_equals_the_gathering_form_it_replaced(cell, engine):
    """held_experts_part, loss and every gradient, in fp32 on a
    configuration's rehearsal shape: the form before PR 41 (a gather over
    all T x k assignments) against the tree's.  The two add a token's at
    most k terms in another order, so they agree to fp32's rounding, not
    to the bit."""
    operands, idx, total = _layer_operands(cell, CELLS.index(cell))
    T, k = idx.shape
    held = operands[2].shape[0]
    # the buffer the tree's conditional picks: the same tiles, so the same
    # order of summation inside the grouped matmuls
    rows = next(b for b in moe_ops.row_buffers(T, k, held, total)
                if int(jnp.sum(idx < held)) <= b)

    def step(part):
        return jax.jit(jax.value_and_grad(
            lambda *floats: jnp.sum(jnp.sin(part(*floats))),
            argnums=(0, 1, 2, 3, 4)))(*operands)

    got = step(lambda x, weight, *w: moe_ops.held_experts_part(
        x, idx, weight, *w, 0, total, engine=engine))
    want = step(lambda x, weight, *w: _held_part_by_gather(
        x, idx, weight, *w, total, rows, moe_ops._engine(engine)))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(got[1], want[1]):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6 * scale)


def _lowered_for_the_tpu(cell, rows, before=False):
    """The StableHLO of held_experts_part with its gradient, as it lowers
    for the TPU (megablox kernels and all) at the cell's real shape in a
    buffer of `rows` rows (`before`: of the gathering form): abstract
    operands, nothing runs."""
    T, d, f, held, total, k = _cell_shape(cell)

    def fn(x, weight, gate_w, up_w, down_w, idx):
        if before:
            return jnp.sum(_held_part_by_gather(
                x, idx, weight, gate_w, up_w, down_w, total, rows,
                "megablox"))
        return jnp.sum(moe_ops.held_experts_part(
            x, idx, weight, gate_w, up_w, down_w, 0, total, rows=rows,
            engine="megablox"))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    wide = sds((held, d, f), jnp.bfloat16)
    return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2, 3, 4))).trace(
        sds((T, d), jnp.bfloat16), sds((T, k), jnp.float32), wide, wide,
        sds((held, f, d), jnp.bfloat16), sds((T, k), jnp.int32)).lower(
            lowering_platforms=("tpu",)).as_text()


def _reductions(text, T, d):
    """The row counts of tokens_from_rows' grouped products, a call site
    each (identical calls share one lowered function)."""
    return [int(n) for n in re.findall(
        rf"call @tgmm\w*\(.*: \(tensor<{BLOCK}x(\d+)x\w+>, "
        rf"tensor<\1x{d}x\w+>, tensor<\d+xi32>\) -> "
        rf"tensor<{-(-T // BLOCK)}x{BLOCK}x{d}x\w+>", text)]


def _assignment_rows_by_d(text, T, k, d):
    return re.findall(rf"tensor<(?:{T * k}x{d}|{T}x{k}x{d})x\w+>", text)


@pytest.mark.parametrize("cell", CELLS)
def test_no_value_of_all_assignments_by_d_in_the_lowered_op(cell):
    """In every buffer but the worst case (which is T x k rows by
    construction) the lowered op, forward and backward, holds no [T x k, d]
    or [T, k, d] value; the form before PR 41 holds several, so the search
    can find one."""
    T, d, f, held, total, k = _cell_shape(cell)
    buffers = moe_ops.row_buffers(T, k, held, total)
    assert len(buffers) == 3 and buffers[-1] == T * k
    for rows in buffers[:-1]:
        text = _lowered_for_the_tpu(cell, rows)
        assert not _assignment_rows_by_d(text, T, k, d), (cell, rows)
        assert _reductions(text, T, d) == 2 * [rows]
    before = _lowered_for_the_tpu(cell, buffers[0], before=True)
    assert len(_assignment_rows_by_d(before, T, k, d)) >= 4


@pytest.mark.parametrize("cell", CELLS)
def test_feature_rows_is_the_count_the_lowered_op_shows(cell):
    """`moe.lower`'s feature_rows against the lowered program: the rows of
    width d its gathers write and its reductions (tgmm's row operand)
    read, forward + backward, plus the gather a layer's recomputation runs
    again (_dispatch's: the recomputed _combine is dead).  Before PR 41 the
    count was 2 x T x k + 3 x rows."""
    T, d, f, held, total, k = _cell_shape(cell)
    rows = moe_ops.row_buffers(T, k, held, total)[0]
    text = _lowered_for_the_tpu(cell, rows)
    gathered = [int(n) for n in re.findall(
        rf'"stablehlo.gather".*-> tensor<(\d+)x{d}x\w+>', text)]
    reduced = _reductions(text, T, d)
    assert gathered == 4 * [rows] and reduced == 2 * [rows], (gathered,
                                                              reduced)
    assert moe_ops.feature_rows(rows) == sum(gathered) + sum(reduced) + rows
    assert moe_ops.feature_rows(rows) < 2 * T * k + 3 * rows
    assert {"mellum-train-swa16k": 229376, "moonlight-train-ep8share": 86016,
            "keye-train-dsa16k": 229376}[cell] == moe_ops.feature_rows(rows)


@pytest.mark.slow
@pytest.mark.parametrize("cell, scope", [
    ("mellum-train-swa16k", "attn."), ("moonlight-train-ep8share", "mla"),
    ("zaya-train-cca16k", "cca.attend")])
def test_a_layers_forward_runs_once_in_the_step_the_chip_compiles(cell,
                                                                  scope):
    """The cell's step as the v5e's compiler leaves it, chip-less at the
    cell's REAL shape and compiled as the chip's own jit compiles it (one
    device, no mesh: the program's temporaries then come out to the byte
    what the chip reports, and core/aot_tpu.py's one-device MESH does not
    reproduce moonlight-train-ep8share's merge either way): one flash
    forward a layer, none under the layer's recomputation, which the
    compiler merges with the first forward.  That is what
    benchmark/configs' attend_passes counts for mellum-train-swa16k's
    attention rooflines (tests/benchmark/test_mellum_benchmark.py holds the
    same at the smallest widths the kernels lower at), and 14.7 ms of
    moonlight-train-ep8share's step.  Two things in ops/moe_ops.py decide
    it: what the expert block's branches hand the layer behind them
    (_experts_by_count's barrier), and that a layer's first forward is op
    for op its recomputed one (_weight_of_row asks `pos`); PERF.md 6,
    PR 41.  In zaya-train-cca16k the compiler merges nothing (the
    recomputation RUNS there), and the forward runs once all the same: the
    site keeps its output and logsumexp through the layer's recomputation
    (kernels/flash_attention.py::_flash_fwd, PR 44), so no second forward
    is traced; the two older cells' merge has to survive that (the
    recomputation reads the kept `out` where it called the kernel)."""
    import paddle_tpu as fluid
    from benchmark.harness import manifest
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.core import aot_tpu

    cell = manifest.Cell(manifest.load_manifest(), cell)
    cfg, mod = dict(cell.config), cell.config_module
    fluid.reset_default_env()
    try:
        spec = mod.build(cfg, 0)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        batch = mod.make_batch(cfg, spec, int(cell.sizing["per_chip_batch"]),
                               0)
        with fluid.flags.tpu_trace_scope(True):
            compiled, *args = exe.capture_program(feed=batch,
                                                  fetch_list=[spec.loss])
            one = SingleDeviceSharding(aot_tpu.tpu_topology().devices[0])
            text = jax.jit(
                compiled.raw_fn, in_shardings=one, out_shardings=one,
                donate_argnums=(1,)).trace(*jax.tree_util.tree_map(
                    aot_tpu._abstract, tuple(args))).lower().compile(
                        ).as_text()
    finally:
        fluid.reset_default_env()
    calls = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="'
        r'([^"]*/(?:fused|latent)_attention/[^"]*pallas_call)"', text)
    forward = [op for op in calls if "/flash.bwd/" not in op]
    assert len(forward) == cfg["num_hidden_layers"], forward
    assert all(f"/{scope}" in op for op in forward), forward
    assert not any("rematted_computation" in op for op in forward)
    assert len(calls) > len(forward)                  # the backward's kernels
