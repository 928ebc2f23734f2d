"""kernels/gated_delta.py's Pallas kernel pair in the interpreter, heads of
128 as a block spec picks them: against the recurrence one token at a time
(forward and every gradient), against the jax.numpy engine, the two hard
inputs, the shape rule, what a recomputed unit runs, and what `kda.lower`
says of a site the kernels take."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.kernels import engine, gated_delta as kda

from test_gated_delta_attention import (_inputs, from_weak_to_strong,
                                        token_recurrence)

H, D = 2, 128
NAMES = ("out", "q", "k", "v", "g", "beta")


def _both_passes(fn, args, weight):
    out, pull = jax.vjp(fn, *args)
    return (out,) + tuple(pull(weight.astype(out.dtype)))


def _kernels(rows, chunk=64, unroll=None):
    return lambda *a: kda.gated_delta_attention(
        *a, heads=H, chunk=chunk, force="interpret", rows=rows,
        unroll=unroll)


def _plain(*a):
    return token_recurrence(*a, heads=H)


def _jnp_engine(*a):
    return kda.gated_delta_attention(*a, heads=H, force="jax")


def _held(args, fn, to, rtol):
    weight = jnp.asarray(np.random.RandomState(1).randn(*args[2].shape),
                         jnp.float32)
    got, want = _both_passes(fn, args, weight), _both_passes(to, args, weight)
    for name, a, b in zip(NAMES, got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.all(np.isfinite(a)), name
        np.testing.assert_allclose(a, b, rtol=rtol, err_msg=name,
                                   atol=rtol * float(np.max(np.abs(b))))


@pytest.mark.parametrize("S,rows,chunk,unroll,key_heads", [
    (256, 128, 64, 1, None),    # two groups of one tile of two chunks
    (512, 256, 64, 1, None),    # two groups of two tiles, a loop over them
    (512, 256, 64, 2, None),    # the same, both tiles in one loop body
    (256, 128, 32, 1, None),    # four chunks a tile
    (256, 128, 128, 1, None),   # a chunk a tile
    # ONE decay a head, g [B, S, H]: key heads 1 : 1, then 1 : 2 (both value
    # heads read the one key head through the index map)
    (256, 128, 64, 1, 2),
    (256, 128, 64, 1, 1),
    (256, 256, 32, 2, 1),       # four chunks a tile, two tiles a loop body
])
def test_the_kernel_pair_is_the_token_recurrence(S, rows, chunk, unroll,
                                                 key_heads):
    """Forward and the gradients of q, k, v, g and beta."""
    _held(_inputs(1, S, H, D, seed=S + chunk, key_heads=key_heads),
          _kernels(rows, chunk, unroll), _plain, rtol=2e-5)


def test_two_sequences_and_a_state_that_starts_at_zero_for_each():
    _held(_inputs(2, 256, H, D, seed=11), _kernels(128), _plain, rtol=2e-5)


@pytest.mark.parametrize("case,rtol", [("decay", 1e-4), ("alike", 2e-4),
                                       ("a-heads-decay", 1e-4),
                                       ("a-heads-decay-alike", 2e-4)])
def test_the_hard_inputs(case, rtol):
    """A decay of e^-1500 inside a chunk (exp(-Gc) would be inf: every
    exponent of the kernels is a difference <= 0 too), and keys alike at
    beta ~ 0.95 (the inverse by doubling, fp32); with one decay a head, one
    head at e^-0.001 and one at e^-21 a token over ONE key head."""
    if case == "decay":
        args = _inputs(1, 256, H, D, seed=3, rate=16.0, shift=1.0)
        assert float(jnp.min(jnp.sum(args[3][:, :64], axis=1))) < -1000
    elif case == "a-heads-decay":
        args = _inputs(1, 256, H, D, seed=3, rate=from_weak_to_strong(H),
                       shift=3.0, key_heads=1)
        per_chunk = jnp.sum(args[3][:, :64], axis=1)
        assert float(per_chunk.min()) < -1000 and float(per_chunk.max()) > -1
    elif case == "a-heads-decay-alike":
        args = _inputs(1, 256, H, D, seed=7, alike=1.0, key_heads=1)
    else:
        args = _inputs(1, 256, H, D, seed=7, alike=1.0)
    _held(args, _kernels(128), _plain, rtol=rtol)


@pytest.mark.parametrize("dtype,rtol,key_heads", [
    ("float32", 1e-5, None), ("bfloat16", 4e-2, None),
    ("float32", 1e-5, 1), ("bfloat16", 4e-2, 1)])
def test_the_kernel_pair_is_the_jnp_engine(dtype, rtol, key_heads):
    """At fp32 the two engines differ by rounding; at bf16 operands by
    where each rounds to bf16 (beta goes into X's right operand here, into
    X there)."""
    args = _inputs(1, 256, H, D, seed=5, key_heads=key_heads)
    args = tuple(t.astype(dtype) for t in args[:3]) + args[3:]
    _held(args, _kernels(128), _jnp_engine, rtol=rtol)


def test_groups_of_rows_change_no_number():
    """One group of two tiles against two groups of one (S 256: at S 512
    and three groupings the interpreter took 31 s of the driver's run, PR
    54; two groups of two tiles are the recurrence's cases above)."""
    args = _inputs(1, 256, H, D, seed=6)
    weight = jnp.ones(args[0].shape, jnp.float32)
    whole = _both_passes(_kernels(256), args, weight)
    for name, a, b in zip(NAMES, _both_passes(_kernels(128), args, weight),
                          whole):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7,
                                   err_msg=f"{name} at 128 rows")


# ---------------------------------------------------------------------------
# the shape rule
# ---------------------------------------------------------------------------
def test_the_cells_site_takes_the_kernels():
    tiles, why = kda.kernel_tiles(1, 4096, 32, 128, 64, jnp.bfloat16)
    assert why == "" and (tiles.rows, tiles.chunk, tiles.unroll) == (512, 64,
                                                                     4)
    assert tiles.fwd_vmem_bytes < tiles.bwd_vmem_bytes \
        <= engine.PLAN_VMEM_BUDGET
    # the engine's choice is a function of its own: `plan` says what it said
    assert kda.plan(1, 4096, 32, 128) == {"chunk": 64, "chunks": 64,
                                          "group": 8}


@pytest.mark.parametrize("site,why", [
    ((1, 4096, 32, 8, 64, "bfloat16"), "not whole 128-lane vectors"),
    ((1, 64, 2, 128, 64, "bfloat16"), "the sequence is one chunk"),
    ((1, 192, 2, 128, 64, "float32"), "not whole tiles of 128 rows"),
    ((1, 256, 2, 128, 8, "bfloat16"), "chunks of 8 do not tile"),
    ((1, 256, 2, 128, 64, "float16"), "operands of float16"),
    ((1, 4096, 2, 1024, 64, "float32"), "do not fit the VMEM budget"),
])
def test_what_the_shape_rule_refuses_and_why(site, why):
    tiles, said = kda.kernel_tiles(*site)
    assert tiles is None and why in said


def test_a_working_set_over_the_budget_takes_fewer_rows(monkeypatch):
    site = (1, 4096, 32, 128, 64, jnp.bfloat16)
    at_512 = kda.kernel_tiles(*site)[0].bwd_vmem_bytes
    monkeypatch.setattr(kda, "PLAN_VMEM_BUDGET", at_512 - 1)
    assert kda.kernel_tiles(*site)[0].rows == 256
    with pytest.raises(ValueError, match="no kernels at 512 rows"):
        kda.engine(*site, force="pallas", rows=512)
    monkeypatch.setattr(kda, "PLAN_VMEM_BUDGET", 1 << 20)
    assert kda.kernel_tiles(*site) == (
        None, "heads of 128 do not fit the VMEM budget")


def test_the_engine_is_read_from_the_shape_and_the_target():
    """No flag, no environment variable: on the CPU the jax.numpy engine;
    where the program is traced for the TPU, the kernels if the shape
    tiles."""
    site = (1, 256, H, D, 64, jnp.float32)
    assert kda.engine(*site) is None
    with fluid.flags.tpu_trace_scope(True):
        assert kda.engine(*site).rows == 256
        assert kda.engine(1, 256, H, 8, 64, jnp.float32) is None
        assert kda.engine(*site, force="jax") is None


# ---------------------------------------------------------------------------
# inside a program
# ---------------------------------------------------------------------------
def test_a_recomputed_units_backward_holds_no_forward_kernel():
    """The forward kernel tags `out` and the group states; the backward
    of a rematerialised unit reads them and runs the backward kernel
    alone."""
    from paddle_tpu.core.compiler import rematerialised

    args = _inputs(1, 256, H, D, seed=4)

    def unit(*a):
        return jnp.sum(_kernels(128)(*a) ** 2)

    def kernels(fn):
        text = str(jax.make_jaxpr(jax.grad(fn, argnums=range(5)))(*args))
        return text.count("pallas_call")

    assert kernels(rematerialised(unit)) == kernels(unit) == 2
    assert kernels(jax.checkpoint(unit)) == 3     # what the tags save
    for a, b in zip(jax.grad(rematerialised(unit), argnums=range(5))(*args),
                    jax.grad(unit, argnums=range(5))(*args)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("S,key_heads", [(4096, None), (8192, 16)])
def test_kda_lower_says_pallas_at_the_cells_shape(S, key_heads):
    """The op lowered (abstractly: nothing compiles) at [1, 4096, 32 x 128]
    for the TPU (whose AMP tier makes the operands bf16): `engine` pallas,
    the rows a grid step and the two working sets beside what the span
    said before.  At qwen3next-train-gdn8k's shape (S 8192, one decay a
    head, 16 key heads) the span is `gdn.lower`, and the lowered op holds
    neither a [8192, 4096] fp32 decay nor a repeated q or k: the kernels'
    operands are the [8192, 2048] q and k as they came and g by tiles."""
    heads = 32
    shapes = [[1, S, (key_heads or heads) * D]] * 2 + [[1, S, heads * D]] \
        + [[1, S, heads * (1 if key_heads else D)]] + [[1, S, heads]]
    fluid.reset_default_env()
    names = ("q", "k", "v", "g", "beta")
    ins = [layers.data(n, s, append_batch_size=False, dtype="float32")
           for n, s in zip(names, shapes)]
    out = layers.gated_delta_attention(*ins, heads=heads)
    feed = {n: np.zeros(s, np.float32) for n, s in zip(names, shapes)}
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        observability.reset()
        with fluid.flags.tpu_trace_scope(True):
            compiled, *rest = fluid.Executor(fluid.CPUPlace()).capture_program(
                feed=feed, fetch_list=[out])
            text = str(jax.make_jaxpr(compiled.raw_fn)(*rest))
        spans = [dict(s.args) for s in
                 observability.default_tracer().spans()
                 if s.name == ("gdn.lower" if key_heads else "kda.lower")]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
        fluid.reset_default_env()
    tiles = kda.kernel_tiles(1, S, heads, D, 64, jnp.bfloat16)[0]
    said = dict(
        heads=heads, head_dim=D, sq=S, chunk=64, chunks=S // 64, group=8,
        engine="pallas", rows=tiles.rows, fwd_vmem_bytes=tiles.fwd_vmem_bytes,
        bwd_vmem_bytes=tiles.bwd_vmem_bytes, state_bytes=4 * heads * D * D,
        kept="out,states",
        kept_bytes=2 * S * heads * D + S // 512 * 4 * heads * D * D)
    if not key_heads:
        assert spans == [dict(
            said, flops=kda.flops(1, S, heads, D, 64),
            moved_bytes=kda.moved_bytes(1, S, heads, D, 2))]
        return
    assert spans == [dict(
        said, flops=kda.flops(1, S, heads, D, 64, key_heads),
        moved_bytes=kda.moved_bytes(1, S, heads, D, 2, key_heads, True),
        decay="head", key_heads=key_heads)]
    # from the kernel on: q and k at their 16 heads, v at its 32, g and
    # beta by tiles; out and the group states: no decay a channel, no q or
    # k at 32 heads
    call = text[text.index("pallas_call"):]
    assert f"f32[1,{S},{heads * D}]" not in call
    assert call.count(f"bf16[1,{S},{heads * D}]") <= 3
