"""The flash forward's grid planned from the shape (PR 28): blocks from
_plan_blocks, causal blocks above the diagonal neither fetched nor computed,
the mask only where a block can be cut.  Everything here runs the kernel
through the Pallas interpreter on the CPU: values and counts, never times.
(d) below: several batch-head rows a grid step where a head is one block
(PR 53), in both kernels, and the engine rule that now counts the step's
scores.
"""

import functools
import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability
from paddle_tpu.kernels import engine

# the kernels package re-exports the flash_attention FUNCTION under the
# same name as its module; go through importlib for the module itself
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


def _qkv(seed, B, H, Sq, Sk, D, dtype):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(B, H, Sq, D), dtype),
            jnp.asarray(rng.randn(B, H, Sk, D), dtype),
            jnp.asarray(rng.randn(B, H, Sk, D), dtype))


def _spans(name, fn, *args):
    """The args of the spans called `name` that lowering `fn` leaves
    (abstractly: nothing compiles or runs); of each of them, by name, where
    `name` is a tuple."""
    observability.reset()
    was = fluid.flags._VALUES["FLAGS_observability"]
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        jax.eval_shape(fn, *args)
        spans = observability.default_tracer().spans()
        if isinstance(name, tuple):
            return {n: [s.args for s in spans if s.name == n] for n in name}
        return [s.args for s in spans if s.name == name]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = was
        observability.reset()


def _plan_spans(fn, *args):
    return _spans("flash.plan", fn, *args)


def _bwd_spans(fn, *args):
    return _spans("flash.bwd_plan", fn, *args)


def _tol(dtype, fp32):
    """bf16 operands against an fp32 reference, or `fp32`'s tolerances."""
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else fp32


# (a) planned blocks against the reference and against the same kernel
# pinned to 128 x 128 ------------------------------------------------------

# name: (B, H, Sq, Sk, D, dtype, causal, k_lengths)
FORWARD_CASES = {
    # the ouro-train-loop4 shape at B*H 2: four k-steps a head, one skipped
    "causal_s2048_head128_bf16": (1, 2, 2048, 2048, 128, jnp.bfloat16,
                                  True, None),
    # a cached prefix: queries sit at the end of the keys (offset 256)
    "causal_sq128_sk384_cache_offset": (2, 2, 128, 384, 64, jnp.float32,
                                        True, None),
    "causal_ragged_s200": (2, 2, 200, 200, 64, jnp.float32, True, None),
    # klen falls inside a k-block for one row, before a whole k-block for
    # another (that block is all mask), and at the end for the third
    "noncausal_klen_blocks_short": (3, 2, 2048, 4096, 128, jnp.bfloat16,
                                    False, [3000, 700, 4096]),
    # a row with no key at all gives zeros, not an average of V
    "fully_masked_row": (2, 2, 256, 256, 64, jnp.float32, True, [0, 256]),
}


@pytest.mark.parametrize("case", sorted(FORWARD_CASES))
def test_planned_forward_matches_reference_and_pinned_128(case):
    B, H, Sq, Sk, D, dtype, causal, lengths = FORWARD_CASES[case]
    q, k, v = _qkv(3, B, H, Sq, Sk, D, dtype)
    klen = jnp.asarray(lengths if lengths is not None else [Sk] * B,
                       jnp.float32)
    scale = 1.0 / np.sqrt(D)
    bq, bk = fa._plan_blocks(Sq, Sk, D, q.dtype, causal, False)
    assert (bq, bk) != (128, 128)           # the case exercises the plan

    planned = fa.flash_attention(q, k, v, causal=causal, k_lengths=klen,
                                 force="interpret")
    pinned, _ = fa._pallas_flash(q, k, v, klen, causal, scale, block_q=128,
                                 block_k=128, interpret=True, need_lse=False)
    want = fa._reference_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal, scale, k_lengths=klen.astype(jnp.int32))
    assert planned.dtype == q.dtype and planned.shape == q.shape
    # operands in the input dtype, everything between in fp32: the two block
    # sizes differ by the order of the online-softmax sums alone
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)
    got, pin = (np.asarray(x.astype(jnp.float32)) for x in (planned, pinned))
    np.testing.assert_allclose(got, pin, **tol)
    np.testing.assert_allclose(got, np.asarray(want), **tol)
    np.testing.assert_allclose(pin, np.asarray(want), **tol)
    if lengths is not None and 0 in lengths:
        assert not np.any(got[lengths.index(0)])


def test_noncausal_case_spans_the_klen_rule():
    """The klen case above must put klen inside a k-block that runs (row 0),
    before a whole k-block (row 1) and at the end of the keys (row 2) at the
    blocks the plan really gives, or it tests nothing."""
    _, _, Sq, Sk, D, dtype, causal, lengths = \
        FORWARD_CASES["noncausal_klen_blocks_short"]
    _, bk = fa._plan_blocks(Sq, Sk, D, dtype, causal, False)
    assert Sk // bk >= 2
    assert lengths[0] % bk and lengths[0] > bk          # inside a later block
    assert lengths[1] < Sk - bk                         # a block wholly past it
    assert lengths[2] == Sk


# (b) a block is skipped if and only if the dense mask has nothing in it ------

SMALL = [(sq, sk, bq, bk)
         for sq, sk in itertools.product((8, 16, 24, 40, 48), repeat=2)
         for bq, bk in itertools.product((4, 8, 16, 24), repeat=2)
         if sq % bq == 0 and sk % bk == 0]


def _dense_blocks_visible(sq, sk, bq, bk):
    """[nqb, nkb] bool: numpy's dense bottom-right-aligned causal mask has a
    visible element in the block."""
    mask = np.tril(np.ones((sq, sk), bool), k=sk - sq)
    return mask.reshape(sq // bq, bq, sk // bk, bk).any(axis=(1, 3))


def test_block_runs_iff_dense_mask_has_a_visible_element():
    assert len(SMALL) > 100
    for sq, sk, bq, bk in SMALL:
        visible = _dense_blocks_visible(sq, sk, bq, bk)
        nqb, nkb = visible.shape
        runs = np.array([[bool(fa._block_runs(i, j, bq, bk, sk - sq))
                          for j in range(nkb)] for i in range(nqb)])
        np.testing.assert_array_equal(runs, visible, err_msg=str(
            (sq, sk, bq, bk)))
        assert fa._skipped_steps(nqb, nkb, bq, bk, sk - sq)[0] == \
            int((~visible).sum())
        # past the last block that runs the K/V index stays put (no DMA);
        # up to it, it is the step's own block
        for i in range(nqb):
            for j in range(nkb):
                at = int(fa._kv_block_index(jnp.int32(i), jnp.int32(j), bq,
                                            bk, sk - sq))
                ran = [jj for jj in range(nkb) if visible[i, jj]]
                assert at == (j if visible[i, j] else (max(ran) if ran
                                                       else 0))


SKIP_CASES = [(16, 16, 4, 4), (16, 16, 8, 4), (8, 24, 4, 8), (24, 8, 8, 4),
              (16, 40, 16, 8)]


@pytest.mark.parametrize("sq,sk,bq,bk", SKIP_CASES)
def test_kernel_computes_exactly_the_blocks_with_a_visible_element(
        sq, sk, bq, bk):
    """Behaviour, not bookkeeping: poison V's k-block j with NaN.  A q-block
    whose step (i, j) runs multiplies it in (0 * NaN is NaN even under the
    mask); one that skips the step never reads it."""
    q, k, v = _qkv(5, 1, 1, sq, sk, 8, jnp.float32)
    klen = jnp.full((1,), sk, jnp.float32)
    visible = _dense_blocks_visible(sq, sk, bq, bk)
    for j in range(sk // bk):
        poisoned = v.at[:, :, j * bk:(j + 1) * bk].set(jnp.nan)
        out, _ = fa._pallas_flash(q, k, poisoned, klen, True, 0.35,
                                  block_q=bq, block_k=bk, interpret=True,
                                  need_lse=False)
        hit = np.isnan(np.asarray(out)[0, 0]).reshape(sq // bq, bq, 8)
        np.testing.assert_array_equal(hit.any(axis=(1, 2)), visible[:, j])
        assert (hit.all(axis=(1, 2)) == hit.any(axis=(1, 2))).all()


def test_skipped_count_in_the_span_equals_the_dense_count():
    for sq, sk, bq, bk in SKIP_CASES:
        q = jax.ShapeDtypeStruct((1, 1, sq, 8), jnp.float32)
        kv = jax.ShapeDtypeStruct((1, 1, sk, 8), jnp.float32)
        klen = jax.ShapeDtypeStruct((1,), jnp.float32)
        spans = _plan_spans(
            lambda q, k, v, klen: fa._pallas_flash(
                q, k, v, klen, True, 0.35, block_q=bq, block_k=bk,
                interpret=True, need_lse=False)[0], q, kv, kv, klen)
        visible = _dense_blocks_visible(sq, sk, bq, bk)
        assert spans == [dict(sq=sq, sk=sk, head_dim=8, block_q=bq,
                              block_k=bk, k_steps=visible.size,
                              k_steps_skipped=int((~visible).sum()),
                              causal=1, window=0, kv_heads=1, chunks=1,
                              skipped_causal=int((~visible).sum()),
                              skipped_window=0, rows_per_step=1,
                              layout="bhsd", form="blocks")]


@pytest.mark.parametrize("causal,pin,k_steps,skipped", [
    (True, 512, 16, 6),       # the cell's shape at 512 x 512: ISSUE 28's count
    (False, 512, 16, 0),
    (True, None, 4, 1),       # and at the blocks the plan gives it
    (False, None, 4, 0),
])
def test_flash_plan_span_at_the_ouro_cell_shape(causal, pin, k_steps,
                                                skipped):
    x = jax.ShapeDtypeStruct((2, 16, 2048, 128), jnp.bfloat16)
    klen = jax.ShapeDtypeStruct((2,), jnp.float32)
    spans = _plan_spans(
        lambda q, k, v, klen: fa._pallas_flash(
            q, k, v, klen, causal, 0.088, block_q=pin, block_k=pin,
            interpret=True, need_lse=False)[0], x, x, x, klen)
    assert len(spans) == 1
    got = spans[0]
    assert (got["k_steps"], got["k_steps_skipped"]) == (k_steps, skipped)
    assert got["causal"] == int(causal) and got["head_dim"] == 128
    assert (got["sq"], got["sk"]) == (2048, 2048)
    if pin is None:
        assert (got["block_q"], got["block_k"]) == fa._plan_blocks(
            2048, 2048, 128, jnp.bfloat16, causal, False)
        assert got["block_q"] * got["block_k"] > 512 * 512


# the plan itself -----------------------------------------------------------

PLAN_SHAPES = [(2048, 2048, 128, "bfloat16"), (256, 256, 64, "bfloat16"),
               (128, 384, 64, "bfloat16"), (200, 200, 64, "float32"),
               (512, 4096, 128, "bfloat16"), (24, 24, 8, "float32"),
               (1000, 1000, 64, "float32"), (2048, 2048, 256, "float32")]


@pytest.mark.parametrize("sq,sk,d,dtype", PLAN_SHAPES)
@pytest.mark.parametrize("causal", [False, True])
def test_plan_is_tiled_inside_its_share_and_never_pads_further(
        sq, sk, d, dtype, causal):
    for emit_lse in (False, True):
        bq, bk = fa._plan_blocks(sq, sk, d, dtype, causal, emit_lse)
        for s, b in ((sq, bq), (sk, bk)):
            if s <= 128:
                assert b == s                 # one block: the sequence itself
            else:
                assert b % 128 == 0
                assert -(-s // b) * b == -(-s // 128) * 128
        assert fa.fwd_working_set_bytes(
            bq, bk, d, -(-sq // bq), dtype, emit_lse) \
            <= engine.PLAN_VMEM_BUDGET
        if max(sq, sk) > 128:
            assert bq * bk > 128 * 128        # what the plan is for


def test_plan_reads_the_shape_and_nothing_else():
    """No flag, environment variable or argument of a model selects blocks:
    the plan is a pure function, and the working set it holds under its
    share counts the score blocks the declared buffers leave out."""
    args = (2048, 2048, 128, "bfloat16", True, False)
    assert fa._plan_blocks(*args) == fa._plan_blocks(*args) == (1024, 1024)
    # a key block as wide as the keys where two plans take as many steps
    assert fa._plan_blocks(2048, 2048, 128, "bfloat16", False, False) == \
        (512, 2048)
    assert fa._plan_blocks(256, 256, 64, "bfloat16", True, False) == (256, 256)
    declared = fa.fwd_vmem_bytes(512, 512, 128, 4, "bfloat16", False)
    assert fa.fwd_working_set_bytes(512, 512, 128, 4, "bfloat16", False) == \
        declared + 2 * 512 * 512 * 4


# (c) the backward: a plan of its own, both directions skipped, the engine
# read from the shape (PR 30) ----------------------------------------------

def _reference_grads(q, k, v, g, klen, causal, scale):
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    _, vjp = jax.vjp(lambda q, k, v: fa._reference_attention(
        q, k, v, causal, scale, k_lengths=klen.astype(jnp.int32)), *f32)
    return vjp(g.astype(jnp.float32))


# name: (B, H, Sq, Sk, D, dtype, causal, k_lengths, forward's pinned
# (block_q, block_k) or None for its plan, backward's pinned pair or None)
BACKWARD_CASES = {
    # blocks that straddle the diagonal, wide and tall
    "causal_diagonal_wide_blocks": (1, 2, 256, 256, 64, jnp.float32, True,
                                    None, None, (64, 128)),
    "causal_diagonal_tall_blocks": (1, 2, 256, 256, 64, jnp.float32, True,
                                    None, None, (128, 64)),
    # a cached prefix: the diagonal is bottom-right aligned (offset 256)
    "causal_sk_longer_offset": (2, 1, 128, 384, 64, jnp.float32, True,
                                [384, 300], None, (64, 128)),
    # more queries than keys: the first q-blocks see no key at all
    "causal_sq_longer_rows_without_keys": (2, 1, 384, 128, 64, jnp.float32,
                                           True, [128, 100], None, (128, 64)),
    # klen inside a k-block, before a whole k-block, and at the end
    "noncausal_klen_cuts_blocks": (3, 1, 256, 512, 64, jnp.float32, False,
                                   [300, 100, 512], None, (128, 128)),
    "causal_padded_last_block": (2, 1, 200, 200, 64, jnp.float32, True,
                                 [200, 150], None, (128, 128)),
    "row_without_keys": (2, 1, 200, 200, 64, jnp.float32, False, [200, 0],
                         None, None),
    "planned_blocks_s1024_bf16": (1, 1, 1024, 1024, 128, jnp.bfloat16, True,
                                  None, None, None),
    "pinned_blocks_bf16": (1, 2, 512, 512, 128, jnp.bfloat16, True, None,
                           None, (128, 256)),
    # the backward's q-block is not the forward's: the packed lse plane is
    # re-cut, for free where both divide one padded length ...
    "bwd_q_block_shorter_than_fwd": (1, 2, 256, 256, 64, jnp.float32, True,
                                     None, (256, 256), (64, 128)),
    "bwd_q_block_longer_than_fwd": (1, 2, 256, 256, 64, jnp.float32, True,
                                    None, (64, 64), (256, 128)),
    # ... and by cutting and padding anew where they pad to other lengths
    "bwd_pads_to_another_length": (1, 2, 200, 200, 64, jnp.float32, True,
                                   None, (128, 128), (40, 40)),
}


@pytest.mark.parametrize("case", sorted(BACKWARD_CASES))
def test_backward_kernels_match_the_reference_vjp(case):
    B, H, Sq, Sk, D, dtype, causal, lengths, fwd_pin, bwd_pin = \
        BACKWARD_CASES[case]
    q, k, v = _qkv(17, B, H, Sq, Sk, D, dtype)
    g = jnp.asarray(np.random.RandomState(18).randn(B, H, Sq, D), dtype)
    klen = jnp.asarray(lengths if lengths is not None else [Sk] * B,
                       jnp.float32)
    scale = 1.0 / np.sqrt(D)
    fq, fk = fwd_pin or (None, None)
    bq, bk = bwd_pin or (None, None)
    out, lse = fa._pallas_flash(q, k, v, klen, causal, scale, block_q=fq,
                                block_k=fk, interpret=True)
    got = fa._pallas_flash_bwd(q, k, v, klen, out, lse, g, causal, scale,
                               block_q=bq, block_k=bk, interpret=True)
    want = _reference_grads(q, k, v, g, klen, causal, scale)
    tol = _tol(dtype, dict(rtol=2e-4, atol=2e-5))
    for name, x, w in zip("qkv", got, want):
        assert x.dtype == dtype and x.shape == w.shape
        np.testing.assert_allclose(np.asarray(x.astype(jnp.float32)),
                                   np.asarray(w), err_msg="d" + name, **tol)
    if lengths is not None and 0 in lengths:
        row = lengths.index(0)
        assert not any(np.any(np.asarray(x)[row]) for x in got)
    if bwd_pin is None and max(Sq, Sk) > 512:    # a plan of its own
        assert fa._plan_bwd_blocks(Sq, Sk, D, dtype, causal) != \
            fa._plan_blocks(Sq, Sk, D, dtype, causal, True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gradient_through_the_custom_vjp_matches_reference(dtype):
    """force="interpret" keeps the Pallas backward at every shape, here one
    the rule would give to XLA (two rows a step are not worth one);
    force="jax" keeps none."""
    B, H, S, D = 1, 2, 256, 64
    assert fa._bwd_plan(S, S, D, dtype, True, bh=B * H)["engine"] == "xla"
    q, k, v = _qkv(11, B, H, S, S, D, dtype)
    klen = jnp.asarray([200.0])
    w = jnp.asarray(np.random.RandomState(12).randn(B, H, S, D), jnp.float32)

    def grads(attend):
        return jax.grad(lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * w), argnums=(0, 1, 2))

    want = grads(lambda q, k, v: fa._reference_attention(
        q, k, v, True, 1.0 / np.sqrt(D), k_lengths=klen.astype(jnp.int32)))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    tol = _tol(dtype, dict(rtol=2e-4, atol=2e-5))
    for force, sites in (("interpret", 1), ("jax", 0)):
        attend = lambda q, k, v: fa.flash_attention(     # noqa: E731
            q, k, v, causal=True, k_lengths=klen, force=force)
        spans = _bwd_spans(grads(attend), q, k, v)
        assert [s["engine"] for s in spans] == ["pallas"] * sites
        for g, w_ in zip(grads(attend)(q, k, v), want):
            np.testing.assert_allclose(np.asarray(g.astype(jnp.float32)),
                                       np.asarray(w_), **tol)


def test_first_q_block_of_a_k_block_is_the_dense_masks():
    """q innermost: before the first q-block that sees k-block j the q/dO
    index waits at that block; from it on, it is the step's own."""
    for sq, sk, bq, bk in SMALL:
        visible = _dense_blocks_visible(sq, sk, bq, bk)
        nqb, nkb = visible.shape
        for j in range(nkb):
            ran = [i for i in range(nqb) if visible[i, j]]
            for i in range(nqb):
                at = int(fa._q_block_index(jnp.int32(i), jnp.int32(j), bq, bk,
                                           sk - sq, nqb))
                want = i if (ran and i >= min(ran)) else (
                    min(ran) if ran else nqb - 1)
                assert at == want, (sq, sk, bq, bk, i, j)


@pytest.mark.parametrize("sq,sk,bq,bk", SKIP_CASES)
def test_backward_computes_exactly_the_blocks_with_a_visible_element(
        sq, sk, bq, bk):
    """Behaviour, in both directions.  Poison V's k-block j: q-block i's dQ
    is NaN if and only if step (i, j) runs.  Poison dO's q-block i: k-block
    j's dK and dV are NaN if and only if step (i, j) runs."""
    q, k, v = _qkv(5, 1, 1, sq, sk, 8, jnp.float32)
    g = jnp.asarray(np.random.RandomState(6).randn(1, 1, sq, 8), jnp.float32)
    klen = jnp.full((1,), sk, jnp.float32)
    visible = _dense_blocks_visible(sq, sk, bq, bk)
    out, lse = fa._pallas_flash(q, k, v, klen, True, 0.35, block_q=bq,
                                block_k=bk, interpret=True)

    def bwd(v, g):
        return fa._pallas_flash_bwd(q, k, v, klen, out, lse, g, True, 0.35,
                                    block_q=bq, block_k=bk, interpret=True)

    def blocks_hit(x, block):
        hit = np.isnan(np.asarray(x)[0, 0]).reshape(-1, block, 8)
        assert (hit.all(axis=(1, 2)) == hit.any(axis=(1, 2))).all()
        return hit.any(axis=(1, 2))

    for j in range(sk // bk):
        dq, _, _ = bwd(v.at[:, :, j * bk:(j + 1) * bk].set(jnp.nan), g)
        np.testing.assert_array_equal(blocks_hit(dq, bq), visible[:, j])
    for i in range(sq // bq):
        _, dk, dv = bwd(v, g.at[:, :, i * bq:(i + 1) * bq].set(jnp.nan))
        np.testing.assert_array_equal(blocks_hit(dk, bk), visible[i])
        np.testing.assert_array_equal(blocks_hit(dv, bk), visible[i])


@pytest.mark.parametrize("sq,sk,bq,bk", SKIP_CASES)
def test_bwd_plan_span_counts_equal_the_dense_count(sq, sk, bq, bk):
    x = jax.ShapeDtypeStruct((1, 1, sq, 8), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 1, sk, 8), jnp.float32)
    klen = jax.ShapeDtypeStruct((1,), jnp.float32)

    def bwd(q, k, v, klen):
        out, lse = fa._pallas_flash(q, k, v, klen, True, 0.35, interpret=True)
        return fa._pallas_flash_bwd(q, k, v, klen, out, lse, q, True, 0.35,
                                    block_q=bq, block_k=bk, interpret=True)

    visible = _dense_blocks_visible(sq, sk, bq, bk)
    # static counts over one batch-head row
    assert _bwd_spans(bwd, x, kv, kv, klen) == [dict(
        sq=sq, sk=sk, head_dim=8, block_q=bq, block_k=bk,
        steps=visible.size, steps_skipped=int((~visible).sum()),
        engine="pallas", window=0, chunks=1, kv_heads=1,
        skipped_causal=int((~visible).sum()), skipped_window=0,
        rows_per_step=1, layout="bhsd", form="blocks")]


def test_repack_is_a_view_where_the_padded_lengths_agree():
    plane = jnp.arange(2 * 4 * 128, dtype=jnp.float32).reshape(2, 4, 128)
    for bq in (128, 256, 512):
        np.testing.assert_array_equal(
            np.asarray(fa._repack(plane, 500, bq, 9.0)).reshape(2, -1),
            np.asarray(plane).reshape(2, -1))
    cut = np.asarray(fa._repack(plane, 300, 100, 9.0))
    assert cut.shape == (2, 3, 100)
    np.testing.assert_array_equal(cut.reshape(2, -1),
                                  np.asarray(plane).reshape(2, -1)[:, :300])
    grown = np.asarray(fa._repack(plane[:, :1], 100, 40, 9.0))
    assert grown.shape == (2, 3, 40)
    assert (grown.reshape(2, -1)[:, 100:] == 9.0).all()
    np.testing.assert_array_equal(grown.reshape(2, -1)[:, :100],
                                  np.asarray(plane)[:, 0, :100])


# the backward's plan and the rule that reads the engine off it -------------

@pytest.mark.parametrize("sq,sk,d,dtype", PLAN_SHAPES)
@pytest.mark.parametrize("causal", [False, True])
def test_bwd_plan_is_tiled_inside_its_share(sq, sk, d, dtype, causal):
    bq, bk = fa._plan_bwd_blocks(sq, sk, d, dtype, causal)
    for s, b in ((sq, bq), (sk, bk)):
        if s <= 128:
            assert b == s
        else:
            assert b % 128 == 0
            assert -(-s // b) * b == -(-s // 128) * 128
    ws = fa.bwd_working_set_bytes(bq, bk, d, -(-sq // bq), dtype)
    assert ws <= engine.PLAN_VMEM_BUDGET or (bq, bk) == (128, 128)
    # four fp32 score planes where the forward counts two
    assert ws - 4 * bq * bk * 4 > 0
    fwd = fa._plan_blocks(sq, sk, d, dtype, causal, True)
    assert bq * bk <= fwd[0] * fwd[1]


# (since PR 53 the batch-head rows a step can take are part of the shape
# the rule reads) name: (B, H, Sq, Sk, D, dtype, causal, engine,
# rows_per_step): the cells' B and H, which the lowering test below cuts
ENGINE_BY_SHAPE = {
    "ouro_2.6b_self": (2, 16, 2048, 2048, 128, "bfloat16", True, "pallas",
                       1),
    "transformer_base_decoder_self": (96, 8, 256, 256, 64, "bfloat16", True,
                                      "pallas", 12),
    "transformer_base_encoder_self": (96, 8, 256, 256, 64, "bfloat16", False,
                                      "pallas", 12),
    "transformer_base_cross": (96, 8, 256, 256, 64, "bfloat16", False,
                               "pallas", 12),
    # one batch-head row at the same S: a step is one 256 x 256 block, and
    # two rows of it are not yet 384 x 384 scores
    "s256_one_row": (1, 1, 256, 256, 64, "bfloat16", True, "xla", 1),
    "s256_two_rows": (1, 2, 256, 256, 64, "bfloat16", True, "xla", 2),
    "s256_three_rows": (3, 1, 256, 256, 64, "bfloat16", True, "pallas", 3),
}


@pytest.mark.parametrize("case", sorted(ENGINE_BY_SHAPE))
def test_engine_is_read_from_the_shape(case):
    B, H, sq, sk, d, dtype, causal, engine, rows = ENGINE_BY_SHAPE[case]
    plan = fa._bwd_plan(sq, sk, d, dtype, causal, bh=B * H)
    assert (plan["engine"], plan["rows_per_step"]) == (engine, rows)
    assert fa._bwd_plan(sq, sk, d, dtype, causal, bh=B * H) == plan  # pure
    assert (plan["block_q"], plan["block_k"]) == fa._plan_bwd_blocks(
        sq, sk, d, dtype, causal)
    # the step's scores are what the rule weighs
    assert (rows * plan["block_q"] * plan["block_k"]
            >= fa._BWD_PALLAS_MIN_BLOCK_SCORES) == (engine == "pallas")


@pytest.mark.parametrize("case", sorted(ENGINE_BY_SHAPE))
def test_lowered_tpu_text_carries_the_backward_kernels_by_shape(case):
    """What a TPU program gets (flags.tpu_trace_scope, force="auto"): the
    forward kernel at every shape; the backward kernel's custom call at the
    ouro-2.6b shape and, since a step takes several rows, at
    transformer-base's three (cut to B 2, H 2: four rows a step), but not
    where the call has one or two batch-head rows in all."""
    B, H, sq, sk, d, dtype, causal, engine, _ = ENGINE_BY_SHAPE[case]
    B, H = min(B, 2), min(H, 2)
    if case == "s256_three_rows":
        B = 3
    ragged = "encoder" in case or "cross" in case
    q = jax.ShapeDtypeStruct((B, H, sq, d), jnp.dtype(dtype))
    kv = jax.ShapeDtypeStruct((B, H, sk, d), jnp.dtype(dtype))
    klen = jax.ShapeDtypeStruct((B,), jnp.float32)

    def loss(q, k, v, klen):
        o = fa.flash_attention(q, k, v, causal=causal,
                               k_lengths=klen if ragged else None)
        return jnp.sum(o.astype(jnp.float32))

    with fluid.flags.tpu_trace_scope(True):
        # the loss is returned too: a step keeps its forward
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).trace(
            q, kv, kv, klen).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == (2 if engine == "pallas" else 1)
    # several rows a step are _flash_bwd_rows_kernel's, one _flash_bwd_kernel's
    rows = fa._bwd_plan(sq, sk, d, dtype, causal, bh=B * H)["rows_per_step"]
    assert ("_flash_bwd_rows_kernel" in text) == (
        engine == "pallas" and rows > 1)
    assert ("_flash_bwd_kernel" in text) == (engine == "pallas" and rows == 1)
    assert ("_flash_rows_kernel" in text) == (B * H > 1 and sq == 256)


# (d) several batch-head rows a grid step (PR 53) ---------------------------

# name: (B, H, dtype, causal, k_lengths, the forward's rows a step, the
# backward's), all at [B, H, 256, 64]: a head is one 256 x 256 block
PACKED_CASES = {
    "causal": (2, 4, jnp.bfloat16, True, None, 8, 8),
    # a step's six rows cross three batch rows, each with its own klen: at
    # the end of the keys, inside the block, and a single key
    "ragged_rows_cross_batch_rows": (3, 2, jnp.bfloat16, False,
                                     [256, 100, 1], 6, 6),
    # a batch row with no key at all, beside rows that have them
    "fully_masked_row": (3, 2, jnp.float32, True, [256, 0, 77], 6, 6),
    # 26 rows: 26 do not fit a step; the forward falls to 13, the backward
    # (four score planes, seven blocks a row) past 13 to 2
    "no_divisor_at_the_most_that_fits": (13, 2, jnp.bfloat16, False,
                                         [256] * 6 + [131] * 7, 13, 2),
    # 23 rows, a prime past what fits: down to 1, the kernel as it always ran
    "prime_rows_fall_to_one": (23, 1, jnp.bfloat16, True, None, 1, 1),
}


@pytest.mark.parametrize("case", sorted(PACKED_CASES))
def test_rows_of_a_step_match_the_reference_forward_and_backward(case):
    """The kernels at the rows a step the plan gives against
    _reference_attention and jax.vjp of it, and against the same kernels
    held to one row a step: the loop changes no value."""
    B, H, dtype, causal, lengths, fwd_rows, bwd_rows = PACKED_CASES[case]
    S, D = 256, 64
    q, k, v = _qkv(53, B, H, S, S, D, dtype)
    g = jnp.asarray(np.random.RandomState(54).randn(B, H, S, D), dtype)
    klen = jnp.asarray(lengths if lengths is not None else [S] * B,
                       jnp.float32)
    scale = 1.0 / np.sqrt(D)

    def fwd(q, k, v, klen, **pins):
        return fa._pallas_flash(q, k, v, klen, causal, scale, interpret=True,
                                **pins)

    def bwd(q, k, v, klen, out, lse, **pins):
        return fa._pallas_flash_bwd(q, k, v, klen, out, lse, g, causal,
                                    scale, interpret=True, **pins)

    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, klen)]
    assert [p["rows_per_step"] for p in _plan_spans(fwd, *shapes)] == \
        [fwd_rows]
    out, lse = fwd(q, k, v, klen)
    assert [p["rows_per_step"] for p in _bwd_spans(
        bwd, *shapes, out, lse)] == [bwd_rows]
    assert fa._bwd_plan(S, S, D, dtype, causal, bh=B * H)[
        "rows_per_step"] == bwd_rows
    grads = bwd(q, k, v, klen, out, lse)

    out1, lse1 = fwd(q, k, v, klen, rows_per_step=1)
    np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                  np.asarray(out1.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse1))
    for x, x1 in zip(grads, bwd(q, k, v, klen, out, lse, rows_per_step=1)):
        np.testing.assert_array_equal(np.asarray(x.astype(jnp.float32)),
                                      np.asarray(x1.astype(jnp.float32)))

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = fa._reference_attention(*f32, causal, scale,
                                   k_lengths=klen.astype(jnp.int32))
    tol = _tol(dtype, dict(rtol=2e-4, atol=2e-5))
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(want), **tol)
    for name, x, w in zip("qkv", grads, _reference_grads(
            q, k, v, g, klen, causal, scale)):
        assert x.dtype == dtype and x.shape == w.shape
        np.testing.assert_allclose(np.asarray(x.astype(jnp.float32)),
                                   np.asarray(w), err_msg="d" + name, **tol)
    if lengths is not None and 0 in lengths:
        row = lengths.index(0)
        assert not np.any(np.asarray(out)[row])
        assert not any(np.any(np.asarray(x)[row]) for x in grads)


@pytest.mark.parametrize("bh,fwd_rows,bwd_rows", [
    (768, 16, 12),      # transformer-base: 96 x 8
    (96, 16, 12), (24, 12, 12), (22, 22, 11), (26, 13, 2), (23, 1, 1),
    (7, 7, 7), (1, 1, 1)])
def test_rows_of_a_step_are_the_most_that_fit_and_divide(bh, fwd_rows,
                                                         bwd_rows):
    """At 256 x 256 x 64 bf16: the largest divisor of B * H whose working
    set is inside the plan's share, down to 1; the declared blocks grow with
    the rows, the fp32 score planes do not, and a step of several rows has
    no scratch."""
    def fwd(n):
        return fa.fwd_working_set_bytes(256, 256, 64, 1, "bfloat16", True,
                                        None, n)

    def bwd(n):
        return fa.bwd_working_set_bytes(256, 256, 64, 1, "bfloat16", None, n)

    assert fa._rows_per_step(bh, True, fwd) == fwd_rows
    assert fa._rows_per_step(bh, True, bwd) == bwd_rows
    assert fa._rows_per_step(bh, False, fwd) == 1    # several blocks a head
    for ws, rows in ((fwd, fwd_rows), (bwd, bwd_rows)):
        assert bh % rows == 0
        assert rows == 1 or ws(rows) <= engine.PLAN_VMEM_BUDGET
        assert all(ws(n) > engine.PLAN_VMEM_BUDGET
                   for n in range(rows + 1, bh + 1) if bh % n == 0)
        assert ws(3) - ws(2) == ws(4) - ws(3) > 0
    assert fwd(1) == fa.fwd_working_set_bytes(256, 256, 64, 1, "bfloat16",
                                              True)
    assert bwd(1) == fa.bwd_working_set_bytes(256, 256, 64, 1, "bfloat16")
    # a row more is a row of the declared blocks more, and nothing else
    assert fwd(3) - fwd(2) == fa.fwd_vmem_bytes(
        256, 256, 64, 1, "bfloat16", True, None, 3) - fa.fwd_vmem_bytes(
            256, 256, 64, 1, "bfloat16", True, None, 2) == fa.fwd_vmem_bytes(
                256, 256, 64, 1, "bfloat16", True, None, 2) // 2


@pytest.mark.parametrize("what", ["window", "grouped", "two_k_blocks",
                                  "two_q_blocks"])
def test_a_step_takes_one_row_where_a_head_is_not_one_plain_block(what):
    """A window, grouped K/V or a second block in either direction: one row
    a step, the call as it always was, in both kernels."""
    B, H, S, D = 2, 4, 256, 64
    G = 2 if what == "grouped" else H
    window = 100 if what == "window" else None
    pins = {"two_k_blocks": dict(block_k=128),
            "two_q_blocks": dict(block_q=128)}.get(what, {})
    q = jax.ShapeDtypeStruct((B, H, S, D), jnp.float32)
    kv = jax.ShapeDtypeStruct((B, G, S, D), jnp.float32)
    klen = jax.ShapeDtypeStruct((B,), jnp.float32)

    def both(q, k, v, klen):
        out, lse = fa._pallas_flash(q, k, v, klen, True, 0.125,
                                    interpret=True, window=window, **pins)
        return fa._pallas_flash_bwd(q, k, v, klen, out, lse, q, True, 0.125,
                                    interpret=True, window=window, **pins)

    # (a lambda each: jax keeps the trace of a function it has seen)
    assert [p["rows_per_step"] for p in _plan_spans(
        lambda *a: both(*a), q, kv, kv, klen)] == [1]
    assert [p["rows_per_step"] for p in _bwd_spans(
        lambda *a: both(*a), q, kv, kv, klen)] == [1]


@pytest.mark.parametrize("window,form", [(100, "band"), (255, "band"),
                                         (256, "blocks"), (1000, "blocks")])
def test_a_window_shorter_than_the_keys_is_the_bands(window, form):
    """flash_attention reads the band off the call (PR 59): a window
    shorter than the keys lowers the band's pair, forward and backward (one
    call, no chunks); a window that holds every key is no window and takes
    the block kernels."""
    q = jax.ShapeDtypeStruct((2, 4, 256, 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((2, 2, 256, 64), jnp.float32)

    def grads(q, k, v):
        return jax.grad(lambda *a: fa.flash_attention(
            *a, causal=True, window=window, force="interpret").sum(),
            argnums=(0, 1, 2))(q, k, v)

    spans = _spans(("flash.plan", "flash.bwd_plan"), grads, q, kv, kv)
    assert [p["form"] for p in spans["flash.plan"]] == [form]
    assert [(p["form"], p["chunks"], p["engine"])
            for p in spans["flash.bwd_plan"]] == [(form, 1, "pallas")]
    assert {p["window"] for name in spans for p in spans[name]} == {
        window if form == "band" else 0}


def test_band_vmem_estimates_match_linter_price():
    """A windowed site's two calls (kernels/flash_attention.py, PR 59): the
    band's analytic counts (the strip's K and V blocks and the group's lse
    plane forward; the ring of dQ and the accumulators backward) equal what
    the linter prices off the traced calls, and what the plans hold under
    their budget adds the score planes no declared buffer shows."""
    from paddle_tpu.analysis import pallas as AP

    B, H, G, S, D, W = 1, 8, 2, 4096, 128, 1024
    q = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, G, S, D), jnp.bfloat16)
    eqns = list(AP.iter_pallas_calls(jax.make_jaxpr(
        jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=W, force="interpret").sum().astype(
                jnp.float32), argnums=(0, 1, 2)))(q, kv, kv)))
    assert [AP.kernel_cost(e).name for e in eqns] == [
        "_band_kernel", "_band_bwd_kernel"]
    group = H // G
    bwd = fa._bwd_plan(S, S, D, jnp.bfloat16, True, window=W, group=group)
    for eqn, b, declared, planned, planes in (
            (eqns[0], fa._plan_band(
                S, S, W, lambda b, n: fa.band_fwd_working_set_bytes(
                    b, n, D, S // b, "bfloat16", True, None, group), True),
             fa.band_fwd_vmem_bytes, fa.band_fwd_working_set_bytes, 2),
            (eqns[1], bwd["block_q"], fa.band_bwd_vmem_bytes,
             fa.band_bwd_working_set_bytes, 4)):
        n = fa._band(b, S // b, S // b, 0, W).n
        assert n == W // b + 1
        lse = (True,) if planes == 2 else ()
        args = (b, n, D, S // b, "bfloat16", *lse, None, group)
        assert AP.kernel_vmem_bytes(eqn) == declared(*args)
        strip = n if planes == 2 else 1
        assert planned(*args) == declared(*args) + planes * strip * b * b * 4
        assert planned(*args) < AP.default_vmem_budget()


# (e) heads-last: [B, S, H * D] operands as the projections write them
# (PR 57) -------------------------------------------------------------------

# name: (B, H, D, dtype, causal, k_lengths, whether the logsumexp is handed
# out and takes a cotangent), all at [B, 256, H * D]: a head is one block
HEADS_LAST_CASES = {
    "causal_bf16": (2, 8, 64, jnp.bfloat16, True, None, False),
    "causal_fp32": (2, 2, 64, jnp.float32, True, None, False),
    # a batch row with no key at all beside rows that have them, one cut
    # inside the block
    "ragged_fully_masked_row_bf16": (3, 4, 64, jnp.bfloat16, False,
                                     [256, 0, 77], False),
    "ragged_fully_masked_row_fp32": (3, 2, 64, jnp.float32, False,
                                     [256, 0, 77], False),
    "causal_dlse_given_fp32": (2, 2, 64, jnp.float32, True, None, True),
    "ragged_dlse_given_bf16": (3, 4, 64, jnp.bfloat16, False, [256, 0, 77],
                               True),
    # a lane tile of sixteen heads (D's constant takes two fp32 tiles of
    # rows) and a head that IS a lane tile (nothing to take apart)
    "sixteen_heads_a_tile_fp32": (2, 16, 8, jnp.float32, True, None, False),
    "a_head_a_tile_fp32": (2, 2, 128, jnp.float32, False, [200, 256], False),
}


def _heads_last_inputs(seed, B, H, S, D, dtype):
    rng = np.random.RandomState(seed)
    q, k, v, g = (jnp.asarray(rng.randn(B, S, H * D), dtype)
                  for _ in range(4))
    return q, k, v, g, jnp.asarray(rng.randn(B, H, S), jnp.float32)


@pytest.mark.parametrize("case", sorted(HEADS_LAST_CASES))
def test_heads_last_matches_the_reference_forward_and_backward(case):
    """flash_attention(heads=H) on [B, 256, H * 64] operands through the
    interpreter against _reference_attention and jax.vjp of it on the
    transposed operands: the output, the logsumexp where it is handed out,
    and dQ, dK, dV in the operands' own layout; `flash.plan` and
    `flash.bwd_plan` say `layout` bshd and batch rows a step."""
    B, H, D, dtype, causal, lengths, with_lse = HEADS_LAST_CASES[case]
    S = 256
    q, k, v, g, dlse = _heads_last_inputs(57, B, H, S, D, dtype)
    klen = None if lengths is None else jnp.asarray(lengths, jnp.float32)
    scale = 1.0 / np.sqrt(D)

    def loss(attend):
        def fn(q, k, v):
            out, lse = attend(q, k, v)
            total = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32))
            if with_lse:    # a row without a key hands out NEG_INF
                total += jnp.sum(jnp.where(lse > fa.NEG_INF / 2, lse, 0.0)
                                 * dlse)
            return total, (out, lse)
        return jax.value_and_grad(fn, argnums=(0, 1, 2), has_aux=True)

    def ours(q, k, v):
        res = fa.flash_attention(q, k, v, causal=causal, k_lengths=klen,
                                 force="interpret", heads=H,
                                 return_lse=with_lse)
        return res if with_lse else (res, jnp.zeros((B, H, S)))

    def reference(q, k, v):
        out, lse = fa._reference_attention(
            *(fa._heads_first(x, H) for x in (q, k, v)), causal, scale,
            k_lengths=None if klen is None else klen.astype(jnp.int32),
            with_lse=True)
        return fa._heads_last(out), lse if with_lse else jnp.zeros((B, H, S))

    assert fa.takes_heads_last(q, k, v, H, force="interpret")
    spans = _spans(("flash.plan", "flash.bwd_plan"), loss(ours), q, k, v)
    rows = fa._heads_last_rows(B, S, S, H, D, str(jnp.dtype(dtype)), True)
    assert [(p["layout"], p["rows_per_step"], p["kv_heads"])
            for p in spans["flash.plan"]] == [("bshd", rows.forward, H)]
    assert [(p["layout"], p["rows_per_step"], p["engine"], p["steps"])
            for p in spans["flash.bwd_plan"]] == [("bshd", rows.backward,
                                                   "pallas", 1)]

    (_, (out, lse)), grads = loss(ours)(q, k, v)
    (_, (want, want_lse)), want_grads = loss(reference)(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    tol = _tol(dtype, dict(rtol=2e-4, atol=2e-5))
    assert out.shape == q.shape and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(want), **tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=1e-5, atol=1e-5)
    for name, x, w in zip("qkv", grads, want_grads):
        assert x.dtype == dtype and x.shape == w.shape
        np.testing.assert_allclose(np.asarray(x.astype(jnp.float32)),
                                   np.asarray(w), err_msg="d" + name, **tol)
    if lengths is not None and 0 in lengths:
        row = lengths.index(0)
        assert not np.any(np.asarray(out)[row])
        assert not any(np.any(np.asarray(x)[row]) for x in grads)
        if with_lse:
            assert np.all(np.asarray(lse)[row] == fa.NEG_INF)


@pytest.mark.parametrize("batch,heads,fwd_rows,fwd_only_rows,bwd_rows", [
    (96, 8, 2, 2, 2),       # transformer-base on one chip
    (24, 8, 2, 2, 2),       # a chip's share of it under dp = 4
    (96, 2, 8, 8, 8), (30, 2, 10, 10, 10), (7, 8, 1, 1, 1), (9, 4, 3, 3, 3), (1, 8, 1, 1, 1)])
def test_heads_last_rows_of_a_step_are_the_most_that_fit_and_divide(
        batch, heads, fwd_rows, fwd_only_rows, bwd_rows):
    """At [B, 256, heads x 64] bf16 a step's rows are BATCH rows of `heads`
    heads: the largest divisor of B whose working set is inside the plan's
    share.  The blocks are whole lane tiles (no 64 padded to 128: a batch
    row of 8 heads is half the 8 heads-first rows' bytes), the forward
    counts a plane more a head (Mosaic lays them out side by side), and the
    backward holds O where the D plane was."""
    def fwd(n, lse=True):
        return fa.fwd_working_set_bytes(256, 256, 64, 1, "bfloat16", lse,
                                        None, n, heads)

    def bwd(n):
        return fa.bwd_working_set_bytes(256, 256, 64, 1, "bfloat16", None, n,
                                        heads)

    assert fa._heads_last_rows(batch, 256, 256, heads, 64, "bfloat16") == \
        (fwd_rows, fwd_only_rows, bwd_rows)
    for ws, rows in ((fwd, fwd_rows), (bwd, bwd_rows)):
        assert batch % rows == 0 and ws(rows) <= engine.PLAN_VMEM_BUDGET
        assert all(ws(n) > engine.PLAN_VMEM_BUDGET
                   for n in range(rows + 1, batch + 1) if batch % n == 0)
    plane = 256 * 256 * 4
    blocks = 256 * heads * 64 * 2
    lse = -(-heads // 8) * 8 * 256 * 4      # whole fp32 tiles of 8 rows
    assert fwd(2) - fwd(1) == 2 * (4 * blocks + lse) + heads * plane
    assert bwd(2) - bwd(1) == 2 * (8 * blocks + lse)
    assert 2 * fa.fwd_vmem_bytes(
        256, 256, 64, 1, "bfloat16", False, None, 1, 8) == fa.fwd_vmem_bytes(
            256, 256, 64, 1, "bfloat16", False, None, 8)


# what: (H, G, Sq, Sk, window) of a heads-last site that is NOT one plain
# block a head, or whose heads do not tile the lanes
NOT_AS_GIVEN = {
    "two_k_blocks": (2, 2, 256, 2304, None),
    "window": (2, 2, 256, 256, 100),
    "grouped": (4, 2, 256, 256, None),
    "the_rehearsals_heads": (4, 4, 16, 16, None),
}


@pytest.mark.parametrize("what", sorted(NOT_AS_GIVEN))
def test_heads_last_site_of_another_shape_gives_the_heads_first_numbers(what):
    """Two k blocks, a window, grouped K/V or 4 heads of 8: the call
    transposes inside itself and runs the heads-first path: the same bits,
    forward and backward, and `flash.plan` says `layout` bhsd."""
    H, G, Sq, Sk, window = NOT_AS_GIVEN[what]
    B, D = 2, 8 if what == "the_rehearsals_heads" else 64
    rng = np.random.RandomState(58)
    q, g = (jnp.asarray(rng.randn(B, Sq, H * D), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(B, Sk, G * D), jnp.float32)
            for _ in range(2))
    klen = jnp.asarray([Sk, Sk // 3], jnp.float32)
    assert not fa.takes_heads_last(q, k, v, H, window, force="interpret")

    def last(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, k_lengths=klen,
                                 force="interpret", window=window, heads=H)
        return jnp.sum(out * g), out

    def first(q, k, v):
        out = fa.flash_attention(
            fa._heads_first(q, H), fa._heads_first(k, G),
            fa._heads_first(v, G), causal=True, k_lengths=klen,
            force="interpret", window=window)
        return jnp.sum(fa._heads_last(out) * g), fa._heads_last(out)

    spans = _plan_spans(lambda *a: last(*a), q, k, v)
    assert [p["layout"] for p in spans] == ["bhsd"]
    (_, out), grads = jax.value_and_grad(last, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    (_, want), want_grads = jax.value_and_grad(first, argnums=(0, 1, 2),
                                               has_aux=True)(q, k, v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    for x, w in zip(grads, want_grads):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(w))


# name: (B, H, G, S, D, Dv, window) of a decoder cell's attention, then what
# the parent commit (PR 52) planned there: the forward's blocks, the
# backward's, its chunks and its engine.  The windowed site's row is PR
# 59's: the band (`form`), one block length a direction, ONE backward call.
DECODER_CELL_PLANS = {
    "ouro_s2048_head128": (
        (2, 16, 16, 2048, 128, 128, None), (1024, 1024), (512, 512), 1),
    "moonlight_s2048_head192_128": (
        (4, 16, 16, 2048, 192, 128, None), (512, 1024), (512, 512), 1),
    "keye_and_mellum_s16384_full_32_on_4": (
        (1, 32, 4, 16384, 128, 128, None), (1024, 1024), (512, 512), 4),
    "mellum_s16384_window1024_32_on_4": (
        (1, 32, 4, 16384, 128, 128, 1024), (512, 512), (512, 512), 1),
    "zaya_s16384_8_on_2": (
        (1, 8, 2, 16384, 128, 128, None), (1024, 1024), (512, 512), 4),
    "kimi_s4096_head192_128": (
        (1, 32, 32, 4096, 192, 128, None), (512, 1024), (512, 512), 2),
    "xing_s4096_head192_128_four_held_heads": (
        (1, 4, 4, 4096, 192, 128, None), (512, 1024), (512, 512), 2),
}


@pytest.mark.parametrize("case", sorted(DECODER_CELL_PLANS))
def test_the_decoder_cells_plans_are_the_parents(case):
    """Several blocks a head, a window, grouped K/V or chunks: one row a
    step and the blocks, chunks and engine these shapes were given before a
    step could take several rows."""
    (B, H, G, S, D, Dv, window), fwd_blocks, bwd_blocks, chunks = \
        DECODER_CELL_PLANS[case]
    q = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, G, S, D), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((B, G, S, Dv), jnp.bfloat16)
    klen = jax.ShapeDtypeStruct((B,), jnp.float32)
    spans = _plan_spans(lambda q, k, v, klen: fa._pallas_flash(
        q, k, v, klen, True, 0.088, interpret=True, window=window)[0],
        q, k, v, klen)
    form = "blocks" if window is None else "band"
    assert [(p["block_q"], p["block_k"], p["rows_per_step"], p["kv_heads"],
             p["form"]) for p in spans] == [(*fwd_blocks, 1, G, form)]
    plan = fa._bwd_plan(S, S, D, jnp.bfloat16, True, v_dim=Dv, window=window,
                        bh=fa._packable_rows(q, k), group=H // G)
    assert (plan["block_q"], plan["block_k"], plan["chunks"], plan["engine"],
            plan["rows_per_step"], plan["form"]) == (
                *bwd_blocks, chunks, "pallas", 1, form)
    assert fa.kept(q, k, v, True, window, force="pallas") == fa.KEPT


def _step_bwd_spans(spec, feed, name="flash.bwd_plan"):
    """flash.bwd_plan spans (or those called `name`) of one training step
    lowered abstractly for the TPU (nothing compiles or runs)."""
    def lower():
        with fluid.flags.tpu_trace_scope(True):
            compiled, feed_vals, state_vals, rng = fluid.Executor(
                fluid.CPUPlace()).capture_program(
                    fluid.default_main_program(), feed=feed)
            return compiled.raw_fn(feed_vals, state_vals, rng)

    return _spans(name, lower)


def _ouro_step():
    """(spec, feed) of ouro-2.6b's attention shape (S 2048, head 128,
    causal) at a width cut to two heads."""
    from paddle_tpu import models

    fluid.reset_default_env()
    spec = models.looped_decoder(models.LoopedDecoderConfig(
        vocab_size=64, max_length=2048, n_layer=4, n_head=2, head_dim=128,
        d_model=256, d_inner=64, loop_steps=4, exit_gate=True,
        use_recompute=True))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    ids = np.zeros((2, 2049), np.int64)
    tokens, labels = spec.feed_names
    return spec, {tokens: ids[:, :-1], labels: ids[:, 1:]}


def _transformer_base_step():
    """(spec, feed) of transformer-base's three attention shapes (S 256,
    head 64) at a width cut to two heads."""
    from paddle_tpu import models

    fluid.reset_default_env()
    spec = models.transformer(models.TransformerConfig(
        src_vocab_size=64, trg_vocab_size=64, max_length=256, n_layer=6,
        n_head=2, d_model=128, d_inner=64, dropout=0.1, label_smooth_eps=0.1,
        use_flash_attention=True, fuse_qkv=True))
    fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    words = np.ones((2, 256), np.int64)
    return spec, {n: words for n in spec.feed_names}


@functools.lru_cache(maxsize=None)
def _transformer_base_spans():
    """flash.plan, flash.bwd_plan and attn.lower of ONE lowering of the
    transformer-base step (three tests read it; 9 s a lowering)."""
    return _step_bwd_spans(*_transformer_base_step(), name=(
        "flash.plan", "flash.bwd_plan", "attn.lower"))


def test_ouro_body_has_four_pallas_backward_sites():
    """The body's four layers are four sites, lowered once for any trip
    count, and every one takes the Pallas backward."""
    spans = _step_bwd_spans(*_ouro_step())
    assert len(spans) == 4
    want = dict(fa._bwd_plan(2048, 2048, 128, jnp.bfloat16, True),
                kv_heads=2)
    assert want["engine"] == "pallas" and want["steps_skipped"] > 0
    assert all(s == want for s in spans)


@pytest.mark.parametrize("name", ["flash.plan", "flash.bwd_plan"])
def test_transformer_base_has_eighteen_sites_of_several_rows_a_step(name):
    """6 encoder self, 6 decoder self, 6 cross: a head is one 256 x 256
    block and the model hands the op its projections' own arrays, so every
    site's forward and backward are the heads-last kernels' (`layout`
    bshd), several BATCH rows a grid step (here both: B 2 of H 2; the
    cell's 96 of 8 heads go 2 a step, 16 heads, forward and backward), and
    the backward is the Pallas kernel's (PR 53: the XLA recompute kept all
    18 before)."""
    spans = _transformer_base_spans()[name]
    assert len(spans) == 18
    assert {(s["layout"], s["rows_per_step"]) for s in spans} == {("bshd", 2)}
    assert {(s["sq"], s["sk"], s["head_dim"], s["block_q"], s["block_k"])
            for s in spans} == {(256, 256, 64, 256, 256)}
    if name == "flash.bwd_plan":
        assert {s["engine"] for s in spans} == {"pallas"}
        assert {(s["steps"], s["chunks"]) for s in spans} == {(1, 1)}
    else:       # static counts over one head, as they always were
        assert {(s["k_steps"], s["causal"]) for s in spans} == \
            {(1, 0), (1, 1)}
    assert {s["layout"] for s in _transformer_base_spans()["attn.lower"]} \
        == {"bshd"}


def test_transformer_base_hands_attention_its_projections_own_arrays():
    """Between a projection and its fused_attention, and between the op
    and the output projection, the main program holds no transpose2 and no
    reshape2: q, k and v are the fused projection's three slices, [B, S,
    H x 64], and the op says `n_head`."""
    _transformer_base_step()       # builds the program into the default env
    block = fluid.default_main_program().global_block()
    ops = [op for op in block.ops if not op.type.endswith("_grad")]
    sites = [op for op in ops if op.type == "fused_attention"]
    assert len(sites) == 18 and {op.attr("n_head") for op in sites} == {2}
    assert not [op.type for op in block.ops
                if op.type.startswith(("transpose2", "reshape2"))]
    made_by = {name: op.type for op in ops
               for name in op.output_arg_names}
    for op in sites:
        assert {made_by[n] for n in op.input("Q") + op.input("K")
                + op.input("V")} <= {"split", "elementwise_add"}
        assert [len(block.var(n).shape) for n in op.output("Out")] == [3]


@pytest.mark.parametrize("spans, sites, kept", [
    (lambda: _step_bwd_spans(*_ouro_step(), name="attn.lower"), 4,
     ("out,lse", 2 * 2 * 2048 * (128 * 2 + 4))),
    (lambda: _transformer_base_spans()["attn.lower"], 18,
     ("out,lse", 2 * 2 * 256 * (64 * 2 + 4)))],
    ids=["ouro", "transformer_base"])
def test_the_sites_that_keep_are_the_sites_on_the_pallas_backward(
        spans, sites, kept):
    """`attn.lower`'s `kept` / `kept_bytes`, a site: the forward's output
    and logsumexp where the backward is the Pallas kernel: 4 of 4 in
    ouro-2.6b's body and, since a step takes several rows, transformer-base's
    18 (which no recomputed unit surrounds: the tag does nothing there)."""
    assert [(s["kept"], s["kept_bytes"]) for s in spans()] == sites * [kept]

