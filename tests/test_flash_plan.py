"""The flash forward's grid planned from the shape (PR 28): blocks from
_plan_blocks, causal blocks above the diagonal neither fetched nor computed,
the mask only where a block can be cut.  Everything here runs the kernel
through the Pallas interpreter on the CPU: values and counts, never times.
"""

import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability

# the kernels package re-exports the flash_attention FUNCTION under the
# same name as its module; go through importlib for the module itself
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


def _qkv(seed, B, H, Sq, Sk, D, dtype):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(B, H, Sq, D), dtype),
            jnp.asarray(rng.randn(B, H, Sk, D), dtype),
            jnp.asarray(rng.randn(B, H, Sk, D), dtype))


def _plan_spans(fn, *args):
    """The flash.plan spans that lowering `fn` leaves (abstractly: nothing
    compiles or runs)."""
    observability.reset()
    was = fluid.flags._VALUES["FLAGS_observability"]
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        jax.eval_shape(fn, *args)
        return [s.args for s in observability.default_tracer().spans()
                if s.name == "flash.plan"]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = was
        observability.reset()


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
        assert fa._skipped_k_steps(nqb, nkb, bq, bk, sk - sq) == \
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
                              causal=1)]


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
            bq, bk, d, -(-sq // bq), dtype, emit_lse) <= fa._PLAN_VMEM_BUDGET
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


# (c) the backward follows the forward's plan --------------------------------

@pytest.mark.parametrize("backward", ["jax", "pallas"])
def test_gradient_matches_reference_at_a_planned_block_q(backward,
                                                         monkeypatch):
    """The packed lse plane [B*H, nqb, block_q] is laid out by the forward's
    plan; the dq/dkv kernels read block_q off it."""
    B, H, S, D = 1, 2, 256, 64
    assert fa._plan_blocks(S, S, D, jnp.float32, True, True)[0] == 256
    if backward == "jax":   # force="interpret" alone always picks pallas
        monkeypatch.setattr(fa, "_pallas_bwd_enabled", lambda force: False)
    q, k, v = _qkv(11, B, H, S, S, D, jnp.float32)
    klen = jnp.asarray([200.0])
    w = jnp.asarray(np.random.RandomState(12).randn(B, H, S, D), jnp.float32)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * w)

    got = jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, k_lengths=klen, force="interpret")),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: fa._reference_attention(
        q, k, v, True, 1.0 / np.sqrt(D), k_lengths=klen.astype(jnp.int32))),
        argnums=(0, 1, 2))(q, k, v)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_), rtol=2e-4,
                                   atol=2e-5)


def test_pallas_backward_reads_block_q_off_the_packed_lse():
    q, k, v = _qkv(13, 1, 1, 256, 256, 64, jnp.float32)
    klen = jnp.full((1,), 256, jnp.float32)
    out, lse = fa._pallas_flash(q, k, v, klen, True, 0.125, interpret=True)
    assert lse.shape == (1, 1, 256)         # one q-block of the planned 256
    out128, lse128 = fa._pallas_flash(q, k, v, klen, True, 0.125,
                                      block_q=128, block_k=128,
                                      interpret=True)
    assert lse128.shape == (1, 2, 128)
    np.testing.assert_allclose(np.asarray(lse).reshape(-1),
                               np.asarray(lse128).reshape(-1), rtol=1e-5)
    g = jnp.ones_like(out)
    for o, l in ((out, lse), (out128, lse128)):
        grads = fa._pallas_flash_bwd(q, k, v, klen, o, l, g, True, 0.125,
                                     interpret=True)
        _, vjp = jax.vjp(lambda q, k, v: fa._reference_attention(
            q, k, v, True, 0.125), q, k, v)
        for got, want in zip(grads, vjp(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-5)
