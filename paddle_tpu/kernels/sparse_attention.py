"""Sparse attention under a learned index (DeepSeek-V3.2-Exp's sparse
attention, here over grouped-query heads), forward AND backward, a chunk of
queries at a time.

One sequence: q [H, S, D] over k, v [G, S, D] (query head j reads key/value
head j // (H / G): K and V are read at their G heads, never repeated), and
the index q_i [Hi, S, Di], k_i [S, Di], w [S, Hi]:

    I[t, s] = sum_j w[t, j] * relu(q_i[j, t] . k_i[s])          s <= t
    S_t     = the `topk` positions s <= t with the largest I[t, s]
              (all of them while t < topk; ties to the lower s), exact
    out     = softmax over s in S_t of scale * q.k, times v
    L_I     = mean over t of KL(p_t || softmax over S_t of I[t, .]),
              p_t the mean over the H heads of the attention's
              probabilities over S_t, detached

The gradient of (out, L_I): q, k, v take theirs from out alone, the index
from L_I alone (p_t is detached and a selection has no gradient), so the
backward is one pass that hands each its own.

No S x S array of any dtype outlives one chunk of `q_chunk` queries: a
lax.scan runs the chunks, forward and backward.  A chunk computes its index
scores against the keys (operands in their dtype, fp32 accumulation; relu,
the head weights and the sum over index heads in fp32), finds each row's
topk-th largest score EXACTLY by a bitwise search over the scores'
order-preserving integer image (32 compare-and-count passes; a sort-sized
lax.top_k on a TPU costs an order more), and hands the attention a
[q_chunk, S] int8 mask.  The chosen SETS are recomputed by the backward,
never saved: the forward keeps each row's threshold (S integers a layer) and
the backward compares the recomputed scores with it (the same kernel on
the same operands gives the same bits).

What survives a layer's recomputation: the forward's out, the rows'
logsumexp and the thresholds (`KEPT`; out's bytes and a little, `kept_bytes`)
are tagged with core.compiler.keep.  Where the layer around the op is a
rematerialised unit (a recurrence's trip under recompute_scope), jax saves
the three at the first forward and the backward's copy of the chunk scan
is dead code: the layer's cheap ops are computed again to hand the
backward q, k, v, q_i, k_i and w, but the index, the search and the attend
kernel run once.  All three or nothing: with one left out the scan still
has to run for it.  An O(S^2) pass for O(S) values, so always, not by
shape.  Outside such a unit the tags do nothing.

The attention is the MASKED BLOCK engine: every [q_chunk, kv_block] score
block with a chosen key is computed on the MXU and masked; a block in which
no query of the chunk chose a key (all of those above the diagonal, and
whatever the index leaves empty) is neither fetched nor computed: two
scalar-prefetched vectors say which blocks live and which block a dead
step should go on holding.  The engine that gathers the chosen keys
instead was measured beside it and not kept (tools/keye_engine_probe.py,
PERF.md PR 33).  The Pallas kernels, a call a chunk each:
- _index_kernel / _index_bwd_kernel: grid (kv blocks): the index's Hi
  products one head after another into one fp32 [q_chunk, kv_block]
  accumulator, and their backward (dq_i and dw stay for the call, dk_i is
  the block's); in plain XLA the [Hi, q_chunk, S] plane of products goes
  through HBM, 537 MB a chunk at the cell's shape.
- _fwd_kernel: grid (G, kv blocks, H / G), the group's heads innermost so a
  K/V block is fetched once for the H / G heads that read it; online
  softmax, out and the rows' logsumexp.
- _bwd_kernel: grid (kv blocks, G, H / G): transposed scores P^T = exp(S^T
  - L), dV += P^T dO, dK += dS^T Q, dQ += dS K into the chunk's whole fp32
  dQ (H x q_chunk x D: 8 MB at 32 x 512 x 128, which is why a chunk and not
  the row bounds it), and the sum over all H heads of P^T, which the index
  loss reads.  With grads=False it is the forward's probability pass alone.
Elsewhere (the CPU) the same chunk is plain jax.numpy and jax.vjp.

Rounding: operands in the input dtype, scores, softmax statistics,
accumulators, the index scores and the loss fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.compiler import keep
from .engine import use_pallas

__all__ = ["sparse_attention", "index_scores", "select_topk", "plan"]

NEG_INF = -1e30
_VMEM_LIMIT = 96 * 1024 * 1024      # of the v5e's 128 MiB


def plan(seq: int, q_chunk: int, kv_chunk: int) -> dict:
    """The tiling of one site, static counts for `dsa.lower`: q_chunk
    queries a chunk, cut to the sequence, and kv_block keys a score block:
    two kv_chunks where the sequence is whole pairs of them (on the chip a
    layer's forward takes 60.7 ms at 1024 keys a block against 79.8 at 512,
    tools/keye_engine_probe.py, PERF.md PR 33: half the grid steps, and a
    step's fixed cost is what a dead block still pays), else one."""
    tq = min(q_chunk, seq)
    tk = min(kv_chunk, seq)
    if seq % tq or seq % tk:
        raise ValueError(f"sequence {seq} is not whole chunks of {tq} "
                         f"queries and {tk} keys")
    if seq % (2 * tk) == 0:
        tk *= 2
    return {"q_chunk": tq, "kv_block": tk}


def index_scores(qi, ki, w):
    """I [Tq, S] fp32 of the index queries qi [Hi, Tq, Di], keys ki [S, Di]
    and head weights w [Tq, Hi] (fp32): sum_j w[t, j] relu(qi[j, t].ki[s]).
    The products are the operands' dtype on the MXU with fp32 accumulation;
    relu, the weights and the sum over heads are fp32 on the VPU (an
    einsum over j would round them to bf16 on a TPU).  A zero comes out as
    +0.0, so that its integer image has one value."""
    s = jax.lax.dot_general(qi, ki, (((2,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    out = jnp.sum(jnp.maximum(s, 0.0)
                  * jnp.swapaxes(w, 0, 1).astype(jnp.float32)[:, :, None],
                  axis=0)
    return jnp.where(out == 0.0, 0.0, out)


def select_topk(scores, valid, k: int, thr=None):
    """(mask [Tq, S] bool, thr [Tq] uint32): for each row the k valid
    positions with the largest score, every valid one where there are at
    most k, ties to the lower position.  Exact: the row's k-th largest
    value is found bit by bit over u, the scores' image in the unsigned
    integers whose order is the floats' (an invalid position images to 0),
    as the largest thr with count(u >= thr) >= k; what lies above it is
    chosen, and of those equal to it the first k - count(u > thr) (a
    cumulative sum, computed only in a chunk where some row has more ties
    than room).  Given `thr` (the backward hands back what the forward
    found for the same scores) the 32 compare-and-count passes are
    skipped."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    u = jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)
    u = jnp.where(valid, u, jnp.uint32(0))

    def bit(i, thr):
        cand = thr | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        count = jnp.sum(u >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(count >= k, cand, thr)

    if thr is None:
        thr = jax.lax.fori_loop(0, 32, bit,
                                jnp.zeros(scores.shape[:1], jnp.uint32))
    above = u > thr[:, None]
    equal = (u == thr[:, None]) & valid
    room = k - jnp.sum(above, axis=1, dtype=jnp.int32, keepdims=True)
    crowded = jnp.any(jnp.sum(equal, axis=1, dtype=jnp.int32,
                              keepdims=True) > room)
    return jax.lax.cond(
        crowded,
        lambda: above | (equal & (jnp.cumsum(equal.astype(jnp.int32), axis=1)
                                  <= room)),
        lambda: (above | equal) & valid), thr


def _chunk_mask(qi_c, ki, w_c, first, topk, tk=None, engine="xla",
                thr=None):
    """(I [Tq, S] fp32, mask [Tq, S] bool, thr [Tq]: select_topk's) of the
    chunk whose first query is at position `first` (traced)."""
    with jax.named_scope("dsa.index"):
        if engine == "xla":
            scores = index_scores(qi_c, ki, w_c)
        else:
            scores = _pallas_index_scores(qi_c, ki, w_c, first, tk,
                                          engine == "interpret")
    with jax.named_scope("dsa.select"):
        t = first + jnp.arange(scores.shape[0], dtype=jnp.int32)[:, None]
        valid = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :] <= t
        return (scores,) + select_topk(scores, valid, topk, thr)


def _index_logq(scores, mask):
    """log softmax of the index scores over the chosen positions (0 where
    not chosen)."""
    z = jnp.where(mask, scores, NEG_INF)
    z = z - jnp.max(z, axis=1, keepdims=True)
    logq = z - jnp.log(jnp.sum(jnp.where(mask, jnp.exp(z), 0.0), axis=1,
                               keepdims=True))
    return jnp.where(mask, logq, 0.0)


def _kl_rows(p, logq, mask):
    """sum over rows of KL(p || q) over the chosen positions."""
    live = mask & (p > 0)
    return jnp.sum(jnp.where(
        live, p * (jnp.log(jnp.where(live, p, 1.0)) - logq), 0.0))


# ---------------------------------------------------------------------------
# the chunk in plain jax.numpy (the CPU's engine, and what the kernels are
# held to)

def _xla_probs(q_c, k, mask, scale):
    """(p [G, r, Tq, S] fp32, lse [G, r, Tq, 1]) of the chunk's masked
    softmax."""
    G = k.shape[0]
    H, tq, d = q_c.shape
    qg = q_c.reshape(G, H // G, tq, d)
    s = jnp.einsum("grtd,gsd->grts", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(e, axis=-1, keepdims=True)
    return e / l, m + jnp.log(l)


def _xla_attend(q_c, k, v, mask, scale):
    """(out [H, Tq, D], lse [H, Tq], the heads' summed probabilities
    [Tq, S])."""
    p, lse = _xla_probs(q_c, k, mask, scale)
    out = jnp.einsum("grts,gsd->grtd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    H, tq, _ = q_c.shape
    return (out.reshape(H, tq, v.shape[-1]).astype(q_c.dtype),
            lse.reshape(H, tq), jnp.sum(p, axis=(0, 1)))


def _xla_attend_bwd(q_c, k, v, mask, scale, do_c):
    def out_of(q_c, k, v):
        out, _, psum = _xla_attend(q_c, k, v, mask, scale)
        return out, psum

    out, vjp, psum = jax.vjp(out_of, q_c, k, v, has_aux=True)
    dq, dk, dv = vjp(do_c.astype(out.dtype))
    return dq, dk.astype(jnp.float32), dv.astype(jnp.float32), psum


# ---------------------------------------------------------------------------
# the chunk on the chip

def _live_blocks(mask, tk):
    """(live [nkb] int32: the block holds a chosen key; fetch [nkb] int32:
    the block a step holds, its own where it lives, else the last live one
    before it, so that a dead step asks the pipeline for nothing new)."""
    tq, s = mask.shape
    live = jnp.any(mask.reshape(tq, s // tk, tk), axis=(0, 2))
    idx = jnp.arange(s // tk, dtype=jnp.int32)
    held = jax.lax.cummax(jnp.where(live, idx, -1))
    first = jnp.argmax(live).astype(jnp.int32)
    return live.astype(jnp.int32), jnp.where(held < 0, first, held)


def _index_kernel(live_ref, qi_ref, ki_ref, w_ref, o_ref):
    """Grid (kv blocks): I[:, block] = sum_j w_j relu(q_j k^T), the Hi
    products one after another into one fp32 [Tq, tk] accumulator (in
    plain XLA the [Hi, Tq, S] plane of products goes through HBM: 537 MB a
    chunk at the cell's shape).  Blocks past the chunk's last query are
    zeros, uncomputed."""
    import jax.experimental.pallas as pl

    kb = pl.program_id(0)

    @pl.when(kb < live_ref[0])
    def _score():
        ki = ki_ref[...]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for j in range(qi_ref.shape[0]):
            s = jax.lax.dot_general(qi_ref[j], ki, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w_ref[j]
        o_ref[...] = jnp.where(acc == 0.0, 0.0, acc)

    @pl.when(kb >= live_ref[0])
    def _dead():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)


def _index_bwd_kernel(live_ref, qi_ref, ki_ref, w_ref, di_ref, dqi_ref,
                      dki_ref, dw_ref):
    """Grid (kv blocks): the index scores' backward for one block of keys,
    head by head: dw_j += rowsum(dI relu(s_j)), dS_j = dI w_j [s_j > 0],
    dq_j += dS_j k, dk += dS_j^T q_j.  dq and dw stay for the whole call,
    dk is the block's."""
    import jax.experimental.pallas as pl

    kb = pl.program_id(0)

    @pl.when(kb == 0)
    def _init():
        dqi_ref[...] = jnp.zeros(dqi_ref.shape, jnp.float32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    @pl.when(kb < live_ref[0])
    def _update():
        ki, di = ki_ref[...], di_ref[...]
        dki = jnp.zeros(dki_ref.shape, jnp.float32)
        for j in range(qi_ref.shape[0]):
            q = qi_ref[j]
            s = jax.lax.dot_general(q, ki, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            dw_ref[j] += jnp.sum(di * jnp.maximum(s, 0.0), axis=1,
                                 keepdims=True)
            ds = jnp.where(s > 0.0, di * w_ref[j], 0.0).astype(q.dtype)
            dqi_ref[j] += jnp.dot(ds, ki, preferred_element_type=jnp.float32)
            dki = dki + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dki_ref[...] = dki

    @pl.when(kb >= live_ref[0])
    def _dead():
        dki_ref[...] = jnp.zeros(dki_ref.shape, jnp.float32)


@functools.lru_cache(maxsize=32)
def _index_call(Hi, S, tq, tk, di, dtype, grads, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def whole(kb, live):
        return (0, 0, 0)

    def keys(kb, live):       # a dead step goes on holding the last live one
        return (jnp.minimum(kb, live[0] - 1), 0)

    in_specs = [pl.BlockSpec((Hi, tq, di), whole),
                pl.BlockSpec((tk, di), keys),
                pl.BlockSpec((Hi, tq, 1), whole)]
    if grads:
        in_specs.append(pl.BlockSpec(
            (tq, tk), lambda kb, live: (0, jnp.minimum(kb, live[0] - 1))))
        out_specs = [pl.BlockSpec((Hi, tq, di), whole),
                     pl.BlockSpec((tk, di), lambda kb, live: (kb, 0)),
                     pl.BlockSpec((Hi, tq, 1), whole)]
        out_shape = [jax.ShapeDtypeStruct((Hi, tq, di), jnp.float32),
                     jax.ShapeDtypeStruct((S, di), jnp.float32),
                     jax.ShapeDtypeStruct((Hi, tq, 1), jnp.float32)]
    else:
        out_specs = [pl.BlockSpec((tq, tk), lambda kb, live: (0, kb))]
        out_shape = [jax.ShapeDtypeStruct((tq, S), jnp.float32)]
    return pl.pallas_call(
        _index_bwd_kernel if grads else _index_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S // tk,), in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _causal_blocks(first, tq, tk):
    """[1] int32: the kv blocks that hold a key some query of the chunk
    starting at `first` may see."""
    return ((first + tq - 1) // tk + 1).astype(jnp.int32).reshape(1)


def _pallas_index_scores(qi_c, ki, w_c, first, tk, interpret):
    Hi, tq, di = qi_c.shape
    (scores,) = _index_call(Hi, ki.shape[0], tq, tk, di, str(qi_c.dtype),
                            False, interpret)(
        _causal_blocks(first, tq, tk), qi_c, ki,
        jnp.swapaxes(w_c, 0, 1)[:, :, None])
    return scores


def _pallas_index_vjp(qi_c, ki, w_c, d_scores, first, tk, interpret):
    Hi, tq, di = qi_c.shape
    dqi, dki, dw = _index_call(Hi, ki.shape[0], tq, tk, di, str(qi_c.dtype),
                               True, interpret)(
        _causal_blocks(first, tq, tk), qi_c, ki,
        jnp.swapaxes(w_c, 0, 1)[:, :, None], d_scores)
    return dqi.astype(qi_c.dtype), dki, jnp.swapaxes(dw[:, :, 0], 0, 1)


def _fwd_kernel(live_ref, fetch_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                lse_ref, m_scr, l_scr, acc_scr, *, scale):
    """Grid (G, kv blocks, r): key/value head g, its kv block, then the r
    query heads that read it.  The online-softmax state of all r heads
    lives in VMEM across the kv blocks; out and lse blocks are the group's
    and leave when g advances."""
    import jax.experimental.pallas as pl

    kb, j = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        # the floor is NEG_INF / 2: a row with no chosen key in the blocks
        # so far keeps exp(NEG_INF - floor) = 0 (kernels/flash_attention.py)
        m_scr[j] = jnp.full(m_scr.shape[1:], NEG_INF / 2, jnp.float32)
        l_scr[j] = jnp.zeros(l_scr.shape[1:], jnp.float32)
        acc_scr[j] = jnp.zeros(acc_scr.shape[1:], jnp.float32)

    @pl.when(live_ref[kb] > 0)
    def _update():
        v = v_ref[0]
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask_ref[...].astype(jnp.int32) != 0, s, NEG_INF)
        m_prev = m_scr[j]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_scr[j] = correction * l_scr[j] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[j] = acc_scr[j] * correction + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[j] = m_new

    @pl.when(kb == pl.num_programs(1) - 1)
    def _finalize():
        l_fin = l_scr[j]
        o_ref[j] = (acc_scr[j] / l_fin).astype(o_ref.dtype)
        lse_ref[j, 0, :] = jnp.transpose(m_scr[j] + jnp.log(l_fin), (1, 0))[0]


@functools.lru_cache(maxsize=32)
def _fwd_call(H, G, S, tq, tk, d, scale, dtype, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = H // G

    def kv_block(g, kb, j, live, fetch):
        return (g, fetch[kb], 0)

    def group(g, kb, j, live, fetch):
        return (g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(G, S // tk, r),
        in_specs=[
            pl.BlockSpec((1, tq, d), lambda g, kb, j, live, fetch:
                         (g * r + j, 0, 0)),
            pl.BlockSpec((1, tk, d), kv_block),
            pl.BlockSpec((1, tk, d), kv_block),
            pl.BlockSpec((tq, tk), lambda g, kb, j, live, fetch:
                         (0, fetch[kb])),
        ],
        out_specs=[pl.BlockSpec((r, tq, d), group),
                   pl.BlockSpec((r, 1, tq), group)],
        scratch_shapes=[pltpu.VMEM((r, tq, 1), jnp.float32),
                        pltpu.VMEM((r, tq, 1), jnp.float32),
                        pltpu.VMEM((r, tq, d), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((H, tq, d), jnp.dtype(dtype)),
                   jax.ShapeDtypeStruct((H, 1, tq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _bwd_kernel(live_ref, fetch_ref, q_ref, k_ref, v_ref, mask_ref, lse_ref,
                *rest, scale, grads):
    """Grid (kv blocks, G, r).  Transposed scores [tk, Tq]: P^T = exp(S^T -
    L) where chosen.  Always: psum^T += P^T over all G x r heads (the
    block stays while g and j run).  With `grads`: dV += P^T dO and dK +=
    dS^T Q over the group's r heads in scratch, written when the last of
    them has run; dQ += dS K into the chunk's whole fp32 dQ, which stays
    for the whole call."""
    import jax.experimental.pallas as pl

    if grads:
        (do_ref, dvec_ref, psum_ref, dq_ref, dk_ref, dv_ref, dk_scr,
         dv_scr) = rest
    else:
        (psum_ref,) = rest
    kb, g, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    r = pl.num_programs(2)
    head = g * r + j
    first_head = jnp.logical_and(g == 0, j == 0)

    @pl.when(first_head)
    def _init_psum():
        psum_ref[...] = jnp.zeros(psum_ref.shape, jnp.float32)

    if grads:
        @pl.when(jnp.logical_and(kb == 0, first_head))
        def _init_dq():
            dq_ref[...] = jnp.zeros(dq_ref.shape, jnp.float32)

        @pl.when(j == 0)
        def _init_dkv():
            dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
            dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(live_ref[kb] > 0)
    def _update():
        q, k = q_ref[0], k_ref[0]
        nt = (((1,), (1,)), ((), ()))            # a @ b.T
        st = jax.lax.dot_general(
            k, q, nt, preferred_element_type=jnp.float32) * scale
        pt = jnp.where(mask_ref[...].astype(jnp.int32) != 0,
                       jnp.exp(st - lse_ref[0]), 0.0)
        psum_ref[...] += pt
        if grads:
            do = do_ref[0]
            dpt = jax.lax.dot_general(v_ref[0], do, nt,
                                      preferred_element_type=jnp.float32)
            dst = (pt * (dpt - dvec_ref[0])).astype(q.dtype)
            dv_scr[...] += jnp.dot(pt.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dk_scr[...] += jnp.dot(dst, q,
                                   preferred_element_type=jnp.float32)
            dq_ref[head] += scale * jax.lax.dot_general(
                dst, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if grads:
        @pl.when(j == r - 1)
        def _write_dkv():
            dk_ref[0] = dk_scr[...] * scale
            dv_ref[0] = dv_scr[...]


@functools.lru_cache(maxsize=32)
def _bwd_call(H, G, S, tq, tk, d, scale, dtype, grads, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = H // G

    def q_block(kb, g, j, live, fetch):
        return (g * r + j, 0, 0)

    def kv_block(kb, g, j, live, fetch):
        return (g, fetch[kb], 0)

    in_specs = [
        pl.BlockSpec((1, tq, d), q_block),
        pl.BlockSpec((1, tk, d), kv_block),
        pl.BlockSpec((1, tk, d), kv_block),
        pl.BlockSpec((tk, tq), lambda kb, g, j, live, fetch:
                     (fetch[kb], 0)),
        pl.BlockSpec((1, 1, tq), q_block),                     # lse
    ]
    out_specs = [pl.BlockSpec((tk, tq), lambda kb, g, j, live, fetch:
                              (kb, 0))]
    out_shape = [jax.ShapeDtypeStruct((S, tq), jnp.float32)]
    scratch = []
    if grads:
        in_specs += [pl.BlockSpec((1, tq, d), q_block),        # dO
                     pl.BlockSpec((1, 1, tq), q_block)]        # D
        out_specs += [
            pl.BlockSpec((H, tq, d), lambda kb, g, j, live, fetch:
                         (0, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda kb, g, j, live, fetch:
                         (g, kb, 0)),
            pl.BlockSpec((1, tk, d), lambda kb, g, j, live, fetch:
                         (g, kb, 0))]
        out_shape += [jax.ShapeDtypeStruct((H, tq, d), jnp.float32),
                      jax.ShapeDtypeStruct((G, S, d), jnp.float32),
                      jax.ShapeDtypeStruct((G, S, d), jnp.float32)]
        scratch = [pltpu.VMEM((tk, d), jnp.float32),
                   pltpu.VMEM((tk, d), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, grads=grads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S // tk, G, r),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _pallas_attend(q_c, k, v, mask, scale, tk, interpret):
    """(out, lse [H, Tq], the heads' summed probabilities [Tq, S])."""
    H, tq, d = q_c.shape
    G, S, _ = k.shape
    live, fetch = _live_blocks(mask, tk)
    out, lse = _fwd_call(H, G, S, tq, tk, d, scale, str(q_c.dtype),
                         interpret)(live, fetch, q_c, k, v,
                                    mask.astype(jnp.int8))
    (psum_t,) = _bwd_call(H, G, S, tq, tk, d, scale, str(q_c.dtype), False,
                          interpret)(live, fetch, q_c, k, v,
                                     mask.T.astype(jnp.int8), lse)
    return out, lse[:, 0], psum_t.T


def _pallas_attend_bwd(q_c, k, v, mask, scale, do_c, out_c, lse_c, tk,
                       interpret):
    H, tq, d = q_c.shape
    G, S, _ = k.shape
    live, fetch = _live_blocks(mask, tk)
    dvec = jnp.sum(do_c.astype(jnp.float32) * out_c.astype(jnp.float32),
                   axis=-1)
    psum_t, dq, dk, dv = _bwd_call(
        H, G, S, tq, tk, d, scale, str(q_c.dtype), True, interpret)(
            live, fetch, q_c, k, v, mask.T.astype(jnp.int8),
            lse_c[:, None], do_c.astype(q_c.dtype), dvec[:, None])
    return dq.astype(q_c.dtype), dk, dv, psum_t.T


# ---------------------------------------------------------------------------
# the whole sequence: a scan over chunks, differentiated by hand

def _chunks(x, axis, n):
    """x with `axis` cut into n chunks, the chunk index first."""
    shape = x.shape[:axis] + (n, x.shape[axis] // n) + x.shape[axis + 1:]
    return jnp.moveaxis(x.reshape(shape), axis, 0)


def _unchunk(x, axis):
    """The inverse of _chunks."""
    x = jnp.moveaxis(x, 0, axis)
    return x.reshape(x.shape[:axis] + (-1,) + x.shape[axis + 2:])


def _forward(q, k, v, qi, ki, w, cfg):
    topk, scale, tq, tk, engine = cfg
    S = q.shape[1]
    n = S // tq

    def chunk(kl, xs):
        c, q_c, qi_c, w_c = xs
        scores, mask, thr = _chunk_mask(qi_c, ki, w_c, c * tq, topk, tk,
                                        engine)
        with jax.named_scope("dsa.attend"):
            if engine == "xla":
                out, lse, psum = _xla_attend(q_c, k, v, mask, scale)
            else:
                out, lse, psum = _pallas_attend(
                    q_c, k, v, mask, scale, tk, engine == "interpret")
        with jax.named_scope("dsa.kl"):
            kl = kl + _kl_rows(psum / q.shape[0], _index_logq(scores, mask),
                               mask)
        return kl, (out, lse, thr)

    kl, (out, lse, thr) = jax.lax.scan(
        chunk, jnp.float32(0),
        (jnp.arange(n, dtype=jnp.int32), _chunks(q, 1, n), _chunks(qi, 1, n),
         _chunks(w, 0, n)))
    return _unchunk(out, 1), _unchunk(lse, 1), kl / S, thr


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _sparse_attention(q, k, v, qi, ki, w, cfg):
    out, _, kl, _ = _forward(q, k, v, qi, ki, w, cfg)
    return out, kl


KEPT = ("out", "lse", "thr")   # of _forward, what _sparse_attention_fwd keeps


def kept_bytes(q) -> int:
    """What a site keeps through its layer's recomputation, of q
    [B, H, S, D]: out in q's dtype, the rows' logsumexp fp32 and a
    threshold a token."""
    B, H, S, D = q.shape
    return B * S * (H * D * q.dtype.itemsize + H * 4 + 4)


def _sparse_attention_fwd(q, k, v, qi, ki, w, cfg):
    out, lse, kl, thr = _forward(q, k, v, qi, ki, w, cfg)
    out, lse, thr = keep(out, lse, thr)
    return (out, kl), (q, k, v, qi, ki, w, out, lse, thr)


def _sparse_attention_bwd(cfg, res, cotangents):
    """One pass over the chunks: each recomputes its index scores and, from
    the thresholds the forward found (S integers; the scores are the same
    kernel's on the same operands, bit for bit), its chosen set; the
    attention's backward gives dQ of the chunk, the
    chunk's share of dK and dV (summed in the carry) and the heads' summed
    probabilities; d L_I / d I = (softmax_I - p) / S over the chosen keys
    goes back through the index scores to q_i, k_i (summed in the carry)
    and w."""
    topk, scale, tq, tk, engine = cfg
    q, k, v, qi, ki, w, out, lse, thr = res
    d_out, d_kl = cotangents
    H, S, _ = q.shape
    n = S // tq

    def chunk(carry, xs):
        dk, dv, dki = carry
        c, q_c, qi_c, w_c, do_c, out_c, lse_c, thr_c = xs
        scores, mask, _ = _chunk_mask(qi_c, ki, w_c, c * tq, topk, tk,
                                      engine, thr_c)
        with jax.named_scope("dsa.attend"):
            if engine == "xla":
                dq_c, dk_c, dv_c, psum = _xla_attend_bwd(
                    q_c, k, v, mask, scale, do_c)
            else:
                dq_c, dk_c, dv_c, psum = _pallas_attend_bwd(
                    q_c, k, v, mask, scale, do_c, out_c, lse_c, tk,
                    engine == "interpret")
        with jax.named_scope("dsa.kl"):
            soft = jnp.where(mask, jnp.exp(_index_logq(scores, mask)), 0.0)
            d_scores = (d_kl / S) * (soft - jnp.where(mask, psum / H, 0.0))
        with jax.named_scope("dsa.index"):
            if engine == "xla":
                dqi_c, dki_c, dw_c = jax.vjp(index_scores, qi_c, ki, w_c)[1](
                    d_scores)
            else:
                dqi_c, dki_c, dw_c = _pallas_index_vjp(
                    qi_c, ki, w_c, d_scores, c * tq, tk,
                    engine == "interpret")
        return ((dk + dk_c, dv + dv_c, dki + dki_c.astype(jnp.float32)),
                (dq_c, dqi_c, dw_c))

    zeros = jnp.zeros(k.shape, jnp.float32)
    (dk, dv, dki), (dq, dqi, dw) = jax.lax.scan(
        chunk, (zeros, zeros, jnp.zeros(ki.shape, jnp.float32)),
        (jnp.arange(n, dtype=jnp.int32), _chunks(q, 1, n), _chunks(qi, 1, n),
         _chunks(w, 0, n), _chunks(d_out, 1, n), _chunks(out, 1, n),
         _chunks(lse, 1, n), thr))
    return (_unchunk(dq, 1), dk.astype(k.dtype), dv.astype(v.dtype),
            _unchunk(dqi, 1), dki.astype(ki.dtype), _unchunk(dw, 0))


_sparse_attention.defvjp(_sparse_attention_fwd, _sparse_attention_bwd)


def sparse_attention(q, k, v, qi, ki, w, topk: int, scale: float,
                     q_chunk: int = 512, kv_chunk: int = 512,
                     force: str = "auto"):
    """(out [B, H, S, D], L_I fp32 scalar) of q [B, H, S, D], k and v
    [B, G, S, D], the index's qi [B, Hi, S, Di], ki [B, S, Di] and w
    [B, S, Hi] (module docstring; L_I the mean over all B x S tokens).
    `force`: kernels/engine.py's door (the masked block kernels, compiled
    for a TPU or interpreted; jax.numpy elsewhere).  The op reads no mesh
    (ROADMAP D25): on several devices the kernels are XLA's to
    partition."""
    engine = ("interpret" if force == "interpret"
              else "pallas" if use_pallas(force) else "xla")
    B, _, S, _ = q.shape
    p = plan(S, q_chunk, kv_chunk)
    cfg = (int(topk), float(scale), p["q_chunk"], p["kv_block"], engine)
    outs, kls = zip(*(
        _sparse_attention(q[b], k[b], v[b], qi[b], ki[b],
                          w[b].astype(jnp.float32), cfg) for b in range(B)))
    return jnp.stack(outs), sum(kls) / B
