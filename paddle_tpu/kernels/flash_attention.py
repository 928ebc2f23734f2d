"""Flash attention for TPU (Pallas), forward AND backward.

The reference computes attention as separate matmul/softmax/matmul ops
(python/paddle/fluid/nets.py scaled_dot_product_attention), materializing
the [Sq, Sk] score matrix in HBM.  The forward kernel streams K/V blocks
through VMEM with the online-softmax recurrence (Dao et al.,
FlashAttention), so HBM traffic stays O(S*D) and the MXU sees back-to-back
block matmuls.

The backward is the FlashAttention-2 recipe in two Pallas kernels — a
round-3 change driven by a chip profile (today: `python3 benchmark/run.py
--workload transformer-train --trace 1`) showing the
previous recompute-with-dense-jax backward's softmax-gradient elementwise
chains dominating transformer step time:
- forward additionally emits the per-row logsumexp L;
- dQ kernel: grid (BH, q-blocks, k-blocks), rebuilds P = exp(S - L) per
  block and accumulates dQ = sum_k (P*(dP - D))*scale @ K in VMEM scratch;
- dK/dV kernel: grid (BH, k-blocks, q-blocks), accumulates
  dK = sum_q dS^T Q and dV = sum_q P^T dO;
- D = rowsum(dO * O) is a cheap fused elementwise pass outside the kernels.
Zero-padded dO rows make padded q rows contribute exactly zero to dK/dV,
and the same key-padding/causal masks as forward zero padded k columns.

Backward selection (FLAGS_flash_bwd): "jax" (default) differentiates the
reference formulation under jax.vjp — a recompute backward XLA fuses well;
"pallas" uses the dq/dkv kernels.  The default stays jax: the two have not
yet been compared on a chip (ROADMAP D7); the kernels are
correctness-tested in interpret mode and compile for v5e
(tests/test_aot_cost.py).  pallas_call instances are memoized by
static config so the 3 distinct attention shapes of an 18-block
transformer serialize to 3 kernel payloads, not 54.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["flash_attention", "fwd_vmem_bytes"]

NEG_INF = -1e30


def fwd_vmem_bytes(block_q: int = 128, block_k: int = 128,
                   head_dim: int = 128, num_q_blocks: int = 1,
                   dtype="float32", emit_lse: bool = True) -> int:
    """Analytic VMEM working set of ONE forward pallas invocation — the
    kernel's own statement of the linter's pricing model
    (paddle_tpu.analysis.pallas.kernel_vmem_bytes; tests hold the two
    equal on the traced call): the double-buffered padded q/k/v/o
    blocks (+ the packed lse plane when emitted) plus the fp32
    online-softmax scratch.  The SMEM klen vector is outside VMEM.
    Default blocks at d=128 sit near 0.5 MB — an order of magnitude
    under the v5e budget, which is why this kernel never needed a tile
    planner (conv_epilogue._plan is the shape that does)."""
    from ..analysis.pallas import tile_padded_bytes

    blocks = [
        ((1, block_q, head_dim), dtype),   # q
        ((1, block_k, head_dim), dtype),   # k
        ((1, block_k, head_dim), dtype),   # v
        ((1, block_q, head_dim), dtype),   # o
    ]
    if emit_lse:
        blocks.append(((1, num_q_blocks, block_q), "float32"))
    scratch = [((block_q, 1), "float32"), ((block_q, 1), "float32"),
               ((block_q, head_dim), "float32")]
    return (2 * sum(tile_padded_bytes(s, d) for s, d in blocks)
            + sum(tile_padded_bytes(s, d) for s, d in scratch))

# The per-row logsumexp/D residuals are PACKED: [B*H, num_q_blocks,
# block_q] fp32, row qi of the packed plane holding q-block qi's
# per-row scalars on the 128 lanes.  TPU pallas rejects blocks whose
# last two dims are neither (8k, 128k)-tiled nor equal to the array
# dims, so a [B*H, Sq] residual with block (1, block_q) cannot lower
# (chip-only failure) — the round-5 fix broadcast the scalars across a
# full 128-lane register instead ([B*H, Sqp, 128] fp32, ~67 MB/tensor at
# the longcontext shape, 128x the payload, and XLA does NOT fuse that
# broadcast away: it materializes as custom-call operands).  The packed
# layout is exact-size ((8,128)-tiled with no replication); each kernel
# step reads its (block_q,) row and transposes it to the [block_q, 1]
# column the softmax math wants — one register-level lane->sublane
# transpose per grid step buys a 128x smaller HBM residual.


def _packed_col(ref, qi):
    """[block_q, 1] column for q-block qi from a packed residual ref
    (block shape [1, num_q_blocks, block_q])."""
    row = ref[0, qi, :].reshape(1, -1)
    return jnp.transpose(row, (1, 0))


def _reference_attention(q, k, v, causal, scale, bias=None, k_lengths=None):
    """Pure-jax attention (fallback + backward recompute).
    q: [B, H, Sq, D], k/v: [B, H, Sk, D], k_lengths: [B] valid key counts."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if bias is not None:
        scores = scores + bias
    if k_lengths is not None:
        kmask = jnp.arange(scores.shape[-1])[None, :] < k_lengths[:, None]
        scores = jnp.where(kmask[:, None, None, :], scores, NEG_INF)
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        scores = jnp.where(mask, scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    # fully-masked rows (padded queries) produce zeros, not uniform weights
    all_masked = jnp.max(scores, axis=-1, keepdims=True) <= NEG_INF / 2
    weights = jnp.where(all_masked, 0.0, weights)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def _block_mask(klen_ref, bi, qi, ki, shape, block_q, block_k, seq_k,
                causal, causal_offset):
    """Key-padding (+ causal) mask for score block (qi, ki) of batch row
    bi — identical in forward and backward."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = k_pos < jnp.minimum(seq_k, klen_ref[bi].astype(jnp.int32))
    if causal:
        # bottom-right alignment (matches jnp.tril(k=Sk-Sq)): with cached
        # keys (Sk > Sq) a query at row i sees keys up to i + Sk - Sq
        mask &= k_pos <= q_pos + causal_offset
    return mask


def _flash_kernel(klen_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr,
                  *, causal, scale, block_q, block_k, seq_k, causal_offset):
    """Grid: (batch*heads, num_q_blocks, num_k_blocks); K innermost so the
    online-softmax state lives in VMEM scratch across K steps.  klen_ref
    (SMEM) holds every batch row's valid key count (key-padding mask),
    indexed by program_id(0).  Emits O and the per-row logsumexp L
    (backward residual)."""
    import jax.experimental.pallas as pl

    bi = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_kb = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        # the running-max floor is NEG_INF/2, NOT NEG_INF: a fully-masked
        # row keeps m at the floor, so p = exp(NEG_INF - NEG_INF/2)
        # underflows to exactly 0 and l stays 0 (with an m floor of
        # NEG_INF itself, masked entries would give exp(0) = 1 and the
        # row would silently average V).  Any real score is far above
        # the floor, so normal rows are unaffected.
        m_scr[:] = jnp.full_like(m_scr, NEG_INF / 2)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # [block_q, D]
    k = k_ref[0]  # [block_k, D]
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    mask = _block_mask(klen_ref, bi, qi, ki, s.shape, block_q, block_k,
                       seq_k, causal, causal_offset)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:]  # [block_q, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    correction = jnp.exp(m_prev - m_new)
    l_new = correction * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * correction + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_scr[:] = m_new
    l_scr[:] = l_new

    @pl.when(ki == num_kb - 1)
    def _finalize():
        l_fin = l_scr[:]
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)
        # logsumexp per row; fully-masked rows get +inf-ish so backward's
        # exp(S - L) underflows to zero instead of NaN
        if lse_ref is not None:  # static: absent on the fwd-only variant
            lse = jnp.where(
                l_fin > 0.0, m_scr[:] + jnp.log(jnp.maximum(l_fin, 1e-30)),
                -NEG_INF,
            )
            # packed residual layout (module comment at NEG_INF): the
            # [block_q, 1] column transposes to q-block qi's row of the
            # [1, num_q_blocks, block_q] block — exact-size, no lane
            # replication
            lse_ref[0, qi, :] = jnp.transpose(lse, (1, 0))[0]


def _flash_bwd_dq_kernel(klen_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         dvec_ref, dq_ref, acc_scr,
                         *, causal, scale, block_q, block_k, seq_k,
                         causal_offset):
    """dQ: grid (BH, num_q_blocks, num_k_blocks), K innermost; the dQ
    accumulator for one q block stays in VMEM across all K blocks."""
    import jax.experimental.pallas as pl

    bi = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_kb = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = _packed_col(lse_ref, qi)    # [block_q, 1] (packed residual)
    dvec = _packed_col(dvec_ref, qi)  # [block_q, 1]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    mask = _block_mask(klen_ref, bi, qi, ki, s.shape, block_q, block_k,
                       seq_k, causal, causal_offset)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - dvec) * scale
    acc_scr[:] = acc_scr[:] + jnp.dot(
        ds.astype(k.dtype), k, preferred_element_type=jnp.float32
    )

    @pl.when(ki == num_kb - 1)
    def _finalize():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(klen_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          dvec_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                          *, causal, scale, block_q, block_k, seq_k,
                          causal_offset):
    """dK/dV: grid (BH, num_k_blocks, num_q_blocks), Q innermost; the
    dK/dV accumulators for one k block stay in VMEM across all Q blocks."""
    import jax.experimental.pallas as pl

    bi = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    num_qb = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = _packed_col(lse_ref, qi)
    dvec = _packed_col(dvec_ref, qi)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    mask = _block_mask(klen_ref, bi, qi, ki, s.shape, block_q, block_k,
                       seq_k, causal, causal_offset)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - dvec) * scale
    dk_scr[:] = dk_scr[:] + jnp.dot(
        ds.T.astype(q.dtype), q, preferred_element_type=jnp.float32
    )
    dv_scr[:] = dv_scr[:] + jnp.dot(
        p.T.astype(do.dtype), do, preferred_element_type=jnp.float32
    )

    @pl.when(qi == num_qb - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pad_seq(x, to):
    pad = (to - x.shape[2] % to) % to
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x


def _flash_kernel_fwd_only(klen_ref, q_ref, k_ref, v_ref, o_ref,
                           m_scr, l_scr, acc_scr, **kw):
    """Inference / recompute-backward variant: no lse output ref — the
    lane-broadcast lse write is pure wasted HBM traffic when nothing
    consumes it (the workloads sit at the HBM roofline)."""
    _flash_kernel(klen_ref, q_ref, k_ref, v_ref, o_ref, None,
                  m_scr, l_scr, acc_scr, **kw)


@functools.lru_cache(maxsize=128)
def _fwd_call(bh, sqp, skp, d, bq, bk, causal, scale, seq_k,
              causal_offset, dtype, interpret, emit_lse=True):
    """Memoized pallas_call: every attention site with the same static
    config reuses ONE traced callable, so XLA sees identical kernel
    payloads (compile-cache friendly) instead of per-site clones.
    emit_lse=False drops the lse output entirely (see
    _flash_kernel_fwd_only)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel = _flash_kernel if emit_lse else _flash_kernel_fwd_only
    nqb = sqp // bq
    out_specs = [pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, sqp, d), jnp.dtype(dtype))]
    if emit_lse:
        # packed lse: one [nqb, bq] plane per batch-head row, revisited
        # across q/k steps and flushed when b advances
        out_specs.append(
            pl.BlockSpec((1, nqb, bq), lambda b, i, j: (b, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((bh, nqb, bq), jnp.float32))
    return pl.pallas_call(
        functools.partial(
            kernel, causal=causal, scale=scale, block_q=bq,
            block_k=bk, seq_k=seq_k, causal_offset=causal_offset,
        ),
        grid=(bh, sqp // bq, skp // bk),
        in_specs=[
            # whole [B*H] vector in SMEM, indexed by program_id(0) in-kernel
            # (TPU rejects rank-1 blocks smaller than the 128 tile)
            pl.BlockSpec((bh,), lambda b, i, j: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )


def _pallas_flash(q, k, v, klen, causal, scale, block_q=128, block_k=128,
                  interpret=False, need_lse=True):
    """Returns (out [B,H,Sq,D], lse [B*H, num_q_blocks, block_q] fp32
    per-row logsumexp in the PACKED residual layout — see the module
    comment; _pallas_flash_bwd consumes it as-is).  need_lse=False
    (inference / the recompute-jax backward) skips the lse output
    entirely — its HBM write is pure waste when nothing consumes it —
    and returns (out, None)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    # pad sequence dims to block multiples (masked in-kernel)
    q = _pad_seq(q, bq)
    k = _pad_seq(k, bk)
    v = _pad_seq(v, bk)
    qf = q.reshape(B * H, q.shape[2], D)
    kf = k.reshape(B * H, k.shape[2], D)
    vf = v.reshape(B * H, v.shape[2], D)
    klen_bh = jnp.repeat(klen, H)  # [B*H] valid key counts

    call = _fwd_call(B * H, qf.shape[1], kf.shape[1], D, bq, bk, causal,
                     scale, Sk, Sk - Sq, str(q.dtype), interpret,
                     emit_lse=need_lse)
    res = call(klen_bh, qf, kf, vf)  # list: [out] or [out, lse]
    out = res[0].reshape(B, H, res[0].shape[1], D)
    if out.shape[2] != Sq:
        out = out[:, :, :Sq]
    if not need_lse:
        return out, None
    return out, res[1]  # packed [B*H, nqb, bq]; the bwd reads it as-is


@functools.lru_cache(maxsize=128)
def _bwd_calls(bh, sqp, skp, d, bq, bk, causal, scale, seq_k,
               causal_offset, q_dtype, k_dtype, v_dtype, interpret):
    """Memoized (dq_call, dkv_call) pair — see _fwd_call."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    common = dict(causal=causal, scale=scale, block_q=bq, block_k=bk,
                  seq_k=seq_k, causal_offset=causal_offset)
    smem = pl.BlockSpec((bh,), lambda *_: (0,), memory_space=pltpu.SMEM)
    nqb = sqp // bq
    # packed lse/dvec residuals: the whole (tiny) [nqb, bq] plane for
    # batch-head row b rides in VMEM; kernels read their q-block's row
    packed = pl.BlockSpec((1, nqb, bq), lambda b, *_: (b, 0, 0))

    dq_call = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(bh, sqp // bq, skp // bk),
        in_specs=[
            smem,
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            packed,
            packed,
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, d), jnp.dtype(q_dtype)),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )

    dkv_call = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(bh, skp // bk, sqp // bq),
        in_specs=[
            smem,
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            packed,
            packed,
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skp, d), jnp.dtype(k_dtype)),
            jax.ShapeDtypeStruct((bh, skp, d), jnp.dtype(v_dtype)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )
    return dq_call, dkv_call


def _pallas_flash_bwd(q, k, v, klen, out, lse, g, causal, scale,
                      block_q=128, block_k=128, interpret=False):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    qp = _pad_seq(q, bq)
    op = _pad_seq(out, bq)
    gp = _pad_seq(g, bq)  # zero-padded dO rows contribute nothing to dK/dV
    kp = _pad_seq(k, bk)
    vp = _pad_seq(v, bk)
    Sqp, Skp = qp.shape[2], kp.shape[2]
    qf = qp.reshape(B * H, Sqp, D)
    of = op.reshape(B * H, Sqp, D)
    gf = gp.reshape(B * H, Sqp, D).astype(qf.dtype)
    kf = kp.reshape(B * H, Skp, D)
    vf = vp.reshape(B * H, Skp, D)
    klen_bh = jnp.repeat(klen, H)
    # D_i = rowsum(dO * O): one fused elementwise+reduce pass, fp32,
    # reshaped (a free, layout-preserving view) straight into the packed
    # [B*H, nqb, bq] residual layout the kernels index — no lane
    # broadcast ever materializes (the old [B*H, Sqp, 128] operands were
    # 128x the payload and did NOT fuse away: custom-call operands are
    # materialized in HBM)
    dvec = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    dvec = dvec.reshape(B * H, Sqp // bq, bq)

    dq_call, dkv_call = _bwd_calls(
        B * H, Sqp, Skp, D, bq, bk, causal, scale, Sk, Sk - Sq,
        str(q.dtype), str(k.dtype), str(v.dtype), interpret,
    )
    dq = dq_call(klen_bh, qf, kf, vf, gf, lse, dvec)
    dk, dv = dkv_call(klen_bh, qf, kf, vf, gf, lse, dvec)

    dq = dq.reshape(B, H, Sqp, D)[:, :, :Sq]
    dk = dk.reshape(B, H, Skp, D)[:, :, :Sk]
    dv = dv.reshape(B, H, Skp, D)[:, :, :Sk]
    return dq, dk, dv


def _on_tpu() -> bool:
    """True when the program being traced is for a TPU: the attached
    device is one, or an Executor opened the TPU trace scope for a
    chip-less compile (cost_analysis(platform="tpu"), the lowering gate,
    analysis capture) — so that what those compile is the chip's program,
    kernels included."""
    from .. import flags

    return flags.tpu_trace_active() or jax.devices()[0].platform == "tpu"


def _use_pallas(force: str) -> bool:
    return force == "pallas" or (force == "auto" and _on_tpu())


def _pallas_bwd_enabled(force: str) -> bool:
    """The dq/dkv kernels run in backward only when asked: force
    'interpret' (CPU correctness tests) or FLAGS_flash_bwd=pallas.  The
    default is the recompute-jax backward (module docstring)."""
    if force == "interpret":
        return True
    if force == "jax":
        return False
    from .. import flags

    return flags.flag("flash_bwd") == "pallas"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, klen, causal, scale, force):
    # klen rides as float32 so custom_vjp treats it uniformly (zero grad)
    if _use_pallas(force):
        return _pallas_flash(q, k, v, klen, causal, scale,
                             need_lse=False)[0]
    if force == "interpret":
        return _pallas_flash(q, k, v, klen, causal, scale, interpret=True,
                             need_lse=False)[0]
    return _reference_attention(
        q, k, v, causal, scale, k_lengths=klen.astype(jnp.int32)
    )


def _flash_fwd(q, k, v, klen, causal, scale, force):
    if _use_pallas(force) or force == "interpret":
        interp = force == "interpret"
        need = _pallas_bwd_enabled(force)
        out, lse = _pallas_flash(q, k, v, klen, causal, scale,
                                 interpret=interp, need_lse=need)
        if need:
            return out, (q, k, v, klen, out, lse)
        # recompute-jax backward: don't hold O/L as residuals (and the
        # forward call above skipped the lse HBM write entirely)
        return out, (q, k, v, klen, None, None)
    out = _reference_attention(
        q, k, v, causal, scale, k_lengths=klen.astype(jnp.int32)
    )
    return out, (q, k, v, klen, None, None)


def _flash_bwd(causal, scale, force, res, g):
    q, k, v, klen, out, lse = res
    if lse is not None:
        dq, dk, dv = _pallas_flash_bwd(
            q, k, v, klen, out, lse, g, causal, scale,
            interpret=(force == "interpret"),
        )
        return dq, dk, dv, jnp.zeros_like(klen)
    # recompute-backward: differentiate the reference formulation
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _reference_attention(
            q_, k_, v_, causal, scale, k_lengths=klen.astype(jnp.int32)
        ),
        q, k, v,
    )
    dq, dk, dv = vjp(g)
    return dq, dk, dv, jnp.zeros_like(klen)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _jaxlib_flash(q, k, v, k_lengths, causal, scale):
    """Route through the jax-shipped TPU pallas flash attention
    (jax.experimental.pallas.ops.tpu.flash_attention) — a maintained
    fwd+bwd kernel pair with its own custom_vjp.  Selected by
    FLAGS_flash_bwd=jaxlib on TPU: an alternative to this module's
    hand-written backward (tools/flash_bwd_probe.py stage 4 compares
    them)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, SegmentIds, flash_attention as jx_flash)

    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    seg = None
    if k_lengths is not None:
        kl = jnp.asarray(k_lengths, jnp.int32).reshape(-1)
        # key-padding semantics: q rows all live (segment 1), padded key
        # positions get segment 2 -> mismatch masks them, matching this
        # module's klen contract
        kvseg = jnp.where(
            jnp.arange(Sk)[None, :] < kl[:, None], 1, 2
        ).astype(jnp.int32)
        seg = SegmentIds(q=jnp.ones((B, Sq), jnp.int32), kv=kvseg)
    bs = BlockSizes.get_default(B, H, Sq, Sk, D)
    return jx_flash(q, k, v, segment_ids=seg, causal=causal,
                    sm_scale=float(scale), block_sizes=bs)


def flash_attention(q, k, v, causal=False, scale=None, k_lengths=None,
                    force="auto"):
    """q/k/v: [B, H, S, D].  k_lengths: optional [B] valid key counts
    (key-padding mask).

    force: "auto" (pallas on TPU, jax elsewhere), "pallas", "interpret"
    (pallas interpreter — CPU testing), or "jax".  Under force="auto" on
    TPU, FLAGS_flash_bwd=jaxlib swaps in the jax-shipped kernel pair
    (fwd AND bwd) instead of this module's kernels."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if force == "auto" and _on_tpu():
        from .. import flags

        if flags.flag("flash_bwd") == "jaxlib":
            return _jaxlib_flash(q, k, v, k_lengths, causal, scale)
    if k_lengths is None:
        klen = jnp.full((q.shape[0],), k.shape[2], dtype=jnp.float32)
    else:
        klen = jnp.asarray(k_lengths, dtype=jnp.float32).reshape(-1)
    return _flash(q, k, v, klen, causal, float(scale), force)
