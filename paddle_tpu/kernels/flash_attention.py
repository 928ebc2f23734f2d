"""Flash attention for TPU (Pallas), forward AND backward.

K/V blocks stream through VMEM under the online-softmax recurrence (Dao et
al., FlashAttention): HBM traffic is O(S D) and nothing score-shaped leaves
VMEM (the reference, fluid/nets.py scaled_dot_product_attention, puts [Sq,
Sk] scores in HBM).  Operands stay in the input dtype; scores, statistics
and accumulators are fp32, the scale is on the fp32 scores, P and dS are
cast to the operand dtype for the MXU alone.  Everything is read from a
call's shape, `causal` and `window`: no flag, no argument a model sets
(`force` is kernels/engine.py's door, the tests' and the probes').

Four kernel families, picked by the shape:
- blocks with state (_flash_kernel, _flash_bwd_kernel), several blocks a
  head.  A grid step costs ~0.35 us before it computes anything, so the
  plans (_plan_blocks, _plan_bwd_blocks) take the blocks with the fewest
  grid steps whose working set fits engine.PLAN_VMEM_BUDGET: S 2048 causal
  runs 1024 x 1024 forward (0.43 ms; 4.58 at 128 x 128) and 512 x 512
  backward (four fp32 score planes and the row's dQ count there); the lse
  plane is re-cut between the two for free (_repack).  The backward is
  FlashAttention-2 in ONE kernel, grid (BH, k-blocks, q-blocks): P^T and
  dS^T = P^T (dP^T - D) are the left operands of plain matmuls, dV and dK
  accumulate across the q-blocks, dQ in an fp32 scratch that stays a whole
  row (a TPU grid runs in order): five matmuls and one exp a step, 0.89 ms
  where a dq and a dkv kernel took 1.29.  D = rowsum(dO * O) is made outside.
- heads-first rows (_flash_rows_kernel, _flash_bwd_rows_kernel): where a
  head's scores are ONE block (no window, a K/V head a query head) a step
  takes the most rows of the flattened [B * H] axis, a divisor of it, that
  fit the budget (_rows_per_step) and computes each whole, with no m / l /
  acc state to initialise, rescale and flush: that state is what a
  one-block step cost (768 x 256 x 64: 1.23 -> 0.50 ms forward, 1.51 -> 1.17
  backward), and the result is the plain softmax bit for bit.
- heads-last rows (_flash_bshd_kernel, _flash_bwd_bshd_kernel):
  `flash_attention(..., heads=H)` takes [B, S, H * D] as a model's
  projections write it (a [B, H, S, 64] array fills half a lane tile and was
  copied at every custom-call boundary, 26 of transformer-train's 113 busy
  ms).  Where a head is one block (_heads_last_rows: S 256 or 384 at head
  64) a step is a few BATCH rows, a lane tile a pair of heads taken apart by
  a select (_tiles_of_heads), the mathematics the rows kernels', and D is
  made inside the backward kernel on the MXU.  Every other shape transposes
  to heads-first and back: the same numbers.
- the band (_band_kernel, _band_bwd_kernel): a causal call whose `window`
  (a query sees the `window` keys that end at its diagonal) is shorter than
  its keys walks the band, not the square.  Queries and keys are cut into
  ONE block length b (_plan_band; _Band): q-block i reads the strip of
  window / b + 1 K/V blocks that ends at its diagonal, the same two arrays
  handed in once a sub-block.  Forward: a step computes its q-block WHOLE,
  the strip's scores, the plain softmax over them (no state, as the rows
  kernels), a P V product a sub-block; the group's query heads are the
  innermost grid axis, so a strip is fetched once for the heads that read
  it.  Backward: ONE call whatever the row, grid (K/V head, k-block, head
  of the group, the q-blocks that see the k-block); dK and dV accumulate in
  VMEM over the group and leave once, already summed; dQ lives in a ring of
  window / b blocks a head (a block is complete when its diagonal k-block
  has run, leaves then and frees the slot of the block that enters): no
  loop over chunks, no per-chunk dK / dV in HBM, no sum after the kernel.
`flash.plan` / `flash.bwd_plan` (spans, at lowering) say what a site was
given: `form` (band | blocks), blocks, steps, `rows_per_step`, `layout`
(bshd | bhsd), `chunks`.

Skipped blocks: under `causal` a k-block above the diagonal is neither
fetched nor computed (pl.when; the index maps repeat the block held or wait
at the first that runs, _kv_block_index, _q_block_index); a block is masked
only where the diagonal, the padded end or klen[b] cuts it, and the plans
count the steps that RUN (_fewest_steps).  Under a window the blocks of the
square outside the band are no grid step at all: a sub-block's place in its
strip is static, so only the oldest takes the window's compare and only the
diagonal one the causal compare, each against a constant (the sub-blocks
between them take none), and the keys' ends are compared only in the steps
whose strip they cut (a scalar test).  The spans count in score blocks of
the square either way (`k_steps` / `steps`, `skipped_causal`,
`skipped_window`), and the band's plan weighs a step by the scores it
computes beside _STEP_COST_SCORES.

Grouped K/V: k, v [B, G, Sk, .], G a divisor of H; query head j reads head
j // (H / G) through the index maps, never repeated in HBM; a group's dK, dV
are added up after the block kernel in fp32 (_bwd_rows), inside the band's.
Long rows: where the row's dQ does not fit VMEM (past S ~4k at head 128)
the block backward loops over chunks of queries around the one kernel
(_bwd_trips; `chunks` on the span); the band's never does.

Two backward engines, one rule (_bwd_plan): the Pallas kernel where a grid
step (score block x the rows it takes, _packable_rows) has at least
_BWD_PALLAS_MIN_BLOCK_SCORES scores to spread its fixed cost over and the
dQ fits; else jax.vjp of the reference, a recompute XLA fuses.  Past
_XLA_BWD_MAX_SCORE_BYTES of scores on a TPU a site takes the longest Pallas
plan that fits whatever its blocks (_bwd_chunk_rows), and is refused at
lowering where none does.  At S 256 three or
more rows are the Pallas pair's (1.57 ms a site; 1.70 with XLA's backward).
force="interpret" keeps the Pallas backward at every shape, "jax" none.
Calls are memoized by static config: a shape's sites share one payload.

Kept through recomputation: where the backward is Pallas, the forward's
output (as bits, _flash_fwd) and logsumexp (`KEPT`, `kept_bytes`) are tagged
core.compiler.keep, and a rematerialised layer traces no second forward.
The logsumexp as an output: `return_lse=True` goes through `_flash_lse`, a
second custom_vjp over the same kernels that hands out (out, lse [B, H, Sq]
fp32; NEG_INF for a row with no key) and takes a cotangent for both (dS = P
(dP - D + dlse), taken off D before the kernel), so calls over disjoint key
sets merge into ONE softmax exactly (merge_attention; eva_attention.py).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.pallas import tile_padded_bytes
from ..core.compiler import keep
from ..observability import span
from .engine import (LANES, PLAN_VMEM_BUDGET, compiler_params, use_pallas,
                     wants_kernels)

__all__ = ["flash_attention", "merge_attention", "fwd_vmem_bytes",
           "fwd_working_set_bytes", "bwd_working_set_bytes",
           "band_fwd_vmem_bytes", "band_fwd_working_set_bytes",
           "band_bwd_vmem_bytes", "band_bwd_working_set_bytes", "KEPT", "kept",
           "kept_bytes", "takes_heads_last", "heads_first_shapes"]

NEG_INF = -1e30

# What a grid step of the band costs before it computes anything, in the
# scores the kernel computes in that time: how _plan_band weighs a windowed
# site's steps against the scores its blocks compute outside the window.
# Settled on the chip (tools/flash_fwd_probe.py, PERF.md PR 59): the forward
# at 32 heads x 16384 x 128, window 1024, reads 4.99 / 3.01 / 2.65 / 3.22 ms
# at blocks of 128 / 256 / 512 / 1024, which is 3.8 ps a score computed and
# 0.85 us a step: ~225 k scores.
_STEP_COST_SCORES = 480 * 480


def fwd_vmem_bytes(block_q: int = 128, block_k: int = 128,
                   head_dim: int = 128, num_q_blocks: int = 1,
                   dtype="float32", emit_lse: bool = True,
                   v_dim: int | None = None, rows_per_step: int = 1,
                   heads: int = 1) -> int:
    """Analytic VMEM footprint of the buffers ONE forward pallas invocation
    declares — the kernel's own statement of the linter's pricing model
    (paddle_tpu.analysis.pallas.kernel_vmem_bytes; tests hold the two
    equal on the traced call): the double-buffered padded q/k/v/o
    blocks (+ the packed lse plane when emitted) plus the fp32
    online-softmax scratch.  The SMEM klen vector is outside VMEM, and so
    are the score blocks the body computes: fwd_working_set_bytes adds
    those, and it is what _plan_blocks holds under its budget.  `v_dim`
    is the width of V and O where it is not the head_dim of Q and K.  The
    blocks are `rows_per_step` batch-head rows deep (_rows_per_step), and
    a step of several declares no scratch (_flash_rows_kernel).  `heads` >
    1: the heads-last call (_flash_bshd_kernel), whose rows are BATCH rows,
    `heads` heads side by side on the lanes, one block a head; it declares
    no scratch at any count."""
    v_dim = head_dim if v_dim is None else v_dim
    blocks = [
        ((rows_per_step, block_q, heads * head_dim), dtype),   # q
        ((rows_per_step, block_k, heads * head_dim), dtype),   # k
        ((rows_per_step, block_k, heads * v_dim), dtype),      # v
        ((rows_per_step, block_q, heads * v_dim), dtype),      # o
    ]
    if emit_lse:
        blocks.append(
            ((rows_per_step, heads * num_q_blocks, block_q), "float32"))
    scratch = [((block_q, 1), "float32"), ((block_q, 1), "float32"),
               ((block_q, v_dim), "float32")] if (
                   rows_per_step == 1 and heads == 1) else []
    return (2 * sum(tile_padded_bytes(s, d) for s, d in blocks)
            + sum(tile_padded_bytes(s, d) for s, d in scratch))


def fwd_working_set_bytes(block_q, block_k, head_dim, num_q_blocks=1,
                          dtype="float32", emit_lse=True, v_dim=None,
                          rows_per_step=1, heads=1) -> int:
    """fwd_vmem_bytes plus what a grid step computes between its two
    matmuls: the fp32 score block and the fp32 probability block, each
    [block_q, block_k].  At 128 x 128 they are 128 KB and were never
    counted; at 512 x 512 they are 2 MB, more than every declared buffer
    together, and they are what bounds the block plan.  A step of several
    rows computes them one after the other, so the count holds one row's
    pair and only the declared blocks grow with `rows_per_step` (Mosaic
    compiles 32 rows of 256 x 256 x 64, 17 MB of blocks, where 32 pairs of
    planes would be 16 MB more).  Not so the heads-last step (`heads` > 1,
    _flash_bshd_kernel), which holds a plane more for every head it lays
    out: chip-less, Mosaic allots 3.41 / 7.85 / 17.13 MB at 1 / 2 / 4 batch
    rows of 8 heads of 256 x 256 x 64 where the declared blocks are 2.02 /
    4.03 / 8.06, and refuses the last (PERF.md PR 57)."""
    planes = 2 if heads == 1 else 2 + rows_per_step * heads
    return (fwd_vmem_bytes(block_q, block_k, head_dim, num_q_blocks, dtype,
                           emit_lse, v_dim, rows_per_step, heads)
            + planes * tile_padded_bytes((block_q, block_k), "float32"))


def _block_lengths(seq: int):
    """The block lengths a sequence of `seq` rows may be cut into: the
    sequence itself where it is no longer than one 128-row tile, else every
    multiple of 128 that divides the sequence rounded up to 128 — so a plan
    never pads by more than the rounding the 128 blocks always paid."""
    if seq <= 128:
        return [seq]
    tiles = -(-seq // 128)
    return [128 * n for n in range(1, tiles + 1) if tiles % n == 0]


def _fewest_steps(sq, sk, causal, working_set):
    """The (block_q, block_k) whose grid takes the fewest steps that run,
    of the pairs whose `working_set(block_q, block_k)` bytes fit the
    plan's budget (kernels/engine.py); the wider key block where two tie.
    Under `causal`
    the steps counted are those that run: _skipped_steps are free."""
    def steps_then_wide(plan):
        bq, bk = plan
        nqb, nkb = -(-sq // bq), -(-sk // bk)
        run = nqb * nkb - sum(_skipped_steps(nqb, nkb, bq, bk, sk - sq,
                                             causal))
        return run, -bk

    plans = [(bq, bk) for bq in _block_lengths(sq)
             for bk in _block_lengths(sk)]
    fits = [plan for plan in plans
            if working_set(*plan) <= PLAN_VMEM_BUDGET]
    # a head so wide that not even the smallest blocks fit the share still
    # gets them: the share is headroom, not the compiler's limit
    return min(fits or plans[:1], key=steps_then_wide)


def _plan_blocks(sq, sk, head_dim, dtype, causal, emit_lse, v_dim=None):
    """(block_q, block_k) of the forward's grid, from the shape alone (a
    windowed site's plan is _plan_band's).

    A grid step costs about the same whatever it computes (the pipeline's
    bookkeeping, two DMAs, a read-modify-write of the fp32 accumulator and
    a dozen VPU passes whose fixed part dominates a small block), so the
    plan takes the fewest steps (_fewest_steps) whose working set
    (fwd_working_set_bytes) fits, and the wider key block where two tie
    (the accumulator is rescaled once a k-step: the probe reads 256 x 1024
    at 0.59 ms, 1024 x 256 at 1.52)."""
    return _fewest_steps(
        sq, sk, causal, lambda bq, bk: fwd_working_set_bytes(
            bq, bk, head_dim, -(-sq // bq), dtype, emit_lse, v_dim))


def bwd_working_set_bytes(block_q, block_k, head_dim, num_q_blocks=1,
                          dtype="float32", v_dim=None,
                          rows_per_step=1, heads=1) -> int:
    """What one grid step of the backward kernel holds: the double-buffered
    q, dO, k, v blocks, the packed lse and D planes and the dK, dV and
    (whole-row) dQ blocks it writes, the fp32 accumulators of dK and dV and
    of the whole row's dQ, and FOUR fp32 [block_k, block_q] planes between
    its matmuls (scores and probabilities, dP, dS and the operand cast for
    the MXU) where the forward holds two.  The dQ row is what bounds the
    sequence: 2 MB of it at 2048 x 128 bf16, 8 MB of the 12 at 8192.
    `v_dim` is the width of V, dO and dV where it is not Q's and K's.  As
    in the forward, only the declared blocks grow with `rows_per_step`, and
    a step of several rows has no accumulators (_flash_bwd_rows_kernel).
    `heads` > 1: the heads-last call (_flash_bwd_bshd_kernel), `heads` heads
    side by side on the lanes of a batch row: O is an operand where the D
    plane was, and there is no accumulator at any count."""
    def tile(shape, dt=dtype):
        return tile_padded_bytes(shape, dt)

    v_dim = head_dim if v_dim is None else v_dim
    rows = num_q_blocks * block_q
    n = rows_per_step
    width, v_width = heads * head_dim, heads * v_dim
    blocks = (tile((n, block_q, width))                 # q
              + tile((n, block_q, v_width))             # dO
              + 2 * tile((n, block_k, width))           # k, dK
              + 2 * tile((n, block_k, v_width))         # v, dV
              + tile((n, rows, width))                  # dQ
              + tile((n, heads * num_q_blocks, block_q), "float32"))  # lse
    blocks += (tile((n, num_q_blocks, block_q), "float32") if heads == 1
               else tile((n, block_q, v_width)))        # D | O
    scratch = (tile((rows, head_dim), "float32")
               + tile((block_k, head_dim), "float32")
               + tile((block_k, v_dim), "float32")) if (
                   n == 1 and heads == 1) else 0
    return (2 * blocks + scratch
            + 4 * tile((block_k, block_q), "float32"))


def _plan_bwd_blocks(sq, sk, head_dim, dtype, causal, v_dim=None):
    """(block_q, block_k) of the backward's grid, on the forward's
    principle: the fewest grid steps that run (_fewest_steps) whose working
    set (bwd_working_set_bytes) fits.  It need not be the forward's pair:
    the packed lse plane is re-cut for free (_repack)."""
    return _fewest_steps(
        sq, sk, causal, lambda bq, bk: bwd_working_set_bytes(
            bq, bk, head_dim, -(-sq // bq), dtype, v_dim))


def _rows_per_step(bh, one_block, working_set):
    """The batch-head rows ONE grid step takes, of a call's `bh` (B * H
    where a step may take several; 1 where K and V are grouped).

    Where a head's whole score matrix is one block (`one_block`: one
    q-block, one k-block, no window), a step of one row is mostly what a
    step costs before it computes anything (768 steps of 256 x 256 x 64:
    PERF.md PR 53), so a step takes the most consecutive rows of the
    flattened [B * H] axis, a divisor of it, whose `working_set(rows)`
    bytes fit the plan's budget, and computes each whole
    (_flash_rows_kernel, _flash_bwd_rows_kernel).  Everywhere else it is 1
    and the call is built as it always was.  Like the blocks it reads the
    shape, nothing else."""
    if not one_block:
        return 1
    return next(n for n in range(bh, 0, -1) if bh % n == 0
                and (n == 1 or working_set(n) <= PLAN_VMEM_BUDGET))


def _block_runs(qi, ki, block_q, block_k, causal_offset):
    """Whether score block (qi, ki) has any key at or under the causal
    diagonal (bottom-right aligned, as _block_mask has it): its first key
    is one the q-block's last row sees.  Python ints or traced scalars."""
    return ki * block_k <= (qi + 1) * block_q - 1 + causal_offset


def _kv_block_index(qi, ki, block_q, block_k, causal_offset):
    """The K/V block a causal grid step (qi, ki) holds: its own while it
    runs; past the last one q-block qi can see (_flash_kernel skips those
    steps) the index stays where it was, so the pipeline sees the block it
    already holds and issues no DMA."""
    last = jnp.maximum((qi + 1) * block_q - 1 + causal_offset, 0) // block_k
    return jnp.minimum(ki, last)


def _oldest_key(row, causal_offset, window):
    """The oldest key query `row` sees under `window` (row t sees the
    `window` keys that end at t + causal_offset); negative where the window
    reaches before the first key.  Python ints or traced scalars."""
    return row + causal_offset - (window - 1)


def _skipped_steps(nqb, nkb, block_q, block_k, causal_offset, causal=True,
                   window=None):
    """(above the diagonal, older than the window): how many of one (batch,
    head)'s nqb x nkb score blocks cost neither a fetch nor a matmul.  For
    each q-block, the k-blocks past the last one that _block_runs, and
    under `window` those before the block of the oldest key its first row
    sees (a row of blocks at a time, so a 128k sequence plans in no
    time)."""
    above = older = 0
    for i in range(nqb):
        under = nkb
        if causal:
            last = ((i + 1) * block_q - 1 + causal_offset) // block_k
            under = min(max(last + 1, 0), nkb)
        above += nkb - under
        if window is not None:
            first = max(_oldest_key(i * block_q, causal_offset, window),
                        0) // block_k
            older += min(first, under)
    return above, older


def _visible_pairs(sq, sk, causal, window=None):
    """The query-key pairs the mask lets through (bottom-right aligned
    diagonal, `window` keys a row), static."""
    if not causal:
        return sq * sk
    seen = np.clip(np.arange(sq, dtype=np.int64) + (sk - sq) + 1, 0, sk)
    if window is not None:
        seen = np.minimum(seen, window)
    return int(seen.sum())


# The per-row logsumexp/D residuals are PACKED: [B*H, num_q_blocks,
# block_q] fp32, row qi of the packed plane holding q-block qi's
# per-row scalars on the 128 lanes.  TPU pallas rejects blocks whose
# last two dims are neither (8k, 128k)-tiled nor equal to the array
# dims, so a [B*H, Sq] residual with block (1, block_q) cannot lower
# (chip-only failure) — the round-5 fix broadcast the scalars across a
# full 128-lane register instead ([B*H, Sqp, 128] fp32, ~67 MB/tensor at
# the longcontext shape, 128x the payload, and XLA does NOT fuse that
# broadcast away: it materializes as custom-call operands).  The packed
# layout is exact-size ((8,128)-tiled with no replication): the forward
# transposes its [block_q, 1] column to the (block_q,) row once a q-block
# (one register-level sublane->lane transpose buys a 128x smaller HBM
# residual), and the backward, whose scores are [block_k, block_q], reads
# the row as it lies.


def _reference_attention(q, k, v, causal, scale, bias=None, k_lengths=None,
                         window=None, with_lse=False):
    """Pure-jax attention (fallback + backward recompute).
    q: [B, H, Sq, D], k/v: [B, G, Sk, D] (query head j reads key/value head
    j // (H / G); K and V are not repeated: the group is an axis of q),
    k_lengths: [B] valid key counts, `window`: a row sees the `window` keys
    that end at its diagonal.  `with_lse`: (out, the rows' logsumexp
    [B, H, Sq] fp32, NEG_INF where a row sees no key)."""
    H, G = q.shape[1], k.shape[1]
    if G != H:
        q = q.reshape(q.shape[0], G, H // G, *q.shape[2:])
        scores = jnp.einsum("bghqd,bgkd->bghqk", q, k) * scale
    else:
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if bias is not None:
        scores = scores + bias
    if k_lengths is not None:
        kmask = jnp.arange(scores.shape[-1])[None, :] < k_lengths[:, None]
        kmask = (kmask[:, None, None, :] if G == H
                 else kmask[:, None, None, None, :])
        scores = jnp.where(kmask, scores, NEG_INF)
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((sq, sk), dtype=bool),
                              k=sk - sq - window)
        scores = jnp.where(mask, scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    # fully-masked rows (padded queries) produce zeros, not uniform weights
    all_masked = jnp.max(scores, axis=-1, keepdims=True) <= NEG_INF / 2
    weights = jnp.where(all_masked, 0.0, weights)
    if G != H:
        out = jnp.einsum("bghqk,bgkd->bghqd", weights, v)
        out = out.reshape(out.shape[0], H, *out.shape[3:])
    else:
        out = jnp.einsum("bhqk,bhkd->bhqd", weights, v)
    if not with_lse:
        return out
    lse = jax.nn.logsumexp(scores.astype(jnp.float32), axis=-1)
    lse = jnp.where(all_masked[..., 0], NEG_INF, lse)
    return out, lse.reshape(out.shape[:3])


def _block_mask(klen_ref, bi, qi, ki, shape, block_q, block_k, seq_k,
                causal, causal_offset, transposed=False):
    """Key-padding (+ causal) mask for score block (qi, ki) of batch row
    bi — identical in forward and backward.  `transposed`: the block is
    [block_k, block_q] (the backward kernel's scores)."""
    q_axis, k_axis = (1, 0) if transposed else (0, 1)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
    mask = k_pos < jnp.minimum(seq_k, klen_ref[bi].astype(jnp.int32))
    if causal:
        # bottom-right alignment (matches jnp.tril(k=Sk-Sq)): with cached
        # keys (Sk > Sq) a query at row i sees keys up to i + Sk - Sq
        mask &= k_pos <= q_pos + causal_offset
    return mask


def _step_cases(klen_ref, bi, qi, ki, *, causal, block_q, block_k, seq_k,
                causal_offset):
    """(runs, cut) of score block (qi, ki), forward and backward: whether
    the block has a key at or under the causal diagonal, and whether the
    diagonal (its last key is past what its first row sees), the padded
    end of the keys or klen[b] (data: a scalar read from SMEM) cuts it.
    Only a block that is cut pays for the iota/compare/select mask."""
    k_end = jnp.minimum(seq_k, klen_ref[bi].astype(jnp.int32))
    cut = (ki + 1) * block_k > k_end
    runs = True
    if causal:
        runs = _block_runs(qi, ki, block_q, block_k, causal_offset)
        cut = jnp.logical_or(
            cut, (ki + 1) * block_k - 1 > qi * block_q + causal_offset)
    return runs, cut


def _when_runs(runs, cut, update):
    """Run update(cut) under pl.when, once for each static value of `cut`."""
    import jax.experimental.pallas as pl

    pl.when(jnp.logical_and(runs, cut))(lambda: update(True))
    pl.when(jnp.logical_and(runs, jnp.logical_not(cut)))(
        lambda: update(False))


def _flash_kernel(klen_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr,
                  *, causal, scale, block_q, block_k, seq_k, causal_offset):
    """Grid: (batch*heads, num_q_blocks, num_k_blocks); K innermost so the
    online-softmax state lives in VMEM scratch across K steps.  klen_ref
    (SMEM) holds every batch row's valid key count (key-padding mask),
    indexed by program_id(0).  Emits O and the per-row logsumexp L
    (backward residual).

    A step does only what its block needs.  Under `causal` a k-block that
    starts past the last key the q-block's last row sees is wholly above
    the diagonal: its body does not run (and _fwd_call's K/V index maps
    hand the pipeline the previous block again, so nothing is fetched).
    Of the blocks that run, only one that can be cut takes the
    iota/compare/select mask: it crosses the diagonal, or it reaches past
    the keys this batch row has (the padded end of the sequence, or
    klen[b], which is data: a scalar read from SMEM)."""
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

    def _update(cut):
        q = q_ref[0]  # [block_q, D]
        k = k_ref[0]  # [block_k, D]
        v = v_ref[0]
        # q @ k.T, contracting the head dim of both: no transposed copy
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if cut:
            mask = _block_mask(klen_ref, bi, qi, ki, s.shape, block_q,
                               block_k, seq_k, causal, causal_offset)
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

    _when_runs(*_step_cases(
        klen_ref, bi, qi, ki, causal=causal, block_q=block_q, block_k=block_k,
        seq_k=seq_k, causal_offset=causal_offset), _update)

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


def _flash_bwd_kernel(klen_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      dvec_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr,
                      dv_scr, *, causal, scale, block_q, block_k, seq_k,
                      causal_offset):
    """dQ, dK and dV in one kernel: grid (BH, num_k_blocks, num_q_blocks),
    Q innermost.  The dK/dV accumulators of one k-block stay in VMEM across
    its q-blocks; dQ accumulates across the k-blocks into an fp32
    [Sqp, D] scratch that stays a whole batch-head row (the TPU grid runs
    in order), and leaves in the input dtype during the last k-block: five
    block matmuls and one exp a step, where a dq and a dkv kernel take
    seven and two.

    The scores are built transposed, [block_k, block_q]: P^T and dS^T are
    then the left operands of plain matmuls for dV and dK, the packed lse/D
    rows broadcast down the sublanes as they lie, and dQ contracts dS^T
    with K over the key dimension of both (no transposed copy).  Under
    `causal` the q-blocks that end before the k-block's first key do not
    run, and _bwd_call holds the q/dO index at the first that does.  The
    scale of dS is applied once, to the fp32 accumulators."""
    import jax.experimental.pallas as pl

    bi = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(ki == 0)
    def _init_dq():
        dq_scr[rows, :] = jnp.zeros((block_q, dq_scr.shape[1]), jnp.float32)

    def _update(cut):
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        nt = (((1,), (1,)), ((), ()))  # a @ b.T on the contracting dims
        st = jax.lax.dot_general(
            k, q, nt, preferred_element_type=jnp.float32) * scale
        pt = jnp.exp(st - lse_ref[0, qi, :].reshape(1, -1))
        if cut:
            pt = jnp.where(
                _block_mask(klen_ref, bi, qi, ki, st.shape, block_q, block_k,
                            seq_k, causal, causal_offset, transposed=True),
                pt, 0.0)
        dpt = jax.lax.dot_general(v_ref[0], do, nt,
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - dvec_ref[0, qi, :].reshape(1, -1))).astype(q.dtype)
        dv_scr[:] = dv_scr[:] + jnp.dot(
            pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dk_scr[:] = dk_scr[:] + jnp.dot(
            dst, q, preferred_element_type=jnp.float32)
        dq_scr[rows, :] = dq_scr[rows, :] + jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _when_runs(*_step_cases(
        klen_ref, bi, qi, ki, causal=causal, block_q=block_q, block_k=block_k,
        seq_k=seq_k, causal_offset=causal_offset), _update)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finalize_dq():
        dq_ref[0, rows, :] = (dq_scr[rows, :] * scale).astype(dq_ref.dtype)


def _rows_of_step(rows_per_step, row):
    """Run row(r, batch-head row) for the `rows_per_step` rows of this grid
    step: row r of the step's blocks is batch-head row program_id(0) *
    rows_per_step + r, which is where its klen lies (a step's rows may cross
    a batch row).  One traced body, laid out `rows_per_step` times in the
    kernel (unroll=True): the rows share nothing, and in straight-line code
    the scheduler runs one row's matmuls under another's softmax (as a
    rolled loop the same body took 0.69 ms where this takes 0.50 at 768 x
    256 x 64, PERF.md PR 53)."""
    import jax.experimental.pallas as pl

    first = pl.program_id(0) * rows_per_step

    def body(r, carry):
        row(r, first + r)
        return carry

    jax.lax.fori_loop(0, rows_per_step, body, 0, unroll=True)


def _head_forward(q, k, v, mask, scale, want_lse):
    """One head whose whole score matrix is ONE block: (its output fp32
    [Sq, width of v], its logsumexp as a column [Sq, 1] or None).  A block
    that is the first and the last of its row needs no state: the
    online-softmax update from the floor (m = NEG_INF/2, l = 0, acc = 0) is
    the plain softmax, bit for bit.  Rounding and masks are _flash_kernel's:
    every head takes the mask (a select that changes nothing where nothing
    cuts the block: a branch on klen, which is data, would keep the heads of
    a step apart)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    # the floor keeps a fully-masked row at p = 0, l = 0 (_flash_kernel)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), NEG_INF / 2)
    p = jnp.exp(s - m)
    l_fin = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    out = acc / jnp.maximum(l_fin, 1e-30)
    if not want_lse:
        return out, None
    return out, jnp.where(
        l_fin > 0.0, m + jnp.log(jnp.maximum(l_fin, 1e-30)), -NEG_INF)


def _head_backward(q, k, v, do, lse_row, dvec_row, mask_t, scale):
    """(dQ, dK, dV), fp32, of one head of one block: _flash_bwd_kernel's five
    matmuls and one exp with nothing to accumulate (the sum of one block
    needs no accumulator: 0 + x is x).  `lse_row`, `dvec_row`: [1, Sq];
    `mask_t`: the transposed block's, [Sk, Sq]."""
    nt = (((1,), (1,)), ((), ()))  # a @ b.T on the contracting dims
    st = jax.lax.dot_general(
        k, q, nt, preferred_element_type=jnp.float32) * scale
    pt = jnp.where(mask_t, jnp.exp(st - lse_row), 0.0)
    dpt = jax.lax.dot_general(v, do, nt, preferred_element_type=jnp.float32)
    dst = (pt * (dpt - dvec_row)).astype(q.dtype)
    dv = jnp.dot(pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
    dk = jnp.dot(dst, q, preferred_element_type=jnp.float32) * scale
    dq = jax.lax.dot_general(
        dst, k, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    return dq, dk, dv


def _flash_rows_kernel(klen_ref, q_ref, k_ref, v_ref, o_ref, lse_ref=None,
                       *, causal, scale, seq_k, causal_offset, rows_per_step):
    """_flash_kernel where a head's whole score matrix is ONE block
    (_rows_per_step): grid (batch*heads / rows_per_step,), a step takes
    `rows_per_step` batch-head rows and computes each whole
    (_head_forward: nothing is initialised, rescaled or read back; at 768
    steps of 256 x 256 x 64 that, and not the pipeline, was what a step
    cost before it computed anything: 1.23 ms a call against 0.50).
    Without `lse_ref` (no scratch follows the outputs, so it is simply
    absent) the lse is not written, as in _flash_kernel_fwd_only."""
    shape = q_ref.shape[1], k_ref.shape[1]

    def _row(r, row):
        mask = _block_mask(klen_ref, row, 0, 0, shape, *shape, seq_k, causal,
                           causal_offset)
        out, lse = _head_forward(q_ref[r], k_ref[r], v_ref[r], mask, scale,
                                 lse_ref is not None)
        o_ref[r] = out.astype(o_ref.dtype)
        if lse is not None:
            # the packed plane [B*H, 1, block_q]: a lane-dense row a head
            lse_ref[r, 0, :] = jnp.transpose(lse, (1, 0))[0]

    _rows_of_step(rows_per_step, _row)


def _flash_bwd_rows_kernel(klen_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                           dvec_ref, dq_ref, dk_ref, dv_ref, *, causal, scale,
                           seq_k, causal_offset, rows_per_step):
    """_flash_bwd_kernel where a head is one block, as _flash_rows_kernel
    is the forward's: `rows_per_step` batch-head rows a step, each row's
    _head_backward written straight to dQ, dK and dV."""
    shape = q_ref.shape[1], k_ref.shape[1]

    def _row(r, row):
        mask_t = _block_mask(klen_ref, row, 0, 0, shape[::-1], *shape, seq_k,
                             causal, causal_offset, transposed=True)
        dq, dk, dv = _head_backward(
            q_ref[r], k_ref[r], v_ref[r], do_ref[r],
            lse_ref[r, 0, :].reshape(1, -1), dvec_ref[r, 0, :].reshape(1, -1),
            mask_t, scale)
        dq_ref[r] = dq.astype(dq_ref.dtype)
        dk_ref[r] = dk.astype(dk_ref.dtype)
        dv_ref[r] = dv.astype(dv_ref.dtype)

    _rows_of_step(rows_per_step, _row)


def _tiles_of_heads(head_dim):
    """(width, per_tile, take, join) of the lane tiles of a batch row [S,
    heads * head_dim] whose heads lie side by side on the lanes
    (heads-last): a tile is `width` lanes, 128 or a head where it is wider,
    and holds `per_tile` heads (head 64: a PAIR).  take(x, a): the tile x
    [S, width] as head a's queries (or dO); join(xs): the tile of the
    heads' results xs.

    A tile stays whole.  Head a's queries are the tile with the other
    heads' lanes zeroed, so q_a @ k2.T contracts all 128 lanes, half of
    them against zeros: on a 128 x 128 MXU the pass a 64-deep contraction
    takes anyway.  K and V are the tile as it lies: what p_a @ v2 puts on
    the other heads' lanes is dropped by join's select.  Every load, store
    and DMA is lane-dense.  (The other way, static 64-lane slices of the
    tile's value and the results concatenated, shifts lanes at a half-tile
    offset: forward with the logsumexp 0.57 ms against 0.26 at 96 x 256 x
    8 x 64, forward and backward 1.04 against 0.73; PERF.md PR 57.)"""
    width = max(LANES, head_dim)
    per_tile = width // head_dim
    if per_tile == 1:
        return width, 1, (lambda x, a: x), (lambda xs: xs[0])

    def head_of_lane():
        return jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // head_dim

    def take(x, a):
        return jnp.where(head_of_lane() == a, x, jnp.zeros_like(x))

    def join(xs):
        out, head = xs[-1], head_of_lane()
        for a in range(per_tile - 2, -1, -1):
            out = jnp.where(head == a, xs[a], out)
        return out

    return width, per_tile, take, join


def _flash_bshd_kernel(klen_ref, q_ref, k_ref, v_ref, o_ref, lse_ref=None,
                       *, causal, scale, seq_k, causal_offset, rows_per_step,
                       heads):
    """_flash_rows_kernel on the layout the projections write: q, k, v and
    o are [B, S, heads * D] (heads-last), a grid step takes `rows_per_step`
    BATCH rows and computes every head of each whole (_head_forward), a
    lane tile at a time (_tiles_of_heads).  The mask is a batch row's, made
    once for its heads; the logsumexp is written as [B, heads, Sq], row h of
    a batch row's plane a lane-dense row (the packed plane of the
    heads-first kernels, [B * heads, 1, Sq], is the same memory)."""
    shape = q_ref.shape[1], k_ref.shape[1]
    width, per_tile, take, join = _tiles_of_heads(q_ref.shape[2] // heads)

    def _row(r, row):
        mask = _block_mask(klen_ref, row, 0, 0, shape, *shape, seq_k, causal,
                           causal_offset)
        for t in range(heads // per_tile):
            tile = slice(t * width, (t + 1) * width)
            q2, k2, v2 = q_ref[r, :, tile], k_ref[r, :, tile], v_ref[r, :, tile]
            outs = []
            for a in range(per_tile):
                out, lse = _head_forward(take(q2, a), k2, v2, mask, scale,
                                         lse_ref is not None)
                outs.append(out)
                if lse is not None:
                    lse_ref[r, t * per_tile + a, :] = jnp.transpose(
                        lse, (1, 0))[0]
            o_ref[r, :, tile] = join(outs).astype(o_ref.dtype)

    _rows_of_step(rows_per_step, _row)


def _flash_bwd_bshd_kernel(klen_ref, q_ref, k_ref, v_ref, o_ref, do_ref,
                           lse_ref, *rest, causal, scale, seq_k,
                           causal_offset, rows_per_step, heads, with_dlse):
    """_flash_bwd_rows_kernel on the heads-last layout, as
    _flash_bshd_kernel is the forward's: q, k, v, o, dO in and dQ, dK, dV
    out are [B, S, heads * D], `rows_per_step` batch rows a step.

    D = rowsum(dO * O) is made here, and as the ROW [1, Sq] the transposed
    scores want: a tile's fp32 products dO * O [Sq, width], contracted over
    their lanes with a constant [8, width] whose row a is 1 on head a's
    lanes, are the heads' D as rows, on the MXU (fp32 in every pass: the
    sum XLA's reduction made, which came out a column and carried a change
    of layout to here).  With `with_dlse` a plane like the logsumexp's
    follows it, the cotangent of the logsumexp handed out (_flash_lse says
    why it leaves D)."""
    dlse_ref = rest[0] if with_dlse else None
    dq_ref, dk_ref, dv_ref = rest[-3:]
    shape = q_ref.shape[1], k_ref.shape[1]
    head_dim = q_ref.shape[2] // heads
    width, per_tile, take, join = _tiles_of_heads(head_dim)
    # row a: the lanes of the tile's head a (whole fp32 tiles of 8 rows)
    ones_shape = (-(-per_tile // 8) * 8, width)
    ones = (jax.lax.broadcasted_iota(jnp.int32, ones_shape, 1) // head_dim
            == jax.lax.broadcasted_iota(jnp.int32, ones_shape, 0)
            ).astype(jnp.float32)

    def _row(r, row):
        mask_t = _block_mask(klen_ref, row, 0, 0, shape[::-1], *shape, seq_k,
                             causal, causal_offset, transposed=True)
        for t in range(heads // per_tile):
            tile = slice(t * width, (t + 1) * width)
            q2, k2, v2 = q_ref[r, :, tile], k_ref[r, :, tile], v_ref[r, :, tile]
            do2 = do_ref[r, :, tile]
            dvecs = jax.lax.dot_general(
                ones, do2.astype(jnp.float32)
                * o_ref[r, :, tile].astype(jnp.float32),
                (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)     # [8 or more, Sq]
            grads = []
            for a in range(per_tile):
                h = t * per_tile + a
                dvec = dvecs[a:a + 1, :]
                if with_dlse:
                    dvec = dvec - dlse_ref[r, h, :].reshape(1, -1)
                grads.append(_head_backward(
                    take(q2, a), k2, v2, take(do2, a),
                    lse_ref[r, h, :].reshape(1, -1), dvec, mask_t, scale))
            for ref, xs in zip((dq_ref, dk_ref, dv_ref), zip(*grads)):
                ref[r, :, tile] = join(xs).astype(ref.dtype)

    _rows_of_step(rows_per_step, _row)


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
              causal_offset, dtype, interpret, emit_lse=True, dv=None,
              group=1, rows_per_step=1):
    """Memoized pallas_call: every attention site with the same static
    config reuses ONE traced callable, so XLA sees identical kernel
    payloads (compile-cache friendly) instead of per-site clones.
    emit_lse=False drops the lse output entirely (see
    _flash_kernel_fwd_only).  `dv` is the width of V and O where it is
    not Q's and K's `d`.  `group` query heads (consecutive rows of q) read
    one K/V head through the index maps: K and V come as [bh / group, skp,
    .] and are never repeated.  `rows_per_step` batch-head rows
    make one block and one step of the first grid axis (_rows_per_step);
    more than one are a head of one block each, which is
    _flash_rows_kernel's to run."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel = _flash_kernel if emit_lse else _flash_kernel_fwd_only
    nqb, nkb = sqp // bq, skp // bk
    dv = d if dv is None else dv

    def kv_block(b, i, j):
        if causal:
            j = _kv_block_index(i, j, bq, bk, causal_offset)
        if group > 1:
            b = b // group
        return (b, j, 0)

    n = rows_per_step
    out_specs = [pl.BlockSpec((n, bq, dv), lambda b, i, j: (b, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, sqp, dv), jnp.dtype(dtype))]
    if emit_lse:
        # packed lse: one [nqb, bq] plane per batch-head row, revisited
        # across q/k steps and flushed when b advances
        out_specs.append(
            pl.BlockSpec((n, nqb, bq), lambda b, i, j: (b, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((bh, nqb, bq), jnp.float32))
    static = dict(causal=causal, scale=scale, block_q=bq, block_k=bk,
                  seq_k=seq_k, causal_offset=causal_offset)
    scratch = [pltpu.VMEM((bq, 1), jnp.float32),
               pltpu.VMEM((bq, 1), jnp.float32),
               pltpu.VMEM((bq, dv), jnp.float32)]
    if n > 1:   # a head is one block, computed whole: no state to keep
        kernel = _flash_rows_kernel
        static = dict(causal=causal, scale=scale, seq_k=seq_k,
                      causal_offset=causal_offset, rows_per_step=n)
        scratch = []
    return pl.pallas_call(
        functools.partial(kernel, **static),
        grid=(bh // n, nqb, nkb),
        in_specs=[
            # whole [B*H] vector in SMEM, indexed by program_id(0) in-kernel
            # (TPU rejects rank-1 blocks smaller than the 128 tile)
            pl.BlockSpec((bh,), lambda b, i, j: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((n, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((n, bk, d), kv_block),
            pl.BlockSpec((n, bk, dv), kv_block),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )


def _packable_rows(q, k):
    """The batch-head rows of a call that grid steps may take several of
    (_rows_per_step): B * H, the flattened axis, where every query head has
    a K/V head of its own; 1 where they are grouped, whose index maps send
    consecutive rows to one K/V head."""
    return q.shape[0] * q.shape[1] if k.shape[1] == q.shape[1] else 1


def _pallas_flash(q, k, v, klen, causal, scale, block_q=None, block_k=None,
                  interpret=False, need_lse=True, window=None,
                  rows_per_step=None):
    """Returns (out [B,H,Sq,Dv], lse [B*H, num_q_blocks, block_q] fp32
    per-row logsumexp in the PACKED residual layout — see the module
    comment; _pallas_flash_bwd re-cuts it to its own q-block).
    need_lse=False (inference / the recompute-jax backward) skips the lse
    output entirely — its HBM write is pure waste when nothing consumes
    it — and returns (out, None).  k and v may have fewer heads than q
    ([B, G, Sk, .]: query head j reads head j // (H / G)).  The blocks come
    from _plan_blocks and the batch-head rows a grid step takes from
    _rows_per_step; block_q / block_k / rows_per_step pin them for a test or
    the probe, never a model.  A `window` is the band's (_pallas_band)."""
    if window is not None:
        return _pallas_band(q, k, v, klen, scale, window, block_q, interpret,
                            need_lse)
    B, H, Sq, D = q.shape
    G, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    plan_q, plan_k = _plan_blocks(Sq, Sk, D, q.dtype, causal, need_lse, Dv)
    bq = plan_q if block_q is None else min(block_q, Sq)
    bk = plan_k if block_k is None else min(block_k, Sk)
    # pad sequence dims to block multiples (masked in-kernel)
    q = _pad_seq(q, bq)
    k = _pad_seq(k, bk)
    v = _pad_seq(v, bk)
    qf = q.reshape(B * H, q.shape[2], D)
    kf = k.reshape(B * G, k.shape[2], D)
    vf = v.reshape(B * G, v.shape[2], Dv)
    klen_bh = jnp.repeat(klen, H)  # [B*H] valid key counts

    nqb, nkb = qf.shape[1] // bq, kf.shape[1] // bk
    above, older = _skipped_steps(nqb, nkb, bq, bk, Sk - Sq, causal)
    if rows_per_step is None:
        rows_per_step = _rows_per_step(
            _packable_rows(q, k), nqb == nkb == 1,
            lambda n: fwd_working_set_bytes(bq, bk, D, 1, q.dtype, need_lse,
                                            Dv, n))
    # at lowering, as recurrence.lower: static counts over one (b, h)
    with span("flash.plan", sq=Sq, sk=Sk, head_dim=D, block_q=bq,
              block_k=bk, k_steps=nqb * nkb, k_steps_skipped=above + older,
              causal=int(causal), window=0, kv_heads=G,
              chunks=1, skipped_causal=above, skipped_window=older,
              rows_per_step=rows_per_step, layout="bhsd", form="blocks"):
        call = _fwd_call(B * H, qf.shape[1], kf.shape[1], D, bq, bk, causal,
                         scale, Sk, Sk - Sq, str(q.dtype), interpret,
                         emit_lse=need_lse, dv=Dv,
                         group=H // G, rows_per_step=rows_per_step)
        res = call(klen_bh, qf, kf, vf)  # list: [out] or [out, lse]
    out = res[0].reshape(B, H, res[0].shape[1], Dv)
    if out.shape[2] != Sq:
        out = out[:, :, :Sq]
    if not need_lse:
        return out, None
    return out, res[1]  # packed [B*H, nqb, bq]; the bwd re-cuts it (_repack)


def _q_block_index(qi, ki, block_q, block_k, causal_offset, nqb):
    """The q/dO block a causal dK/dV grid step (ki, qi) holds: its own from
    the first q-block whose last row sees k-block ki's first key; before
    that one (the kernel skips those steps) the index waits there, so the
    first block that runs is the only one fetched."""
    first = jnp.maximum(ki * block_k - causal_offset, 0) // block_q
    return jnp.maximum(qi, jnp.minimum(first, nqb - 1))


@functools.lru_cache(maxsize=128)
def _bwd_call(bh, sqp, skp, d, bq, bk, causal, scale, seq_k,
              causal_offset, q_dtype, k_dtype, v_dtype, interpret, dv=None,
              group=1, rows_per_step=1):
    """Memoized pallas_call of _flash_bwd_kernel — see _fwd_call.  With
    `group` > 1 K and V come as [bh / group, skp, .] and are read through
    the index maps; dK and dV leave a query head each, [bh, skp, .], and
    _bwd_rows adds a group's up."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nqb = sqp // bq
    dv = d if dv is None else dv
    n = rows_per_step
    # packed lse/dvec residuals: the whole (tiny) [nqb, bq] plane for
    # batch-head row b rides in VMEM; the kernel reads its q-block's row
    packed = pl.BlockSpec((n, nqb, bq), lambda b, j, i: (b, 0, 0))
    k_block = pl.BlockSpec((n, bk, d), lambda b, j, i: (b, j, 0))
    v_block = pl.BlockSpec((n, bk, dv), lambda b, j, i: (b, j, 0))
    k_in, v_in = k_block, v_block
    if group > 1:
        k_in = pl.BlockSpec((1, bk, d), lambda b, j, i: (b // group, j, 0))
        v_in = pl.BlockSpec((1, bk, dv), lambda b, j, i: (b // group, j, 0))

    def q_of_kv(b, j, i):
        if causal:
            i = _q_block_index(i, j, bq, bk, causal_offset, nqb)
        return (b, i, 0)

    kernel = _flash_bwd_kernel
    static = dict(causal=causal, scale=scale, block_q=bq, block_k=bk,
                  seq_k=seq_k, causal_offset=causal_offset)
    scratch = [pltpu.VMEM((sqp, d), jnp.float32),
               pltpu.VMEM((bk, d), jnp.float32),
               pltpu.VMEM((bk, dv), jnp.float32)]
    if n > 1:   # as in _fwd_call
        kernel = _flash_bwd_rows_kernel
        static = dict(causal=causal, scale=scale, seq_k=seq_k,
                      causal_offset=causal_offset, rows_per_step=n)
        scratch = []
    return pl.pallas_call(
        functools.partial(kernel, **static),
        grid=(bh // n, skp // bk, nqb),
        in_specs=[
            pl.BlockSpec((bh,), lambda b, j, i: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((n, bq, d), q_of_kv),
            k_in,
            v_in,
            pl.BlockSpec((n, bq, dv), q_of_kv),
            packed,
            packed,
        ],
        out_specs=[
            # dQ: one block a batch-head row, written back when b advances
            pl.BlockSpec((n, sqp, d), lambda b, j, i: (b, 0, 0)),
            k_block,
            v_block,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sqp, d), jnp.dtype(q_dtype)),
            jax.ShapeDtypeStruct((bh, skp, d), jnp.dtype(k_dtype)),
            jax.ShapeDtypeStruct((bh, skp, dv), jnp.dtype(v_dtype)),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )


def _repack(plane, sq, block_q, fill):
    """A packed per-row plane [B*H, n, b] re-cut to q-blocks of `block_q`:
    it is a view of [B*H, n * b], so a block length that divides the same
    padded length is a free reshape; otherwise the rows past `sq` are cut
    and padded anew with `fill`."""
    flat = plane.reshape(plane.shape[0], -1)
    sqp = -(-sq // block_q) * block_q
    if flat.shape[1] != sqp:
        flat = jnp.pad(flat[:, :sq], ((0, 0), (0, sqp - sq)),
                       constant_values=fill)
    return flat.reshape(plane.shape[0], sqp // block_q, block_q)


def _packed_d(gf, of, dlse, sq, block_q):
    """D_i = rowsum(dO * O) of gf, of [B*H, Sqp, Dv] in the packed layout
    the backward kernels index, [B*H, Sqp / block_q, block_q] fp32: one
    fused elementwise+reduce pass, reshaped (a free, layout-preserving
    view), so no lane broadcast ever materializes (the old [B*H, Sqp, 128]
    operands were 128x the payload and did NOT fuse away: custom-call
    operands are materialized in HBM).  `dlse` [B, H, sq] is the cotangent
    of the rows' logsumexp where the call handed it out (_flash_lse): dS =
    P (dP - D + dlse), so it is taken off D and the kernels are the same."""
    rows, sqp = gf.shape[:2]
    dvec = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    if dlse is not None:
        dvec = dvec - jnp.pad(dlse.reshape(rows, sq).astype(jnp.float32),
                              ((0, 0), (0, sqp - sq)))
    return dvec.reshape(rows, sqp // block_q, block_q)


def _bwd_rows(q, k, v, klen, out, lse, g, causal, scale, causal_offset,
              bq, bk, interpret, rows_per_step=1, dlse=None):
    """(dq, dk, dv) of the queries q [B, H, Sq, D] (with their out, dO `g`
    and packed lse) over the keys k, v [B, G, Sk, .] by ONE call of
    _flash_bwd_kernel: a whole row, or one trip of _pallas_flash_bwd's loop
    over chunks of queries, which hands in the keys the chunk sees and the
    diagonal's place among them (`causal_offset`: row i sees keys up to
    i + causal_offset).  dk and dv come per K/V head: where a group of
    query heads shares one, the kernel writes a query head's each and they
    are added up here, in fp32.  `dlse`: _packed_d."""
    B, H, Sq, D = q.shape
    G, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    qp = _pad_seq(q, bq)
    op = _pad_seq(out, bq)
    gp = _pad_seq(g, bq)  # zero-padded dO rows contribute nothing to dK/dV
    kp = _pad_seq(k, bk)
    vp = _pad_seq(v, bk)
    Sqp, Skp = qp.shape[2], kp.shape[2]
    qf = qp.reshape(B * H, Sqp, D)
    of = op.reshape(B * H, Sqp, Dv)
    gf = gp.reshape(B * H, Sqp, Dv).astype(qf.dtype)
    kf = kp.reshape(B * G, Skp, D)
    vf = vp.reshape(B * G, Skp, Dv)
    klen_bh = jnp.repeat(klen, H)
    # a padded row's lse is the fully-masked row's: exp(s - lse) is 0
    lse = _repack(lse, Sq, bq, -NEG_INF)
    dvec = _packed_d(gf, of, dlse, Sq, bq)

    call = _bwd_call(B * H, Sqp, Skp, D, bq, bk, causal, scale, Sk,
                     causal_offset, str(q.dtype), str(k.dtype), str(v.dtype),
                     interpret, dv=Dv, group=H // G,
                     rows_per_step=rows_per_step)
    dq, dk, dv = call(klen_bh, qf, kf, vf, gf, lse, dvec)

    dq = dq.reshape(B, H, Sqp, D)[:, :, :Sq]
    if G != H:
        dk = dk.reshape(B, G, H // G, Skp, D).astype(jnp.float32).sum(2)
        dv = dv.reshape(B, G, H // G, Skp, Dv).astype(jnp.float32).sum(2)
    dk = dk.reshape(B, G, Skp, D)[:, :, :Sk]
    dv = dv.reshape(B, G, Skp, Dv)[:, :, :Sk]
    return dq, dk, dv


def _pallas_flash_bwd(q, k, v, klen, out, lse, g, causal, scale,
                      block_q=None, block_k=None, interpret=False,
                      window=None, chunk=None, rows_per_step=None,
                      dlse=None):
    """(dq, dk, dv) by _flash_bwd_kernel at the backward's own plan
    (_bwd_plan; block_q / block_k / chunk / rows_per_step pin it for a test
    or the probe).
    `lse` is the forward's packed plane, whatever q-block laid it out.

    Where the row's dQ fits VMEM the row is one call, as it always was.
    Past that (S ~4k at head 128) the kernel runs in an outer loop over
    chunks of queries: a chunk's dQ is the whole "row" of its call, and it
    is handed only the keys it can see (up to its last row's diagonal),
    whose dK and dV are added, in fp32, into the sequence's.  A `window` is
    the band's, one call whatever the row (_pallas_band_bwd)."""
    if window is not None:
        return _pallas_band_bwd(q, k, v, klen, out, lse, g, scale, window,
                                block_q, interpret, dlse)
    B, H, Sq, D = q.shape
    G, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    bh = _packable_rows(q, k)
    trips = _bwd_trips(Sq, Sk, D, q.dtype, causal, block_q, block_k, Dv,
                       chunk, bh, B * H)
    plan = dict(_bwd_plan(Sq, Sk, D, q.dtype, causal, block_q, block_k, Dv,
                          None, chunk, bh, site_bh=B * H),
                engine="pallas", kv_heads=G)
    if rows_per_step is not None:
        plan["rows_per_step"] = rows_per_step
    with span("flash.bwd_plan", **plan):  # at lowering, as flash.plan
        if len(trips) == 1:
            _, _, _, _, bq, bk = trips[0]
            dq, dk, dv = _bwd_rows(q, k, v, klen, out, lse, g, causal, scale,
                                   Sk - Sq, bq, bk, interpret,
                                   plan["rows_per_step"], dlse)
            if G != H:
                dk, dv = dk.astype(k.dtype), dv.astype(v.dtype)
            return dq, dk, dv
        lse = lse.reshape(B * H, -1)[:, :Sq]
        dqs = []
        dk = jnp.zeros((B, G, Sk, D), jnp.float32)
        dv = jnp.zeros((B, G, Sk, Dv), jnp.float32)
        for q0, q1, k0, k1, bq, bk in trips:
            dq_c, dk_c, dv_c = _bwd_rows(
                q[:, :, q0:q1], k[:, :, k0:k1], v[:, :, k0:k1],
                jnp.clip(klen - k0, 0, k1 - k0), out[:, :, q0:q1],
                lse[:, None, q0:q1], g[:, :, q0:q1], causal, scale,
                q0 + (Sk - Sq) - k0, bq, bk, interpret,
                dlse=None if dlse is None else dlse[:, :, q0:q1])
            dqs.append(dq_c)
            dk = dk.at[:, :, k0:k1].add(dk_c.astype(jnp.float32))
            dv = dv.at[:, :, k0:k1].add(dv_c.astype(jnp.float32))
        return (jnp.concatenate(dqs, axis=2), dk.astype(k.dtype),
                dv.astype(v.dtype))


# The backward's engine is read from the shape: the Pallas kernel where
# a grid step (its score block [block_k, block_q], times the batch-head
# rows the step takes) has at least this many scores over which to spread
# what a step costs before it computes anything (and the row's dQ, or a
# chunk's, fits VMEM at all), the XLA recompute backward elsewhere.
# Settled on the chip by tools/flash_bwd_probe.py (PERF.md, PR 30; the
# step's rows PR 53).
_BWD_PALLAS_MIN_BLOCK_SCORES = 384 * 384

# The XLA recompute backward materialises a site's fp32 scores, [B, H, Sq,
# Sk].  Past this many bytes of them a site that no Pallas plan takes is
# refused at lowering (_flash_bwd) rather than compiled: 32 x 16384 x 16384
# would be 34 GB on a 16 GB chip.
_XLA_BWD_MAX_SCORE_BYTES = 2 << 30


def _chunk_keys(q1, causal_offset, sk, causal):
    """[k0, k1) of the keys that the queries up to row q1 see: from the
    first, up to the last row's diagonal under `causal`."""
    k1 = min(q1 + causal_offset, sk) if causal else sk
    return 0, max(k1, 1)


def _bwd_rows_per_step(bh, sq, sk, block_q, block_k, head_dim, dtype, v_dim):
    """_rows_per_step of a backward call of one trip at these blocks."""
    return _rows_per_step(
        bh, sq <= block_q and sk <= block_k,
        lambda n: bwd_working_set_bytes(block_q, block_k, head_dim, 1, dtype,
                                        v_dim, n))


def _xla_bwd_score_bytes(site_bh, sq, sk):
    """The fp32 scores [B, H, Sq, Sk] that the XLA recompute backward of a
    site of `site_bh` batch x heads materialises."""
    return 4 * site_bh * sq * sk


@functools.lru_cache(maxsize=128)
def _bwd_chunk_rows(sq, sk, head_dim, dtype, causal, v_dim, bh=1,
                    site_bh=None):
    """(rows of queries a call of the backward kernel takes, engine).  The
    whole row where its dQ fits VMEM beside a grid step worth taking (of the
    call's `bh` packable batch-head rows a step may take several,
    _rows_per_step, and it is the step's scores that count), as it
    always was.  Else the longest cut of the row
    (_block_lengths) whose plan fits with a score block worth a grid step.
    Where no cut does, the row stays whole and the engine is XLA's, unless
    XLA's engine would be refused the site (`site_bh`, its batch x heads
    however they are grouped; `bh` where not given): _flash_bwd's own
    test, so one rule decides.  Such a site takes the longest plan that
    fits, at its smaller blocks."""
    def plan(rows, keys, bh=1):
        bq, bk = _plan_bwd_blocks(rows, keys, head_dim, dtype, causal, v_dim)
        fits = bwd_working_set_bytes(
            bq, bk, head_dim, -(-rows // bq), dtype, v_dim
        ) <= PLAN_VMEM_BUDGET
        step = bq * bk * _bwd_rows_per_step(bh, rows, keys, bq, bk, head_dim,
                                            dtype, v_dim)
        return fits, step >= _BWD_PALLAS_MIN_BLOCK_SCORES

    fits, worth = plan(sq, sk, bh)
    if fits and worth:
        return sq, "pallas"
    cuts, fitting = [], [sq] if fits else []
    for rows in _block_lengths(sq)[:-1]:
        q0 = (sq - 1) // rows * rows            # the last chunk sees most
        k0, k1 = _chunk_keys(sq, sk - sq, sk, causal)
        fits, worth = plan(min(rows, sq - q0), k1 - k0)
        if fits:
            (cuts if worth else fitting).append(rows)
    if cuts:
        return max(cuts), "pallas"
    # e.g. head 256 in fp32, where no block worth a grid step fits (512 x
    # 512 is 12.5 of 12 MiB), over 16 heads of 8192 rows
    if fitting and _xla_bwd_score_bytes(
            bh if site_bh is None else site_bh, sq, sk
    ) > _XLA_BWD_MAX_SCORE_BYTES:
        return max(fitting), "pallas"
    return sq, "xla"


def _bwd_trips(sq, sk, head_dim, dtype, causal, block_q=None, block_k=None,
               v_dim=None, chunk=None, bh=1, site_bh=None):
    """[(q0, q1, k0, k1, block_q, block_k)]: the calls of the backward
    kernel that one site with no window makes, one where the row is whole.
    `chunk` pins the rows a trip, `block_q` / `block_k` the blocks (a test
    or the probe), else _bwd_chunk_rows and each trip's own
    _plan_bwd_blocks."""
    rows = (_bwd_chunk_rows(sq, sk, head_dim, dtype, causal, v_dim, bh,
                            site_bh)[0]
            if chunk is None else min(chunk, sq))
    trips = []
    for q0 in range(0, sq, rows):
        q1 = min(q0 + rows, sq)
        k0, k1 = (0, sk) if rows >= sq else _chunk_keys(
            q1, sk - sq, sk, causal)
        bq, bk = _plan_bwd_blocks(q1 - q0, k1 - k0, head_dim, dtype, causal,
                                  v_dim)
        bq = bq if block_q is None else min(block_q, q1 - q0)
        bk = bk if block_k is None else min(block_k, k1 - k0)
        trips.append((q0, q1, k0, k1, bq, bk))
    return trips


def _bwd_plan(sq, sk, head_dim, dtype, causal, block_q=None, block_k=None,
              v_dim=None, window=None, chunk=None, bh=1, group=1,
              site_bh=None):
    """What the backward of one attention call of this shape is given, the
    `flash.bwd_plan` span's counts: block_q, block_k (pinned by a test or
    the probe, else _plan_bwd_blocks'; the last trip's where there are
    several), chunks (the outer loop's trips, 1 where the row is whole),
    steps, steps_skipped and its two parts skipped_causal and
    skipped_window (static, over one batch-head row, all trips),
    rows_per_step (the batch-head rows a grid step takes, of the call's `bh`
    packable ones, _packable_rows: part of the shape, as `site_bh`, all of
    its batch x heads, is: _bwd_chunk_rows), layout ("bhsd": these
    are the heads-first kernels; _pallas_flash_bwd_bshd says "bshd" and
    counts batch rows), form ("blocks"; under a `window` shorter than the
    keys "band", _band_bwd_plan's counts: one call, `group` query heads a
    K/V head) and engine, "pallas" or "xla": the one place that says
    which."""
    if window is not None:
        return _band_bwd_plan(sq, sk, head_dim, dtype, block_q, v_dim,
                              window, group)[0]
    engine = _bwd_chunk_rows(sq, sk, head_dim, dtype, causal, v_dim, bh,
                             site_bh)[1]
    trips = _bwd_trips(sq, sk, head_dim, dtype, causal, block_q, block_k,
                       v_dim, chunk, bh, site_bh)
    steps = above = 0
    for q0, q1, k0, k1, bq, bk in trips:
        nqb, nkb = -(-(q1 - q0) // bq), -(-(k1 - k0) // bk)
        steps += nqb * nkb
        above += _skipped_steps(nqb, nkb, bq, bk, q0 + (sk - sq) - k0,
                                causal)[0]
    rows_per_step = 1 if len(trips) > 1 else _bwd_rows_per_step(
        bh, sq, sk, trips[0][4], trips[0][5], head_dim, dtype, v_dim)
    return dict(sq=sq, sk=sk, head_dim=head_dim, block_q=trips[-1][4],
                block_k=trips[-1][5], steps=steps, steps_skipped=above,
                engine=engine, window=0, chunks=len(trips),
                skipped_causal=above, skipped_window=0,
                rows_per_step=rows_per_step, layout="bhsd", form="blocks")


# ---------------------------------------------------------------------------
# The band: a site whose `window` is shorter than its keys (PR 59)
# ---------------------------------------------------------------------------

class _Band(NamedTuple):
    """The strip of K/V blocks a q-block reads under a window, both cut into
    blocks of `block` rows: q-block i reads the `n` sub-blocks i + lo .. i
    + lo + n - 1 (its rows' oldest key lies in the first, its last row's
    diagonal in the last), and which of them an edge can cut, a flag a
    sub-block: `window_cut` (a row's oldest key is past the sub-block's
    first) and `causal_cut` (a row's diagonal is before its last).  The
    sub-blocks between the two take no mask."""
    block: int
    lo: int
    n: int
    window_cut: tuple
    causal_cut: tuple

    @property
    def hi(self) -> int:
        return self.lo + self.n - 1


@functools.lru_cache(maxsize=256)
def _band(block, nqb, nkb, causal_offset, window) -> _Band:
    """The _Band of `nqb` q-blocks over `nkb` K/V blocks (row t sees the
    `window` keys that end at t + causal_offset, which is not negative).
    Everything is static: sub-block s of q-block i is K/V block i + lo + s
    whatever i, so its keys lie at one offset from its queries and an edge
    is a compare against a constant.  Sub-blocks that no q-block has a K/V
    block for (a strip longer than the keys) are left out."""
    b = block
    oldest = causal_offset - window + 1     # of row 0, among the keys
    lo = oldest // b
    m = oldest - lo * b
    n = (m + b + window - 2) // b + 1
    first = max(0, -lo - (nqb - 1))
    last = min(n - 1, nkb - 1 - lo)
    assert 0 <= first <= last, (block, nqb, nkb, causal_offset, window)
    return _Band(b, lo + first, last - first + 1,
                 tuple(m + b - 1 > s * b for s in range(first, last + 1)),
                 tuple(m + window - 1 < (s + 1) * b - 1
                       for s in range(first, last + 1)))


def _band_mask(shape, transposed, newest=None, oldest=None, first=None,
               end=None):
    """Which scores of one sub-block stay, or None where nothing is
    compared.  A key's offset from a query inside the sub-block (iota -
    iota) is at most `newest` (the diagonal) and at least `oldest` (the
    window's far edge), and the key itself lies in [first, end) (the keys
    the batch row has): each a Python int, a traced scalar, or None for no
    compare.  `transposed`: [keys, queries]."""
    if newest is None and oldest is None and end is None:
        return None
    q_axis, k_axis = (1, 0) if transposed else (0, 1)
    key = jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
    rel = key - jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    stay = [] if newest is None else [rel <= newest]
    stay += [] if oldest is None else [rel >= oldest]
    stay += [] if end is None else [key >= first, key < end]
    return functools.reduce(jnp.logical_and, stay)


def _band_kernel(klen_ref, q_ref, *refs, band, scale, seq_k, causal_offset,
                 window, group, emit_lse):
    """Grid (batch * K/V heads, q-blocks, the group's query heads), the
    heads innermost so that a strip of K/V is fetched once for the heads
    that read it.  refs: the strip's `n` K blocks, its `n` V blocks (the
    same two arrays, a sub-block an operand: _band_fwd_call), out and the
    group's packed logsumexp plane.

    A step computes its q-block whole: the strip's scores, the plain
    softmax over them (the mathematics of _head_forward: no m / l / acc
    state to initialise, rescale and flush), one P V product a sub-block.
    A sub-block takes only the compares its place in the strip can need
    (_Band: constants), and the keys' ends (klen[b], the padded end, a
    strip that starts before the first key) only in the steps whose strip
    they cut, a scalar test."""
    import jax.experimental.pallas as pl

    n, b = band.n, band.block
    k_refs, v_refs, o_ref = refs[:n], refs[n:2 * n], refs[2 * n]
    lse_ref = refs[2 * n + 1] if emit_lse else None
    qi, h = pl.program_id(1), pl.program_id(2)
    first = qi + band.lo        # the strip's first K/V block, unclamped
    k_end = jnp.minimum(
        seq_k, klen_ref[pl.program_id(0) * group + h].astype(jnp.int32))

    def strip(bounded):
        q = q_ref[0]
        scores = []
        for s in range(n):
            x = jax.lax.dot_general(
                q, k_refs[s][0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            newest = causal_offset - (band.lo + s) * b
            mask = _band_mask(
                x.shape, False, newest if band.causal_cut[s] else None,
                newest - window + 1 if band.window_cut[s] else None,
                *((-(first + s) * b, k_end - (first + s) * b) if bounded
                  else ()))
            scores.append(x if mask is None else jnp.where(mask, x, NEG_INF))
        # the floor keeps a fully-masked row at p = 0, l = 0 (_flash_kernel)
        m = functools.reduce(jnp.maximum, [
            jnp.max(x, axis=-1, keepdims=True) for x in scores])
        m = jnp.maximum(m, NEG_INF / 2)
        l_fin = acc = None
        for s, x in enumerate(scores):
            p = jnp.exp(x - m)
            v = v_refs[s][0]
            l_s = jnp.sum(p, axis=-1, keepdims=True)
            acc_s = jnp.dot(p.astype(v.dtype), v,
                            preferred_element_type=jnp.float32)
            l_fin = l_s if l_fin is None else l_fin + l_s
            acc = acc_s if acc is None else acc + acc_s
        o_ref[0] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:
            lse = jnp.where(
                l_fin > 0.0, m + jnp.log(jnp.maximum(l_fin, 1e-30)), -NEG_INF)
            # the packed plane, a lane-dense row a (head, q-block)
            lse_ref[h, qi, :] = jnp.transpose(lse, (1, 0))[0]

    bounded = jnp.logical_or(first < 0, (first + n) * b > k_end)
    pl.when(bounded)(lambda: strip(True))
    pl.when(jnp.logical_not(bounded))(lambda: strip(False))


def _band_bwd_kernel(klen_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                     dq_ref, dk_ref, dv_ref, ring, dk_scr, dv_scr, *, band,
                     scale, seq_k, causal_offset, window, group, nqb):
    """dQ, dK and dV of a windowed site in ONE call.  Grid (rows of
    `group` query heads that read one K/V head, k-blocks j, the row's heads
    h, the band's `n` q-blocks that see k-block j): step t takes q-block i =
    j - hi + t, whose strip holds block j as sub-block n - 1 - t.  The five
    matmuls and the one exp a score block are _flash_bwd_kernel's, the
    scores transposed as there.

    dK and dV of k-block j accumulate in VMEM over the row's heads and the
    q-blocks and leave ONCE, summed over them (a row is a K/V head's whole
    group wherever the ring fits, _band_bwd_heads).  dQ lives in a ring: a
    q-block takes its first contribution at t = n - 1 (k-block i + lo, the
    slot is assigned) and its last at t = 0 (k-block i + hi: it leaves, the
    ring's sum and the step's), so n - 1 blocks a head are live between two
    k-blocks and the block that leaves frees the slot of the one that
    enters.  The q-blocks whose strip starts before the first key enter at
    j = 0 by addition, into slots zeroed there.  A dQ block before the
    first q-block (keys the queries' first rows never see, Sk > Sq) is
    written to block 0 and written over when block 0 leaves; a k-block past
    the last one (the padded queries' diagonal) is masked whole."""
    import jax.experimental.pallas as pl

    n, b = band.n, band.block
    g, j, h, t = (pl.program_id(a) for a in range(4))
    qi = j - band.hi + t
    slots = max(n - 1, 1)
    slot = h * slots + jax.lax.rem(jnp.maximum(qi, 0), slots)
    k_end = jnp.minimum(seq_k, klen_ref[g * group + h].astype(jnp.int32))

    @pl.when(jnp.logical_and(h == 0, t == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if n > 1:
        @pl.when(jnp.logical_and(j == 0, t == 0))
        def _init_ring():
            for r in range(slots):
                ring[h * slots + r] = jnp.zeros(ring.shape[1:], jnp.float32)

    def _update(sub):
        """`sub`: the sub-block this step's k-block is in its q-block's
        strip where its masks are the constants', None where the keys' ends
        cut it and every compare is made (scalars)."""
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        nt = (((1,), (1,)), ((), ()))  # a @ b.T on the contracting dims
        st = jax.lax.dot_general(
            k, q, nt, preferred_element_type=jnp.float32) * scale
        pt = jnp.exp(st - lse_ref[0, qi, :].reshape(1, -1))
        if sub is None:
            newest = causal_offset - (band.lo + n - 1 - t) * b
            mask = _band_mask(st.shape, True, newest, newest - window + 1,
                              -j * b, k_end - j * b)
        else:
            newest = causal_offset - (band.lo + sub) * b
            mask = _band_mask(
                st.shape, True, newest if band.causal_cut[sub] else None,
                newest - window + 1 if band.window_cut[sub] else None)
        if mask is not None:
            pt = jnp.where(mask, pt, 0.0)
        dpt = jax.lax.dot_general(v_ref[0], do, nt,
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - dvec_ref[0, qi, :].reshape(1, -1))).astype(q.dtype)
        dv_scr[:] = dv_scr[:] + jnp.dot(
            pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dk_scr[:] = dk_scr[:] + jnp.dot(
            dst, q, preferred_element_type=jnp.float32)
        dq = jax.lax.dot_general(dst, k, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if n == 1:
            dq_ref[0] = (dq * scale).astype(dq_ref.dtype)
            return

        @pl.when(t == 0)
        def _leaves():
            dq_ref[0] = ((ring[slot] + dq) * scale).astype(dq_ref.dtype)

        @pl.when(t == n - 1)
        def _enters():
            ring[slot] = dq

        if n > 2:
            @pl.when(jnp.logical_and(t > 0, t < n - 1))
            def _adds():
                ring[slot] = ring[slot] + dq

    runs = jnp.logical_and(qi >= 0, qi < nqb)
    bounded = (j + 1) * b > k_end
    pl.when(jnp.logical_and(runs, bounded))(lambda: _update(None))
    free = jnp.logical_and(runs, jnp.logical_not(bounded))
    plain = [s for s in range(n)
             if not (band.window_cut[s] or band.causal_cut[s])]
    for s in sorted(set(range(n)) - set(plain)):
        pl.when(jnp.logical_and(free, t == n - 1 - s))(
            functools.partial(_update, s))
    if plain:       # consecutive: the cuts are at the strip's two ends
        pl.when(jnp.logical_and(free, jnp.logical_and(
            t >= n - 1 - plain[-1], t <= n - 1 - plain[0])))(
                functools.partial(_update, plain[0]))

    @pl.when(jnp.logical_and(h == group - 1, t == n - 1))
    def _finalize():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def band_fwd_vmem_bytes(block, strip, head_dim, num_q_blocks=1,
                        dtype="float32", emit_lse=True, v_dim=None,
                        group=1) -> int:
    """fwd_vmem_bytes of the band's forward call (_band_fwd_call): the
    double-buffered q and o blocks, `strip` K and `strip` V blocks and the
    group's packed logsumexp plane; it declares no scratch."""
    v_dim = head_dim if v_dim is None else v_dim
    blocks = ([((1, block, head_dim), dtype), ((1, block, v_dim), dtype)]
              + strip * [((1, block, head_dim), dtype),
                         ((1, block, v_dim), dtype)])
    if emit_lse:
        blocks.append(((group, num_q_blocks, block), "float32"))
    return 2 * sum(tile_padded_bytes(s, d) for s, d in blocks)


def band_fwd_working_set_bytes(block, strip, head_dim, num_q_blocks=1,
                               dtype="float32", emit_lse=True, v_dim=None,
                               group=1) -> int:
    """band_fwd_vmem_bytes plus the two fp32 planes of the strip a step
    holds between its matmuls, scores and probabilities [block, strip *
    block]: what _plan_band holds under the budget."""
    return (band_fwd_vmem_bytes(block, strip, head_dim, num_q_blocks, dtype,
                                emit_lse, v_dim, group)
            + 2 * strip * tile_padded_bytes((block, block), "float32"))


def band_bwd_vmem_bytes(block, strip, head_dim, num_q_blocks=1,
                        dtype="float32", v_dim=None, group=1) -> int:
    """What the band's backward call declares (_band_bwd_call): the
    double-buffered q, dO, k, v, dQ, dK, dV blocks and a head's packed lse
    and D planes, the fp32 accumulators of dK and dV and the ring of dQ,
    `strip` - 1 blocks a head of the group."""
    def tile(shape, dt=dtype):
        return tile_padded_bytes(shape, dt)

    v_dim = head_dim if v_dim is None else v_dim
    blocks = (3 * tile((1, block, head_dim))            # q, k, dK
              + 3 * tile((1, block, v_dim))             # dO, v, dV
              + tile((1, block, head_dim))              # dQ
              + 2 * tile((1, num_q_blocks, block), "float32"))
    scratch = (tile((group * max(strip - 1, 1), block, head_dim), "float32")
               + tile((block, head_dim), "float32")
               + tile((block, v_dim), "float32"))
    return 2 * blocks + scratch


def band_bwd_working_set_bytes(block, strip, head_dim, num_q_blocks=1,
                               dtype="float32", v_dim=None, group=1) -> int:
    """band_bwd_vmem_bytes plus the four fp32 [block, block] planes a step
    holds between its matmuls (bwd_working_set_bytes)."""
    return (band_bwd_vmem_bytes(block, strip, head_dim, num_q_blocks, dtype,
                                v_dim, group)
            + 4 * tile_padded_bytes((block, block), "float32"))


def _plan_band(sq, sk, window, working_set, a_strip_a_step):
    """The block length of a windowed site's band, from the shape: of the
    lengths both sequences are cut into (_block_lengths) whose
    `working_set(block, strip)` fits the plan's budget, the one whose grid
    computes the least, a step counted as the scores it computes beside
    _STEP_COST_SCORES; the longer where two tie.  `a_strip_a_step`: a step
    computes a q-block's whole strip (the forward), else one score block
    (the backward, whose steps before the first key do not run)."""
    def band(b):
        return _band(b, -(-sq // b), -(-sk // b), sk - sq, window)

    def cost(b):
        nqb, nkb = -(-sq // b), -(-sk // b)
        if a_strip_a_step:
            return nqb * (band(b).n * b * b + _STEP_COST_SCORES), -b
        run = nqb * nkb - sum(_skipped_steps(nqb, nkb, b, b, sk - sq, True,
                                             window))
        return run * (b * b + _STEP_COST_SCORES), -b

    lens = [b for b in _block_lengths(sq) if b in _block_lengths(sk)] or [128]
    fits = [b for b in lens if working_set(b, band(b).n) <= PLAN_VMEM_BUDGET]
    return min(fits or lens[:1], key=cost)


@functools.lru_cache(maxsize=128)
def _band_fwd_call(bgs, group, sqp, skp, d, dv, band, scale, seq_k,
                   causal_offset, window, dtype, interpret, emit_lse):
    """Memoized pallas_call of _band_kernel (see _fwd_call).  q and out
    are [bgs * group, sqp, .], a block a (head, q-block); K and V [bgs, skp,
    .] are handed in once a sub-block of the strip, each with the index map
    of its place (block i + lo + s of q-block i, held inside the keys: a
    sub-block outside them is masked whole, by its unclamped place); the
    logsumexp leaves as the group's packed plane [group, nqb, block],
    written a row a step and flushed when the K/V head advances."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n = band.block, band.n
    nqb, nkb = sqp // b, skp // b

    def head_block(g, i, h):
        return (g * group + h, i, 0)

    def strip_block(s):
        return lambda g, i, h: (g, jnp.clip(i + band.lo + s, 0, nkb - 1), 0)

    out_specs = [pl.BlockSpec((1, b, dv), head_block)]
    out_shape = [jax.ShapeDtypeStruct((bgs * group, sqp, dv),
                                      jnp.dtype(dtype))]
    if emit_lse:
        out_specs.append(
            pl.BlockSpec((group, nqb, b), lambda g, i, h: (g, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((bgs * group, nqb, b), jnp.float32))
    return pl.pallas_call(
        functools.partial(
            _band_kernel, band=band, scale=scale, seq_k=seq_k,
            causal_offset=causal_offset, window=window, group=group,
            emit_lse=emit_lse),
        grid=(bgs, nqb, group),
        in_specs=[pl.BlockSpec((bgs * group,), lambda g, i, h: (0,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, b, d), head_block)]
        + [pl.BlockSpec((1, b, d), strip_block(s)) for s in range(n)]
        + [pl.BlockSpec((1, b, dv), strip_block(s)) for s in range(n)],
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=compiler_params(
            3 * ("arbitrary",), band_fwd_working_set_bytes(
                b, n, d, nqb, dtype, emit_lse, dv, group)),
        interpret=interpret)


@functools.lru_cache(maxsize=128)
def _band_bwd_call(rows, group, shares, sqp, skp, steps, d, dv, band, scale,
                   seq_k, causal_offset, window, q_dtype, k_dtype, v_dtype,
                   interpret):
    """Memoized pallas_call of _band_bwd_kernel (see _bwd_call): `rows`
    rows of `group` query heads each, `shares` consecutive rows reading one
    K/V head (1 where a row is the K/V head's whole group).  The k-axis has
    `steps` blocks: the keys' and, where the padded queries' diagonal runs
    past them, as many more as let the last q-block leave; dK and dV are
    [rows, steps * block, .], summed over a row's heads, and the caller
    adds the shares up and cuts them to the keys."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n = band.block, band.n
    nqb, nkb = sqp // b, skp // b

    def q_block(g, j, h, t):
        return (g * group + h, jnp.clip(j - band.hi + t, 0, nqb - 1), 0)

    def k_in(g, j, h, t):
        return (g // shares, jnp.minimum(j, nkb - 1), 0)

    packed = pl.BlockSpec((1, nqb, b), lambda g, j, h, t: (g * group + h, 0,
                                                           0))
    return pl.pallas_call(
        functools.partial(
            _band_bwd_kernel, band=band, scale=scale, seq_k=seq_k,
            causal_offset=causal_offset, window=window, group=group,
            nqb=nqb),
        grid=(rows, steps, group, n),
        in_specs=[pl.BlockSpec((rows * group,), lambda g, j, h, t: (0,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, b, d), q_block),
                  pl.BlockSpec((1, b, d), k_in),
                  pl.BlockSpec((1, b, dv), k_in),
                  pl.BlockSpec((1, b, dv), q_block),
                  packed, packed],
        out_specs=[
            # the q-block that leaves at k-block j
            pl.BlockSpec((1, b, d), lambda g, j, h, t: (
                g * group + h, jnp.clip(j - band.hi, 0, nqb - 1), 0)),
            pl.BlockSpec((1, b, d), lambda g, j, h, t: (g, j, 0)),
            pl.BlockSpec((1, b, dv), lambda g, j, h, t: (g, j, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((rows * group, sqp, d), jnp.dtype(q_dtype)),
            jax.ShapeDtypeStruct((rows, steps * b, d), jnp.dtype(k_dtype)),
            jax.ShapeDtypeStruct((rows, steps * b, dv), jnp.dtype(v_dtype))],
        scratch_shapes=[
            pltpu.VMEM((group * max(n - 1, 1), b, d), jnp.float32),
            pltpu.VMEM((b, d), jnp.float32),
            pltpu.VMEM((b, dv), jnp.float32)],
        compiler_params=compiler_params(
            4 * ("arbitrary",), band_bwd_working_set_bytes(
                b, n, d, nqb, q_dtype, dv, group)),
        interpret=interpret)


def _band_counts(sq, sk, block, window):
    """(blocks of the square, above the diagonal, older than the window):
    what a band of `block` x `block` score blocks leaves out of the square
    the spans count in, over one (batch, head)."""
    nqb, nkb = -(-sq // block), -(-sk // block)
    return (nqb * nkb, *_skipped_steps(nqb, nkb, block, block, sk - sq, True,
                                       window))


def _pallas_band(q, k, v, klen, scale, window, block=None, interpret=False,
                 need_lse=True):
    """_pallas_flash of a causal call under a `window` shorter than its
    keys: (out, the packed logsumexp [B*H, num_q_blocks, block] or None) by
    _band_kernel at _plan_band's block (`block` pins it for a test or the
    probe).  Queries before the first key (Sq > Sk) see none: they are cut
    off and come back as zeros."""
    B, H, Sq, D = q.shape
    G, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    if Sq > Sk:
        out, lse = _pallas_band(q[:, :, Sq - Sk:], k, v, klen, scale, window,
                                block, interpret, need_lse)
        out = jnp.pad(out, ((0, 0), (0, 0), (Sq - Sk, 0), (0, 0)))
        if lse is not None:
            lse = jnp.pad(lse.reshape(B * H, -1)[:, :Sk],
                          ((0, 0), (Sq - Sk, 0)),
                          constant_values=-NEG_INF)[:, None]
        return out, lse
    group = H // G
    b = block or _plan_band(
        Sq, Sk, window, lambda b, n: band_fwd_working_set_bytes(
            b, n, D, -(-Sq // b), q.dtype, need_lse, Dv, group), True)
    q, k, v = _pad_seq(q, b), _pad_seq(k, b), _pad_seq(v, b)
    sqp, skp = q.shape[2], k.shape[2]
    band = _band(b, sqp // b, skp // b, Sk - Sq, window)
    blocks, above, older = _band_counts(Sq, Sk, b, window)
    # counted in score blocks of the square, as the block kernels' plans
    with span("flash.plan", sq=Sq, sk=Sk, head_dim=D, block_q=b, block_k=b,
              k_steps=blocks, k_steps_skipped=above + older, causal=1,
              window=window, kv_heads=G, chunks=1, skipped_causal=above,
              skipped_window=older, rows_per_step=1, layout="bhsd",
              form="band"):
        call = _band_fwd_call(B * G, group, sqp, skp, D, Dv, band, scale, Sk,
                              Sk - Sq, window, str(q.dtype), interpret,
                              need_lse)
        kf, vf = k.reshape(B * G, skp, D), v.reshape(B * G, skp, Dv)
        res = call(jnp.repeat(klen, H), q.reshape(B * H, sqp, D),
                   *(band.n * [kf]), *(band.n * [vf]))
    out = res[0].reshape(B, H, sqp, Dv)[:, :, :Sq]
    return out, (res[1] if need_lse else None)


def _band_bwd_heads(block, strip, head_dim, num_q_blocks, dtype, v_dim,
                    group):
    """The query heads of a K/V head's `group` that ONE row of the band's
    backward grid takes (their dK, dV summed in VMEM, a ring of dQ each):
    the most, a divisor of the group, whose working set fits the plan's
    budget; None where not even one head's does.  The whole group at the
    cell's shape (8 heads of 128 in bf16 under window 1024, blocks of 512:
    10.5 of 12 MiB); half of it where the operands are fp32 (12.25)."""
    return next((n for n in range(group, 0, -1) if group % n == 0
                 and band_bwd_working_set_bytes(
                     block, strip, head_dim, num_q_blocks, dtype, v_dim, n)
                 <= PLAN_VMEM_BUDGET), None)


def _band_bwd_plan(sq, sk, head_dim, dtype, block=None, v_dim=None,
                   window=None, group=1):
    """(_bwd_plan's counts for a windowed site, the heads a row of its grid
    takes): one call (`chunks` 1) of _band_bwd_kernel at _plan_band's block
    (or `block`, pinned) over rows of _band_bwd_heads' heads, the score
    blocks counted in the square as the forward's; the engine is Pallas
    where a block has _BWD_PALLAS_MIN_BLOCK_SCORES scores, XLA's recompute
    elsewhere."""
    rows = min(sq, sk)      # queries before the first key are cut off
    # a block length fits where one head's ring does
    b = block or _plan_band(
        rows, sk, window, lambda b, n: band_bwd_working_set_bytes(
            b, n, head_dim, -(-rows // b), dtype, v_dim, 1), False)
    blocks, above, older = _band_counts(rows, sk, b, window)
    heads = _band_bwd_heads(
        b, _band(b, -(-rows // b), -(-sk // b), sk - rows, window).n,
        head_dim, -(-rows // b), dtype, v_dim, group)
    return dict(sq=sq, sk=sk, head_dim=head_dim, block_q=b, block_k=b,
                steps=blocks, steps_skipped=above + older,
                engine=("pallas" if b * b >= _BWD_PALLAS_MIN_BLOCK_SCORES
                        else "xla"),
                window=window, chunks=1, skipped_causal=above,
                skipped_window=older, rows_per_step=1, layout="bhsd",
                form="band"), heads or 1


def _pallas_band_bwd(q, k, v, klen, out, lse, g, scale, window, block=None,
                     interpret=False, dlse=None):
    """(dq, dk, dv) of _pallas_band's call by ONE call of _band_bwd_kernel:
    D = rowsum(dO * O) and the lse's re-cut are made here as _bwd_rows makes
    them; dK and dV come back a K/V head's, summed over its group in the
    kernel (where the group's rings do not fit VMEM together, over shares
    of it, added up here in fp32: _band_bwd_heads)."""
    B, H, Sq, D = q.shape
    G, Sk, Dv = k.shape[1], k.shape[2], v.shape[3]
    if Sq > Sk:         # as _pallas_band: the first rows see no key
        cut = Sq - Sk
        dq, dk, dv = _pallas_band_bwd(
            q[:, :, cut:], k, v, klen, out[:, :, cut:],
            lse.reshape(B * H, -1)[:, None, cut:Sq], g[:, :, cut:], scale,
            window, block, interpret,
            None if dlse is None else dlse[:, :, cut:])
        return jnp.pad(dq, ((0, 0), (0, 0), (cut, 0), (0, 0))), dk, dv
    plan, heads = _band_bwd_plan(Sq, Sk, D, q.dtype, block, Dv, window,
                                 H // G)
    b, shares = plan["block_q"], H // G // heads
    with span("flash.bwd_plan", **dict(plan, engine="pallas", kv_heads=G)):
        # at lowering, as flash.plan
        qp, op, gp = _pad_seq(q, b), _pad_seq(out, b), _pad_seq(g, b)
        kp, vp = _pad_seq(k, b), _pad_seq(v, b)
        sqp, skp = qp.shape[2], kp.shape[2]
        band = _band(b, sqp // b, skp // b, Sk - Sq, window)
        steps = max(skp // b, sqp // b + band.hi)
        qf = qp.reshape(B * H, sqp, D)
        gf = gp.reshape(B * H, sqp, Dv).astype(qf.dtype)
        of = op.reshape(B * H, sqp, Dv)
        # a padded row's lse is the fully-masked row's: exp(s - lse) is 0
        lse = _repack(lse, Sq, b, -NEG_INF)
        call = _band_bwd_call(B * G * shares, heads, shares, sqp, skp, steps,
                              D, Dv, band, scale, Sk, Sk - Sq, window,
                              str(q.dtype), str(k.dtype), str(v.dtype),
                              interpret)
        dq, dk, dv = call(
            jnp.repeat(klen, H), qf, kp.reshape(B * G, skp, D),
            vp.reshape(B * G, skp, Dv), gf, lse,
            _packed_d(gf, of, dlse, Sq, b))
    if shares > 1:
        dk, dv = (x.reshape(B * G, shares, *x.shape[1:]).astype(
            jnp.float32).sum(1).astype(x.dtype) for x in (dk, dv))
    return (dq.reshape(B, H, sqp, D)[:, :, :Sq],
            dk.reshape(B, G, steps * b, D)[:, :, :Sk],
            dv.reshape(B, G, steps * b, Dv)[:, :, :Sk])


def _pallas_backward(q, k, v, causal, force, window=None) -> bool:
    """Whether this call's backward runs the Pallas kernel (and so its
    forward emits lse): by the shape under "auto"/"pallas", always under
    "interpret" (the CPU tests' door), never under "jax"."""
    if force == "interpret":
        return True
    return use_pallas(force) and _bwd_plan(
        q.shape[2], k.shape[2], q.shape[3], q.dtype, causal,
        v_dim=v.shape[3], window=window, bh=_packable_rows(q, k),
        group=q.shape[1] // k.shape[1],
        site_bh=q.shape[0] * q.shape[1])["engine"] == "pallas"


def _forward(q, k, v, klen, causal, scale, force, need_lse, window=None):
    """(out, packed lse or None) by the engine `force` names."""
    if wants_kernels(force):
        return _pallas_flash(q, k, v, klen, causal, scale,
                             interpret=(force == "interpret"),
                             need_lse=need_lse, window=window)
    return _reference_attention(
        q, k, v, causal, scale, k_lengths=klen.astype(jnp.int32),
        window=window), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, klen, causal, scale, force, window):
    # klen rides as float32 so custom_vjp treats it uniformly (zero grad)
    return _forward(q, k, v, klen, causal, scale, force, False, window)[0]


KEPT = ("out", "lse")   # of _forward, what _flash_fwd keeps
_BITS = {2: jnp.uint16, 4: jnp.uint32}     # an output's bits, by its width


def kept(q, k, v, causal, window=None, force="auto", heads=None) -> tuple:
    """What a call of this shape keeps through the recomputation of the
    unit around it: KEPT where its backward is the Pallas kernel (the
    forward then has the logsumexp in hand), nothing where it is the XLA
    recompute, which reads neither.  `heads`: the operands are heads-last,
    as flash_attention takes them."""
    if heads is not None:   # heads-last: as given, or transposed to here
        if takes_heads_last(q, k, v, heads, window, force):
            return KEPT
        q, k, v = heads_first_shapes(q, k, v, heads)
    if window is not None and window >= k.shape[2]:
        window = None           # as flash_attention reads it
    return KEPT if _pallas_backward(q, k, v, causal, force, window) else ()


def heads_first_shapes(q, k, v, heads):
    """The shapes [B, H, Sq, D], [B, G, Sk, D], [B, G, Sk, Dv] of heads-last
    operands q [B, Sq, heads * D], k [B, Sk, G * D], v [B, Sk, G * Dv]:
    what flash_attention hands its heads-first kernels where the heads-last
    ones do not take a call, for `kept` and `kept_bytes`."""
    head_dim = q.shape[2] // heads
    kv_heads = k.shape[2] // head_dim
    return tuple(jax.ShapeDtypeStruct(
        (x.shape[0], n, x.shape[1], x.shape[2] // n), x.dtype)
        for x, n in ((q, heads), (k, kv_heads), (v, kv_heads)))


def kept_bytes(q, v) -> int:
    """What a site that keeps holds, of q [B, H, S, D] and v [B, G, S, Dv]:
    out [B, H, S, Dv] in q's dtype and the rows' logsumexp fp32 (its packed
    plane, which is padded where S is no multiple of the forward's
    q-block)."""
    B, H, S, _ = q.shape
    return B * H * S * (v.shape[3] * q.dtype.itemsize + 4)


def _flash_fwd(q, k, v, klen, causal, scale, force, window):
    # the XLA recompute backward holds neither O nor L as residuals, and
    # its forward skips the lse HBM write entirely
    out, lse = _forward(
        q, k, v, klen, causal, scale, force,
        _pallas_backward(q, k, v, causal, force, window), window)
    if lse is None:
        return out, (q, k, v, klen, None, None)
    # an O(S^2) pass for two O(S) arrays: under a recomputed unit the
    # backward reads the first forward's, and the forward runs once.  `out`
    # is kept as its bits: on a floating-point value that a unit saves jax
    # puts a reduce_precision to the value's own precision, which XLA runs
    # as a pass over it (0.7 ms a layer at 32 heads x 16384, PERF.md PR 44)
    # and a kernel's output, written at its own width, has no use for
    bits, lse = keep(jax.lax.bitcast_convert_type(
        out, _BITS[out.dtype.itemsize]), lse)
    return (jax.lax.bitcast_convert_type(bits, out.dtype),
            (q, k, v, klen, bits, lse))


def _flash_bwd(causal, scale, force, window, res, g):
    q, k, v, klen, bits, lse = res
    with jax.named_scope("flash.bwd"):
        if lse is not None:
            dq, dk, dv = _pallas_flash_bwd(
                q, k, v, klen, jax.lax.bitcast_convert_type(bits, q.dtype),
                lse, g, causal, scale,
                interpret=(force == "interpret"), window=window,
            )
            return dq, dk, dv, jnp.zeros_like(klen)
        if use_pallas(force):
            site_bh = q.shape[0] * q.shape[1]
            scores = _xla_bwd_score_bytes(site_bh, q.shape[2], k.shape[2])
            if scores > _XLA_BWD_MAX_SCORE_BYTES:
                raise ValueError(
                    f"flash_attention: no Pallas backward plan takes q "
                    f"{q.shape} over k {k.shape} (_bwd_plan), and the XLA "
                    f"recompute backward would materialise {scores / 1e9:.1f}"
                    " GB of fp32 scores")
            # at lowering, beside flash.plan: the site keeps the XLA engine
            with span("flash.bwd_plan", **_bwd_plan(
                    q.shape[2], k.shape[2], q.shape[3], q.dtype, causal,
                    v_dim=v.shape[3], window=window,
                    bh=_packable_rows(q, k), group=q.shape[1] // k.shape[1],
                    site_bh=site_bh), kv_heads=k.shape[1]):
                pass
        # recompute-backward: differentiate the reference formulation
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _reference_attention(
                q_, k_, v_, causal, scale, k_lengths=klen.astype(jnp.int32),
                window=window),
            q, k, v,
        )
        dq, dk, dv = vjp(g)
        return dq, dk, dv, jnp.zeros_like(klen)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _lse_rows(packed, shape):
    """The packed logsumexp plane of a forward over q [B, H, Sq, .]
    (`shape`'s first three) as [B, H, Sq]: NEG_INF where a row saw no key
    (the kernel writes -NEG_INF there, which keeps its backward's
    exp(S - L) at 0)."""
    B, H, Sq = shape[:3]
    rows = packed.reshape(B, H, -1)[:, :, :Sq]
    return jnp.where(rows >= -NEG_INF / 2, NEG_INF, rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_lse(q, k, v, klen, causal, scale, force, window):
    """_flash that also hands out the rows' logsumexp [B, H, Sq] fp32, and
    takes a cotangent for it: the Pallas engines only (flash_attention
    sends a call that no kernel takes to _reference_attention, which jax
    differentiates as it is)."""
    out, lse = _forward(q, k, v, klen, causal, scale, force, True, window)
    return out, _lse_rows(lse, q.shape)


def _flash_lse_fwd(q, k, v, klen, causal, scale, force, window):
    out, lse = _forward(q, k, v, klen, causal, scale, force, True, window)
    if not _pallas_backward(q, k, v, causal, force, window):
        return (out, _lse_rows(lse, q.shape)), (q, k, v, klen, None, None)
    # kept as _flash_fwd keeps them: both outputs are made of the two
    bits, lse = keep(jax.lax.bitcast_convert_type(
        out, _BITS[out.dtype.itemsize]), lse)
    return ((jax.lax.bitcast_convert_type(bits, out.dtype),
             _lse_rows(lse, q.shape)), (q, k, v, klen, bits, lse))


def _flash_lse_bwd(causal, scale, force, window, res, cts):
    """dS = P (dP - D + dlse): a row's logsumexp moves with its scores by
    their probabilities, so its cotangent enters where D = rowsum(dO O)
    leaves, and the kernel runs as it is on D - dlse (_bwd_rows).  A row
    that saw no key has P = 0 and takes nothing."""
    q, k, v, klen, bits, lse = res
    g, dlse = cts
    with jax.named_scope("flash.bwd"):
        if lse is not None:
            dq, dk, dv = _pallas_flash_bwd(
                q, k, v, klen, jax.lax.bitcast_convert_type(bits, q.dtype),
                lse, g, causal, scale, interpret=(force == "interpret"),
                window=window, dlse=dlse)
        else:
            _, vjp = jax.vjp(
                lambda q_, k_, v_: _reference_attention(
                    q_, k_, v_, causal, scale,
                    k_lengths=klen.astype(jnp.int32), window=window,
                    with_lse=True), q, k, v)
            dq, dk, dv = vjp((g, dlse))
    return dq, dk, dv, jnp.zeros_like(klen)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# Heads-last (PR 57; the module docstring says why): q, k, v as the
# projections write them, [B, S, H * D], taken as they lie where a head is
# one block and transposed to the kernels above everywhere else.

class _HeadsLastRows(NamedTuple):
    """The BATCH rows a grid step of the heads-last kernels takes."""
    forward: int            # with the logsumexp: a training step's
    forward_only: int       # without it
    backward: int


def _heads_last_rows(batch, sq, sk, heads, head_dim, dtype, interpret=False):
    """The batch rows a grid step takes (_HeadsLastRows) where the
    heads-last kernels take a call of this shape, None where they do not
    and the call is transposed to heads-first.  They take: heads that tile the lanes (a head 128 / n
    lanes, a batch row whole 128-lane tiles), whole 128-row tiles of
    queries and keys, ONE block a head in both directions with a batch row
    of them inside the plan's share of VMEM, and a backward step that the
    engine rule (_BWD_PALLAS_MIN_BLOCK_SCORES) gives the Pallas kernel.  It
    reads the shape, nothing else."""
    if (LANES % head_dim or (heads * head_dim) % LANES or sq % LANES
            or sk % LANES):
        return None

    def fwd(lse):
        return lambda n: fwd_working_set_bytes(
            sq, sk, head_dim, 1, dtype, lse, None, n, heads)

    def bwd(n):
        return bwd_working_set_bytes(sq, sk, head_dim, 1, dtype, None, n,
                                     heads)

    if max(fwd(True)(1), bwd(1)) > PLAN_VMEM_BUDGET:
        return None
    rows = _HeadsLastRows(*(_rows_per_step(batch, True, ws)
                            for ws in (fwd(True), fwd(False), bwd)))
    if (not interpret and sq * sk * heads * rows.backward
            < _BWD_PALLAS_MIN_BLOCK_SCORES):
        return None
    return rows


def takes_heads_last(q, k, v, heads, window=None, force="auto"):
    """Whether flash_attention(q [B, Sq, heads * D], k, v, heads=heads) runs
    the heads-last kernels (`layout` bshd on `flash.plan`) or transposes to
    the heads-first ones: by the shape (_heads_last_rows), no window, a K/V
    head a query head of one width, and an engine that is Pallas."""
    if not wants_kernels(force):
        return False
    if window is not None and window < k.shape[1]:
        return False
    if not (q.shape[2] == k.shape[2] == v.shape[2]) or q.shape[2] % heads:
        return False
    return _heads_last_rows(
        q.shape[0], q.shape[1], k.shape[1], heads, q.shape[2] // heads,
        str(q.dtype), force == "interpret") is not None


def _bshd_specs(batch, rows_per_step):
    """(the [B] valid key counts whole in SMEM, rows(length, width): the
    block of `rows_per_step` batch rows of a [B, length, width] operand)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def rows(length, width):
        return pl.BlockSpec((rows_per_step, length, width),
                            lambda b: (b, 0, 0))

    return pl.BlockSpec((batch,), lambda b: (0,),
                        memory_space=pltpu.SMEM), rows


@functools.lru_cache(maxsize=128)
def _fwd_bshd_call(batch, sq, sk, heads, d, causal, scale, dtype, interpret,
                   emit_lse, rows_per_step):
    """Memoized pallas_call of _flash_bshd_kernel (see _fwd_call), jitted:
    a body that lays out sixteen heads in Python is traced once a shape and
    not once a site (kernels/kda_mix.py: PERF.md PR 49)."""
    import jax.experimental.pallas as pl

    n = rows_per_step
    klen, rows = _bshd_specs(batch, n)
    out_specs = [rows(sq, heads * d)]
    out_shape = [jax.ShapeDtypeStruct((batch, sq, heads * d),
                                      jnp.dtype(dtype))]
    if emit_lse:
        out_specs.append(rows(heads, sq))
        out_shape.append(
            jax.ShapeDtypeStruct((batch, heads, sq), jnp.float32))
    return jax.jit(pl.pallas_call(
        functools.partial(
            _flash_bshd_kernel, causal=causal, scale=scale, seq_k=sk,
            causal_offset=sk - sq, rows_per_step=n, heads=heads),
        grid=(batch // n,),
        in_specs=[klen, rows(sq, heads * d), rows(sk, heads * d),
                  rows(sk, heads * d)],
        out_specs=out_specs, out_shape=out_shape, interpret=interpret))


@functools.lru_cache(maxsize=128)
def _bwd_bshd_call(batch, sq, sk, heads, d, causal, scale, q_dtype, k_dtype,
                   v_dtype, interpret, with_dlse, rows_per_step):
    """Memoized pallas_call of _flash_bwd_bshd_kernel (see _bwd_call),
    jitted as _fwd_bshd_call is."""
    import jax.experimental.pallas as pl

    n = rows_per_step
    klen, rows = _bshd_specs(batch, n)
    q_rows, k_rows, plane = (rows(sq, heads * d), rows(sk, heads * d),
                             rows(heads, sq))
    return jax.jit(pl.pallas_call(
        functools.partial(
            _flash_bwd_bshd_kernel, causal=causal, scale=scale, seq_k=sk,
            causal_offset=sk - sq, rows_per_step=n, heads=heads,
            with_dlse=with_dlse),
        grid=(batch // n,),
        in_specs=[klen, q_rows, k_rows, k_rows, q_rows, q_rows, plane]
        + [plane] * with_dlse,
        out_specs=[q_rows, k_rows, k_rows],
        out_shape=[
            jax.ShapeDtypeStruct((batch, sq, heads * d), jnp.dtype(q_dtype)),
            jax.ShapeDtypeStruct((batch, sk, heads * d), jnp.dtype(k_dtype)),
            jax.ShapeDtypeStruct((batch, sk, heads * d), jnp.dtype(v_dtype))],
        interpret=interpret))


def _pallas_flash_bshd(q, k, v, klen, heads, causal, scale, interpret=False,
                       need_lse=True, rows_per_step=None):
    """_pallas_flash of heads-last operands the kernels take as given
    (takes_heads_last): q [B, Sq, heads * D], k, v [B, Sk, heads * D];
    (out [B, Sq, heads * D], the logsumexp [B, heads, Sq] fp32 or None).
    No transposition, no padding, no reshape.  `rows_per_step` (batch rows)
    pins the plan for a test or the probe."""
    B, Sq, width = q.shape
    Sk, D = k.shape[1], width // heads
    if rows_per_step is None:
        rows = _heads_last_rows(B, Sq, Sk, heads, D, str(q.dtype), interpret)
        rows_per_step = rows.forward if need_lse else rows.forward_only
    with span("flash.plan", sq=Sq, sk=Sk, head_dim=D, block_q=Sq, block_k=Sk,
              k_steps=1, k_steps_skipped=0, causal=int(causal), window=0,
              kv_heads=heads, chunks=1, skipped_causal=0, skipped_window=0,
              rows_per_step=rows_per_step, layout="bshd", form="blocks"):
        res = _fwd_bshd_call(B, Sq, Sk, heads, D, causal, scale, str(q.dtype),
                             interpret, need_lse, rows_per_step)(klen, q, k, v)
    return res[0], (res[1] if need_lse else None)


def _pallas_flash_bwd_bshd(q, k, v, klen, out, lse, g, heads, causal, scale,
                           interpret=False, rows_per_step=None, dlse=None):
    """(dq, dk, dv) of _pallas_flash_bshd's call, each in its operand's
    layout and dtype.  D = rowsum(dO * O) is the kernel's own work (`out`
    is its operand); `dlse` [B, heads, Sq] is the cotangent of the
    logsumexp where the call handed it out."""
    B, Sq, width = q.shape
    Sk, D = k.shape[1], width // heads
    if rows_per_step is None:
        rows_per_step = _heads_last_rows(B, Sq, Sk, heads, D, str(q.dtype),
                                         interpret).backward
    with span("flash.bwd_plan", sq=Sq, sk=Sk, head_dim=D, block_q=Sq,
              block_k=Sk, steps=1, steps_skipped=0, engine="pallas", window=0,
              chunks=1, skipped_causal=0, skipped_window=0,
              rows_per_step=rows_per_step, kv_heads=heads, layout="bshd",
              form="blocks"):
        call = _bwd_bshd_call(B, Sq, Sk, heads, D, causal, scale,
                              str(q.dtype), str(k.dtype), str(v.dtype),
                              interpret, dlse is not None, rows_per_step)
        planes = (lse,) if dlse is None else (lse, dlse.astype(jnp.float32))
        return call(klen, q, k, v, out, g.astype(q.dtype), *planes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_bshd(q, k, v, klen, heads, causal, scale, interpret, return_lse):
    """_flash / _flash_lse of heads-last operands on the heads-last
    kernels: out [B, Sq, heads * D], with `return_lse` (out, the rows'
    logsumexp [B, heads, Sq])."""
    out, lse = _pallas_flash_bshd(q, k, v, klen, heads, causal, scale,
                                  interpret, need_lse=return_lse)
    return (out, _lse_rows(lse, lse.shape)) if return_lse else out


def _flash_bshd_fwd(q, k, v, klen, heads, causal, scale, interpret,
                    return_lse):
    out, lse = _pallas_flash_bshd(q, k, v, klen, heads, causal, scale,
                                  interpret)
    # kept as _flash_fwd keeps them
    bits, lse = keep(jax.lax.bitcast_convert_type(
        out, _BITS[out.dtype.itemsize]), lse)
    out = jax.lax.bitcast_convert_type(bits, out.dtype)
    return ((out, _lse_rows(lse, lse.shape)) if return_lse else out,
            (q, k, v, klen, bits, lse))


def _flash_bshd_bwd(heads, causal, scale, interpret, return_lse, res, cts):
    q, k, v, klen, bits, lse = res
    g, dlse = cts if return_lse else (cts, None)
    with jax.named_scope("flash.bwd"):
        dq, dk, dv = _pallas_flash_bwd_bshd(
            q, k, v, klen, jax.lax.bitcast_convert_type(bits, q.dtype), lse,
            g, heads, causal, scale, interpret, dlse=dlse)
    return dq, dk, dv, jnp.zeros_like(klen)


_flash_bshd.defvjp(_flash_bshd_fwd, _flash_bshd_bwd)


def _heads_first(x, heads):
    """x [B, S, heads * D] as [B, heads, S, D]."""
    B, S, width = x.shape
    return x.reshape(B, S, heads, width // heads).transpose(0, 2, 1, 3)


def _heads_last(x):
    """x [B, H, S, D] as [B, S, H * D]."""
    B, H, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)


def merge_attention(parts):
    """One softmax over several key sets from each set's own: `parts` is
    [(out [B, H, Sq, Dv], lse [B, H, Sq])], a set's attention output and
    logsumexp (flash_attention's `return_lse`); the result is the output
    of one softmax over the union of the sets, out_i weighted by exp(lse_i
    - logsumexp_i lse_i), in fp32, in the first output's dtype.  An empty
    set (lse NEG_INF) weighs 0; one set at least has to see a key."""
    lses = jnp.stack([lse for _, lse in parts])
    weights = jax.nn.softmax(lses, axis=0)[..., None]
    merged = sum(w * out.astype(jnp.float32)
                 for w, (out, _) in zip(weights, parts))
    return merged.astype(parts[0][0].dtype)


def flash_attention(q, k, v, causal=False, scale=None, k_lengths=None,
                    force="auto", window=None, return_lse=False, heads=None):
    """q: [B, H, Sq, D]; k/v: [B, G, Sk, .] with G = H or a divisor of it
    (grouped-query attention: query head j reads key/value head
    j // (H / G); K and V are never repeated).  k_lengths: optional [B]
    valid key counts (key-padding mask).  window: under `causal`, a query
    sees the `window` keys that end at its diagonal (itself and the
    window - 1 before it); None: all of them.

    heads: the operands are heads-LAST, q [B, Sq, heads * D], k/v [B, Sk,
    G * .], the arrays a model's projections write, and so is the output,
    [B, Sq, heads * Dv].  Where a head is one block the kernels take them as
    they lie (takes_heads_last; `flash.plan` says `layout` bshd);
    everywhere else they are transposed here, to the call above and back:
    the same numbers at every shape.

    force: "auto" (pallas on TPU, jax elsewhere), "pallas", "interpret"
    (pallas interpreter — CPU testing), or "jax".  The backward's engine
    is read from the shape (_bwd_plan): never from a flag.

    return_lse: (out, the rows' logsumexp [B, H, Sq] fp32, NEG_INF where a
    row sees no key, k_lengths 0) and both take a cotangent, so that
    several calls over disjoint key sets merge into one softmax exactly
    (merge_attention), forward and backward."""
    if heads is not None:
        if q.shape[2] % heads or k.shape[2] % (q.shape[2] // heads):
            raise ValueError(f"flash_attention: {heads} heads-last heads in "
                             f"q {q.shape} over k {k.shape}")
        kv_heads = k.shape[2] // (q.shape[2] // heads)
        if not takes_heads_last(q, k, v, heads, window, force):
            res = flash_attention(
                _heads_first(q, heads), _heads_first(k, kv_heads),
                _heads_first(v, kv_heads), causal, scale, k_lengths, force,
                window, return_lse)
            return ((_heads_last(res[0]), res[1]) if return_lse
                    else _heads_last(res))
    elif q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(f"flash_attention: {q.shape[1]} query heads over "
                         f"{k.shape[1]} key and {v.shape[1]} value heads")
    if window is not None:
        window = int(window)
        if not causal or window < 1:
            raise ValueError("flash_attention: `window` needs causal=True "
                             f"and at least 1 key, got {window}")
        if window >= k.shape[-2]:
            window = None       # every causal key is inside it
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1] // (heads or 1))
    if k_lengths is None:
        klen = jnp.full((q.shape[0],), k.shape[-2], dtype=jnp.float32)
    else:
        klen = jnp.asarray(k_lengths, dtype=jnp.float32).reshape(-1)
    if heads is not None:
        return _flash_bshd(q, k, v, klen, heads, causal, float(scale),
                           force == "interpret", return_lse)
    if not return_lse:
        return _flash(q, k, v, klen, causal, float(scale), force, window)
    if wants_kernels(force):
        return _flash_lse(q, k, v, klen, causal, float(scale), force, window)
    return _reference_attention(
        q, k, v, causal, float(scale), k_lengths=klen.astype(jnp.int32),
        window=window, with_lse=True)
