"""Decode attention over a paged KV cache (serving/kvcache.py pool).

The decode-step contract: one query token per sequence (Sq=1) attends to
that sequence's cached keys/values, which live scattered across
fixed-size pages of a shared pool.  Three implementations sit behind ONE
call signature so the serving loop never changes when the selection
flips:

- ``impl="reference"``: gather the sequence's pages into a contiguous
  [B, H, S, D] view (S = max pages * page_size over the batch) and run
  the existing flash_attention ragged ``k_lengths`` tier — the exact
  masking contract tests/test_serving.py's decode-parity suite pins
  down.  The gather materializes O(B*S*D) bytes per layer per token
  (pages read + contiguous copy written + copy read back by attention
  = ~3x the pallas path's traffic), which dominates decode bytes/step
  as contexts grow; fine for CPU correctness and small batches.

- ``impl="pallas"`` (Ragged Paged Attention, arxiv 2604.15464): a
  kernel whose grid walks each sequence's page table — prefetched to
  SMEM via ``PrefetchScalarGridSpec``, so the table entry indexes the
  DMA of the NEXT page while the current one computes — and streams
  K/V pages straight from the pool arrays in HBM into the
  online-softmax recurrence proven in flash_attention._flash_kernel
  (VMEM-scratch m/l/acc, running-max floor NEG_INF/2).  No contiguous
  KV copy ever exists: per layer per token the path reads each live
  page exactly once.  Ragged tails (and the zero-padded tail of short
  sequences' page tables) are masked by position against ``lengths``.

- ``impl="interpret"``: the same pallas kernel under the Pallas
  interpreter — CPU-testable parity against reference, the tier-1
  contract suite.

GROUPED-QUERY ATTENTION (ISSUE 12).  The pool may hold H_kv < H_q
heads (GQA/MQA): query head ``h`` reads KV head ``h // (H_q/H_kv)``.
The kernel grid is (B, H_kv, pages) — each KV page block is streamed
from HBM ONCE per sequence while ALL H_q/H_kv query heads of the group
score against it in VMEM: the group rides the padded query-row dim
(one fp32 sublane holds up to 8 group members; larger groups pad to
the next sublane multiple), and the online-softmax scratch state is
per ROW, i.e. per query head — the rows never mix.  Decode KV traffic
and pool storage both shrink H_q/H_kv x.  ``H_q % H_kv != 0`` raises
the typed :class:`GroupedHeadsError` — it is a config error, not an
envelope miss, so it never silently falls back.

INT8 KV PAGES.  An int8 pool carries one fp32 scale per (layer, page)
for each of K and V (amax quantization — serving/kvcache.py owns the
write-side math).  The kernel takes the layer's ``[P]`` scale rows as
two more scalar-prefetch operands and fuses dequantization into the
page-stream inner loop: the SMEM page-table entry that indexes the
page's DMA also indexes its scale, so ``k_f32 = k_i8 * scale`` costs
one VPU multiply per streamed block and HBM still only ever sees the
1-byte elements — KV bytes halve again vs bf16.  The reference gather
dequantizes the same way (``gather_kv_pages(..., scales=)``).

Selection (a measured Mosaic
envelope, explicit fallback, flag-driven): ``FLAGS_serving_paged_impl``
(auto|reference|pallas|interpret) supplies the default; ``auto`` picks
pallas on TPU when ``pallas_paged_viable`` accepts the pool geometry
and reference everywhere else; an explicit ``pallas`` outside the
envelope falls back to reference with a one-time log, never a Mosaic
compile bomb.  The envelope: head_dim a lane multiple (128) and
page_size a sublane multiple (8 fp32 / 16 bf16 / 32 int8), so every
K/V page block is natively (sublane, lane)-tiled — the constraint
class that produced the flash residual-layout and unaligned-window
'non-native tiling' chip failures.

Pool layout is KERNEL-NATIVE by default: [H_kv, P, page_size, D] per
layer (heads outermost), so a (1, 1, page_size, D) page block's last
two dims are exactly (page_size, head_dim) — Mosaic-tileable without
relayout.  The decode query rides as a [B, H_kv, G_pad, D] block (the
group's rows zero-padded to a whole fp32 sublane; padded rows compute
discarded lanes) for the same reason.

LAYOUT CONSUMPTION (ISSUE 14 — the ROADMAP "layout tax" erased).  When
the pool is scatter-updated INSIDE the same program (the SPMD decode
step's in-place K/V append), XLA prefers the {3,0,2,1}-major layout on
the [H_kv, P, ps, D] slice — physical [P, ps, H_kv, D], the order the
one-row-per-token append writes — and a kernel pinning row-major
forces a relayout copy-pair around the custom call.
``pool_layout="xla"`` makes the lowering CONSUME the preferred layout
instead: the K/V operands are re-viewed as [P, ps, H_kv*D] (a
transpose+reshape that is physically the identity on the preferred
layout, so XLA folds it to a bitcast), the page block becomes
(1, ps, D) — still natively (sublane, lane)-tiled — and the index map
picks the head's D-column window on the packed feature dim.
serving/distributed/sharded.py pins the same layout at the program
boundary (``kv_pool_layout``), so the donated pool lives relayout-free
across its serving life; the banked ``sharded_decode`` zoo entry holds
relayout-copy-pair at 0 and the ~20% bytes/step win.

MULTI-TOKEN VERIFY (ISSUE 13 — speculative decoding).  The decode
query generalizes to ``Sq = 1 + d`` rows per sequence: the last
committed token plus ``d`` drafted continuation tokens, verified in ONE
step.  ``q_lengths`` ([B] int32, ragged — sequences in the same batch
may carry different draft depths) joins ``lengths`` as one more
scalar-prefetch operand, and query row ``t`` of sequence ``b`` sits at
absolute position ``lengths[b] - q_lengths[b] + t`` — the causal
frontier INSIDE the draft block, masked in-kernel exactly like the
ragged tail.  The payoff is the whole point of speculation: the page
walk is UNCHANGED — each live KV page still streams from HBM exactly
once per (sequence, KV head) regardless of d — so verify-step KV bytes
are flat in d while the step commits up to d+1 tokens
(``attention_bytes_per_step(q_tokens=)`` prices it; the only term that
grows is the query/output block).  Query rows ride the same padded
sublane block as the GQA group, GROUP-MAJOR: row ``g * Sq + t`` is
(group member g, draft token t) — the layout that folds and unfolds as
pure reshapes, so no relayout copy brackets the custom call — padded
to a whole sublane, per-row online-softmax state, sliced off
host-side.  ``Sq == 1`` keeps the exact pre-ISSUE-13 kernel (no
q_lengths operand), so the banked zoo entries are byte-identical.

LONG CONTEXT (ISSUE 20).  Past ~8k tokens the SCALAR operands start to
hurt: a 128k sequence is ~1k pages, so the flat [B, max_pages] table is
kilobytes of SMEM per call and an int8 pool adds two POOL-sized [P]
fp32 scale rows on top.  Two extensions keep the envelope flat:

- **Two-level page tables** (:class:`TwoLevelTables`): the prefetch
  operand becomes a compact L1 directory [B, n_l1] over shared L2
  table blocks [n_blocks, bs] — the kernel's index map does the nested
  SMEM read ``l2[l1[b, p//bs], p%bs]`` — plus a parallel [n_blocks,
  bs] block of absolute page START positions.  int8 scales ride as
  [n_blocks, bs] blocks gathered through ``l2`` outside the kernel, so
  SMEM grows with the blocks the batch actually WALKS, never with pool
  size.  Explicit starts (``PAD_START`` sentinel in padding slots) are
  what let an evicted sequence walk a compacted table: position masking
  reads the page's true start from SMEM instead of assuming
  ``p * page_size``.

- **Sliding-window + attention-sink masking** (``windows``/``sinks``,
  [B] int32 per-request): key page with start ``s_p`` is visible to the
  query at absolute position ``p`` iff ``s_p < sinks[b]`` (an
  attention-sink page) or ``s_p + page_size > p + 1 - windows[b]``
  (page overlaps the recent window) — PAGE-granular, exactly the rule
  serving/kvcache.py uses to DROP interior pages, so the kernel mask
  and the pool's eviction are the same contract and the walk shrinks
  to sinks + window regardless of context length.  Non-windowed rows
  pass ``windows = PAD_START`` (everything visible).  All of it is
  opt-in: absent operands keep the banked entries byte-identical.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math

import jax
import jax.numpy as jnp

from .engine import on_tpu
from .flash_attention import NEG_INF, flash_attention

__all__ = [
    "GroupedHeadsError",
    "PAD_START",
    "TwoLevelTables",
    "attention_bytes_per_step",
    "fallback_count",
    "gather_kv_pages",
    "paged_decode_attention",
    "pallas_paged_viable",
    "repeat_kv",
    "resolve_paged_impl",
]

_IMPLS = ("auto", "reference", "pallas", "interpret")

# sentinel start position for padding slots of an explicit-starts
# operand (two-level L2 blocks, or a flat page_starts row past the
# sequence's live pages): far past any real length, so the position
# mask hides the dummy page-0 DMA exactly like the zero-padded flat
# table tail
PAD_START = 0x3FFFFFFF


@dataclasses.dataclass(frozen=True)
class TwoLevelTables:
    """Two-level page-table view for long contexts (ISSUE 20).

    A flat [B, max_pages] table prefetches B*max_pages SMEM words per
    call and an int8 pool adds two POOL-sized [P] fp32 scale rows — at
    128k (~1k pages/seq) the scalar operands themselves strain SMEM.
    This view prefetches a compact L1 directory over shared L2 table
    BLOCKS instead, so SMEM grows with the blocks the batch walks:

    - ``l1`` [B, n_l1] int32: entry j of row b names the L2 block
      holding that sequence's table entries [j*bs, (j+1)*bs)
    - ``l2`` [n_blocks, bs] int32: page ids (dummy page 0 in padding
      slots — fully masked by position)
    - ``starts`` [n_blocks, bs] int32: absolute token position of each
      walked page's slot 0 (:data:`PAD_START` in padding slots).
      Explicit starts — not ``p * page_size`` — are what let an
      EVICTED sequence walk a compacted table: live pages keep their
      true positions for the mask.
    - ``block_size``: bs, the L2 block width.

    The kernel grid walks ``n_l1 * bs`` page slots; its index maps do
    the nested SMEM read ``l2[l1[b, p // bs], p % bs]``.  Per-page int8
    scales ride as [n_blocks, bs] blocks gathered through ``l2``
    OUTSIDE the kernel (``scales[l2]``) — block-sized SMEM, never
    pool-sized.  serving/kvcache.py builds the view host-side
    (``KVCachePool.two_level_tables``)."""

    l1: object
    l2: object
    starts: object
    block_size: int

    @property
    def max_pages(self) -> int:
        return self.l1.shape[1] * self.block_size

    def flatten(self):
        """(tables [B, max_pages], starts [B, max_pages]) flat views —
        what the reference gather arm consumes."""
        l1 = jnp.asarray(self.l1, jnp.int32)
        l2 = jnp.asarray(self.l2, jnp.int32)
        st = jnp.asarray(self.starts, jnp.int32)
        b, n_l1 = l1.shape
        return (l2[l1].reshape(b, n_l1 * self.block_size),
                st[l1].reshape(b, n_l1 * self.block_size))

# the query block is one fp32 sublane: a query group of G <= 8 heads
# (G = 1 without GQA) occupies rows 0..G-1, the rest are zero padding
# whose outputs are sliced off host-side; groups larger than 8 pad to
# the next sublane multiple
_SQ_PAD = 8


class GroupedHeadsError(ValueError):
    """H_q is not a multiple of H_kv: no query-head group maps cleanly
    onto a KV head.  A config error — raised typed so callers cannot
    confuse it with an envelope miss (which falls back instead)."""


def _group_size(num_q_heads: int, num_kv_heads: int) -> int:
    """Query heads per KV head, or GroupedHeadsError — the ONE
    divisibility check every GQA entry point (kernel, pool, config)
    funnels through."""
    if num_kv_heads < 1 or num_q_heads % num_kv_heads:
        raise GroupedHeadsError(
            f"{num_q_heads} query heads do not group over {num_kv_heads} "
            "KV heads — H_q must be a positive multiple of H_kv")
    return num_q_heads // num_kv_heads


def repeat_kv(k, v, group: int):
    """Broadcast KV heads over their query groups for a NON-grouped
    attention compute: [.., H_kv, ..] -> [.., H_q, ..] on axis 1, with
    query head h reading KV head h // group.  ``jnp.repeat`` — NOT tile
    — is load-bearing: it keeps each group's heads adjacent, the same
    order the grouped kernel's fold/unfold uses.  No-op when group is
    1, so callers can apply it unconditionally."""
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


def gather_kv_pages(pages, page_tables, scales=None):
    """Reference page gather: pages [H_kv, P, page_size, D] (one layer
    of the pool) + page_tables [B, max_pages] int32 -> contiguous
    [B, H_kv, S, D] with S = max_pages * page_size.  With ``scales``
    (the layer's [P] per-page fp32 quantization scales) the gathered
    int8 content is dequantized to fp32: row blocks multiply by their
    OWN page's scale, gathered through the same table.  Rows past a
    sequence's length are whatever the padding pages hold — callers
    MUST mask via k_lengths."""
    tables = jnp.asarray(page_tables, jnp.int32)
    b, n_pages = tables.shape
    g = jnp.take(pages, tables.reshape(-1), axis=1)  # [H, B*maxp, page, D]
    if scales is not None:
        s = jnp.take(jnp.asarray(scales, jnp.float32), tables.reshape(-1))
        g = g.astype(jnp.float32) * s[None, :, None, None]
    h, _, page, d = g.shape
    return jnp.transpose(
        g.reshape(h, b, n_pages * page, d), (1, 0, 2, 3))


def pallas_paged_viable(page_size: int, head_dim: int,
                        dtype="float32") -> bool:
    """True when the pallas page reader supports this pool geometry on
    TPU — the measured Mosaic envelope: K/V page blocks must be natively
    (sublane, lane)-tiled, i.e. head_dim a 128-lane multiple and
    page_size a sublane multiple (8 for fp32, 16 for bf16, 32 for int8
    pages).  Out of envelope the selection falls back to the reference
    gather — explicitly, not at compile time."""
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.float32):
        sublane = 8
    elif dt == jnp.dtype(jnp.bfloat16):
        sublane = 16
    elif dt == jnp.dtype(jnp.int8):
        sublane = 32
    else:
        return False
    return head_dim % 128 == 0 and page_size % sublane == 0 and \
        page_size >= sublane


_fallback_noted = False
# every out-of-envelope fallback resolution, counted (the one-time log
# above is human-visible but was invisible to gates — serve_bench banks
# {"paged_fallbacks": 0} and asserts no unexpected fallbacks)
_fallback_total = 0


def fallback_count() -> int:
    """Process-wide count of resolve_paged_impl calls that fell back off
    an explicit 'pallas' request (serving gates assert this stays 0 for
    in-envelope pool geometries)."""
    return _fallback_total


def _record_fallback() -> None:
    global _fallback_total
    _fallback_total += 1
    from .. import flags

    if flags.flag("FLAGS_observability"):
        from ..serving.metrics import record_fallback

        record_fallback(kernel="paged_attention")


def resolve_paged_impl(impl, page_size: int, head_dim: int,
                       dtype="float32") -> str:
    """Resolve the requested impl (None -> FLAGS_serving_paged_impl) to
    the one that will actually run: 'auto' takes pallas on TPU inside
    the envelope and reference otherwise; an explicit 'pallas' outside
    the envelope falls back to 'reference' with a one-time log (the
    fallback contract: never a Mosaic compile failure)."""
    global _fallback_noted
    if impl is None:
        from .. import flags

        impl = flags.flag("serving_paged_impl")
    if impl not in _IMPLS:
        raise ValueError(
            f"paged-attention impl must be one of {_IMPLS}, got {impl!r}")
    if impl == "auto":
        if on_tpu() and not pallas_paged_viable(page_size, head_dim,
                                                 dtype):
            # auto on a TPU host WANTED pallas; an out-of-envelope pool
            # geometry silently degrading to the reference gather is the
            # drift the fallback gate exists to catch (a CPU host's
            # auto->reference is expected and stays uncounted)
            _record_fallback()
            return "reference"
        return ("pallas" if on_tpu() else "reference")
    if impl == "pallas" and not pallas_paged_viable(
            page_size, head_dim, dtype):
        if not _fallback_noted:
            _fallback_noted = True
            logging.getLogger("paddle_tpu").info(
                "pallas paged attention outside the Mosaic envelope "
                "(page_size=%d head_dim=%d dtype=%s) — reference gather "
                "fallback", page_size, head_dim, jnp.dtype(dtype).name)
        _record_fallback()
        return "reference"
    return impl


def attention_bytes_per_step(impl: str, batch: int, max_pages: int,
                             page_size: int, num_heads: int, head_dim: int,
                             itemsize: int = 4, num_layers: int = 1,
                             num_kv_heads: int | None = None,
                             dtype=None, q_tokens: int = 1) -> int:
    """Analytic HBM bytes one decode step moves through the attention
    KV path (the serving metrics gauge; the chip-less cost tier banks
    the compiler-measured counterpart in AOT_COST_ZOO.json).

    ``num_kv_heads`` (None: num_heads) is the POOL's head count — the
    GQA win is exactly this arm: KV traffic scales with H_kv, never
    H_q, because the grouped kernel streams each KV page once per
    group.  ``dtype`` (None: use ``itemsize`` as given) pins the pool
    element size explicitly — pass the pool's real dtype instead of
    assuming the fp32 default; int8 pools additionally charge the two
    fp32 per-page scales each walked page reads.

    Per layer, with E_kv = batch * max_pages * page_size * num_kv_heads
    * head_dim elements for ONE of K or V (E_q the same at num_heads):

    - reference: pages read at the pool itemsize + contiguous
      [B,H_kv,S,D] gather copy written at the COMPUTE itemsize (fp32
      for dequantized int8, the pool dtype otherwise) + — GQA only —
      the jnp.repeat group broadcast materialized at H_q (written) +
      the H_q-sized copy read back by attention, for K and V.  With
      H_kv == H_q this collapses to the classic pages + copy-written +
      copy-read 3x; under grouping the reference arm genuinely pays
      the E_q-sized broadcast the grouped kernel never materializes,
      and the model says so;
    - pallas/interpret: each page streamed exactly once at the pool
      itemsize, K and V — E_kv always, that IS the win.

    Query/output terms (batch*heads*head_dim) are negligible at decode
    shapes and excluded — EXCEPT for a multi-token verify step
    (``q_tokens = 1 + d`` > 1, ISSUE 13), where they are the ONLY term
    that grows with the draft depth and are priced explicitly: the KV
    page stream is INVARIANT in q_tokens (each live page reads once per
    sequence either way), which is exactly the amortization speculative
    decoding banks — bytes/step at d=4 stays ~1x the d=0 step while the
    step can commit 5 tokens."""
    import numpy as np

    h_kv = num_kv_heads if num_kv_heads is not None else num_heads
    group = _group_size(int(num_heads), int(h_kv))
    if dtype is not None:
        itemsize = np.dtype(dtype).itemsize
    quantized = dtype is not None and np.dtype(dtype) == np.dtype(np.int8)
    elems = batch * max_pages * page_size * h_kv * head_dim
    compute_itemsize = 4 if quantized else itemsize
    if impl in ("pallas", "interpret"):
        per_layer = 2 * elems * itemsize
    else:
        elems_q = elems * group
        # pages read + gather copy written (H_kv) + [G>1: repeat
        # broadcast written at H_q] + attention reads the H_q copy
        per_layer = 2 * (elems * itemsize + elems * compute_itemsize
                         + (elems_q * compute_itemsize if group > 1
                            else 0)
                         + elems_q * compute_itemsize)
    if quantized:
        # one fp32 K scale + one fp32 V scale per page walked
        per_layer += 2 * batch * max_pages * 4
    if int(q_tokens) > 1:
        # the verify step's query read + output write — the only term
        # scaling with the draft depth (kept at 0 extra for q_tokens=1
        # so the banked single-token entries stay byte-identical)
        per_layer += (2 * batch * int(q_tokens) * num_heads * head_dim
                      * compute_itemsize)
    return per_layer * int(num_layers)


def _paged_kernel(tables_ref, lengths_ref, *refs, scale, page_size,
                  quantized, sq, group, slot_major, block_size=0,
                  has_starts=False, windowed=False):
    """Grid (B, H_kv, max_pages); pages innermost so the online-softmax
    state for one (sequence, KV head) lives in VMEM scratch across the
    page walk.  tables_ref/lengths_ref are SMEM scalar-prefetch refs:
    tables drives the K/V BlockSpec index maps (the page DMA), lengths
    masks the ragged tail in-kernel.  Quantized pools prefetch two more
    SMEM operands — the layer's per-page K/V scales — and the same
    table entry that picked the page picks its scale (dequant fused
    into the stream).  The query block rows are the KV head's QUERY
    GROUP (G heads + padding): the m/l/acc recurrence is per row, so
    every group member keeps its own softmax state while sharing the
    one streamed page.  With ``sq > 1`` (multi-token speculative
    verify) the rows are the whole draft block — row ``g * sq + t``
    is (group member g, draft token t), group-major — and one more
    prefetched SMEM operand, the ragged per-sequence ``q_lengths``,
    sets each row's causal frontier: query token t sits at absolute
    position ``lengths[b] - q_lengths[b] + t``, so keys past it mask
    exactly like the ragged tail.  Page table rows are zero-padded — the dummy
    page-0 reads those DMAs issue are fully masked by position >=
    length, exactly the flash fully-masked-block contract (m floor
    NEG_INF/2, p underflows to 0, l stays 0).

    LONG-CONTEXT OPERANDS (ISSUE 20), all opt-in: with ``block_size``
    the table operand is the two-level L1 directory and two more SMEM
    operands follow — the L2 page blocks and their per-page absolute
    START positions (the index map already resolved the page DMA; the
    body re-reads l1/l2 only for the start and the block-indexed
    scales).  ``has_starts`` is the flat counterpart (one [B,
    max_pages] starts operand).  Either way ``pos`` comes from the
    prefetched start instead of ``p * page_size`` — the compacted
    table of an evicted sequence masks by TRUE position.  ``windowed``
    adds per-request [B] ``windows``/``sinks`` operands and the
    page-granular visibility rule ``start < sinks or start + page_size
    > q_pos + 1 - window`` on top of the causal/ragged mask — the same
    rule serving/kvcache.py evicts by, so mask and eviction agree."""
    import jax.experimental.pallas as pl

    refs = list(refs)
    if block_size:
        l2_ref = refs.pop(0)
        starts_ref = refs.pop(0)
    elif has_starts:
        l2_ref = None
        starts_ref = refs.pop(0)
    else:
        l2_ref = starts_ref = None
    if windowed:
        win_ref = refs.pop(0)
        sink_ref = refs.pop(0)
    q_lens_ref = refs.pop(0) if sq > 1 else None
    if quantized:
        k_scales_ref, v_scales_ref, q_ref, k_ref, v_ref, o_ref, \
            m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs

    b = pl.program_id(0)
    p = pl.program_id(2)
    num_pages = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF / 2)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]  # [rows_pad, D] — the KV head's query group/block
    if slot_major:
        # layout-consuming K/V view (pool_layout="xla"): the operand is
        # [P, ps, H_kv*D] — page outermost, this head's D-column block
        # picked by the index map — so the block is already [ps, D]
        k = k_ref[0]
        v = v_ref[0]
    else:
        k = k_ref[0, 0]  # [page_size, D]
        v = v_ref[0, 0]
    if block_size:
        blk = tables_ref[b, p // block_size]
        slot = p % block_size
        start = starts_ref[blk, slot]
    elif has_starts:
        start = starts_ref[b, p]
    else:
        start = p * page_size
    if quantized:
        if block_size:
            # block-indexed scales: the [n_blocks, bs] gather already
            # aligned scale slots with l2 slots, so (blk, slot) is it
            k = k.astype(jnp.float32) * k_scales_ref[blk, slot]
            v = v.astype(jnp.float32) * v_scales_ref[blk, slot]
        else:
            page = tables_ref[b, p]
            k = k.astype(jnp.float32) * k_scales_ref[page]
            v = v.astype(jnp.float32) * v_scales_ref[page]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if sq > 1:
        # per-row causal frontier: rows are GROUP-MAJOR (row g*sq + t
        # is group member g, draft token t — the layout that makes the
        # host fold/unfold pure reshapes), so row r verifies token
        # r % sq at absolute position q_start + r % sq (padding rows
        # mask conservatively and are sliced off host-side); the
        # < lengths term still hides the table tail
        t_row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % sq
        q_start = lengths_ref[b] - q_lens_ref[b]
        q_pos = q_start + t_row
        visible = (pos <= q_pos) & (pos < lengths_ref[b])
    else:
        q_pos = lengths_ref[b] - 1
        visible = pos < lengths_ref[b]
    if windowed:
        # page-granular window + sink rule, per request: a sink page
        # (start < sinks[b]) or a page overlapping the recent window
        # stays visible; everything else masks — kvcache eviction drops
        # exactly the pages this term hides for ALL future q_pos
        visible = visible & (
            (start < sink_ref[b])
            | (start + page_size > q_pos + 1 - win_ref[b]))
    s = jnp.where(visible, s, NEG_INF)

    m_prev = m_scr[:]  # [G_pad, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p_w = jnp.exp(s - m_new)
    correction = jnp.exp(m_prev - m_new)
    l_scr[:] = correction * l_scr[:] + jnp.sum(p_w, axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * correction + jnp.dot(
        p_w.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[:] = m_new

    @pl.when(p == num_pages - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(
            o_ref.dtype)


@functools.lru_cache(maxsize=128)
def _paged_call(batch, kv_heads, rows_pad, max_pages, page_size, head_dim,
                scale, kv_dtype, interpret, quantized, sq, group,
                slot_major=False, block_size=0, has_starts=False,
                windowed=False):
    """Memoized pallas_call — one traced callable per static config, so
    every decode layer/step of a model reuses ONE kernel payload (the
    flash_attention._fwd_call compile-cache contract).  ``sq`` is the
    (padded-max) query tokens per sequence — 1 for plain decode, 1+d
    for a speculative verify step, which adds the ragged ``q_lengths``
    scalar-prefetch operand; ``rows_pad`` is sq*group rounded up to a
    whole sublane.  ``slot_major`` switches the K/V operands to the
    layout-consuming [P, ps, H_kv*D] view (pool_layout="xla"): the page
    block is then (1, ps, D) — still natively (sublane, lane)-tiled —
    with this head's columns picked on the packed feature dim."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dt = jnp.dtype(kv_dtype)
    # the dequantized (and padded-query) compute runs in fp32; an
    # unquantized pool computes/outputs in its own dtype as before
    out_dt = jnp.float32 if quantized else dt
    multi = sq > 1
    n_prefetch = (2 + (2 if block_size else (1 if has_starts else 0))
                  + (2 if windowed else 0) + (1 if multi else 0)
                  + (2 if quantized else 0))
    # index maps see every scalar-prefetch operand after the grid ids;
    # only the table operands matter to them — swallow the rest
    if n_prefetch == 2:
        pad = lambda f: f
    else:
        pad = lambda f: (lambda b, h, p, t, l, *rest: f(b, h, p, t, l))
    if block_size:
        # two-level walk: the L1 directory names the L2 block, the L2
        # slot names the pool page — two nested SMEM reads per step
        bs = block_size
        if slot_major:
            kv_spec = pl.BlockSpec(
                (1, page_size, head_dim),
                lambda b, h, p, l1, lengths, l2, *rest: (
                    l2[l1[b, p // bs], p % bs], 0, h))
        else:
            kv_spec = pl.BlockSpec(
                (1, 1, page_size, head_dim),
                lambda b, h, p, l1, lengths, l2, *rest: (
                    h, l2[l1[b, p // bs], p % bs], 0, 0))
    elif slot_major:
        kv_spec = pl.BlockSpec(
            (1, page_size, head_dim),
            pad(lambda b, h, p, tables, lengths: (tables[b, p], 0, h)))
    else:
        # the page walk: the SMEM table entry picks which pool page
        # the next grid step DMAs — no gather ever materializes
        kv_spec = pl.BlockSpec(
            (1, 1, page_size, head_dim),
            pad(lambda b, h, p, tables, lengths: (h, tables[b, p], 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(batch, kv_heads, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, rows_pad, head_dim),
                         pad(lambda b, h, p, tables, lengths: (b, h, 0, 0))),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, rows_pad, head_dim),
            pad(lambda b, h, p, tables, lengths: (b, h, 0, 0))),
        scratch_shapes=[
            pltpu.VMEM((rows_pad, 1), jnp.float32),
            pltpu.VMEM((rows_pad, 1), jnp.float32),
            pltpu.VMEM((rows_pad, head_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, page_size=page_size,
                          quantized=quantized, sq=sq, group=group,
                          slot_major=slot_major, block_size=block_size,
                          has_starts=has_starts, windowed=windowed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (batch, kv_heads, rows_pad, head_dim), out_dt),
        interpret=interpret,
    )


def _pallas_paged(q, k_pages, v_pages, page_tables, lengths, scale,
                  interpret=False, k_scales=None, v_scales=None,
                  q_lengths=None, slot_major=False, page_starts=None,
                  windows=None, sinks=None):
    B, Hq, Sq, D = q.shape
    Hkv, P, page_size, _ = k_pages.shape
    G = Hq // Hkv
    rows = Sq * G
    rows_pad = -(-rows // _SQ_PAD) * _SQ_PAD
    quantized = k_scales is not None
    two = isinstance(page_tables, TwoLevelTables)
    if two:
        tl = page_tables
        tables = jnp.asarray(tl.l1, jnp.int32)
        l2 = jnp.asarray(tl.l2, jnp.int32)
        starts = jnp.asarray(tl.starts, jnp.int32)
        block_size = int(tl.block_size)
        max_pages = tables.shape[1] * block_size
        has_starts = False
    else:
        tables = jnp.asarray(page_tables, jnp.int32)
        block_size = 0
        max_pages = tables.shape[1]
        has_starts = page_starts is not None
    windowed = windows is not None
    lengths = jnp.asarray(lengths, jnp.int32)
    if Sq > 1:
        # fold (group member, token) onto the KV head GROUP-MAJOR: row
        # g*Sq + t is (query head h_kv*G + g, draft token t) — a pure
        # reshape both ways (no transpose, no relayout copy around the
        # custom call), matching the kernel's r % sq frontier
        qg = q.reshape(B, Hkv, rows, D)
    else:
        # row g of group h_kv is query head h_kv * G + g
        qg = q[:, :, 0, :].reshape(B, Hkv, G, D)
    qg = qg.astype(jnp.float32 if quantized else k_pages.dtype)
    qp = jnp.pad(qg, ((0, 0), (0, 0), (0, rows_pad - rows), (0, 0)))
    if slot_major:
        # the layout-consuming view (pool_layout="xla"): re-express the
        # kernel-native [H_kv, P, ps, D] pool slice as [P, ps, H_kv*D].
        # Logically a transpose+reshape; physically it is EXACTLY the
        # {3,0,2,1} layout XLA prefers for a scatter-updated pool (the
        # in-place K/V append writes one [H, D] row per token, so XLA
        # wants D, then H, innermost) — layout assignment folds both
        # ops into a bitcast and the custom call consumes the preferred
        # layout instead of forcing a row-major relayout copy-pair
        k_pages = k_pages.transpose(1, 2, 0, 3).reshape(P, page_size,
                                                        Hkv * D)
        v_pages = v_pages.transpose(1, 2, 0, 3).reshape(P, page_size,
                                                        Hkv * D)
    call = _paged_call(B, Hkv, rows_pad, max_pages, page_size, D,
                       float(scale), str(k_pages.dtype), interpret,
                       quantized, Sq, G, slot_major=slot_major,
                       block_size=block_size, has_starts=has_starts,
                       windowed=windowed)
    args = [tables, lengths]
    if two:
        args += [l2, starts]
    elif has_starts:
        args.append(jnp.asarray(page_starts, jnp.int32))
    if windowed:
        args.append(jnp.asarray(windows, jnp.int32))
        args.append(jnp.zeros((B,), jnp.int32) if sinks is None
                    else jnp.asarray(sinks, jnp.int32))
    if Sq > 1:
        ql = (jnp.full((B,), Sq, jnp.int32) if q_lengths is None
              else jnp.asarray(q_lengths, jnp.int32))
        args.append(ql)
    if quantized:
        ksc = jnp.asarray(k_scales, jnp.float32)
        vsc = jnp.asarray(v_scales, jnp.float32)
        if two:
            # per-block scale blocks: gather the pool-sized [P] rows
            # through the L2 page ids OUTSIDE the kernel, so the SMEM
            # operands ride the walked blocks — the scale half of the
            # two-level SMEM win
            ksc, vsc = ksc[l2], vsc[l2]
        args += [ksc, vsc]
    out = call(*args, qp, k_pages, v_pages)
    out = out[:, :, :rows, :].reshape(B, Hq, Sq, D)
    return out.astype(q.dtype)


_POOL_LAYOUTS = ("head", "xla")


def paged_decode_attention(q, k_pages, v_pages, page_tables, lengths,
                           scale=None, impl: str | None = None,
                           force: str = "auto", k_scales=None,
                           v_scales=None, q_lengths=None,
                           pool_layout: str = "head", page_starts=None,
                           windows=None, sinks=None):
    """q: [B, H_q, Sq, D] decode queries — Sq=1 for plain decode, Sq =
    1+d for a speculative multi-token verify step (the last committed
    token plus d drafted continuations, ISSUE 13); k_pages/v_pages:
    [H_kv, P, page_size, D] one layer of the pool (H_kv <= H_q for
    GQA/MQA — query head h reads KV head h // (H_q/H_kv); H_q % H_kv
    != 0 raises :class:`GroupedHeadsError`); page_tables: [B,
    max_pages] int32; lengths: [B] valid token counts (the fed block
    already appended).

    ``q_lengths`` ([B] int32, Sq > 1 only; None means every sequence
    fed the full Sq rows): ragged valid query rows per sequence —
    query row t of sequence b sits at absolute position ``lengths[b] -
    q_lengths[b] + t`` and is causal-masked there, INSIDE the draft
    block.  Rows past ``q_lengths[b]`` compute garbage the caller must
    ignore (the serving loop pads ragged draft blocks to the batch
    max).

    ``k_scales``/``v_scales`` ([P] fp32, required together): the
    layer's per-page quantization scales for an int8 pool — dequant is
    fused into the pallas page stream and into the reference gather.

    Returns [B, H_q, Sq, D].  For Sq=1 causality is implied: the
    single query IS the last valid position, so masking keys at >=
    lengths is exactly the causal frontier.

    `impl`: None reads FLAGS_serving_paged_impl; see resolve_paged_impl
    for the auto/envelope/fallback contract.  `force` forwards to
    flash_attention (single-token reference impl only).

    ``pool_layout`` is the layout-consumption contract (the ROADMAP
    "layout tax" fix): ``"head"`` (default) pins the kernel-native
    row-major [H_kv, P, ps, D] operand — right when the pool is a plain
    program parameter (nothing upstream prefers another layout);
    ``"xla"`` has the pallas lowering consume XLA's preferred layout
    for a pool that is scatter-updated INSIDE the same program (the
    SPMD decode step's in-place append): the K/V operands are re-viewed
    as [P, ps, H_kv*D] — physically identical to the {3,0,2,1} layout
    XLA assigns the scatter result, so the transpose+reshape folds to a
    bitcast and no relayout copy-pair brackets the custom call.  The
    arguments are ALWAYS passed head-major; the view lives entirely in
    the lowering, and the reference/interpret tiers compute identically
    under either contract (parity-tested).

    LONG-CONTEXT SURFACES (ISSUE 20).  ``page_tables`` may be a
    :class:`TwoLevelTables` (compact L1 directory + L2 blocks + starts
    — SMEM rides walked blocks, not pool pages); a flat table may carry
    ``page_starts`` ([B, max_pages] int32, :data:`PAD_START`-padded) —
    the absolute slot-0 position of each table entry, REQUIRED once
    eviction has compacted a table so the position mask stays true.
    ``windows``/``sinks`` ([B] int32; sinks needs windows) apply the
    page-granular sliding-window + attention-sink visibility rule per
    request: key page start ``s_p`` visible to the query at position
    ``p`` iff ``s_p < sinks[b]`` or ``s_p + page_size > p + 1 -
    windows[b]`` — exactly the rule the pool evicts by, so a windowed
    request computes identically before and after its interior pages
    are dropped.  Non-windowed rows in a windowed batch pass
    ``windows[b] = PAD_START``."""
    if q.ndim != 4:
        raise ValueError(f"decode query must be [B, H, Sq, D], got {q.shape}")
    Sq = q.shape[2]
    if Sq < 1:
        raise ValueError(f"decode query must carry >= 1 token, got {q.shape}")
    if Sq == 1 and q_lengths is not None:
        raise ValueError(
            "q_lengths is the multi-token verify contract — a single-"
            "token decode step has nothing ragged to mask")
    if pool_layout not in _POOL_LAYOUTS:
        raise ValueError(
            f"pool_layout must be one of {_POOL_LAYOUTS}, got "
            f"{pool_layout!r}")
    G = _group_size(q.shape[1], k_pages.shape[0])
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    if k_scales is None and jnp.dtype(k_pages.dtype) == jnp.dtype(jnp.int8):
        raise ValueError(
            "an int8 KV pool needs its per-page k_scales/v_scales — "
            "raw int8 content is meaningless without them")
    two = isinstance(page_tables, TwoLevelTables)
    if two and page_starts is not None:
        raise ValueError(
            "a TwoLevelTables walk carries its own per-block starts — "
            "page_starts is the flat-table contract")
    if sinks is not None and windows is None:
        raise ValueError(
            "sinks only pin attention-sink pages against a sliding "
            "window — pass windows with them")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    impl = resolve_paged_impl(impl, k_pages.shape[2], q.shape[3],
                              k_pages.dtype)
    if impl in ("pallas", "interpret"):
        return _pallas_paged(q, k_pages, v_pages, page_tables, lengths,
                             scale, interpret=(impl == "interpret"),
                             k_scales=k_scales, v_scales=v_scales,
                             q_lengths=q_lengths,
                             slot_major=(pool_layout == "xla"),
                             page_starts=page_starts, windows=windows,
                             sinks=sinks)
    if two:
        tables_flat, starts_flat = page_tables.flatten()
    else:
        tables_flat = page_tables
        starts_flat = (None if page_starts is None
                       else jnp.asarray(page_starts, jnp.int32))
    # dequantized pools gather straight to fp32; bf16/fp32 pools pass
    # through at the POOL dtype (no widening copy — the byte model
    # prices the copy terms at the pool itemsize)
    k = gather_kv_pages(k_pages, tables_flat, scales=k_scales)
    v = gather_kv_pages(v_pages, tables_flat, scales=v_scales)
    # the reference arm materializes the group broadcast the pallas
    # kernel never pays for (attention_bytes_per_step charges it)
    k, v = repeat_kv(k, v, G)
    if starts_flat is None and windows is None:
        if Sq == 1:
            return flash_attention(q, k, v, causal=False, scale=scale,
                                   k_lengths=lengths, force=force)
        return _reference_verify(q, k, v, lengths, q_lengths, scale)
    return _reference_windowed(q, k, v, lengths, q_lengths, starts_flat,
                               windows, sinks, scale, k_pages.shape[2])


@functools.lru_cache(maxsize=1)
def _verify_jit():
    """One jitted dense-verify body (compiled per input-shape set, like
    every other step kernel) — the eager op-by-op chain recompiled its
    tiny executables every step, which dominated verify wall time."""
    def body(q, k, v, ln, ql, *, scale):
        Sq, S = q.shape[2], k.shape[2]
        pos_q = (ln - ql)[:, None] \
            + jnp.arange(Sq, dtype=jnp.int32)[None, :]
        key_j = jnp.arange(S, dtype=jnp.int32)[None, None, :]
        mask = (key_j <= pos_q[:, :, None]) & (key_j < ln[:, None, None])
        scores = jnp.einsum("bhtd,bhjd->bhtj", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhtj,bhjd->bhtd", w, v.astype(jnp.float32))

    return jax.jit(body, static_argnames=("scale",))


def _reference_verify(q, k, v, lengths, q_lengths, scale):
    """Multi-token reference arm: dense attention over the gathered
    [B, H_q, S, D] view with the per-row draft-block causal mask — key
    j visible to query row t of sequence b iff ``j <= lengths[b] -
    q_lengths[b] + t`` and ``j < lengths[b]`` (the jnp.where also
    neutralizes NaN scores from padding pages, the chunk_prefill_step
    contract)."""
    B, _, Sq, _ = q.shape
    ln = jnp.asarray(lengths, jnp.int32)
    ql = (jnp.full((B,), Sq, jnp.int32) if q_lengths is None
          else jnp.asarray(q_lengths, jnp.int32))
    out = _verify_jit()(q, k, v, ln, ql, scale=float(scale))
    return out.astype(q.dtype)


@functools.lru_cache(maxsize=1)
def _windowed_ref_jit():
    """One jitted body for every explicit-starts / windowed reference
    arm (Sq >= 1): key positions come from the per-page starts instead
    of arange(S), and the page-granular window+sink rule joins the
    causal/ragged mask — the _verify_jit compile-cache contract."""
    def body(q, k, v, ln, ql, st, win, snk, *, scale, page_size):
        Sq, S = q.shape[2], k.shape[2]
        # per-key page start and absolute position, from the [B,
        # n_pages] starts row (PAD_START pads mask themselves out)
        pstart = jnp.repeat(st, page_size, axis=1)  # [B, S]
        kpos = pstart + jnp.tile(
            jnp.arange(page_size, dtype=jnp.int32), S // page_size)[None]
        pos_q = (ln - ql)[:, None] \
            + jnp.arange(Sq, dtype=jnp.int32)[None, :]
        kp = kpos[:, None, :]       # [B, 1, S]
        sp = pstart[:, None, :]
        pq = pos_q[:, :, None]      # [B, Sq, 1]
        mask = (kp <= pq) & (kp < ln[:, None, None]) & (
            (sp < snk[:, None, None])
            | (sp + page_size > pq + 1 - win[:, None, None]))
        scores = jnp.einsum("bhtd,bhjd->bhtj", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhtj,bhjd->bhtd", w, v.astype(jnp.float32))

    return jax.jit(body, static_argnames=("scale", "page_size"))


def _reference_windowed(q, k, v, lengths, q_lengths, starts, windows,
                        sinks, scale, page_size):
    """Reference arm for the long-context surfaces (ISSUE 20): dense
    attention over the gathered view where key j's position comes from
    its page's explicit start (an evicted sequence's compacted table,
    or a TwoLevelTables flatten) and the page-granular window+sink
    visibility rule masks on top of the causal frontier — key page
    start ``s_p`` visible to the query at absolute position ``p`` iff
    ``s_p < sinks`` or ``s_p + page_size > p + 1 - window``.  ``starts
    = None`` (windowed but unevicted) falls back to the implicit
    ``page * page_size`` positions; ``windows = None`` (starts without
    a window) masks nothing beyond causality via the PAD_START
    window."""
    B, _, Sq, _ = q.shape
    n_pages = k.shape[2] // page_size
    ln = jnp.asarray(lengths, jnp.int32)
    ql = (jnp.full((B,), Sq, jnp.int32) if q_lengths is None
          else jnp.asarray(q_lengths, jnp.int32))
    if starts is None:
        st = jnp.broadcast_to(
            jnp.arange(n_pages, dtype=jnp.int32)[None] * page_size,
            (B, n_pages))
    else:
        st = jnp.asarray(starts, jnp.int32)
    win = (jnp.full((B,), PAD_START, jnp.int32) if windows is None
           else jnp.asarray(windows, jnp.int32))
    snk = (jnp.zeros((B,), jnp.int32) if sinks is None
           else jnp.asarray(sinks, jnp.int32))
    out = _windowed_ref_jit()(q, k, v, ln, ql, st, win, snk,
                              scale=float(scale),
                              page_size=int(page_size))
    return out.astype(q.dtype)
