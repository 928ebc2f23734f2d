"""Continuous-batching autoregressive decode over the paged KV cache.

Serving an autoregressive transformer one request at a time recomputes
full-sequence attention every token (O(S^2) per generated token) and —
worse for TPU throughput — runs at batch 1.  This module fixes both:

- **KV caching**: each generated token's per-layer K/V lands in the
  KVCachePool (kvcache.py); decode attention is one Sq=1 query against
  the cached keys through kernels/paged_attention.py —
  FLAGS_serving_paged_impl (or the loop's ``paged_impl``) selects the
  pallas ragged page-streaming kernel vs the reference gather, with the
  envelope/fallback contract documented there.
- **Batched prefill**: an admitted prompt's K/V is written by ONE
  whole-prompt causal pass (``prefill_step`` — O(1) model steps per
  prompt instead of one step per prompt token), ragged prompts padded
  to the co-admitted max and masked via the flash ``k_lengths`` tier.
  ``prefill="token"`` keeps the old token-by-token path as the A/B arm
  and parity oracle.
- **Continuous batching**: the loop keeps up to ``max_batch`` sequences
  in flight and admits a waiting sequence the moment a finished one
  retires (its pages return to the free pool) — batch occupancy stays
  high across mixed-length workloads instead of draining to 1 while the
  longest straggler finishes (the occupancy-dominates-throughput result
  of arxiv 2605.25645).

The model is the decoder half of models/transformer.py as a jax-level
step function: post-norm residual blocks (LayerNorm(x + sublayer(x)),
matching _Builder.sublayer), scaled embedding + sinusoid positions
(matching _Builder.embed; the table is literally
models.transformer._sinusoid_table), tied input/output embeddings, no
cross-attention.

``full_decode`` is the correctness oracle: per-sequence greedy decode
that recomputes the whole prefix each token with ordinary causal
attention and no cache.  tests/test_serving.py holds the paged loop to
it within fp32 tolerance — and, because batched prefill changes
arithmetic order (one padded causal pass vs Sq=1 steps), the prefill
parity suite additionally pins ``prefill_step`` to ``full_forward``
(the batched-reference oracle) and batched-vs-token generations to
token identity.

ISSUE 13 adds SPECULATIVE DECODING and the per-request SAMPLING
contract:

- ``ContinuousBatchingLoop(speculate=d)`` (default
  ``FLAGS_serving_speculate``) arms draft-model-free speculation: a
  prompt-lookup drafter (serving/speculative.py — pure host n-gram
  matching over prompt + generation history, no second model, no
  extra HBM) proposes up to ``d`` continuation tokens per generating
  sequence, and ``verify_step`` feeds the last committed token plus
  the draft block through ONE model step — Sq = 1+d ragged query rows
  per sequence through ``paged_decode_attention(q_lengths=)``, the
  page stream still reading each live KV page once.  For GREEDY rows
  acceptance is longest-prefix-match against the model's own (biased)
  argmax, so every emitted token is argmax given an exactly-correct
  prefix: greedy speculative decode is TOKEN-IDENTICAL to
  ``full_decode`` by construction, and the existing oracle keeps
  pinning correctness.  Rejected draft tokens roll back as pure host
  bookkeeping — ``KVCachePool.truncate_seq`` shrinks the page table
  atomically (refcount/CoW-aware, int8 scales cleared with freed
  pages) — which continuous batching already tolerates as ragged
  per-sequence progress.  EOS / stop sequences / max_new are checked
  after EVERY emitted token, so a stop landing inside an accepted
  draft block retires the sequence at that position with the surplus
  fed tokens truncated from the page table.
- ``DecodeRequest.sampling`` (serving/sampling.py SamplingParams)
  widens the decode contract: temperature/top-k/top-p through ONE
  jitted sampling epilogue per step, logit bias (greedy included),
  stop sequences, per-request max_new.

ISSUE 16 makes speculation distribution-exact and UNIVERSAL:

- SAMPLED (temp>0) rows draft too.  Their verify outcome goes through
  the exact accept/resample epilogue (``sampling.spec_sample_rows``,
  one fused jitted call for every drafted sampled row of the batch):
  draft token t accepts with probability ``min(1, p_target(t) /
  p_draft(t))`` — the target probability itself under the
  prompt-lookup drafter's point-mass proposal — and a rejection
  resamples the residual ``max(0, p_target - p_draft)`` renormalized,
  so emitted tokens are DISTRIBUTION-IDENTICAL to unspeculated
  sampling while the (seed, token-index)-keyed Gumbel stream stays
  replayable (bonus/no-draft draws use the plain epilogue's unsalted
  key, so a never-drafting sequence keeps its old stream byte for
  byte).  Per-row accepted counts come back from the same fused call
  — no per-sequence host sync.
- SPMD programs speculate.  A program exposing ``verify_step(pool,
  seq_ids, blocks, start_positions, pad_to=)`` (e.g.
  ``serving.distributed.ShardedDecodeProgram``) runs the multi-token
  verify under its own mesh; only a custom program WITHOUT one
  degrades the loop to d=0 — surfaced as a
  ``paddle_tpu_serving_spec_disabled_total{reason=}`` counter and a
  flight event, never just a log line.
- The default drafter rides the prefix cache's trie as a shared
  CORPUS (``PromptLookupDrafter(corpus=prefix_cache)``):
  shared-prefix fleet traffic drafts from continuations other
  sequences already decoded, with per-request fallback to
  own-history matching.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import flags as _flags
from ..kernels.flash_attention import (
    NEG_INF,
    flash_attention,
)
from ..kernels.paged_attention import (
    PAD_START,
    attention_bytes_per_step,
    gather_kv_pages,
    paged_decode_attention,
    repeat_kv,
    resolve_paged_impl,
)
from ..observability import flight as _flight
from ..observability import requesttrace as _rtrace
from ..models.transformer import _sinusoid_table
from . import metrics as _smetrics
from . import prefill_sched as _psched
from .adapters import AdapterError
from .kvcache import KVCachePool
from .sampling import (
    SamplingParams,
    apply_bias,
    sample_rows,
    spec_sample_rows,
    stop_hit,
)
from .speculative import PromptLookupDrafter

_log = logging.getLogger("paddle_tpu.serving")

__all__ = [
    "DecodeConfig",
    "DecodeRequest",
    "GeneratedSequence",
    "ContinuousBatchingLoop",
    "NonFiniteSequenceError",
    "init_decode_params",
    "full_forward",
    "full_decode",
    "window_mask",
    "prefill_step",
    "chunk_prefill_step",
    "verify_step",
]


class NonFiniteSequenceError(RuntimeError):
    """One sequence's decode logits went non-finite: that sequence was
    QUARANTINED — evicted from the continuous batch, its pages returned
    to the pool — while its batch-mates decode on.  The batch-granular
    counterpart of resilience.NonFiniteStepError: a poisoned sequence
    costs one request, never the batch (and never the engine)."""

    def __init__(self, seq_id: int, step: int):
        self.seq_id = seq_id
        self.step = step
        super().__init__(
            f"sequence {seq_id} produced non-finite logits at loop step "
            f"{step}; it was evicted from the batch (pages freed) and "
            "its batch-mates decoded on")

    def __reduce__(self):
        # default Exception pickling replays args=(message,), which does
        # not match this two-arg __init__; the process fleet ships these
        # across sockets inside GeneratedSequence.error
        return (type(self), (self.seq_id, self.step))


@dataclasses.dataclass
class DecodeConfig:
    """Decoder-only slice of models.transformer.TransformerConfig.

    ``n_kv_head`` (None: n_head — classic MHA) enables grouped-query /
    multi-query attention: K/V project to n_kv_head heads, the KV pool
    stores and streams H_q/H_kv x less, and query head h reads KV head
    ``h // (n_head/n_kv_head)``."""

    vocab_size: int = 128
    d_model: int = 32
    n_head: int = 4
    n_layer: int = 2
    d_inner: int = 64
    max_length: int = 96
    eos_id: Optional[int] = None  # None: sequences retire on max_new only
    n_kv_head: Optional[int] = None  # None: n_head (no grouping)

    @property
    def head_dim(self) -> int:
        if self.d_model % self.n_head:
            raise ValueError("d_model must divide by n_head")
        return self.d_model // self.n_head

    @property
    def num_kv_heads(self) -> int:
        h_kv = self.n_kv_head if self.n_kv_head is not None else self.n_head
        from ..kernels.paged_attention import _group_size

        _group_size(self.n_head, h_kv)  # typed GroupedHeadsError raise
        return h_kv

    @property
    def group_size(self) -> int:
        """Query heads per KV head (1 without grouping)."""
        return self.n_head // self.num_kv_heads


def init_decode_params(cfg: DecodeConfig, seed: int = 0) -> Dict:
    """Deterministic fp32 params; weights at 1/sqrt(fan_in) scale."""
    rng = np.random.RandomState(seed)

    def mat(d_in, d_out):
        return (rng.standard_normal((d_in, d_out)) / np.sqrt(d_in)).astype(
            np.float32)

    d, f = cfg.d_model, cfg.d_inner
    d_kv = cfg.num_kv_heads * cfg.head_dim  # K/V project to H_kv heads
    layers = []
    for _ in range(cfg.n_layer):
        layers.append({
            "wq": mat(d, d), "wk": mat(d, d_kv), "wv": mat(d, d_kv),
            "wo": mat(d, d),
            "ln1_g": np.ones(d, np.float32), "ln1_b": np.zeros(d, np.float32),
            "w1": mat(d, f), "b1": np.zeros(f, np.float32),
            "w2": mat(f, d), "b2": np.zeros(d, np.float32),
            "ln2_g": np.ones(d, np.float32), "ln2_b": np.zeros(d, np.float32),
        })
    return {
        "embed": (rng.standard_normal((cfg.vocab_size, d)) / np.sqrt(d)
                  ).astype(np.float32),
        "pos": _sinusoid_table(cfg.max_length, d),
        "layers": layers,
    }


def _layernorm(x, g, b, eps: float = 1e-5):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


@functools.lru_cache(maxsize=32)
def _oracle_jit(H: int, Hkv: int, Dh: int):
    """The oracle's arithmetic as ONE traced function of (params, tokens
    [L], mask [L, L]): a compile a model, where op by op it cost some
    fifty small executables a new length, and ``full_decode`` meets a
    new length every token."""
    import jax
    import jax.numpy as jnp

    def body(params, tokens, mask):
        L, d = tokens.shape[0], H * Dh
        h = params["embed"][tokens] * np.sqrt(d) + params["pos"][:L]
        for lp in params["layers"]:
            q = (h @ lp["wq"]).reshape(L, H, Dh).transpose(1, 0, 2)[None]
            k = (h @ lp["wk"]).reshape(L, Hkv, Dh).transpose(1, 0, 2)[None]
            v = (h @ lp["wv"]).reshape(L, Hkv, Dh).transpose(1, 0, 2)[None]
            k, v = repeat_kv(k, v, H // Hkv)  # query head h reads h // G
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (Dh ** -0.5)
            scores = jnp.where(mask, scores, NEG_INF)
            attn = jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(scores, axis=-1), v)
            attn = attn[0].transpose(1, 0, 2).reshape(L, d)
            h = _layernorm(h + attn @ lp["wo"], lp["ln1_g"], lp["ln1_b"])
            ff = jnp.maximum(h @ lp["w1"] + lp["b1"], 0.0) @ lp["w2"] \
                + lp["b2"]
            h = _layernorm(h + ff, lp["ln2_g"], lp["ln2_b"])
        return h @ params["embed"].T

    return jax.jit(body)


def full_forward(params: Dict, cfg: DecodeConfig, tokens,
                 mask=None) -> np.ndarray:
    """Oracle forward: full-sequence causal attention, no cache.
    tokens [S] int -> logits [S, V].  ``mask`` (optional [S, S] bool,
    query x key) REPLACES the causal mask — the windowed-decode oracle
    passes ``window_mask`` so sliding-window + attention-sink parity
    checks against dense arithmetic, not against another paged path.

    Every length runs the one program of ``cfg.max_length`` rows: the
    rows past S are padding that no row before them sees (their keys are
    masked, so they add exact zeros), each sees itself, and they are cut
    from the result."""
    tokens = np.asarray(tokens, np.int32)
    S, L = tokens.shape[0], cfg.max_length
    if S > L:
        raise ValueError(f"sequence length {S} > max_length {L}")
    seen = np.tril(np.ones((L, L), bool))
    if mask is not None:
        seen[:S, :S] = np.asarray(mask, bool)
    padded = np.zeros(L, np.int32)
    padded[:S] = tokens
    logits = _oracle_jit(cfg.n_head, cfg.num_kv_heads, cfg.head_dim)(
        params, padded, seen)
    return np.asarray(logits[:S])


def window_mask(S: int, prompt_len: int, window: int, sinks: int,
                page_size: int) -> np.ndarray:
    """The [S, S] query x key visibility the long-context serving path
    implements (ISSUE 20) — THE contract shared by the kernel's
    per-page mask, the pool's eviction rule, and the oracle:

    - prompt queries (position < prompt_len) attend fully causal:
      window/sinks shape DECODE attention only, so prefill K/V content
      is identical to the unwindowed model's;
    - a decode query at position p sees key j iff ``j <= p`` AND j's
      PAGE is a sink page (``(j // page_size) * page_size < sinks``) or
      overlaps the trailing window
      (``page_start + page_size > p + 1 - window``).

    Page-granular on purpose: the paged kernel decides visibility per
    page start (one scalar compare per DMA'd page), and the pool drops
    exactly the pages this mask can never light again — which is what
    makes windowed paged decode token-identical to ``full_decode`` of
    the same mask rather than merely close."""
    if window < 1:
        raise ValueError(f"window must be >= 1 token, got {window}")
    j = np.arange(S)
    p = np.arange(S)[:, None]
    page_start = (j // page_size) * page_size
    vis = (j[None, :] <= p) & (
        (p < prompt_len)
        | (page_start[None, :] < sinks)
        | (page_start[None, :] + page_size > p + 1 - window))
    return vis


def full_decode(params: Dict, cfg: DecodeConfig, prompt: Sequence[int],
                max_new_tokens: int, window: Optional[int] = None,
                sinks: int = 0, page_size: int = 1,
                ) -> Tuple[List[int], List[np.ndarray]]:
    """Greedy per-sequence decode, recomputing the full prefix each token
    (the O(S^2)-per-token baseline the paged path must match).  Returns
    (generated tokens, the [V] logits row behind each of them).
    ``window``/``sinks``/``page_size`` (ISSUE 20) apply the
    page-granular sliding-window + attention-sink decode mask — the
    oracle the windowed paged loop must be token-identical to."""
    tokens = [int(t) for t in prompt]
    out: List[int] = []
    rows: List[np.ndarray] = []
    for _ in range(max_new_tokens):
        mask = (window_mask(len(tokens), len(prompt), window, sinks,
                            page_size)
                if window is not None else None)
        row = full_forward(params, cfg, tokens, mask=mask)[-1]
        nxt = int(row.argmax())
        rows.append(row)
        out.append(nxt)
        tokens.append(nxt)
        if cfg.eos_id is not None and nxt == cfg.eos_id:
            break
    return out, rows


def _apply_adapters(y, x, name, li, adapters, slots):
    """Per-row batched-LoRA delta (ISSUE 19): add each row's
    ``(x @ A) @ B`` for projection `name` at layer `li`, gathering the
    row's A/B from the packed pool arrays by its adapter slot — the
    same scalar-prefetch page-table idiom as paged attention, so ONE
    step mixes tenants.  Slot 0 is the pool's permanent all-zero
    identity: base-model rows ride the same einsum and add exact fp32
    zeros (no masking, no divergent compile shape).  ``adapters=None``
    is the guaranteed zero-cost path — today's code byte for byte."""
    if adapters is None:
        return y
    import jax.numpy as jnp

    A, B = adapters[name]
    Al = A[slots, li]  # [B, d_in, r] per-row gather
    Bl = B[slots, li]  # [B, r, d_out]
    if x.ndim == 2:
        return y + jnp.einsum("br,bro->bo",
                              jnp.einsum("bd,bdr->br", x, Al), Bl)
    return y + jnp.einsum("bsr,bro->bso",
                          jnp.einsum("bsd,bdr->bsr", x, Al), Bl)


def _adapter_slot_array(adapters, adapter_slots):
    """Validate + stage the per-row slot vector for one step call."""
    if adapters is None:
        return None
    import jax.numpy as jnp

    if adapter_slots is None:
        raise ValueError("adapters without adapter_slots")
    return jnp.asarray(np.asarray(adapter_slots, np.int32))


def _step_tables(pool: KVCachePool, seq_ids: Sequence[int],
                 windows, sinks, table_block: Optional[int]):
    """One step's page-table view + windowing operands (ISSUE 20).
    Returns ``(tables, lengths, kw)`` where ``tables`` is a flat
    [B, max_pages] array or a TwoLevelTables and ``kw`` is the extra
    kwargs dict for ``paged_decode_attention``.  Flat tables ship
    explicit per-page starts whenever a row is windowed OR any table
    was evicted (implicit ``i * page_size`` positions stop being true
    then); a TwoLevelTables always carries its starts."""
    windowed = windows is not None
    kw = {}
    if windowed:
        kw["windows"] = np.asarray(windows, np.int32)
        kw["sinks"] = (np.asarray(sinks, np.int32)
                       if sinks is not None
                       else np.zeros(len(seq_ids), np.int32))
    if table_block:
        tables, lengths = pool.two_level_tables(seq_ids, table_block)
    elif windowed:
        tables, starts, lengths = pool.page_tables_with_starts(seq_ids)
        kw["page_starts"] = starts
    else:
        tables, lengths = pool.page_table_batch(seq_ids)
    return tables, lengths, kw


def decode_step(params: Dict, cfg: DecodeConfig, pool: KVCachePool,
                seq_ids: Sequence[int], tokens, positions,
                force: str = "auto", impl: Optional[str] = None,
                adapters=None, adapter_slots=None,
                windows=None, sinks=None,
                table_block: Optional[int] = None) -> np.ndarray:
    """One continuous-batching step: feed token[i] at position[i] for
    every active sequence, append its K/V to the pool, and return the
    next-token logits [B, V].  All sequences share the batch regardless
    of phase — a prefilling sequence and a deep-decode sequence differ
    only in k_lengths.  `impl` selects the paged-attention path (None:
    FLAGS_serving_paged_impl).  ``adapters``/``adapter_slots`` (an
    AdapterPool's ``device_arrays()`` + row i's slot index) apply each
    row's low-rank tenant deltas per projection — None is the base
    model, unchanged.  ``windows``/``sinks`` ([B] int arrays; a
    non-windowed row passes ``PAD_START``/0) apply the per-row
    sliding-window + attention-sink decode mask; ``table_block`` routes
    the page tables through the two-level SMEM layout (ISSUE 20)."""
    import jax.numpy as jnp

    tokens = np.asarray(tokens, np.int32)
    positions = np.asarray(positions, np.int32)
    B = tokens.shape[0]
    d, H, Dh = cfg.d_model, cfg.n_head, cfg.head_dim
    Hkv = cfg.num_kv_heads
    aslots = _adapter_slot_array(adapters, adapter_slots)
    h = jnp.asarray(params["embed"])[tokens] * np.sqrt(d) \
        + jnp.asarray(params["pos"])[positions]
    pages, slots = pool.append_token(seq_ids)
    tables, lengths, wkw = _step_tables(pool, seq_ids, windows, sinks,
                                        table_block)
    for li, lp in enumerate(params["layers"]):
        q = _apply_adapters(h @ lp["wq"], h, "wq", li, adapters,
                            aslots).reshape(B, H, Dh)
        k = _apply_adapters(h @ lp["wk"], h, "wk", li, adapters,
                            aslots).reshape(B, Hkv, Dh)
        v = _apply_adapters(h @ lp["wv"], h, "wv", li, adapters,
                            aslots).reshape(B, Hkv, Dh)
        pool.write_kv(li, pages, slots, k, v)
        k_scales, v_scales = pool.layer_scales(li)
        attn = paged_decode_attention(
            q[:, :, None, :], pool.k_pages[li], pool.v_pages[li],
            tables, lengths, scale=Dh ** -0.5, impl=impl, force=force,
            k_scales=k_scales, v_scales=v_scales, **wkw,
        )  # [B, H, 1, Dh]
        attn = attn[:, :, 0, :].reshape(B, d)
        h = _layernorm(h + _apply_adapters(attn @ lp["wo"], attn, "wo",
                                           li, adapters, aslots),
                       lp["ln1_g"], lp["ln1_b"])
        u = jnp.maximum(_apply_adapters(h @ lp["w1"], h, "w1", li,
                                        adapters, aslots) + lp["b1"],
                        0.0)
        ff = _apply_adapters(u @ lp["w2"], u, "w2", li, adapters,
                             aslots) + lp["b2"]
        h = _layernorm(h + ff, lp["ln2_g"], lp["ln2_b"])
    return np.asarray(h @ jnp.asarray(params["embed"]).T)


def prefill_step(params: Dict, cfg: DecodeConfig, pool: KVCachePool,
                 seq_ids: Sequence[int], prompts: Sequence[Sequence[int]],
                 force: str = "auto", adapters=None,
                 adapter_slots=None) -> np.ndarray:
    """Batched whole-prompt prefill: ONE causal pass over every prompt
    (ragged lengths padded to the co-admitted max, masked through the
    flash ``k_lengths`` tier) writes each prompt token's per-layer K/V
    into the pool and returns the next-token logits [B, V] after each
    prompt — the logits token-by-token prefill would only reach after
    len(prompt) model steps.  Padded rows compute garbage that is never
    read: attention masks them as keys, their K/V is never written
    (only the claimed (page, slot)s are), and the returned row is
    gathered at each sequence's true last position."""
    import jax.numpy as jnp

    lens = np.asarray([len(p) for p in prompts], np.int32)
    if not len(lens) or lens.min() < 1:
        raise ValueError("prefill needs non-empty prompts")
    B, Smax = len(prompts), int(lens.max())
    if Smax > cfg.max_length:
        # before append_tokens: a failed prefill must not leave claimed
        # slots with no K/V behind (the pool's atomicity contract)
        raise ValueError(
            f"prompt length {Smax} > max_length {cfg.max_length}")
    d, H, Dh = cfg.d_model, cfg.n_head, cfg.head_dim
    Hkv, G = cfg.num_kv_heads, cfg.group_size
    tokens = np.zeros((B, Smax), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :lens[i]] = p
    # flat (sequence order, token order) claim — matches append_tokens
    pages, slots = pool.append_tokens(seq_ids, lens)
    b_idx = np.repeat(np.arange(B), lens)
    t_idx = np.concatenate([np.arange(n) for n in lens])
    aslots = _adapter_slot_array(adapters, adapter_slots)

    h = jnp.asarray(params["embed"])[tokens] * np.sqrt(d) \
        + jnp.asarray(params["pos"])[None, :Smax]  # [B, Smax, d]
    for li, lp in enumerate(params["layers"]):
        q = _apply_adapters(h @ lp["wq"], h, "wq", li, adapters,
                            aslots).reshape(B, Smax, H, Dh)
        k = _apply_adapters(h @ lp["wk"], h, "wk", li, adapters,
                            aslots).reshape(B, Smax, Hkv, Dh)
        v = _apply_adapters(h @ lp["wv"], h, "wv", li, adapters,
                            aslots).reshape(B, Smax, Hkv, Dh)
        # valid tokens only ([T, H_kv, Dh] rows in claim order) reach
        # the pool (an int8 pool quantizes them on the way in)
        pool.write_kv(li, pages, slots, k[b_idx, t_idx], v[b_idx, t_idx])
        kh, vh = repeat_kv(k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), G)
        attn = flash_attention(
            q.transpose(0, 2, 1, 3), kh, vh, causal=True,
            scale=Dh ** -0.5, k_lengths=lens, force=force)
        attn = attn.transpose(0, 2, 1, 3).reshape(B, Smax, d)
        h = _layernorm(h + _apply_adapters(attn @ lp["wo"], attn, "wo",
                                           li, adapters, aslots),
                       lp["ln1_g"], lp["ln1_b"])
        u = jnp.maximum(_apply_adapters(h @ lp["w1"], h, "w1", li,
                                        adapters, aslots) + lp["b1"],
                        0.0)
        ff = _apply_adapters(u @ lp["w2"], u, "w2", li, adapters,
                             aslots) + lp["b2"]
        h = _layernorm(h + ff, lp["ln2_g"], lp["ln2_b"])
    h_last = h[jnp.arange(B), lens - 1]  # [B, d] true last positions
    return np.asarray(h_last @ jnp.asarray(params["embed"]).T)


def chunk_prefill_step(params: Dict, cfg: DecodeConfig, pool: KVCachePool,
                       seq_ids: Sequence[int],
                       chunks: Sequence[Sequence[int]],
                       start_positions: Sequence[int],
                       adapters=None, adapter_slots=None) -> np.ndarray:
    """Suffix/chunk prefill: process ``chunks[i]`` consecutive prompt
    tokens for sequence i starting at absolute position
    ``start_positions[i]`` — which need NOT be 0.  The chunk's queries
    attend over everything the sequence's page table already holds (a
    prefix-cache-attached shared prefix, earlier chunks of a long
    prompt) PLUS the chunk itself causally, so prefill can resume
    mid-prompt: the prefix-cache hit path pays model compute only for
    the unshared tail, and the chunked-prefill scheduler splits a long
    prompt across engine steps.

    The chunk's per-layer K/V lands in the pool through the same atomic
    ``append_tokens`` claim as every other write — a shared
    partially-filled tail page copy-on-writes right there.  Attention
    is the explicit reference tier: gather the sequence's pages and
    mask by absolute position (key j visible to query at position p
    iff j <= p — cached prefix fully visible, in-chunk causal, padding
    and unwritten slots masked).  A pallas chunk kernel is future work;
    decode steps keep the paged impl selection.

    Returns the logits [B, V] at each sequence's LAST chunk token —
    meaningful only for chunks that complete their prompt."""
    import jax
    import jax.numpy as jnp

    lens = np.asarray([len(c) for c in chunks], np.int32)
    if not len(lens) or lens.min() < 1:
        raise ValueError("chunk prefill needs non-empty chunks")
    starts = np.asarray(start_positions, np.int32)
    B, Cmax = len(chunks), int(lens.max())
    if int((starts + lens).max()) > cfg.max_length:
        # before append_tokens: a failed chunk must not leave claimed
        # slots with no K/V behind (the pool's atomicity contract)
        raise ValueError(
            f"chunk reaches position {int((starts + lens).max())} > "
            f"max_length {cfg.max_length}")
    d, H, Dh = cfg.d_model, cfg.n_head, cfg.head_dim
    Hkv, G = cfg.num_kv_heads, cfg.group_size
    tokens = np.zeros((B, Cmax), np.int32)
    for i, c in enumerate(chunks):
        tokens[i, :lens[i]] = c
    for s in seq_ids:
        if getattr(pool._tables[s], "starts", None) is not None:
            # the gather below places key j at implicit position j —
            # an evicted (compacted) table's pages no longer sit there,
            # so the mask would light the wrong keys silently
            raise ValueError(
                f"sequence {s} is window-evicted — chunk prefill over "
                "a compacted page table is unsupported (windows shape "
                "decode only; prefill before evicting)")
    pages, slots = pool.append_tokens(seq_ids, lens)
    tables, _total = pool.page_table_batch(seq_ids)
    b_idx = np.repeat(np.arange(B), lens)
    t_idx = np.concatenate([np.arange(n) for n in lens])
    S = tables.shape[1] * pool.page_size
    pos = starts[:, None] + np.arange(Cmax)[None, :]  # absolute positions
    pos_c = np.minimum(pos, cfg.max_length - 1)  # padded rows: clamp only
    # key j visible to query (b, i) iff j <= pos[b, i]; the jnp.where
    # also neutralizes NaN scores from masked garbage (padding pages)
    mask = jnp.asarray(np.arange(S)[None, None, :] <= pos[:, :, None])
    aslots = _adapter_slot_array(adapters, adapter_slots)
    h = jnp.asarray(params["embed"])[tokens] * np.sqrt(d) \
        + jnp.asarray(params["pos"])[pos_c]  # [B, Cmax, d]
    scale = Dh ** -0.5
    for li, lp in enumerate(params["layers"]):
        q = _apply_adapters(h @ lp["wq"], h, "wq", li, adapters,
                            aslots).reshape(B, Cmax, H, Dh)
        k = _apply_adapters(h @ lp["wk"], h, "wk", li, adapters,
                            aslots).reshape(B, Cmax, Hkv, Dh)
        v = _apply_adapters(h @ lp["wv"], h, "wv", li, adapters,
                            aslots).reshape(B, Cmax, Hkv, Dh)
        pool.write_kv(li, pages, slots, k[b_idx, t_idx], v[b_idx, t_idx])
        k_scales, v_scales = pool.layer_scales(li)
        k_full = gather_kv_pages(pool.k_pages[li], tables,
                                 scales=k_scales)  # [B, H_kv, S, Dh]
        v_full = gather_kv_pages(pool.v_pages[li], tables,
                                 scales=v_scales)
        k_full, v_full = repeat_kv(k_full, v_full, G)
        scores = jnp.einsum("bihd,bhjd->bhij", q, k_full) * scale
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhij,bhjd->bihd", w, v_full).reshape(B, Cmax, d)
        h = _layernorm(h + _apply_adapters(attn @ lp["wo"], attn, "wo",
                                           li, adapters, aslots),
                       lp["ln1_g"], lp["ln1_b"])
        u = jnp.maximum(_apply_adapters(h @ lp["w1"], h, "w1", li,
                                        adapters, aslots) + lp["b1"],
                        0.0)
        ff = _apply_adapters(u @ lp["w2"], u, "w2", li, adapters,
                             aslots) + lp["b2"]
        h = _layernorm(h + ff, lp["ln2_g"], lp["ln2_b"])
    h_last = h[jnp.arange(B), lens - 1]  # [B, d] true last chunk tokens
    return np.asarray(h_last @ jnp.asarray(params["embed"]).T)


def verify_step(params: Dict, cfg: DecodeConfig, pool: KVCachePool,
                seq_ids: Sequence[int], blocks: Sequence[Sequence[int]],
                start_positions: Sequence[int], force: str = "auto",
                impl: Optional[str] = None,
                pad_to: Optional[int] = None,
                adapters=None, adapter_slots=None,
                windows=None, sinks=None,
                table_block: Optional[int] = None) -> np.ndarray:
    """One speculative verify step: sequence i feeds ``blocks[i]`` —
    its last committed token plus d_i drafted continuations — starting
    at absolute position ``start_positions[i]``, appends every fed
    token's per-layer K/V to the pool (ONE atomic ``append_tokens``
    claim), and returns the logits [B, Sq_max, V] at every fed
    position: row t predicts the token at position start+t+1, which is
    exactly what draft token t+1 claims to be.  Ragged draft depths
    ride the ``q_lengths`` arm of ``paged_decode_attention`` — the KV
    page stream is the SAME as a single-token step's (each live page
    reads once per sequence), which is the amortization speculation
    banks.  Rows past ``len(blocks[i])`` are padding garbage the
    caller must ignore.  A block of length 1 is exactly ``decode_step``
    for that sequence, so mixed draft/no-draft batches share the step.

    The caller owns acceptance and ROLLBACK: rejected tokens' K/V
    stays claimed until ``pool.truncate_seq`` undoes it (the loop does
    both in the same scheduler turn)."""
    import jax.numpy as jnp

    lens = np.asarray([len(b) for b in blocks], np.int32)
    if not len(lens) or lens.min() < 1:
        raise ValueError("verify needs >= 1 fed token per sequence")
    starts = np.asarray(start_positions, np.int32)
    # pad_to pins the query width to one static shape (the loop passes
    # speculate+1) so the jitted finite scan and the memoized pallas
    # kernel compile ONCE per batch size instead of once per distinct
    # ragged draft mix — the padded rows are q_lengths-masked garbage
    # either way
    B, Sqm = len(blocks), int(lens.max())
    if pad_to is not None:
        if pad_to < Sqm:
            raise ValueError(f"pad_to {pad_to} < longest block {Sqm}")
        Sqm = int(pad_to)
    if int((starts + lens).max()) > cfg.max_length:
        # before append_tokens: a failed verify must not leave claimed
        # slots with no K/V behind (the pool's atomicity contract)
        raise ValueError(
            f"verify block reaches position {int((starts + lens).max())} "
            f"> max_length {cfg.max_length}")
    d, H, Dh = cfg.d_model, cfg.n_head, cfg.head_dim
    Hkv = cfg.num_kv_heads
    tokens = np.zeros((B, Sqm), np.int32)
    for i, b in enumerate(blocks):
        tokens[i, :lens[i]] = b
    pages, slots = pool.append_tokens(seq_ids, lens)
    tables, lengths, wkw = _step_tables(pool, seq_ids, windows, sinks,
                                        table_block)
    if not table_block and tables.shape[1] % 8:
        # bucket the table width to multiples of 8 pages: decode compile
        # shapes change once per 8 pages of growth instead of every
        # page, so the verify kernels reach steady state quickly (the
        # padded entries are dummy page-0 walks fully masked by
        # ``lengths`` — the existing zero-padded-table contract).  A
        # two-level table buckets at block granularity already, and its
        # explicit-starts arm pads with PAD_START (the position mask
        # kills the dummy walks when implicit positions no longer hold)
        padded = -(-tables.shape[1] // 8) * 8
        grow = padded - tables.shape[1]
        tables = np.pad(tables, ((0, 0), (0, grow)))
        if "page_starts" in wkw:
            wkw["page_starts"] = np.pad(
                wkw["page_starts"], ((0, 0), (0, grow)),
                constant_values=PAD_START)
    b_idx = np.repeat(np.arange(B), lens)
    t_idx = np.concatenate([np.arange(n) for n in lens])
    # stable-shape writes: pad the scatter to B*Sqm rows by REPEATING
    # the last claimed (page, slot) and its row — duplicate scatter
    # indices carrying identical values are a no-op, and the fixed row
    # count means the write kernels compile once per (B, Sqm) instead
    # of once per distinct ragged draft mix
    T = len(b_idx)
    pad_rows = B * Sqm - T
    if pad_rows:
        b_idx = np.concatenate([b_idx, np.full(pad_rows, b_idx[-1])])
        t_idx = np.concatenate([t_idx, np.full(pad_rows, t_idx[-1])])
        pages = np.concatenate([pages, np.full(pad_rows, pages[-1],
                                                pages.dtype)])
        slots = np.concatenate([slots, np.full(pad_rows, slots[-1],
                                                slots.dtype)])
    pos = starts[:, None] + np.arange(Sqm)[None, :]
    pos_c = np.minimum(pos, cfg.max_length - 1)  # padded rows: clamp only
    aslots = _adapter_slot_array(adapters, adapter_slots)
    h = jnp.asarray(params["embed"])[tokens] * np.sqrt(d) \
        + jnp.asarray(params["pos"])[pos_c]  # [B, Sqm, d]
    for li, lp in enumerate(params["layers"]):
        q = _apply_adapters(h @ lp["wq"], h, "wq", li, adapters,
                            aslots).reshape(B, Sqm, H, Dh)
        k = _apply_adapters(h @ lp["wk"], h, "wk", li, adapters,
                            aslots).reshape(B, Sqm, Hkv, Dh)
        v = _apply_adapters(h @ lp["wv"], h, "wv", li, adapters,
                            aslots).reshape(B, Sqm, Hkv, Dh)
        # valid rows (plus the identical-value padding) in claim order
        pool.write_kv(li, pages, slots, k[b_idx, t_idx], v[b_idx, t_idx])
        k_scales, v_scales = pool.layer_scales(li)
        attn = paged_decode_attention(
            q.transpose(0, 2, 1, 3), pool.k_pages[li], pool.v_pages[li],
            tables, lengths, scale=Dh ** -0.5, impl=impl, force=force,
            k_scales=k_scales, v_scales=v_scales, q_lengths=lens, **wkw,
        )  # [B, H, Sqm, Dh]
        attn = attn.transpose(0, 2, 1, 3).reshape(B, Sqm, d)
        h = _layernorm(h + _apply_adapters(attn @ lp["wo"], attn, "wo",
                                           li, adapters, aslots),
                       lp["ln1_g"], lp["ln1_b"])
        u = jnp.maximum(_apply_adapters(h @ lp["w1"], h, "w1", li,
                                        adapters, aslots) + lp["b1"],
                        0.0)
        ff = _apply_adapters(u @ lp["w2"], u, "w2", li, adapters,
                             aslots) + lp["b2"]
        h = _layernorm(h + ff, lp["ln2_g"], lp["ln2_b"])
    return np.asarray(h @ jnp.asarray(params["embed"]).T)  # [B, Sqm, V]


@dataclasses.dataclass
class DecodeRequest:
    prompt: Sequence[int]
    max_new_tokens: int
    # carried through from Engine.submit when the decode loop fronts an
    # engine; None (the default) mints a fresh id at run() when
    # FLAGS_observability is on
    trace_id: Optional[str] = None
    # per-request sampling contract (serving/sampling.py) — None is
    # exact greedy, the full_decode-oracle arm; non-greedy params
    # auto-disable speculation for THIS sequence only
    sampling: Optional[SamplingParams] = None
    # disaggregated serving (serving/fleet): a prefilled-elsewhere
    # payload.  The carrier must expose ``matched_tokens`` (prefix
    # tokens the destination re-attaches from its own cache),
    # ``admit(pool, prefix_cache, seq_id)`` (attach + import the
    # shipped pages), and ``first_token``/``first_logits`` (the token
    # the prefill side already chose and the row behind it).  The loop
    # then skips prefill entirely: admission imports the pages, emits
    # the first token, and the sequence decodes like any other
    handoff: Optional[object] = None
    # tiered KV cache (serving/kvtier): the multi-turn session this
    # request continues.  When the loop carries a session_manager,
    # admission asks it to resume the session's retained KV (resident
    # in the pool, or parked in the host tier) and retirement keeps the
    # sequence's pages resident for the next turn instead of freeing
    # them.  None (the default) is the ordinary one-shot request
    session: Optional[object] = None
    # multi-tenant serving (serving/adapters): the model VARIANT this
    # request decodes under.  The loop acquires it from its
    # AdapterPool at admission (an unloadable/corrupt adapter rejects
    # typed BEFORE any KV page is claimed) and every step applies the
    # variant's low-rank deltas to just this request's rows.  None
    # (the default) is the base model — the guaranteed zero-cost path
    adapter_id: Optional[str] = None
    # long-context serving (ISSUE 20): sliding-window decode attention.
    # A decode query sees the last `window` tokens (page-granular: any
    # page overlapping the window) plus the first `sinks` tokens' pages
    # (attention sinks); prefill stays full attention.  The loop evicts
    # pages the mask can never light again before each decode step, so
    # a 128k-context sequence's per-step KV traffic and page residency
    # are bounded by window + sinks, not context length.  None (the
    # default) is full attention — exactly today's path.  Output is
    # token-identical to full_decode under the SAME window_mask.
    window: Optional[int] = None
    sinks: int = 0


@dataclasses.dataclass
class GeneratedSequence:
    """One finished sequence: generated tokens + the logits row behind
    each (the parity surface vs full_decode), and latency accounting.
    `error` is set (NonFiniteSequenceError) when the sequence was
    quarantined instead of retiring cleanly — its tokens/logits stop at
    the last finite step."""

    seq_id: int
    prompt: List[int]
    tokens: List[int] = dataclasses.field(default_factory=list)
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)
    admitted_at: float = 0.0
    ttft_s: Optional[float] = None
    finished_at: float = 0.0
    error: Optional[Exception] = None
    # request trace id (None when FLAGS_observability was off): the join
    # key into the merged trace, metric exemplars, and flight events
    trace_id: Optional[str] = None


class _Active:
    __slots__ = ("req", "seq_id", "pos", "result", "rt", "matched",
                 "charged", "whole", "chunk_mode", "inserted",
                 "drafted", "accepted", "aslot", "spec_source")

    def __init__(self, req: DecodeRequest, seq_id: int,
                 result: GeneratedSequence, rt=None):
        self.req = req
        self.seq_id = seq_id
        self.pos = 0  # next position to feed
        self.result = result
        self.rt = rt  # RequestTrace (None with observability off)
        self.matched = 0   # prompt tokens served from the prefix cache
        self.charged = 0   # pages this admission reserved (prefix-aware)
        self.whole = False       # whole-prompt prefill_step at admission
        self.chunk_mode = False  # tail/capped prefill via chunk steps
        self.inserted = False    # prompt pages offered to the cache
        self.drafted = 0   # speculative tokens proposed for this seq
        self.accepted = 0  # ... of which the verifier accepted
        self.aslot = 0     # adapter device slot (0 = base-model identity)
        self.spec_source = "own"  # n-gram source of the LAST proposal


class ContinuousBatchingLoop:
    """Admit-as-they-retire greedy decode over one KVCachePool.

    Admission control is reservation-based: a request is admitted only
    when the pool can cover EVERY admitted sequence's worst-case
    footprint (ceil((len(prompt)+max_new)/page_size) pages), so
    append_token can never raise mid-decode — a sequence, once admitted,
    always runs to completion.  Waiting requests admit in FIFO order the
    moment retirements free enough pages.

    ``prefill="batched"`` (default) runs each co-admitted group's
    prompts through ONE whole-prompt ``prefill_step`` — prefill model
    steps per admission group are O(1) instead of O(max prompt len),
    counted separately in ``prefill_steps``/``decode_steps``.
    ``prefill="token"`` is the original token-by-token arm (the parity
    oracle and A/B baseline).  ``paged_impl`` selects the decode
    attention path (None: FLAGS_serving_paged_impl; resolved against
    the pool geometry once, so metrics are labeled with the impl that
    actually runs).

    ``prefix_cache`` (a serving.PrefixCache over the same pool) turns
    shared-prefix prompts into page reuse: admission matches the
    longest cached prefix, attaches its pages read-only (refcount++,
    charged ZERO fresh pages for matched full pages), and prefill
    covers only the unshared tail via ``chunk_prefill_step`` (the
    token arm and SPMD programs resume at the matched position
    instead).  Completed prefills insert their prompt pages back into
    the cache; retirement frees only refcount-zero pages; a
    quarantined hit invalidates its cached chain.  ``prefill_chunk``
    (None: FLAGS_serving_prefill_chunk; 0 = uncapped) bounds the
    PREFILL tokens any single engine step may process, and the
    scheduler alternates chunk and decode steps when both kinds of
    work exist — long prompts stop stalling in-flight sequences'
    per-token latency.  Counters: ``prefix_hits``/``prefix_misses``,
    ``cached_prefill_tokens``, ``prefill_tokens``,
    ``max_prefill_tokens_step``.

    Fault isolation: every step's logits pass a per-ROW jitted
    finite-check (resilience.sentinel.rows_finite — ONE fused jit call
    per step, no per-sequence host sync); a non-finite row QUARANTINES
    only that sequence (its result carries NonFiniteSequenceError, its
    pages return to the pool) while batch-mates decode on.  Any
    exception escaping a prefill/decode step frees every stepping
    sequence's pages before propagating — a raise can cost the run,
    never pool pages.  ``check_every=N`` additionally audits the pool
    (KVCachePool.check_invariants) every N steps and repairs detected
    leaks via reclaim_orphans."""

    def __init__(self, params: Dict, cfg: DecodeConfig, pool: KVCachePool,
                 max_batch: int = 4, force: str = "auto",
                 paged_impl: Optional[str] = None,
                 prefill: str = "batched", check_every: int = 0,
                 program=None, prefix_cache=None,
                 prefill_chunk: Optional[int] = None,
                 speculate: Optional[int] = None, drafter=None,
                 session_manager=None, adapter_pool=None,
                 table_block: Optional[int] = None,
                 prefill_flops: Optional[float] = None):
        if prefill not in ("batched", "token"):
            raise ValueError(
                f"prefill must be 'batched' or 'token', got {prefill!r}")
        if prefix_cache is not None and prefix_cache.pool is not pool:
            raise ValueError(
                "prefix_cache is wired to a different pool — shared "
                "pages and refcounts must live in the pool this loop "
                "appends to")
        if session_manager is not None:
            if session_manager.pool is not pool:
                raise ValueError(
                    "session_manager is wired to a different pool — "
                    "sessions spill from and resume into the pool this "
                    "loop appends to")
            if session_manager.cache is not None \
                    and session_manager.cache is not prefix_cache:
                raise ValueError(
                    "session_manager carries a different prefix cache "
                    "than the loop — spill-time pins and resume-time "
                    "attaches must agree on one trie")
        if adapter_pool is not None and program is not None:
            raise ValueError(
                "SPMD program loops do not support adapter_pool — the "
                "per-row adapter gather lives in this module's step "
                "functions, not in custom programs (yet)")
        self.params = params
        self.cfg = cfg if cfg is not None else getattr(program, "cfg", None)
        if self.cfg is None:
            raise ValueError("pass cfg (or a program that carries one)")
        if getattr(pool, "num_kv_heads", None) not in (
                None, self.cfg.num_kv_heads):
            raise ValueError(
                f"pool holds {pool.num_kv_heads} KV heads but the model "
                f"projects {self.cfg.num_kv_heads} (cfg.n_kv_head) — a "
                "mismatched pool would scatter K/V across wrong heads")
        self.pool = pool
        self.max_batch = int(max_batch)
        self.force = force
        self.prefill = prefill
        self.check_every = int(check_every)
        # program: an object exposing decode_step(pool, seq_ids, tokens,
        # positions) and prefill_step(pool, seq_ids, prompts) — e.g.
        # serving.distributed.ShardedDecodeProgram.  The loop's
        # admission / quarantine / retirement / watchdog machinery is
        # step-implementation-agnostic, so the SPMD program rides it
        # unchanged; None keeps this module's single-device math.
        self.program = program
        if program is not None:
            self.paged_impl = program.resolve_impl(pool)
        else:
            self.paged_impl = resolve_paged_impl(
                paged_impl, pool.page_size, self.cfg.head_dim,
                pool.k_pages.dtype)
        self.prefix_cache = prefix_cache
        # tiered KV cache (serving/kvtier.TieredSessionManager):
        # requests carrying a .session resume retained KV at admission
        # and keep their pages resident at retirement
        self.session_manager = session_manager
        # multi-tenant adapters (serving/adapters.AdapterPool):
        # requests carrying an adapter_id acquire their variant at
        # admission and decode through per-row low-rank deltas
        self.adapter_pool = adapter_pool
        # prefill-token cap per engine step (0 = uncapped); None reads
        # FLAGS_serving_prefill_chunk
        self._prefill_chunk = int(
            prefill_chunk if prefill_chunk is not None
            else _flags._VALUES["FLAGS_serving_prefill_chunk"])
        if self._prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0")
        # compute-budgeted chunked prefill (ISSUE 20): bound each chunk
        # step's ESTIMATED ATTENTION WORK (token·resident-position
        # units — prefill_sched.plan_chunks) instead of / on top of its
        # token count, so a 100-token chunk at a 100k-token resident
        # prefix stops costing 1000x a cold one under the same cap.
        # None keeps the pure token budget
        self._prefill_flops = (float(prefill_flops)
                               if prefill_flops is not None else None)
        if self._prefill_flops is not None and self._prefill_flops <= 0:
            raise ValueError("prefill_flops must be > 0 (or None)")
        if self._prefill_flops is not None and not self._prefill_chunk:
            # the FLOP budget rides the chunk-step scheduler; without a
            # token cap, whole-prompt prefill bypasses plan_chunks
            # entirely and the budget would silently never apply
            raise ValueError(
                "prefill_flops needs chunked prefill — also pass a "
                "nonzero prefill_chunk (it still clamps tokens; the "
                "FLOP budget binds where it is tighter)")
        # two-level page tables (ISSUE 20): route decode/verify steps'
        # scalar-prefetch tables through the [B, ceil(P/block)] L1 +
        # per-block L2 layout, bounding SMEM by LIVE table blocks.
        # None keeps flat tables — mandatory for SPMD programs (their
        # step functions own their table plumbing)
        self._table_block = int(table_block) if table_block else None
        if table_block is not None and int(table_block) < 1:
            raise ValueError("table_block must be >= 1 (or None)")
        if self._table_block and program is not None:
            raise ValueError(
                "table_block is not supported with a custom program — "
                "the program's decode_step owns its page-table layout")
        # speculative decoding (ISSUE 13/16): d draft tokens per
        # generating sequence per step, verified in one multi-token
        # model step.  None reads FLAGS_serving_speculate; 0 disables.
        # Program-driven (SPMD) loops speculate through the program's
        # own verify_step; only a custom program WITHOUT one degrades
        # to d=0 — surfaced as a spec_disabled counter + flight event
        # so a fleet where speculation quietly stopped paying stays
        # diagnosable (ISSUE 16 bugfix: this used to be a log line)
        self._speculate = int(
            speculate if speculate is not None
            else _flags._VALUES["FLAGS_serving_speculate"])
        if self._speculate < 0:
            raise ValueError("speculate must be >= 0")
        if self._speculate and program is not None \
                and not hasattr(program, "verify_step"):
            _log.info(
                "program %s exposes no verify_step — speculative "
                "decoding degrades to d=0 for this loop",
                type(program).__name__)
            if _flags._VALUES["FLAGS_observability"]:
                _smetrics.record_spec_disabled("program_no_verify")
                _flight.default_flight().record(
                    "spec_disabled", reason="program_no_verify",
                    program=type(program).__name__)
            self._speculate = 0
        self.drafter = drafter if drafter is not None else (
            PromptLookupDrafter(
                max_draft=self._speculate,
                corpus=(prefix_cache if hasattr(
                    prefix_cache, "ngram_continuation") else None))
            if self._speculate else None)
        self._next_seq_id = 0
        self.steps = 0
        self.prefill_steps = 0
        self.decode_steps = 0
        self.quarantined = 0
        self.reclaimed_pages = 0
        self.invariant_violations = 0
        self._occupancy_sum = 0.0
        # prefix-cache / chunked-prefill accounting (serve_bench banks
        # hit rate + cached tokens; tests counter-assert the chunk cap)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.cached_prefill_tokens = 0
        self.prefill_tokens = 0
        self.max_prefill_tokens_step = 0
        self._prefer_prefill = True
        # speculation accounting (serve_bench banks acceptance_rate and
        # tokens/step; traces/flight carry the per-sequence split)
        self.spec_steps = 0
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.rolled_back_tokens = 0
        # tiered-session accounting (serve_bench banks the resume hit
        # rate of the multi-turn workload off these)
        self.session_resumes = 0
        self.session_resumed_tokens = 0
        self.session_fresh = 0
        # multi-tenant adapter accounting (serve_bench --tenants banks
        # hit rate and gather bytes/step off these + the pool's stats)
        self.adapter_rejects = 0
        self.adapter_rows = 0
        self.adapter_gather_bytes = 0.0
        # long-context accounting (ISSUE 20): window/sink eviction
        # volume, and decode-step wall times taken WHILE chunked
        # prefill work was still pending — the per-step latency hit a
        # long prefill inflicts on in-flight sequences, the number the
        # compute budget exists to bound (serve_bench banks its p99)
        self.pages_evicted = 0
        self._decode_durs_during_prefill: List[float] = []
        # widest page-table walk any decode/verify step paid (max over
        # steps of the batch's max live-page count) — post-eviction,
        # so serve_bench can price the analytic decode bytes/step a
        # windowed long context actually streams
        self.max_decode_table_pages = 0

    def decode_step_p99_during_prefill_s(self) -> float:
        """p99 decode-step wall time over steps that ran while chunked
        prefill was pending (0.0 when no such step ran)."""
        durs = self._decode_durs_during_prefill
        if not durs:
            return 0.0
        return float(np.percentile(np.asarray(durs), 99))

    def acceptance_rate(self) -> float:
        """Accepted / drafted speculative tokens (0.0 before any
        draft) — the number that decides whether speculation paid."""
        return (self.accepted_tokens / self.drafted_tokens
                if self.drafted_tokens else 0.0)

    def _max_new(self, a: "_Active") -> int:
        """Effective generation cap: the request's max_new_tokens,
        tightened by SamplingParams.max_new when present."""
        p = a.req.sampling
        if p is not None and p.max_new is not None:
            return min(a.req.max_new_tokens, p.max_new)
        return a.req.max_new_tokens

    def _spec_room(self, a: "_Active") -> int:
        """Draft tokens sequence `a` may carry THIS step: capped by the
        loop's d and by the sequence's remaining generation headroom
        (the worst-case admission reservation must still cover the
        transiently-fed block — ceil((prompt+max_new)/page_size) pages
        bound pos+1+d), and zero while the prompt still prefills.
        Sampled (temp>0) rows draft too — their verify outcome goes
        through the exact accept/resample epilogue instead of the
        greedy longest-prefix walk (ISSUE 16)."""
        if not self._speculate or a.pos < len(a.result.prompt):
            return 0
        return min(self._speculate,
                   self._max_new(a) - len(a.result.tokens))

    def _footprint(self, req: DecodeRequest, matched: int = 0) -> int:
        """Worst-case pages a request pulls from the FREE list.  With
        `matched` prompt tokens served by the prefix cache, only the
        unshared region is charged: the matched FULL pages attach
        refcounted (no free-list pressure), and the pages for
        everything past them — including the copy-on-write replacement
        of a shared partial tail page — are exactly
        ceil((total - matched_full) / page_size)."""
        total = len(req.prompt) + req.max_new_tokens
        if total > self.cfg.max_length:
            raise ValueError(
                f"prompt+max_new={total} exceeds max_length "
                f"{self.cfg.max_length}")
        matched_full = (int(matched) // self.pool.page_size) \
            * self.pool.page_size
        return KVCachePool.pages_needed(total - matched_full,
                                        self.pool.page_size)

    def run(self, requests: Sequence[DecodeRequest]) -> List[GeneratedSequence]:
        obs_on = _flags._VALUES["FLAGS_observability"]
        waiting: List[Tuple[DecodeRequest, GeneratedSequence, object]] = []
        results: List[GeneratedSequence] = []
        for req in requests:
            if not len(req.prompt):
                raise ValueError("empty prompt")
            if req.sampling is not None \
                    and req.sampling.max_bias_token() >= self.cfg.vocab_size:
                # part of the same validate-before-any-work pass: an
                # out-of-vocab bias id would IndexError mid-step and
                # cost the whole batch instead of this one request
                raise ValueError(
                    f"logit_bias token {req.sampling.max_bias_token()} "
                    f">= vocab_size {self.cfg.vocab_size}")
            if req.adapter_id is not None and self.adapter_pool is None:
                # operator config error, not a per-request one: a loop
                # with no pool can never serve ANY adapter request, so
                # fail the run up front like every other validate check
                raise ValueError(
                    f"request names adapter {req.adapter_id!r} but the "
                    "loop carries no adapter_pool")
            if req.window is not None:
                if req.window < 1:
                    raise ValueError(
                        f"window must be >= 1 token, got {req.window}")
                if self.program is not None:
                    raise ValueError(
                        "windowed decode is not supported with a "
                        "custom program — its step functions own the "
                        "attention mask")
            if req.sinks < 0:
                raise ValueError(f"sinks must be >= 0, got {req.sinks}")
            if req.sinks and req.window is None:
                raise ValueError(
                    "sinks without a window has no meaning — sink "
                    "pages are the exception to a window's eviction")
            # validate EVERY request (max_length AND whole-pool fit)
            # before any work: a mid-run raise would strand allocated
            # pages and throw away already-finished sequences' results.
            # A handoff's reserved prefix pages are refcount-pinned on
            # THIS pool, so (unlike a mere cache match, which eviction
            # could still void) they are safe to subtract here
            need = self._footprint(
                req, int(getattr(req.handoff, "matched_tokens", 0))
                if req.handoff is not None else 0)
            if need > self.pool.num_pages:
                from .kvcache import PagePoolExhausted

                raise PagePoolExhausted(
                    f"request needs {need} pages worst-case but the pool "
                    f"has {self.pool.num_pages} total")
            seq = GeneratedSequence(seq_id=-1, prompt=[int(t) for t in req.prompt])
            rt = None
            if obs_on:
                # sequence lifecycle trace: queued (here) -> admitted ->
                # prefill -> decode -> retired/quarantined
                rt = _rtrace.default_request_tracer().start(
                    name="sequence", trace_id=req.trace_id)
                seq.trace_id = rt.trace_id
            results.append(seq)
            waiting.append((req, seq, rt))
        active: List[_Active] = []
        reserved_pages = 0

        def quarantine(batch: List[_Active], logits,
                       step_idx: int) -> Tuple[np.ndarray, set, float]:
            """Evict every non-finite row of this step's logits through
            the shared blast radius (prefill_sched.evict_nonfinite:
            chaos poisoning, the ONE fused [B]-bool scan before the
            single host materialization, page scrub+free, prefix-chain
            quarantine, the quarantined-sequence metric); what is THIS
            loop's alone — batch removal, the result's error/timestamps,
            drafter release, reservation accounting, trace finish —
            rides the on_evict callback.  Returns (host logits, the
            surviving row indices, the post-sync step-end timestamp)."""
            nonlocal reserved_pages

            def on_evict(i: int, err: BaseException, now: float) -> None:
                nonlocal reserved_pages
                a = batch[i]
                active.remove(a)
                err.trace_id = a.result.trace_id
                a.result.error = err
                a.result.finished_at = now
                if getattr(self.drafter, "stateful", False):
                    self.drafter.release(a.seq_id)
                if self.session_manager is not None \
                        and a.req.session is not None:
                    # the evictor already scrubbed + freed the pool
                    # side — reset the session so its next turn
                    # prefills fresh instead of resuming poisoned KV
                    self.session_manager.on_quarantine(a.req.session)
                if a.aslot and self.adapter_pool is not None:
                    self.adapter_pool.release(a.req.adapter_id)
                reserved_pages -= a.charged
                self.quarantined += 1
                if obs_on:
                    _flight.default_flight().record(
                        "quarantine", seq_id=a.seq_id, step=step_idx,
                        trace_id=a.result.trace_id)
                    kept = False
                    if a.rt is not None:
                        # quarantined sequences are forced-keep: the
                        # poisoned request is the one worth reading
                        a.rt.annotate(tokens=len(a.result.tokens),
                                      quarantined_step=step_idx)
                        kept = _rtrace.default_request_tracer().finish(
                            a.rt, outcome="quarantined", t_end=now)
                    if a.result.ttft_s is not None:
                        _smetrics.record_ttft(
                            a.result.ttft_s,
                            trace_id=(a.result.trace_id if kept
                                      else None))

            logits, finite, now = _psched.evict_nonfinite(
                self.pool, self.prefix_cache,
                [a.seq_id for a in batch], [a.matched for a in batch],
                logits, step_idx, on_evict)
            return logits, {i for i in range(len(batch)) if finite[i]}, now

        def emit(a: _Active, row: np.ndarray, t0: float, now: float,
                 tok: Optional[int] = None) -> bool:
            """Record one generated token; True when the sequence is
            done (effective max_new, EOS, or a stop sequence — checked
            after EVERY token, so a stop emitted from inside an
            accepted draft block retires the sequence right there).
            `tok` is the already-chosen token for sampled sequences and
            the speculative walk; None takes the (bias-shifted) greedy
            argmax — exactly full_decode's choice when no bias."""
            params = a.req.sampling
            if tok is None:
                tok = int(apply_bias(row, params).argmax())
            a.result.tokens.append(tok)
            a.result.logits.append(row)
            if a.result.ttft_s is None:
                a.result.ttft_s = now - a.result.admitted_at
                if obs_on and a.rt is not None:
                    a.rt.event("sequence.prefill",
                               a.result.admitted_at, now)
            if obs_on:
                _smetrics.record_token(now - t0, impl=self.paged_impl)
            return (len(a.result.tokens) >= self._max_new(a)
                    or (self.cfg.eos_id is not None
                        and tok == self.cfg.eos_id)
                    or stop_hit(a.result.tokens, params))

        def emit_batch(pairs, t0: float, now: float) -> List[_Active]:
            """Emit one token for every (sequence, logits-row) pair —
            non-greedy rows resolved by the ONE jitted sampling
            epilogue call this step, greedy rows by host argmax (the
            oracle's arithmetic).  Returns the finished sequences."""
            toks: List[Optional[int]] = [None] * len(pairs)
            sampled = [(j, a, row) for j, (a, row) in enumerate(pairs)
                       if a.req.sampling is not None
                       and not a.req.sampling.greedy]
            if sampled:
                rows = np.stack([apply_bias(r, a.req.sampling)
                                 for _, a, r in sampled])
                chosen = sample_rows(
                    rows, [a.req.sampling for _, a, _ in sampled],
                    [len(a.result.tokens) for _, a, _ in sampled])
                for (j, _, _), tk in zip(sampled, chosen):
                    toks[j] = int(tk)
            done: List[_Active] = []
            for (a, row), tk in zip(pairs, toks):
                if emit(a, row, t0, now, tok=tk):
                    done.append(a)
            return done

        def retire(batch: List[_Active], now: float) -> None:
            nonlocal reserved_pages
            for a in batch:
                active.remove(a)
                a.result.finished_at = now
                resident = False
                if self.session_manager is not None \
                        and a.req.session is not None:
                    # tiered session: the manager adopts the retired
                    # sequence's pages (they stay resident for the
                    # next turn, spillable to the host tier under
                    # pressure) — the reservation charge still drops,
                    # the pages move into the manager-locked set the
                    # admission bound sets aside
                    resident = self.session_manager.on_retire(
                        a.req.session, a.seq_id, a.result.prompt,
                        a.result.tokens, trace_id=a.result.trace_id,
                        adapter_id=a.req.adapter_id)
                if not resident:
                    self.pool.free_seq(a.seq_id)
                if a.aslot and self.adapter_pool is not None:
                    self.adapter_pool.release(a.req.adapter_id)
                reserved_pages -= a.charged
                if self.prefix_cache is not None:
                    self.prefix_cache.forget_seq(a.seq_id)
                if getattr(self.drafter, "stateful", False):
                    self.drafter.release(a.seq_id)
                if obs_on:
                    _smetrics.record_sequence("retired")
                    kept = False
                    if a.rt is not None:
                        if a.result.ttft_s is not None:
                            a.rt.event(
                                "sequence.decode",
                                a.result.admitted_at + a.result.ttft_s,
                                now, tokens=len(a.result.tokens))
                        a.rt.annotate(tokens=len(a.result.tokens))
                        if a.drafted:
                            # where speculation paid or thrashed for
                            # THIS request — tail-kept traces carry it
                            a.rt.annotate(
                                drafted=a.drafted, accepted=a.accepted,
                                rejected=a.drafted - a.accepted)
                        kept = _rtrace.default_request_tracer().finish(
                            a.rt, outcome="ok", t_end=now)
                    if a.result.ttft_s is not None:
                        # observed at retirement, where the sampling
                        # verdict is known: the exemplar must reference
                        # a trace that exists in the merged trace
                        _smetrics.record_ttft(
                            a.result.ttft_s,
                            trace_id=(a.result.trace_id if kept
                                      else None))

        def adapter_args(group: List[_Active]):
            """Per-step adapter inputs for one stepping group: (the
            pool's packed device arrays, row i's slot index) — or
            (None, None), the guaranteed zero-cost identity path, when
            no row carries an adapter.  Also banks the analytic
            gather-bytes accounting serve_bench --tenants reports."""
            if self.adapter_pool is None \
                    or not any(a.aslot for a in group):
                return None, None
            asl = np.asarray([a.aslot for a in group], np.int32)
            rows = int((asl > 0).sum())
            self.adapter_rows += rows
            gb = self.adapter_pool.gather_bytes_per_step(rows)
            self.adapter_gather_bytes += gb
            if obs_on:
                _smetrics.record_adapter_gather_bytes(gb)
            return self.adapter_pool.device_arrays(), asl

        def window_args(group: List[_Active]):
            """Per-step (windows, sinks) [B] int32 operands — or (None,
            None), the zero-cost full-attention path, when no row in
            the group is a GENERATING windowed sequence.  A windowed
            sequence still prefilling (token arm) rides full attention
            this step (PAD_START row), exactly the prefill-is-full
            contract."""
            if not any(a.req.window is not None
                       and a.pos >= len(a.result.prompt) for a in group):
                return None, None
            win = np.full(len(group), PAD_START, np.int32)
            snk = np.zeros(len(group), np.int32)
            for i, a in enumerate(group):
                if a.req.window is not None \
                        and a.pos >= len(a.result.prompt):
                    win[i] = a.req.window
                    snk[i] = a.req.sinks
            return win, snk

        def evict_windowed(group: List[_Active]) -> None:
            """Drop every GENERATING windowed sequence's dead interior
            pages before the step's appends: a page entirely past the
            sinks and entirely outside every future query's window can
            never be read again (window_mask is monotone in the query
            position), so the paged walk shrinks to sinks + window
            pages no matter how deep the context runs."""
            for a in group:
                w = a.req.window
                if w is not None and a.pos >= len(a.result.prompt):
                    self.pages_evicted += self.pool.evict_interior(
                        a.seq_id, w, a.req.sinks)

        try:
            while waiting or active:
                # admit (FIFO) while a slot and a worst-case reservation
                # fit.  The reservation is PREFIX-AWARE: a cached-prefix
                # hit charges only the unshared tail, and the bound
                # additionally sets aside every live attached page no
                # admission charge covers (pool.uncharged_live_pages —
                # ground truth off the allocator map, so a cache entry
                # being dropped cannot hide a still-attached page;
                # slightly conservative, never over-committed)
                newly: List[_Active] = []
                while waiting and len(active) < self.max_batch:
                    req, seq, rt = waiting[0]
                    hd = req.handoff
                    mgr = self.session_manager
                    plan = None
                    m = None
                    matched = 0
                    if hd is not None:
                        # disaggregated handoff: the destination-side
                        # cache match was reserved by the handoff
                        # broker; the payload ships only the tail
                        matched = int(getattr(hd, "matched_tokens", 0))
                    else:
                        if mgr is not None and req.session is not None:
                            # tiered session: can retained KV (pool-
                            # resident or host-parked) serve this turn?
                            # Planning pins the session against the
                            # spill writer until admit/abort
                            plan = mgr.plan_resume(
                                req.session, seq.prompt,
                                adapter_id=req.adapter_id)
                        if plan is not None:
                            # parked resumes discount only the prefix
                            # pages pinned across the park (they attach
                            # without free-list pressure — the handoff
                            # reservation argument); a RESIDENT resume
                            # charges its full footprint, conservative
                            # but sound once its pages stop being
                            # manager-locked
                            matched = plan.charge_matched
                        elif self.prefix_cache is not None:
                            # namespaced by adapter: LoRA on wq/wk/wv
                            # changes K/V content, so a base-model
                            # cached prefix must never serve a tenant
                            m = self.prefix_cache.match(
                                req.prompt, adapter_id=req.adapter_id)
                            matched = m.tokens
                    need = self._footprint(req, matched)
                    locked = (self.pool.uncharged_live_pages()
                              if (self.prefix_cache is not None
                                  or mgr is not None) else 0)
                    if mgr is not None:
                        # idle sessions' resident pages are set aside
                        # like live attached pages — no admission
                        # charge covers them, but make_room below can
                        # spill them to the host tier on demand
                        locked += mgr.locked_pages()
                    if reserved_pages + need > self.pool.num_pages - locked:
                        if plan is not None:
                            mgr.abort_resume(plan)
                        if mgr is not None:
                            short = (reserved_pages + need
                                     - (self.pool.num_pages - locked))
                            if mgr.make_room(short) > 0:
                                continue  # re-plan against freed pages
                        break  # wait for retirements
                    waiting.pop(0)
                    aslot = 0
                    if req.adapter_id is not None:
                        try:
                            # pin the variant (faulting it in if cold)
                            # BEFORE any page is claimed: an unloadable
                            # / corrupt / pool-full adapter is a typed
                            # per-request rejection that costs nothing
                            aslot = self.adapter_pool.acquire(
                                req.adapter_id)
                        except AdapterError as err:
                            if plan is not None:
                                mgr.abort_resume(plan)
                            now_r = time.perf_counter()
                            err.trace_id = seq.trace_id
                            seq.error = err
                            seq.finished_at = now_r
                            self.adapter_rejects += 1
                            if obs_on:
                                _smetrics.record_adapter_event("reject")
                                _flight.default_flight().record(
                                    "adapter_reject",
                                    adapter=req.adapter_id,
                                    trace_id=seq.trace_id)
                                if rt is not None:
                                    _rtrace.default_request_tracer() \
                                        .finish(rt, outcome="rejected",
                                                t_end=now_r)
                            continue
                    if plan is not None and plan.kind == "resident":
                        # the session's sequence (and its pages) are
                        # still in the pool — continue it instead of
                        # allocating a fresh table
                        seq.seq_id = plan.session.seq_id
                    else:
                        seq.seq_id = self._next_seq_id
                        self._next_seq_id += 1
                        self.pool.allocate(seq.seq_id)
                    if hd is not None:
                        # attach the reserved shared prefix (if any)
                        # and import the shipped pages — ONE atomic
                        # claim charges the imported footprint.  A
                        # payload stamped with another adapter rejects
                        # typed here (AdapterMismatchError) — one
                        # request's problem, never the batch's
                        try:
                            hd.admit(self.pool, self.prefix_cache,
                                     seq.seq_id)
                        except AdapterError as err:
                            self.pool.free_seq(seq.seq_id)
                            hd.release(self.pool)
                            if aslot and self.adapter_pool is not None:
                                self.adapter_pool.release(req.adapter_id)
                            now_r = time.perf_counter()
                            err.trace_id = seq.trace_id
                            seq.error = err
                            seq.finished_at = now_r
                            self.adapter_rejects += 1
                            if obs_on:
                                _smetrics.record_adapter_event("reject")
                                _flight.default_flight().record(
                                    "adapter_reject",
                                    adapter=req.adapter_id,
                                    trace_id=seq.trace_id)
                                if rt is not None:
                                    _rtrace.default_request_tracer() \
                                        .finish(rt, outcome="rejected",
                                                t_end=now_r)
                            continue
                        if matched:
                            self.prefix_hits += 1
                            self.cached_prefill_tokens += matched
                        elif self.prefix_cache is not None:
                            self.prefix_misses += 1
                    elif plan is not None:
                        # resume the session's KV: resident tables
                        # continue in place (truncated where the new
                        # prompt diverges); parked payloads re-attach
                        # their pinned prefix and import the tail — a
                        # corrupt/lost payload degrades to the prefix
                        # alone (typed, counted), never garbage
                        matched = mgr.resume(plan, seq.seq_id,
                                             trace_id=seq.trace_id)
                        self.session_resumes += 1
                        self.session_resumed_tokens += matched
                        if self.prefix_cache is not None:
                            if matched:
                                self.prefix_hits += 1
                                self.cached_prefill_tokens += matched
                            else:
                                self.prefix_misses += 1
                    elif m is not None:
                        matched = self.prefix_cache.attach(seq.seq_id, m)
                        if matched:
                            self.prefix_hits += 1
                            self.cached_prefill_tokens += matched
                        else:
                            self.prefix_misses += 1
                    if mgr is not None and req.session is not None \
                            and hd is None and plan is None:
                        self.session_fresh += 1
                    seq.admitted_at = time.perf_counter()
                    a = _Active(req, seq.seq_id, seq, rt=rt)
                    a.pos = matched
                    a.matched = matched
                    a.charged = need
                    a.aslot = aslot
                    # whole-prompt prefill keeps its one-pass fast path
                    # when nothing is cached and no chunk cap binds;
                    # everything else goes through chunk steps (or, for
                    # an SPMD program, token-fed decode steps — the
                    # program's prefill starts at position 0)
                    a.whole = (hd is None and self.prefill == "batched"
                               and _psched.whole_eligible(
                                   matched, self._prefill_chunk))
                    a.chunk_mode = (hd is None
                                    and self.prefill == "batched"
                                    and not a.whole
                                    and self.program is None)
                    active.append(a)
                    newly.append(a)
                    reserved_pages += need
                    if obs_on:
                        _smetrics.record_sequence("admitted")
                        extra = ({"adapter": req.adapter_id}
                                 if req.adapter_id is not None else {})
                        _flight.default_flight().record(
                            "admit", seq_id=seq.seq_id,
                            trace_id=seq.trace_id,
                            prompt_len=len(seq.prompt),
                            cached_tokens=matched,
                            reserved_pages=reserved_pages, **extra)
                        if matched:
                            _flight.default_flight().record(
                                "prefix_hit", seq_id=seq.seq_id,
                                trace_id=seq.trace_id, tokens=matched)
                        if rt is not None:
                            rt.event("sequence.queued", rt.t0,
                                     seq.admitted_at)
                            rt.annotate(seq_id=seq.seq_id,
                                        prompt_len=len(seq.prompt),
                                        cached_tokens=matched)
                    if hd is not None:
                        # the prompt's K/V is fully present (imported +
                        # re-attached) and the prefill side already
                        # chose the first token against its own logits
                        # — emit it here and let the sequence join the
                        # decode batch at position len(prompt)
                        a.pos = len(seq.prompt)
                        self._cache_insert(a)
                        now0 = time.perf_counter()
                        if emit(a, np.asarray(hd.first_logits),
                                seq.admitted_at, now0,
                                tok=int(hd.first_token)):
                            retire([a], now0)
                # NOTE: waiting-but-nothing-active cannot happen — the
                # up-front validation guarantees the head request fits an
                # empty pool (locked pages are 0 with no live readers,
                # and manager-locked sessions spill to the host tier via
                # make_room before admission gives up), so admission
                # always progresses

                whole_group = [a for a in newly if a.whole]
                if whole_group:
                    # ONE whole-prompt pass for the co-admitted group:
                    # every prompt token's K/V lands in the pool and each
                    # sequence gets its first generated token — O(1)
                    # model steps per admission group vs O(max prompt
                    # len) token-by-token
                    t0 = time.perf_counter()
                    step_idx = self.steps
                    if self.program is not None:
                        logits = self.program.prefill_step(
                            self.pool, [a.seq_id for a in whole_group],
                            [a.result.prompt for a in whole_group])
                    else:
                        ad, asl = adapter_args(whole_group)
                        logits = prefill_step(
                            self.params, self.cfg, self.pool,
                            [a.seq_id for a in whole_group],
                            [a.result.prompt for a in whole_group],
                            force=self.force, adapters=ad,
                            adapter_slots=asl)
                    self.steps += 1
                    self.prefill_steps += 1
                    ntok = sum(len(a.result.prompt) for a in whole_group)
                    self.prefill_tokens += ntok
                    self.max_prefill_tokens_step = max(
                        self.max_prefill_tokens_step, ntok)
                    self._occupancy_sum += \
                        len(whole_group) / float(self.max_batch)
                    logits, ok, now = quarantine(whole_group, logits,
                                                 step_idx)
                    pairs = []
                    for i, a in enumerate(whole_group):
                        a.pos = len(a.result.prompt)
                        if i not in ok:
                            continue  # quarantined at prefill
                        self._cache_insert(a)
                        pairs.append((a, np.asarray(logits[i])))
                    retire(emit_batch(pairs, t0, now), now)
                    if obs_on:
                        self._note_attention_bytes()
                    self._watchdog()
                    continue  # re-admit into freed slots before decoding

                if not active:
                    continue

                # chunk-mode sequences (cached-prefix tails, capped long
                # prompts) prefill through chunk steps; everyone else —
                # generating sequences and token-arm/program prefillers —
                # steps through the decode path.  When both kinds of
                # work exist the scheduler ALTERNATES, so a long
                # prompt's chunks interleave with in-flight sequences'
                # decode steps instead of stalling them
                chunkers = [a for a in active if a.chunk_mode
                            and a.pos < len(a.result.prompt)]
                decodable = [a for a in active if not (
                    a.chunk_mode and a.pos < len(a.result.prompt))]
                if chunkers and (not decodable or self._prefer_prefill):
                    t0 = time.perf_counter()
                    step_idx = self.steps
                    idx, chunks, starts = _psched.plan_chunks(
                        [a.result.prompt for a in chunkers],
                        [a.pos for a in chunkers], self._prefill_chunk,
                        flop_budget=self._prefill_flops)
                    sel = [chunkers[i] for i in idx]
                    ad, asl = adapter_args(sel)
                    logits = chunk_prefill_step(
                        self.params, self.cfg, self.pool,
                        [a.seq_id for a in sel], chunks, starts,
                        adapters=ad, adapter_slots=asl)
                    self.steps += 1
                    self.prefill_steps += 1
                    ntok = sum(len(c) for c in chunks)
                    self.prefill_tokens += ntok
                    self.max_prefill_tokens_step = max(
                        self.max_prefill_tokens_step, ntok)
                    self._occupancy_sum += len(sel) / float(self.max_batch)
                    logits, ok, now = quarantine(sel, logits, step_idx)
                    pairs = []
                    for i, a in enumerate(sel):
                        if i not in ok:
                            continue  # quarantined at this chunk
                        a.pos += len(chunks[i])
                        if a.pos >= len(a.result.prompt):
                            self._cache_insert(a)
                            pairs.append((a, np.asarray(logits[i])))
                    retire(emit_batch(pairs, t0, now), now)
                    if obs_on:
                        self._note_attention_bytes()
                    self._watchdog()
                    self._prefer_prefill = False
                    continue

                # one token per stepping sequence — or, with speculation
                # armed, 1+d_i tokens for generating greedy sequences
                # (DRAFT phase: prompt-lookup proposals, pure host).
                # Under prefill="token" (and program-driven
                # cached-prefix tails) a still-prefilling sequence and a
                # deep-decode sequence share the batch and differ only
                # in k_lengths / q_lengths.  The chunk cap bounds how
                # many prefill tokens (one per prefilling sequence
                # here) ride one step
                batch = list(decodable)
                if self._prefill_chunk:
                    pre = [a for a in batch
                           if a.pos < len(a.result.prompt)]
                    if len(pre) > self._prefill_chunk:
                        keep = set(
                            id(a) for a in pre[:self._prefill_chunk])
                        batch = [a for a in batch
                                 if a.pos >= len(a.result.prompt)
                                 or id(a) in keep]
                if not batch:
                    continue
                evict_windowed(batch)
                blocks: List[List[int]] = []
                for a in batch:
                    if a.pos < len(a.result.prompt):
                        blocks.append([a.result.prompt[a.pos]])
                        continue
                    blk = [a.result.tokens[-1]]
                    room = self._spec_room(a)
                    if room > 0 and self.drafter is not None:
                        # clamp to room: a custom drafter ignoring its
                        # max_draft must not breach the pad_to width or
                        # the admission page reservation.  A stateful
                        # drafter (PromptLookupDrafter) gets the seq_id
                        # so its incremental suffix index answers the
                        # probe in O(d) instead of re-scanning the
                        # whole context every step
                        ctx = list(a.result.prompt) + a.result.tokens
                        if getattr(self.drafter, "stateful", False):
                            # adapter-aware drafters probe the corpus
                            # trie within the request's namespace only
                            # — cross-tenant continuations must not
                            # leak through draft proposals
                            if getattr(self.drafter, "adapter_aware",
                                       False):
                                proposal = self.drafter.draft(
                                    ctx, room, seq_id=a.seq_id,
                                    adapter_id=a.req.adapter_id)
                            else:
                                proposal = self.drafter.draft(
                                    ctx, room, seq_id=a.seq_id)
                        else:
                            proposal = self.drafter.draft(ctx, room)
                        if len(proposal):
                            # draft-source attribution (ISSUE 20): who
                            # proposed THIS block — labels the verify
                            # outcome so own-vs-corpus acceptance is a
                            # dashboard ratio
                            a.spec_source = getattr(
                                self.drafter, "last_source", "own")
                        blk += list(proposal)[:room]
                    blocks.append(blk)
                t0 = time.perf_counter()
                step_idx = self.steps
                seq_ids = [a.seq_id for a in batch]

                if max(len(b) for b in blocks) > 1:
                    # VERIFY phase: one multi-token model step feeds
                    # every sequence's block (ragged q_lengths); each
                    # emitted token is the model's own argmax given an
                    # exactly-verified prefix, so greedy output is
                    # token-identical to full_decode with up to d_i+1
                    # tokens committed per step
                    drafted_now = sum(len(b) - 1 for b in blocks)
                    if obs_on:
                        for a, b in zip(batch, blocks):
                            if len(b) > 1:
                                _flight.default_flight().record(
                                    "draft", seq_id=a.seq_id,
                                    step=step_idx, tokens=len(b) - 1,
                                    source=a.spec_source,
                                    trace_id=a.result.trace_id)
                    if self.program is not None:
                        logits3 = self.program.verify_step(
                            self.pool, seq_ids, blocks,
                            [a.pos for a in batch],
                            pad_to=self._speculate + 1)
                    else:
                        ad, asl = adapter_args(batch)
                        win, snk = window_args(batch)
                        logits3 = verify_step(
                            self.params, self.cfg, self.pool, seq_ids,
                            blocks, [a.pos for a in batch],
                            force=self.force, impl=self.paged_impl,
                            pad_to=self._speculate + 1,
                            adapters=ad, adapter_slots=asl,
                            windows=win, sinks=snk,
                            table_block=self._table_block)
                        self.max_decode_table_pages = max(
                            self.max_decode_table_pages,
                            max(len(self.pool._tables[a.seq_id].pages)
                                for a in batch))
                    self.steps += 1
                    self.decode_steps += 1
                    self.spec_steps += 1
                    self.drafted_tokens += drafted_now
                    ntok = sum(1 for a in batch
                               if a.pos < len(a.result.prompt))
                    if ntok:
                        self.prefill_tokens += ntok
                        self.max_prefill_tokens_step = max(
                            self.max_prefill_tokens_step, ntok)
                    self._occupancy_sum += \
                        len(batch) / float(self.max_batch)
                    logits3, ok, now = quarantine(batch, logits3,
                                                  step_idx)
                    if chunkers:
                        self._decode_durs_during_prefill.append(now - t0)
                    pairs = []
                    spec_rows: List[Tuple[int, _Active]] = []
                    retired: List[_Active] = []
                    for i, a in enumerate(batch):
                        blk = blocks[i]
                        start = a.pos
                        if i not in ok:
                            continue  # quarantined (pages already freed)
                        if a.pos < len(a.result.prompt):
                            a.pos += 1
                            if a.pos == len(a.result.prompt):
                                self._cache_insert(a)
                                pairs.append(
                                    (a, np.asarray(logits3[i, 0])))
                            continue
                        params_i = a.req.sampling
                        if params_i is not None and not params_i.greedy:
                            if len(blk) == 1:
                                # un-drafted sampled row riding the
                                # step at d=0 — the PLAIN epilogue
                                # (unsalted key) keeps its stream
                                # byte-identical to an unspeculated run
                                a.pos += 1
                                pairs.append(
                                    (a, np.asarray(logits3[i, 0])))
                                continue
                            # drafted sampled row: the fused
                            # accept/resample epilogue decides it below
                            spec_rows.append((i, a))
                            continue
                        # ACCEPTANCE walk (longest prefix match): row t
                        # predicts position start+t+1 — emit its argmax
                        # and keep walking only while it matches the
                        # draft (whose K/V is then already committed)
                        accepted = 0
                        done = False
                        for t in range(len(blk)):
                            row = np.asarray(logits3[i, t])
                            tok = int(apply_bias(row, params_i).argmax())
                            fed = t + 1 < len(blk) and tok == blk[t + 1]
                            if fed:
                                accepted += 1
                            done = emit(a, row, t0, now, tok=tok)
                            if done or not fed:
                                break
                        drafted = len(blk) - 1
                        a.drafted += drafted
                        a.accepted += accepted
                        self.accepted_tokens += accepted
                        # ROLLBACK: rejected draft tokens (and fed
                        # tokens past an in-block EOS/stop) leave the
                        # page table atomically — pure host bookkeeping
                        new_len = start + 1 + accepted
                        rolled = start + len(blk) - new_len
                        if rolled:
                            self.pool.truncate_seq(a.seq_id, new_len)
                            self.rolled_back_tokens += rolled
                        a.pos = new_len
                        if obs_on and drafted:
                            _smetrics.record_spec(drafted, accepted,
                                                  source=a.spec_source)
                            _flight.default_flight().record(
                                "verify", seq_id=a.seq_id,
                                step=step_idx, accepted=accepted,
                                rejected=drafted - accepted,
                                source=a.spec_source,
                                trace_id=a.result.trace_id)
                            if rolled:
                                _flight.default_flight().record(
                                    "rollback", seq_id=a.seq_id,
                                    step=step_idx, tokens=rolled,
                                    length=new_len,
                                    trace_id=a.result.trace_id)
                        if done:
                            retired.append(a)
                    if spec_rows:
                        # EXACT SPECULATIVE SAMPLING (ISSUE 16): one
                        # fused accept/resample call decides every
                        # drafted sampled row — per-row accepted counts
                        # come back device-side (no per-sequence host
                        # sync), then the host walk mirrors the greedy
                        # walk's emit/rollback bookkeeping exactly
                        # (EOS/stop inside an accepted prefix retires
                        # at that position and truncates the surplus)
                        sqw = self._speculate + 1
                        sub = np.stack([
                            np.stack([
                                apply_bias(np.asarray(logits3[i, t]),
                                           a.req.sampling)
                                for t in range(sqw)])
                            for i, a in spec_rows])
                        acc, spec_toks = spec_sample_rows(
                            sub,
                            [a.req.sampling for _, a in spec_rows],
                            [len(a.result.tokens)
                             for _, a in spec_rows],
                            [blocks[i][1:] for i, _ in spec_rows])
                        for r, (i, a) in enumerate(spec_rows):
                            blk = blocks[i]
                            start = a.pos
                            n_acc = int(acc[r])
                            accepted = 0
                            done = False
                            for t in range(n_acc + 1):
                                row = np.asarray(logits3[i, t])
                                fed = t < n_acc
                                if fed:
                                    accepted += 1
                                done = emit(a, row, t0, now,
                                            tok=int(spec_toks[r, t]))
                                if done or not fed:
                                    break
                            drafted = len(blk) - 1
                            a.drafted += drafted
                            a.accepted += accepted
                            self.accepted_tokens += accepted
                            new_len = start + 1 + accepted
                            rolled = start + len(blk) - new_len
                            if rolled:
                                self.pool.truncate_seq(a.seq_id,
                                                       new_len)
                                self.rolled_back_tokens += rolled
                            a.pos = new_len
                            if obs_on and drafted:
                                _smetrics.record_spec(
                                    drafted, accepted,
                                    source=a.spec_source)
                                _flight.default_flight().record(
                                    "verify", seq_id=a.seq_id,
                                    step=step_idx, accepted=accepted,
                                    rejected=drafted - accepted,
                                    source=a.spec_source,
                                    trace_id=a.result.trace_id)
                                if rolled:
                                    _flight.default_flight().record(
                                        "rollback", seq_id=a.seq_id,
                                        step=step_idx, tokens=rolled,
                                        length=new_len,
                                        trace_id=a.result.trace_id)
                            if done:
                                retired.append(a)
                    retired.extend(emit_batch(pairs, t0, now))
                    retire(retired, now)
                    if obs_on:
                        self._note_attention_bytes()
                    self._watchdog()
                    self._prefer_prefill = True
                    continue

                tokens = [b[0] for b in blocks]
                positions = [a.pos for a in batch]
                if self.program is not None:
                    logits = self.program.decode_step(
                        self.pool, seq_ids, tokens, positions)
                else:
                    ad, asl = adapter_args(batch)
                    win, snk = window_args(batch)
                    logits = decode_step(
                        self.params, self.cfg, self.pool, seq_ids, tokens,
                        positions, force=self.force, impl=self.paged_impl,
                        adapters=ad, adapter_slots=asl,
                        windows=win, sinks=snk,
                        table_block=self._table_block)
                    self.max_decode_table_pages = max(
                        self.max_decode_table_pages,
                        max(len(self.pool._tables[a.seq_id].pages)
                            for a in batch))
                self.steps += 1
                self.decode_steps += 1
                ntok = sum(1 for a in batch
                           if a.pos < len(a.result.prompt))
                if ntok:
                    self.prefill_tokens += ntok
                    self.max_prefill_tokens_step = max(
                        self.max_prefill_tokens_step, ntok)
                self._occupancy_sum += len(batch) / float(self.max_batch)
                logits, ok, now = quarantine(batch, logits, step_idx)
                if chunkers:
                    self._decode_durs_during_prefill.append(now - t0)

                pairs = []
                for i, a in enumerate(batch):
                    a.pos += 1
                    if i not in ok:
                        continue  # quarantined this step
                    if a.pos < len(a.result.prompt):
                        continue  # still prefilling; logits unused
                    if a.pos == len(a.result.prompt):
                        # the fed token completed the prompt's K/V:
                        # offer its pages to the prefix cache
                        self._cache_insert(a)
                    pairs.append((a, np.asarray(logits[i])))
                retire(emit_batch(pairs, t0, now), now)
                if obs_on:
                    self._note_attention_bytes()
                self._watchdog()
                self._prefer_prefill = True
        except BaseException:
            # ANY raise out of a prefill/decode step (or admission): the
            # stepping sequences' pages go back to the pool BEFORE the
            # error propagates — a failed run must never strand pages
            # (the acknowledged hazard this loop previously carried)
            for a in active:
                self.pool.free_seq(a.seq_id)
                if self.prefix_cache is not None:
                    self.prefix_cache.forget_seq(a.seq_id)
                if getattr(self.drafter, "stateful", False):
                    self.drafter.release(a.seq_id)
                if self.session_manager is not None \
                        and a.req.session is not None:
                    # the pool side is freed above: the session must
                    # not believe it still owns a resident sequence
                    self.session_manager.on_quarantine(a.req.session)
                if a.aslot and self.adapter_pool is not None:
                    self.adapter_pool.release(a.req.adapter_id)
            active.clear()
            raise
        return results

    def _cache_insert(self, a: _Active) -> None:
        """Offer a fully-prefilled prompt's pages to the prefix cache
        (once per sequence): future prompts sharing the prefix attach
        them instead of re-prefilling."""
        if self.prefix_cache is None or a.inserted:
            return
        a.inserted = True
        self.prefix_cache.insert(a.seq_id, a.result.prompt,
                                 adapter_id=a.req.adapter_id)

    def _watchdog(self) -> None:
        """Every check_every steps: audit pool integrity and repair
        detected leaks (orphaned pages return to the free list)."""
        if not self.check_every or self.steps % self.check_every:
            return
        report = self.pool.check_invariants()
        if report["ok"]:
            return
        self.invariant_violations += 1
        reclaimed = self.pool.reclaim_orphans()
        self.reclaimed_pages += reclaimed
        _log.warning(
            "KV pool '%s' failed its invariant audit at step %d "
            "(orphaned=%s double_owned=%s free_errors=%s); reclaimed %d "
            "orphaned pages", self.pool.name, self.steps,
            report["orphaned_pages"], report["double_owned_pages"],
            report["free_list_errors"], reclaimed)
        if _flags._VALUES["FLAGS_observability"] and reclaimed:
            _smetrics.record_pool_reclaim(reclaimed, pool=self.pool.name)
            _flight.default_flight().record(
                "page_reclaim", pool=self.pool.name, pages=reclaimed,
                step=self.steps)

    def _note_attention_bytes(self) -> None:
        """Attention-bytes-per-step gauge for the CURRENT pool contents,
        labeled with the impl that runs AND the pool's kv_dtype —
        callers gate on the observability flag (zero-work disabled
        path).  The byte model takes the pool's explicit dtype and KV
        head count: GQA and int8 pools price H_q/H_kv x and itemsize/4 x
        below the fp32 full-head default, which is the win the gauge
        exists to make visible."""
        st = self.pool.stats()
        if not st["live_sequences"]:
            return
        maxp = self.pool.max_live_pages()
        kv_dtype = np.dtype(self.pool.k_pages.dtype).name
        _smetrics.record_attention_bytes(
            attention_bytes_per_step(
                self.paged_impl, st["live_sequences"], maxp,
                self.pool.page_size, self.pool.num_heads,
                self.pool.head_dim,
                num_layers=self.pool.num_layers,
                num_kv_heads=self.pool.num_kv_heads,
                dtype=self.pool.k_pages.dtype),
            impl=self.paged_impl, kv_dtype=kv_dtype)

    def mean_occupancy(self) -> float:
        return self._occupancy_sum / self.steps if self.steps else 0.0
