"""Long-context serving (ISSUE 20).

(a) sliding-window + attention-sink decode: the windowed paged loop is
    TOKEN-IDENTICAL to the full_decode oracle under the same
    page-granular mask, across GQA x two-level-tables x int8 x
    prefix-hit x speculation arms, with interior pages actually
    evicted and nothing leaked;
(b) the two-level page-table view round-trips every pool mutation the
    flat view does (eviction, CoW, defrag, truncate, export/import)
    — ``flatten()`` must equal ``page_tables_with_starts`` after each;
(c) eviction vs readers: a dropped interior page another holder still
    reads RELEASES this sequence's hold, never frees;
(d) tiered-KV spill staging (D2H copy) runs OUTSIDE the pool lock — a
    concurrent append must not serialize behind a parking export;
(e) compute-budgeted chunked prefill: ``plan_chunks`` prices a chunk
    by estimated attention work (quadratic in resident prefix), the
    head never starves, both budgets compose;
(f) the SMEM linter prices the flat ~1k-page table out of scalar
    memory and the two-level view back in — from the traced jaxpr,
    no chip, no AOT client;
(g) the acceptance arithmetic: under the same window+sinks, a 128k
    context's decode bytes/step (priced over WALKED post-eviction
    pages) stays within 1.15x of 8k's.
"""

import functools
import threading
import time

import numpy as np
import pytest

from paddle_tpu.kernels.paged_attention import (
    PAD_START,
    TwoLevelTables,
    attention_bytes_per_step,
    paged_decode_attention,
)
from paddle_tpu.serving.generate import (
    ContinuousBatchingLoop,
    DecodeConfig,
    DecodeRequest,
    chunk_prefill_step,
    full_decode,
    init_decode_params,
)
from paddle_tpu.serving.kvcache import KVCachePool
from paddle_tpu.serving.prefill_sched import plan_chunks

# -- (a) windowed decode parity matrix ----------------------------------

PS = 4
WIN, SNK = 8, 4
MAX_NEW = 8
CFG = DecodeConfig(vocab_size=64, d_model=32, n_head=4, n_kv_head=2,
                   n_layer=2, max_length=96, eos_id=None)
PARAMS = init_decode_params(CFG, seed=0)
_rng = np.random.default_rng(1)
# lengths next to each other and 8 new tokens (12, 7, 20 and 16 before PR
# 46): the oracle compiles ~50 small executables for every sequence length
# it meets, 10 lengths here and 29 then, and the windowed arms still evict,
# draft and hit the prefix cache
PROMPTS = tuple(tuple(int(t) for t in _rng.integers(0, 64, n))
                for n in (18, 19, 20))


@functools.lru_cache(maxsize=None)
def _oracle(window, sinks):
    kw = ({"window": window, "sinks": sinks, "page_size": PS}
          if window else {})
    return tuple(tuple(full_decode(PARAMS, CFG, list(p), MAX_NEW, **kw)[0])
                 for p in PROMPTS)


@functools.lru_cache(maxsize=None)
def _arm(window=None, sinks=0, dtype="float32", speculate=0,
         table_block=None):
    """One loop replay; returns (tokens, pages_evicted, drafted)."""
    pool = KVCachePool(num_pages=256, page_size=PS, num_layers=CFG.n_layer,
                       num_heads=CFG.n_head, head_dim=CFG.head_dim,
                       num_kv_heads=CFG.n_kv_head, dtype=dtype)
    loop = ContinuousBatchingLoop(PARAMS, CFG, pool, max_batch=3,
                                  speculate=speculate,
                                  table_block=table_block, check_every=1)
    res = loop.run([DecodeRequest(list(p), MAX_NEW, window=window,
                                  sinks=sinks) for p in PROMPTS])
    rep = pool.check_invariants()
    assert rep["ok"], rep
    assert rep["used_pages"] == 0, rep
    return (tuple(tuple(r.tokens) for r in res), loop.pages_evicted,
            loop.drafted_tokens)


def test_unwindowed_decode_matches_oracle():
    toks, evicted, _ = _arm()
    assert toks == _oracle(None, 0)
    assert evicted == 0


def test_windowed_decode_matches_masked_oracle_and_evicts():
    toks, evicted, _ = _arm(window=WIN, sinks=SNK)
    assert toks == _oracle(WIN, SNK)
    assert evicted > 0


def test_windowed_two_level_tables_token_identical():
    toks, evicted, _ = _arm(window=WIN, sinks=SNK, table_block=2)
    assert toks == _arm(window=WIN, sinks=SNK)[0]
    assert evicted == _arm(window=WIN, sinks=SNK)[1]


def test_windowed_speculation_token_identical():
    toks, _, drafted = _arm(window=WIN, sinks=SNK, speculate=3)
    assert toks == _arm(window=WIN, sinks=SNK)[0]
    assert drafted > 0  # speculation really ran under the window


def test_windowed_int8_flat_equals_two_level():
    # int8 re-quantizes per page so the fp32 oracle is only close; the
    # flat and two-level views of the SAME quantized pool must still be
    # bit-identical — they gather identical pages
    assert (_arm(window=WIN, sinks=SNK, dtype="int8")[0]
            == _arm(window=WIN, sinks=SNK, dtype="int8", table_block=4)[0])


def test_windowed_prefix_hit_token_identical():
    from paddle_tpu.serving.prefixcache import PrefixCache

    pool = KVCachePool(num_pages=256, page_size=PS, num_layers=CFG.n_layer,
                       num_heads=CFG.n_head, head_dim=CFG.head_dim,
                       num_kv_heads=CFG.n_kv_head)
    loop = ContinuousBatchingLoop(PARAMS, CFG, pool, max_batch=2,
                                  prefix_cache=PrefixCache(pool),
                                  check_every=1)
    base = list(PROMPTS[2])
    r1 = loop.run([DecodeRequest(base, 10, window=WIN, sinks=SNK)])
    r2 = loop.run([DecodeRequest(base, 10, window=WIN, sinks=SNK)])
    assert loop.prefix_hits >= 1
    oracle, _ = full_decode(PARAMS, CFG, base, 10, window=WIN, sinks=SNK,
                            page_size=PS)
    assert r1[0].tokens == oracle and r2[0].tokens == oracle
    assert pool.check_invariants()["ok"]


# -- (b) two-level table view round-trips pool mutations ----------------

def _mk_pool(n=64, name="t"):
    return KVCachePool(num_pages=n, page_size=PS, num_layers=2,
                       num_heads=2, head_dim=8, name=name)


def _views_agree(pool, seq_ids, block_size=2):
    """flatten() of the two-level view must equal the flat view."""
    t, st, ln = pool.page_tables_with_starts(seq_ids)
    tl, ln2 = pool.two_level_tables(seq_ids, block_size=block_size)
    ft, fs = (np.asarray(a) for a in tl.flatten())
    np.testing.assert_array_equal(np.asarray(ln), np.asarray(ln2))
    for i, s in enumerate(seq_ids):
        live = len(pool._tables[s].pages)
        np.testing.assert_array_equal(ft[i, :live], np.asarray(t)[i, :live])
        np.testing.assert_array_equal(fs[i, :live], np.asarray(st)[i, :live])
        assert (fs[i, live:] == PAD_START).all()


def test_two_level_view_tracks_eviction_append_truncate():
    pool = _mk_pool()
    pool.allocate(0)
    pool.append_tokens([0], [30])
    pool.evict_interior(0, window=6, sinks=4)
    pool.append_tokens([0], [2])
    pool.append_tokens([0], [5])
    pool.truncate_seq(0, 34)
    pool.allocate(1)
    pool.append_tokens([1], [5])  # short row: pads with the shared block
    t, st, ln = pool.page_tables_with_starts([0, 1])
    assert list(st[0]) == [0, 24, 28, 32]
    assert list(st[1]) == [0, 4, PAD_START, PAD_START]
    _views_agree(pool, [0, 1])
    assert pool.check_invariants()["ok"]


def test_two_level_view_tracks_cow_and_defrag():
    pool = _mk_pool(n=16)
    pool.allocate(0)
    pg, sl = pool.append_tokens([0], [6])  # page 2 half-filled
    k = np.arange(6 * 2 * 8, dtype=np.float32).reshape(6, 2, 8)
    pool.write_kv(0, pg, sl, k, k)
    # share all of 0's pages into 1, then diverge: the shared
    # partially-filled tail page must copy-on-write
    pool.allocate(1)
    pool.attach_prefix(1, pool._tables[0].pages, 6)
    _views_agree(pool, [0, 1])
    tail_before = pool._tables[1].pages[-1]
    pool.append_tokens([1], [3])
    assert pool._tables[1].pages[-1] != tail_before  # CoW happened
    assert pool._tables[0].pages[-1] == tail_before
    _views_agree(pool, [0, 1])
    # punch a hole and defrag: pages remap, both views must follow
    pool.allocate(2)
    pool.append_tokens([2], [8])
    pool.free_seq(0)
    assert pool.defrag() > 0
    _views_agree(pool, [1, 2])
    assert pool.check_invariants()["ok"]


def test_export_import_preserves_evicted_starts():
    pool = _mk_pool()
    pool.allocate(0)
    pool.append_tokens([0], [30])
    pool.evict_interior(0, window=6, sinks=4)
    pool.append_tokens([0], [7])
    pool.truncate_seq(0, 34)
    exp = pool.export_seq(0)
    assert exp.starts == [0, 24, 28, 32]
    dst = _mk_pool(n=32, name="dst")
    dst.allocate(7)
    dst.import_seq(exp, 7)
    h = dst._tables[7]
    assert h.starts == [0, 24, 28, 32] and h.length == 34
    # appends on the imported, evicted table keep extending starts
    dst.append_tokens([7], [3])
    assert h.length == 37 and h.starts == [0, 24, 28, 32, 36]
    _views_agree(dst, [7])
    assert dst.check_invariants()["ok"]


# -- (c) eviction vs readers --------------------------------------------

def test_evicted_shared_page_releases_never_frees():
    pool = _mk_pool()
    pool.allocate(0)
    pool.append_tokens([0], [30])
    h = pool._tables[0]
    pool.evict_interior(0, window=6, sinks=4)
    pool.append_tokens([0], [7])
    pool.truncate_seq(0, 34)
    # pin one kept page like the prefix cache would (hold + owner hook
    # so check_invariants can explain the extra refcount)
    pinned = h.pages[1]  # starts at 24: a tighter window drops it
    pins = {pinned: 1}
    pool.register_owner(lambda: pins)
    pool.retain_pages([pinned])
    pool.evict_interior(0, window=2, sinks=0)
    assert pinned not in h.pages  # dropped from THIS table...
    assert pool.refcount(pinned) == 1  # ...but the reader keeps it live
    assert pinned not in pool._free
    assert pool.check_invariants()["ok"]
    pins.clear()
    pool.release_pages([pinned])
    assert pool.refcount(pinned) == 0
    pool.free_seq(0)
    rep = pool.check_invariants()
    assert rep["ok"] and rep["used_pages"] == 0, rep


def test_int8_eviction_clears_dropped_scales():
    pool = KVCachePool(num_pages=16, page_size=PS, num_layers=1,
                       num_heads=2, head_dim=8, dtype="int8", name="q")
    pool.allocate(0)
    pg, sl = pool.append_tokens([0], [16])
    rng = np.random.default_rng(0)
    pool.write_kv(0, pg, sl, rng.standard_normal((16, 2, 8), np.float32),
                  rng.standard_normal((16, 2, 8), np.float32))
    h = pool._tables[0]
    dropped = [p for p, st in zip(h.pages, range(0, 16, PS))
               if st >= PS and st + PS <= 16 - 2]
    assert dropped
    pool.evict_interior(0, window=2, sinks=4)
    for p in dropped:  # freed pages must not leave stale scales behind
        assert pool.k_scales[0, p] == 0.0 and pool.v_scales[0, p] == 0.0
    assert pool.check_invariants()["ok"]


# -- (d) spill staging off the pool lock --------------------------------

def test_export_d2h_stage_does_not_block_appends():
    pool = KVCachePool(num_pages=64, page_size=PS, num_layers=1,
                       num_heads=2, head_dim=8, num_kv_heads=2)
    pool.allocate(1)
    pool.append_tokens([1], [12])
    pool.allocate(2)
    pool.append_tokens([2], [4])
    gate, entered = threading.Event(), threading.Event()
    orig = pool._stage_d2h

    def slow(k_src, v_src, idx):
        entered.set()
        assert gate.wait(10), "gate never opened"
        return orig(k_src, v_src, idx)

    pool._stage_d2h = slow
    out = {}
    t = threading.Thread(target=lambda: out.update(e=pool.export_seq(1)))
    t.start()
    try:
        assert entered.wait(10)
        # export is parked mid-D2H: an append on ANOTHER sequence must
        # not serialize behind it
        t0 = time.perf_counter()
        pool.append_tokens([2], [4])
        dt = time.perf_counter() - t0
        assert dt < 1.0, f"append serialized behind export: {dt}s"
    finally:
        gate.set()
        t.join(10)
    assert out["e"].length == 12  # the parked export still lands whole
    assert pool.check_invariants()["ok"]


# -- (e) compute-budgeted chunk planning --------------------------------

def test_plan_chunks_flop_budget_arithmetic():
    # pos 0, budget 50: n*(0 + n/2) <= 50 -> n = 10
    _, ch, _ = plan_chunks([[1] * 100], [0], 0, flop_budget=50.0)
    assert len(ch[0]) == 10
    # deep prefix: the quadratic term shrinks the chunk, head gets >= 1
    _, ch, _ = plan_chunks([[1] * 100], [90], 0, flop_budget=5.0)
    assert len(ch[0]) == 1
    # the token cap composes and binds where tighter
    _, ch, _ = plan_chunks([[1] * 50, [2] * 50], [0, 0], 8, flop_budget=1e9)
    assert [len(c) for c in ch] == [8]
    with pytest.raises(ValueError):
        plan_chunks([[1]], [0], 0, flop_budget=0)


@pytest.mark.parametrize("windowed", [False, True],
                         ids=["unwindowed", "windowed"])
def test_prefill_flops_loop_parity(windowed):
    """Prompts of three and of two compute-budgeted chunks (28 tokens:
    16, 8 under the budget at position 16, 4; 24 tokens: 16, 8) and four
    tokens decoded: the loop and the oracle compile every chunk and every
    sequence length they see op by op (~2 s each on the CPU), so the
    sizes are the smallest at which the budget, not the token cap, cuts a
    chunk and the window's first decode step drops the prompt's pages
    between the sinks and the window."""
    cfg = DecodeConfig(vocab_size=64, d_model=32, n_head=4, n_kv_head=2,
                       n_layer=2, max_length=128, eos_id=None)
    params = init_decode_params(cfg, seed=0)
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, 64, 28)), list(rng.integers(0, 64, 24))]
    new = 4
    req_kw = {"window": WIN, "sinks": SNK} if windowed else {}
    pool = KVCachePool(num_pages=256, page_size=PS,
                       num_layers=cfg.n_layer, num_heads=cfg.n_head,
                       head_dim=cfg.head_dim, num_kv_heads=cfg.n_kv_head)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=2,
                                  prefill_chunk=16, prefill_flops=200.0,
                                  check_every=1)
    res = loop.run([DecodeRequest(p, new, **req_kw) for p in prompts])
    oracle_kw = dict(req_kw, page_size=PS) if windowed else {}
    for p, r in zip(prompts, res):
        assert r.tokens == full_decode(params, cfg, p, new, **oracle_kw)[0]
    assert loop.prefill_steps > 2  # the prompts went in by chunks
    if windowed:
        assert loop.pages_evicted > 0
    else:
        assert loop.decode_step_p99_during_prefill_s() >= 0.0


def test_longctx_validation_errors():
    pool = _mk_pool()
    sid = 7
    pool.allocate(sid)
    pool.append_tokens([sid], [24])
    pool.evict_interior(sid, window=6, sinks=4)
    cfg = DecodeConfig(vocab_size=64, d_model=16, n_head=2, n_layer=2,
                       d_inner=32, max_length=64)
    params = init_decode_params(cfg, seed=0)
    # chunk-prefill can never extend a window-evicted table: the chunk's
    # queries would attend a prefix that is no longer resident
    with pytest.raises(ValueError, match="window-evicted"):
        chunk_prefill_step(params, cfg, pool, [sid], [[1, 2, 3]], [24])
    # a FLOP budget without chunked prefill has nothing to budget
    with pytest.raises(ValueError, match="prefill_chunk"):
        ContinuousBatchingLoop(params, cfg, pool, prefill_flops=100.0)
    for bad in (DecodeRequest([1, 2, 3], 4, window=0),
                DecodeRequest([1, 2, 3], 4, sinks=2)):  # sinks w/o window
        with pytest.raises(ValueError):
            ContinuousBatchingLoop(params, cfg, pool,
                                   max_batch=1).run([bad])


# -- (f) SMEM pricing: flat ~1k-page tables out, two-level in -----------

def _smem_art(two_level):
    """Trace the longctx decode shape (B=4, 1024 pages/seq, int8) into a
    bare ProgramArtifacts — jaxpr-only, so the detector needs no AOT
    client and no chip."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.analysis.capture import ProgramArtifacts

    B, Hq, Hkv, D, ps, maxp = 4, 8, 2, 128, 32, 1024
    P = 16384  # POOL pages — the flat path's [P] scale rows ride SMEM
    q = jax.ShapeDtypeStruct((B, Hq, 1, D), jnp.float32)
    kp = jax.ShapeDtypeStruct((Hkv, P, ps, D), jnp.int8)
    ln = jax.ShapeDtypeStruct((B,), jnp.int32)
    sc = jax.ShapeDtypeStruct((P,), jnp.float32)
    if two_level:
        bs = 128
        n_blocks = B * (maxp // bs) + 1
        l1 = jax.ShapeDtypeStruct((B, maxp // bs), jnp.int32)
        blk = jax.ShapeDtypeStruct((n_blocks, bs), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, l1, l2, st, l, w, s, ks, vs:
                paged_decode_attention(
                    q, k, v, TwoLevelTables(l1, l2, st, bs), l,
                    impl="pallas", windows=w, sinks=s,
                    k_scales=ks, v_scales=vs))(
            q, kp, kp, l1, blk, blk, ln, ln, ln, sc, sc)
    else:
        tb = jax.ShapeDtypeStruct((B, maxp), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, t, st, l, w, s, ks, vs: paged_decode_attention(
                q, k, v, t, l, impl="pallas", page_starts=st,
                windows=w, sinks=s, k_scales=ks, v_scales=vs))(
            q, kp, kp, tb, tb, ln, ln, ln, sc, sc)
    return ProgramArtifacts(name="longctx_smem", jaxpr=jaxpr, stablehlo="",
                            hlo="", cost={})


def test_smem_linter_flat_overflows_two_level_fits():
    from paddle_tpu.analysis.pallas import (
        default_smem_budget,
        detect_smem_overflow,
        iter_pallas_calls,
        kernel_smem_bytes,
    )

    flat = detect_smem_overflow(_smem_art(two_level=False))
    assert len(flat) == 1 and flat[0].detector == "smem-overflow"
    # the [P] scale rows and the [B, max_pages] table are what blew it
    assert "float32[16384]" in flat[0].message
    assert detect_smem_overflow(_smem_art(two_level=True)) == []
    # the two-level walk prices by LIVE blocks: under budget, and well
    # under the flat arm's pool-sized scalar footprint
    (flat_eqn,) = iter_pallas_calls(_smem_art(two_level=False).jaxpr)
    (tl_eqn,) = iter_pallas_calls(_smem_art(two_level=True).jaxpr)
    assert kernel_smem_bytes(tl_eqn) < default_smem_budget()
    assert kernel_smem_bytes(tl_eqn) < kernel_smem_bytes(flat_eqn) // 2


# -- (g) the acceptance arithmetic: 128k within 1.15x of 8k -------------

def test_128k_decode_bytes_within_1p15x_of_8k_under_window():
    ps, win, snk = 32, 1024, 128
    nl, hq, hkv, d = 1, 8, 2, 128

    def walked_pages(ctx):
        pool = KVCachePool(num_pages=ctx // ps + 8, page_size=ps,
                           num_layers=nl, num_heads=hq, head_dim=8,
                           num_kv_heads=hkv)
        pool.allocate(0)
        pool.append_tokens([0], [ctx])
        pool.evict_interior(0, window=win, sinks=snk)
        assert pool.check_invariants()["ok"]
        return len(pool._tables[0].pages)

    def bytes_per_step(pages):
        return attention_bytes_per_step(
            "pallas", 1, pages, ps, hq, d, num_layers=nl,
            num_kv_heads=hkv, dtype="int8")

    p8k, p128k = walked_pages(8 << 10), walked_pages(128 << 10)
    # residency is window + sinks + the in-progress tail page — NOT
    # context: 16x more context costs at most one boundary page
    assert p128k <= p8k + 1
    assert bytes_per_step(p128k) <= 1.15 * bytes_per_step(p8k)
