"""Speculative decoding + the sampling contract (ISSUE 13).

Acceptance pinned here:
(a) greedy speculative decode (prompt-lookup drafting, multi-token
    paged verify, page-table rollback) is token-EXACT vs the
    ``full_decode`` oracle across overlapping ragged sequences WITH
    rollbacks occurring — the interpret-tier parity matrix spans
    d in {1, 2, 4} x H_kv in {8, 2} x {fp32, int8} pools x a
    prefix-cache-hit arm, each with zero leaked pages and
    ``check_invariants`` green after every truncation;
(b) the multi-token verify kernel: ragged ``q_lengths`` blocks under
    the interpret kernel match the dense reference row-for-row AND
    match stacked single-token steps (the in-block causal frontier is
    exact), quantized arm included; the byte model's KV stream is
    INVARIANT in q_tokens (only the query/output term grows);
(c) ``KVCachePool.truncate_seq`` rollback invariants: freeing only
    emptied refcount-zero pages, releasing (never freeing) shared
    prefix pages, clearing int8 scales with freed pages, and CoW-ing
    correctly on the next append after a rollback into a shared page;
(d) EOS / stop sequences / per-request max_new are honored INSIDE an
    accepted draft block: the sequence retires at the stop position
    and the surplus fed tokens leave both result.tokens and the page
    table;
(e) SamplingParams: temperature/top-k/top-p through the one jitted
    epilogue (deterministic per (seed, token-index), independent of
    batch composition), logit bias shifting greedy argmax, sampled
    rows drafting through the exact accept/resample epilogue
    (ISSUE 16), and Engine.submit threading the params in
    pass-through mode;
(i) ISSUE 16 exactness: the accept/resample epilogue's emitted-token
    distribution matches the plain sampler's over thousands of
    replayed draws (TV-distance bound across temp/top-k/top-p arms,
    chi-square sanity vs the exact filtered distribution), its
    accept/resample stream replays bit-identically per (seed, step),
    the spec_disabled counter surfaces a program without verify_step,
    and the corpus drafter (``PrefixCache.ngram_continuation``) follows
    the own-history-first decision rule — a corpus continuation only
    displaces the sequence's own draft when STRICTLY longer;
(f), (g) the serve_bench scenarios and the banked spec_verify entries:
    tests/test_speculative_bench.py (one file until PR 54);
(h) observability: draft/verify/rollback flight events and the
    per-sequence accepted/rejected span annotation.
"""

import functools

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.kernels.paged_attention import (
    attention_bytes_per_step,
    paged_decode_attention,
)
from paddle_tpu.serving import (
    ContinuousBatchingLoop,
    DecodeConfig,
    DecodeRequest,
    KVCachePool,
    PrefixCache,
    PromptLookupDrafter,
    SamplingParams,
    full_decode,
    init_decode_params,
    verify_step,
)
from paddle_tpu.serving.sampling import (
    apply_bias,
    sample_rows,
    spec_sample_rows,
    stop_hit,
)


# ---------------------------------------------------------------------------
# (b) kernel level: multi-token ragged verify


def _random_pool_state(rng, Hkv=2, P=16, ps=4, D=8, B=3, maxp=5):
    kp = rng.standard_normal((Hkv, P, ps, D)).astype(np.float32)
    vp = rng.standard_normal((Hkv, P, ps, D)).astype(np.float32)
    tables = rng.randint(0, P, size=(B, maxp)).astype(np.int32)
    return kp, vp, tables


def test_verify_kernel_interpret_matches_reference_ragged():
    rng = np.random.RandomState(0)
    kp, vp, tables = _random_pool_state(rng)
    lengths = np.array([18, 7, 13], np.int32)
    qlens = np.array([3, 1, 4], np.int32)
    q = rng.standard_normal((3, 4, 4, 8)).astype(np.float32)
    ref = paged_decode_attention(q, kp, vp, tables, lengths,
                                 impl="reference", q_lengths=qlens)
    it = paged_decode_attention(q, kp, vp, tables, lengths,
                                impl="interpret", q_lengths=qlens)
    for b in range(3):
        n = qlens[b]
        np.testing.assert_allclose(np.asarray(it)[b, :, :n],
                                   np.asarray(ref)[b, :, :n],
                                   rtol=2e-5, atol=2e-5)


def test_verify_block_rows_equal_stacked_single_token_steps():
    """The in-block causal frontier: row t of a verify block must equal
    a single-token decode at position lengths - q_lengths + t with the
    keys truncated there — speculation changes NOTHING about what each
    row attends to."""
    rng = np.random.RandomState(1)
    kp, vp, tables = _random_pool_state(rng)
    lengths = np.array([18, 7, 13], np.int32)
    qlens = np.array([3, 1, 4], np.int32)
    q = rng.standard_normal((3, 4, 4, 8)).astype(np.float32)
    blk = paged_decode_attention(q, kp, vp, tables, lengths,
                                 impl="reference", q_lengths=qlens)
    for b in range(3):
        for t in range(qlens[b]):
            ln_t = lengths.copy()
            ln_t[b] = lengths[b] - qlens[b] + t + 1
            single = paged_decode_attention(
                q[:, :, t:t + 1], kp, vp, tables, ln_t, impl="reference")
            np.testing.assert_allclose(np.asarray(blk)[b, :, t],
                                       np.asarray(single)[b, :, 0],
                                       rtol=2e-5, atol=2e-5)


def test_verify_kernel_int8_dequant_parity():
    rng = np.random.RandomState(2)
    Hkv, P, ps, D, B, maxp = 2, 16, 4, 8, 3, 5
    kf = rng.standard_normal((Hkv, P, ps, D)).astype(np.float32)
    vf = rng.standard_normal((Hkv, P, ps, D)).astype(np.float32)
    k_sc = np.abs(kf).max(axis=(0, 2, 3)) / 127.0
    v_sc = np.abs(vf).max(axis=(0, 2, 3)) / 127.0
    k8 = np.clip(np.round(kf / k_sc[None, :, None, None]),
                 -127, 127).astype(np.int8)
    v8 = np.clip(np.round(vf / v_sc[None, :, None, None]),
                 -127, 127).astype(np.int8)
    tables = rng.randint(0, P, size=(B, maxp)).astype(np.int32)
    lengths = np.array([15, 9, 20], np.int32)
    qlens = np.array([2, 4, 3], np.int32)
    q = rng.standard_normal((B, 4, 4, D)).astype(np.float32)
    ref = paged_decode_attention(q, k8, v8, tables, lengths,
                                 impl="reference", q_lengths=qlens,
                                 k_scales=k_sc, v_scales=v_sc)
    it = paged_decode_attention(q, k8, v8, tables, lengths,
                                impl="interpret", q_lengths=qlens,
                                k_scales=k_sc, v_scales=v_sc)
    for b in range(B):
        n = qlens[b]
        np.testing.assert_allclose(np.asarray(it)[b, :, :n],
                                   np.asarray(ref)[b, :, :n],
                                   rtol=1e-4, atol=1e-4)


def test_verify_query_validation():
    rng = np.random.RandomState(3)
    kp, vp, tables = _random_pool_state(rng)
    lengths = np.array([8, 8, 8], np.int32)
    q1 = rng.standard_normal((3, 4, 1, 8)).astype(np.float32)
    with pytest.raises(ValueError, match="q_lengths"):
        paged_decode_attention(q1, kp, vp, tables, lengths,
                               impl="reference",
                               q_lengths=np.ones(3, np.int32))
    with pytest.raises(ValueError, match=">= 1 token"):
        paged_decode_attention(q1[:, :, :0], kp, vp, tables, lengths,
                               impl="reference")


def test_bytes_model_kv_stream_invariant_in_q_tokens():
    """The whole amortization claim in one assertion: the pallas KV
    stream bytes do not change with the draft depth; only the (small)
    query/output term rides on top, so bytes/step at d=4 is far under
    2x the d=0 step."""
    kw = dict(batch=4, max_pages=32, page_size=16, num_heads=8,
              head_dim=128, num_layers=1, num_kv_heads=2)
    d0 = attention_bytes_per_step("pallas", **kw)
    d4 = attention_bytes_per_step("pallas", q_tokens=5, **kw)
    qo = 2 * 4 * 5 * 8 * 128 * 4  # query read + output write at fp32
    assert d4 == d0 + qo
    assert d4 < 2 * d0
    # at full acceptance the step commits 5 tokens: >= 2x (here ~4x)
    # effective bytes-per-token reduction
    assert d0 / (d4 / 5) > 2.0
    # q_tokens=1 is byte-identical to the pre-ISSUE-13 model (banked
    # zoo entries unchanged)
    assert attention_bytes_per_step("pallas", q_tokens=1, **kw) == d0


# ---------------------------------------------------------------------------
# (c) truncate_seq rollback invariants


def _pool(dtype="float32", pages=16, ps=4):
    return KVCachePool(num_pages=pages, page_size=ps, num_layers=2,
                       num_heads=2, head_dim=4, dtype=dtype)


def _fill(pool, seq_id, n, value=1.0):
    pages, slots = pool.append_tokens([seq_id], [n])
    rows = np.full((n, pool.num_kv_heads, pool.head_dim), value,
                   np.float32)
    for li in range(pool.num_layers):
        pool.write_kv(li, pages, slots, rows, rows)
    return pages, slots


def test_truncate_seq_frees_emptied_pages_only():
    pool = _pool()
    pool.allocate(0)
    _fill(pool, 0, 10)
    assert pool.used_pages == 3
    assert pool.truncate_seq(0, 5) == 1  # page 3 emptied
    assert pool.length(0) == 5 and pool.used_pages == 2
    assert pool.check_invariants()["ok"]
    assert pool.truncate_seq(0, 5) == 0  # no-op
    assert pool.truncate_seq(0, 0) == 2
    assert pool.used_pages == 0 and pool.check_invariants()["ok"]
    with pytest.raises(ValueError, match="truncate"):
        pool.truncate_seq(0, 1)  # growth is append's job
    pool.free_seq(0)


def test_truncate_seq_through_shared_prefix_releases_not_frees():
    """A rollback crossing a prefix-cache share drops only THIS
    sequence's hold: the share survives for its other readers and the
    audit stays green (the never-strand-a-share contract)."""
    pool = _pool(dtype="int8")
    pool.allocate(0)
    _fill(pool, 0, 8)
    shared, _ = pool.table_snapshot(0)
    pool.retain_pages(shared)  # the cache's entry hold
    holds = {p: 1 for p in shared}
    pool.register_owner(lambda: dict(holds))
    pool.allocate(1)
    pool.attach_prefix(1, shared, 8)
    _fill(pool, 1, 5, value=2.0)  # 2 own pages on top
    own = [p for p in pool.table_snapshot(1)[0] if p not in shared]
    assert pool.check_invariants()["ok"]
    # roll back 3 tokens: one own page frees, its int8 scales clear
    assert pool.truncate_seq(1, 10) == 1
    assert float(pool.k_scales[0, own[-1]]) == 0.0
    assert float(pool.k_scales[0, own[0]]) != 0.0
    assert pool.check_invariants()["ok"]
    # roll back INTO the shared region: shared pages drop this
    # sequence's hold but stay live (seq 0 + cache still read them)
    pool.truncate_seq(1, 3)
    assert all(pool.refcount(p) >= 2 for p in shared[:1])
    rep = pool.check_invariants()
    assert rep["ok"], rep
    assert pool.stats()["tokens_truncated"] == 3 + 7
    # cleanup leaves nothing behind (the "cache" drops its entry too)
    pool.free_seq(1)
    pool.free_seq(0)
    holds.clear()
    pool.release_pages(shared)
    assert pool.used_pages == 0 and pool.check_invariants()["ok"]


def test_append_after_rollback_into_shared_page_cows():
    """After truncating into a shared partially-filled page, the next
    append must copy-on-write it — rollback cannot turn a shared page
    writable."""
    pool = _pool()
    pool.allocate(0)
    _fill(pool, 0, 6)  # 2 pages, second partial
    shared, _ = pool.table_snapshot(0)
    pool.allocate(1)
    pool.attach_prefix(1, shared, 6)
    _fill(pool, 1, 4, value=2.0)  # CoWs the partial tail + 1 more page
    cows0 = pool.stats()["cow_copies"]
    assert cows0 == 1
    pool.truncate_seq(1, 5)  # back INSIDE the shared page-1 span? no:
    # 5 tokens = page0(4) + 1 token in seq1's CoW'd page — the shared
    # page-1 left the table, refcount back to seq0's
    tab1, _ = pool.table_snapshot(1)
    assert pool.check_invariants()["ok"]
    # appending again writes into seq 1's own (or fresh) pages — never
    # the shared ones
    _fill(pool, 1, 3, value=3.0)
    assert pool.check_invariants()["ok"]
    for p in pool.table_snapshot(0)[0]:
        assert pool.refcount(p) >= 1
    pool.free_seq(1)
    pool.free_seq(0)
    assert pool.used_pages == 0


# ---------------------------------------------------------------------------
# drafter unit behavior


def test_prompt_lookup_drafter():
    d = PromptLookupDrafter(max_draft=4, max_ngram=3)
    assert d.draft([5, 6, 7, 9, 5, 6, 7]) == [9, 5, 6, 7]
    assert d.draft([1, 2, 3]) == []
    assert d.draft([4, 4, 4, 4]) == [4, 4, 4]  # longest partial
    assert d.draft([5, 6, 7, 9, 5, 6, 7], max_draft=2) == [9, 5]
    assert d.draft([1, 2, 1, 2, 1, 2]) == [1, 2, 1, 2]
    assert d.draft([3]) == [] and d.draft([]) == []
    with pytest.raises(ValueError):
        PromptLookupDrafter(max_draft=0)
    with pytest.raises(ValueError):
        PromptLookupDrafter(min_ngram=3, max_ngram=2)


# ---------------------------------------------------------------------------
# (a) the interpret-tier parity matrix


def _matrix_model(h_kv):
    cfg = DecodeConfig(vocab_size=61, d_model=32, n_head=8, n_layer=2,
                       d_inner=48, max_length=48, n_kv_head=h_kv)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in (8, 9, 10, 11)]
    return cfg, init_decode_params(cfg, seed=2), prompts


@functools.lru_cache(maxsize=None)
def _matrix_oracle(h_kv):
    """full_decode of the matrix's prompts, once a K/V head count: the
    oracle runs op by op and compiles every sequence length it meets, so
    the six cases of one model share its answer (and the prompts' lengths
    sit next to each other and 8 tokens are decoded: 11 lengths where
    (6, 9, 4, 11) and 10 made 17)."""
    cfg, params, prompts = _matrix_model(h_kv)
    return [full_decode(params, cfg, p, 8) for p in prompts]


@pytest.mark.parametrize("d", [1, 2, 4])
@pytest.mark.parametrize("h_kv", [8, 2])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_speculative_parity_matrix_vs_full_decode(d, h_kv, dtype):
    """Greedy speculative decode through the REAL multi-token kernel
    (interpret mode) is token-EXACT vs full_decode on overlapping
    ragged sequences, drafts genuinely fire, and every rollback leaves
    the audited pool clean with zero leaked pages."""
    cfg, params, prompts = _matrix_model(h_kv)
    pool = KVCachePool(num_pages=48, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim,
                       num_kv_heads=h_kv, dtype=dtype)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3,
                                  paged_impl="interpret", speculate=d,
                                  check_every=1)
    results = loop.run([DecodeRequest(p, 8) for p in prompts])
    tol = 2e-2 if dtype == "int8" else 1e-4
    for res, (want_tokens, want_logits) in zip(results,
                                               _matrix_oracle(h_kv)):
        assert res.tokens == want_tokens  # greedy tokens EXACT
        for got, want in zip(res.logits, want_logits):
            np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert loop.drafted_tokens > 0  # speculation actually ran
    assert loop.spec_steps > 0
    assert pool.free_pages == pool.num_pages  # zero leaked pages
    assert loop.invariant_violations == 0
    assert pool.check_invariants()["ok"]


def test_speculative_rollbacks_occur_and_stay_clean():
    """The acceptance wording is explicit: rollbacks must OCCUR.  On the
    parity matrix's model and prompts (PR 46: the model, its prompts and
    the oracle's answer are the module's, built and compiled once; the
    test had a model of its own and a 21-length oracle, 135 s) the
    drafter over-proposes at depth 3 and the verifier rejects some
    tokens — truncations fire and the pool audit stays green after
    every one (check_every=1)."""
    cfg, params, prompts = _matrix_model(8)
    pool = KVCachePool(num_pages=80, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=4,
                                  speculate=3, check_every=1)
    results = loop.run([DecodeRequest(p, 8) for p in prompts])
    for res, (want_tokens, _) in zip(results, _matrix_oracle(8)):
        assert res.tokens == want_tokens
    assert loop.rolled_back_tokens > 0
    assert loop.accepted_tokens < loop.drafted_tokens
    assert 0.0 < loop.acceptance_rate() < 1.0
    assert pool.stats()["tokens_truncated"] == loop.rolled_back_tokens
    assert loop.invariant_violations == 0
    assert pool.free_pages == pool.num_pages
    # fewer model steps than unspeculated decode for the same tokens
    loop0 = ContinuousBatchingLoop(
        params, cfg,
        KVCachePool(num_pages=80, page_size=4, num_layers=cfg.n_layer,
                    num_heads=cfg.n_head, head_dim=cfg.head_dim),
        max_batch=4, speculate=0)
    loop0.run([DecodeRequest(p, 8) for p in prompts])
    assert loop.steps < loop0.steps


def test_speculation_composes_with_prefix_cache_hits():
    """Prefix-cache hits + speculation + rollback in one run: token
    parity holds, hits and drafts both fire, and truncation through
    refcounted tables never corrupts the audit."""
    cfg = DecodeConfig(vocab_size=61, d_model=32, n_head=8, n_layer=2,
                       d_inner=48, max_length=48, n_kv_head=2)
    params = init_decode_params(cfg, seed=2)
    rng = np.random.RandomState(2)
    shared = rng.randint(1, 61, size=9).tolist()
    prompts = [shared + rng.randint(1, 61, size=3).tolist()
               for _ in range(5)]
    pool = KVCachePool(num_pages=60, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim,
                       num_kv_heads=2, dtype="int8")
    cache = PrefixCache(pool)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=2,
                                  paged_impl="interpret", speculate=3,
                                  prefix_cache=cache, check_every=1)
    results = loop.run([DecodeRequest(p, 8) for p in prompts])
    for p, res in zip(prompts, results):
        assert res.tokens == full_decode(params, cfg, p, 8)[0]
    assert loop.prefix_hits > 0 and loop.drafted_tokens > 0
    cache.clear()
    assert pool.free_pages == pool.num_pages
    assert pool.check_invariants()["ok"]


# ---------------------------------------------------------------------------
# (d) stops inside an accepted block


class _OracleDrafter:
    """Proposes the exact greedy continuation — forces full acceptance
    so EOS/stop/max_new land INSIDE accepted blocks."""

    def __init__(self, prompt, tokens):
        self.seq = list(prompt) + list(tokens)

    def draft(self, context, max_draft=None):
        n = len(context)
        return self.seq[n:n + (max_draft or 4)]


@functools.lru_cache(maxsize=None)
def _oracle_setup_once(seed, max_new):
    """One small model, its prompt and its greedy continuation, made once
    a module: seven tests start from it, and the oracle compiles every
    sequence length it meets (10 new tokens since PR 46, 14 before)."""
    cfg0 = DecodeConfig(vocab_size=61, d_model=16, n_head=2, n_layer=2,
                        d_inner=32, max_length=64)
    params = init_decode_params(cfg0, seed=seed)
    prompt = list(np.random.RandomState(seed).randint(1, 61, size=6))
    want, _ = full_decode(params, cfg0, prompt, max_new)
    return cfg0, params, tuple(prompt), tuple(want)


def _oracle_setup(seed=0, max_new=10):
    cfg0, params, prompt, want = _oracle_setup_once(seed, max_new)
    return cfg0, params, list(prompt), list(want)


def test_eos_inside_accepted_draft_block_truncates_both_sides():
    cfg0, params, prompt, want = _oracle_setup()
    eos = want[4]
    cfg = DecodeConfig(vocab_size=61, d_model=16, n_head=2, n_layer=2,
                       d_inner=32, max_length=64, eos_id=int(eos))
    want_e, _ = full_decode(params, cfg, prompt, 10)
    assert want_e[-1] == eos and len(want_e) < 10
    pool = KVCachePool(num_pages=32, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim)
    loop = ContinuousBatchingLoop(
        params, cfg, pool, max_batch=2, speculate=4,
        drafter=_OracleDrafter(prompt, want))
    res = loop.run([DecodeRequest(prompt, 10)])[0]
    # retires AT the EOS position: no surplus tokens in the result...
    assert res.tokens == want_e
    # ...and none left in the page table: the fed-but-dead tail was
    # truncated before retirement freed the rest
    assert loop.rolled_back_tokens > 0
    assert pool.free_pages == pool.num_pages
    assert pool.check_invariants()["ok"]


def test_stop_sequence_and_max_new_inside_blocks():
    cfg0, params, prompt, want = _oracle_setup()
    pool = KVCachePool(num_pages=64, page_size=4, num_layers=cfg0.n_layer,
                       num_heads=cfg0.n_head, head_dim=cfg0.head_dim)
    loop = ContinuousBatchingLoop(
        params, cfg0, pool, max_batch=4, speculate=4,
        drafter=_OracleDrafter(prompt, want))
    stop = tuple(want[2:4])
    res = loop.run([
        DecodeRequest(prompt, 10),
        DecodeRequest(prompt, 10, sampling=SamplingParams(stop=[stop])),
        DecodeRequest(prompt, 10, sampling=SamplingParams(max_new=3)),
    ])
    assert res[0].tokens == want
    # the stop-seq arm ends the moment its generated tokens end with
    # the stop — the shortest such prefix of the oracle stream
    got = res[1].tokens
    assert tuple(got[-2:]) == stop
    assert got == want[:len(got)]
    assert all(tuple(got[i - 1:i + 1]) != stop
               for i in range(1, len(got) - 1))
    # per-request max_new caps below the request's own limit
    assert res[2].tokens == want[:3]
    assert pool.free_pages == pool.num_pages
    assert pool.check_invariants()["ok"]


# ---------------------------------------------------------------------------
# (e) the sampling contract


def test_sampling_params_validation_and_normalization():
    p = SamplingParams(stop=[[1, 2]], logit_bias={3: 2.0, 1: -1.0})
    assert p.greedy and p.stop == ((1, 2),)
    assert p.logit_bias == ((1, -1.0), (3, 2.0))
    assert p.max_bias_token() == 3 and SamplingParams().max_bias_token() == -1
    assert hash(p) is not None  # frozen + normalized: usable as a key
    for bad in (dict(temperature=-1), dict(top_k=-1), dict(top_p=0.0),
                dict(top_p=1.5), dict(max_new=0), dict(stop=[[]]),
                # a bad seed/bias must fail THIS request's construction,
                # never the shared batch mid-step
                dict(seed=-1), dict(seed=2 ** 32),
                dict(logit_bias={-2: 1.0})):
        with pytest.raises(ValueError):
            SamplingParams(**bad)
    assert stop_hit([9, 1, 2], p) and not stop_hit([1, 2, 9], p)
    row = np.zeros(8, np.float32)
    biased = apply_bias(row, p)
    assert biased[3] == 2.0 and biased[1] == -1.0 and row[3] == 0.0


def test_out_of_vocab_bias_rejected_at_admission():
    cfg = DecodeConfig(vocab_size=31, d_model=16, n_head=2, n_layer=1,
                       d_inner=16, max_length=32)
    pool = KVCachePool(num_pages=16, page_size=4, num_layers=1,
                       num_heads=2, head_dim=8)
    loop = ContinuousBatchingLoop(init_decode_params(cfg), cfg, pool)
    with pytest.raises(ValueError, match="vocab_size"):
        loop.run([DecodeRequest([1, 2], 2,
                  sampling=SamplingParams(logit_bias={99: 1.0}))])
    assert pool.free_pages == pool.num_pages  # before-any-work raise


def test_rogue_drafter_output_clamped_to_room():
    """A custom drafter ignoring max_draft must not breach the pad_to
    width or the admission page reservation — the loop clamps."""
    cfg = DecodeConfig(vocab_size=31, d_model=16, n_head=2, n_layer=1,
                       d_inner=16, max_length=32)
    params = init_decode_params(cfg)
    pool = KVCachePool(num_pages=16, page_size=4, num_layers=1,
                       num_heads=2, head_dim=8)

    class Rogue:
        def draft(self, context, max_draft=None):
            return [1, 2, 3, 4, 5, 6, 7]

    loop = ContinuousBatchingLoop(params, cfg, pool, speculate=2,
                                  drafter=Rogue())
    res = loop.run([DecodeRequest([1, 2, 3], 4)])
    assert res[0].tokens == full_decode(params, cfg, [1, 2, 3], 4)[0]
    assert pool.free_pages == pool.num_pages


def test_top_p_default_is_a_true_no_op():
    """The fp32 cumsum of sorted softmax probs often tops out below
    1.0; top_p=1.0 (the documented 'off') must still keep the whole
    vocab — hot-temperature draws stay genuinely random instead of
    collapsing to argmax."""
    rng = np.random.RandomState(0)
    logits = rng.standard_normal((120, 32)).astype(np.float32)
    ps = [SamplingParams(temperature=1.0, seed=i) for i in range(120)]
    toks = sample_rows(logits, ps, list(range(120)))
    assert float((toks == logits.argmax(-1)).mean()) < 0.5


def test_sample_rows_epilogue_semantics():
    rng = np.random.RandomState(0)
    logits = rng.standard_normal((4, 32)).astype(np.float32)
    ps = [SamplingParams(temperature=0.8, seed=i) for i in range(4)]
    t1 = sample_rows(logits, ps, [0] * 4)
    assert (t1 == sample_rows(logits, ps, [0] * 4)).all()  # deterministic
    assert (t1 != sample_rows(logits, ps, [1] * 4)).any()  # per-step keys
    # top_k=1 and a vanishing top_p both collapse to argmax even hot
    for collapse in (dict(top_k=1), dict(top_p=1e-7)):
        pc = [SamplingParams(temperature=5.0, seed=i, **collapse)
              for i in range(4)]
        assert (sample_rows(logits, pc, [0] * 4)
                == logits.argmax(-1)).all()
    # greedy rows are the host argmax path's job, never the epilogue's
    with pytest.raises(ValueError, match="greedy"):
        sample_rows(logits, [SamplingParams()] * 4, [0] * 4)


# ---------------------------------------------------------------------------
# (i) ISSUE 16: the exact accept/resample epilogue — distribution,
# replay, degrade surfacing, and the corpus drafter decision rule


def _exact_filtered_probs(row, p):
    """Host-side exact target: the SAME ``_filter_scaled`` both jitted
    epilogues trace, applied eagerly to one row, then softmax."""
    import jax.numpy as jnp

    from paddle_tpu.serving import sampling as _sampling

    x = np.asarray(_sampling._filter_scaled(
        jnp.asarray(row[None], jnp.float32),
        jnp.asarray([p.temperature], jnp.float32),
        jnp.asarray([p.top_k], jnp.int32),
        jnp.asarray([p.top_p], jnp.float32), row.shape[0]))[0]
    x = x - x[np.isfinite(x)].max()
    e = np.where(np.isfinite(x), np.exp(x), 0.0)
    return e / e.sum()


@pytest.mark.parametrize("kw", [
    dict(temperature=0.8),
    dict(temperature=0.9, top_k=8),
    dict(temperature=1.0, top_p=0.85),
], ids=["temp", "topk", "topp"])
def test_spec_epilogue_emitted_distribution_is_exact(kw):
    """The exactness theorem, empirically: with a fixed drafted token,
    the FIRST emitted token of the accept/resample walk (the draft when
    accepted, the masked residual resample otherwise) must be
    distributed exactly as the plain filtered sampler.  Checked three
    ways over thousands of independent seeds: TV distance against
    ``sample_rows``'s empirical histogram, chi-square against the exact
    filtered softmax, and the acceptance frequency against p(draft)
    itself — with both the accept and resample arms firing."""
    V, B = 32, 8192
    rng = np.random.RandomState(5)
    row = rng.standard_normal(V).astype(np.float32)
    ps = [SamplingParams(seed=i, **kw) for i in range(B)]
    steps = [0] * B
    p_exact = _exact_filtered_probs(row, ps[0])
    draft = int(np.argsort(p_exact)[-2])  # in-support, not the mode
    spec_logits = np.broadcast_to(row, (B, 2, V)).copy()
    acc, toks = spec_sample_rows(spec_logits, ps, steps, [[draft]] * B)
    emitted = toks[:, 0]
    accepted = acc >= 1
    assert 0 < accepted.sum() < B              # both arms exercised
    assert (emitted[accepted] == draft).all()  # accepts emit the draft
    assert (emitted[~accepted] != draft).all()  # residual masks it out
    # TV distance vs the plain epilogue's empirical distribution
    plain = sample_rows(np.broadcast_to(row, (B, V)).copy(), ps, steps)
    h_spec = np.bincount(emitted, minlength=V) / B
    h_plain = np.bincount(plain, minlength=V) / B
    assert 0.5 * np.abs(h_spec - h_plain).sum() < 0.05
    # chi-square vs the exact filtered softmax (loose bound — a wrong
    # residual, e.g. forgetting to mask the draft, misses it by miles)
    exp = p_exact * B
    keep = exp >= 5
    chi2 = float((((np.bincount(emitted, minlength=V) - exp) ** 2
                   / np.maximum(exp, 1e-9))[keep]).sum())
    dof = int(keep.sum()) - 1
    assert chi2 < dof + 6 * np.sqrt(2 * dof), (chi2, dof)
    # acceptance itself is a Bernoulli(p(draft)) draw per row
    p_d = float(p_exact[draft])
    assert abs(float(accepted.mean()) - p_d) \
        < 5 * np.sqrt(p_d * (1 - p_d) / B)
    # exact replay: the (seed, token-index) stream is bit-identical
    acc2, toks2 = spec_sample_rows(spec_logits, ps, steps,
                                   [[draft]] * B)
    assert (acc2 == acc).all() and (toks2 == toks).all()


def test_spec_epilogue_no_draft_row_is_exactly_sample_rows():
    """A row with an empty draft walks zero accepts and lands on the
    bonus draw — the UNSALTED Gumbel at key_g — so it must be
    byte-identical to the plain epilogue at the same (seed, step)."""
    rng = np.random.RandomState(7)
    B, V = 64, 32
    logits = rng.standard_normal((B, V)).astype(np.float32)
    ps = [SamplingParams(temperature=0.7 + 0.01 * i, seed=i)
          for i in range(B)]
    steps = list(range(B))
    acc, toks = spec_sample_rows(logits[:, None, :], ps, steps,
                                 [[]] * B)
    assert (acc == 0).all()
    assert (toks[:, 0] == sample_rows(logits, ps, steps)).all()


def test_spec_epilogue_rejects_greedy_rows_and_overfull_drafts():
    logits = np.zeros((2, 3, 8), np.float32)
    sp = SamplingParams(temperature=0.8, seed=0)
    with pytest.raises(ValueError, match="greedy"):
        spec_sample_rows(logits, [SamplingParams(), sp], [0, 0],
                         [[1], [1]])
    with pytest.raises(ValueError, match="at most"):
        spec_sample_rows(logits, [sp, sp], [0, 0], [[1, 2, 3], [1]])


def test_sampled_spec_arms_roll_back_and_leak_nothing():
    """Every sampling scenario speculates now: the epilogue rejects
    (rollbacks occur), the pool comes back fully free with invariants
    audited every step, and the replayed stream is identical."""
    cfg0, params, prompt, _ = _oracle_setup()
    prompt = prompt[:3] * 2  # a repeating prompt: drafting fires early
    for arm in (dict(temperature=1.0), dict(temperature=0.9, top_k=12),
                dict(temperature=0.9, top_p=0.9)):

        def run():
            pool = KVCachePool(num_pages=64, page_size=4,
                               num_layers=cfg0.n_layer,
                               num_heads=cfg0.n_head,
                               head_dim=cfg0.head_dim)
            loop = ContinuousBatchingLoop(params, cfg0, pool,
                                          max_batch=4, speculate=3,
                                          check_every=1)
            reqs = [DecodeRequest(prompt, 10,
                                  sampling=SamplingParams(seed=s,
                                                          **arm))
                    for s in range(3)]
            out = loop.run(reqs)
            assert pool.free_pages == pool.num_pages
            assert loop.invariant_violations == 0
            return loop, [o.tokens for o in out]

        loop, toks = run()
        assert loop.drafted_tokens > 0, arm
        assert loop.rolled_back_tokens > 0, arm  # rejections happened
        _, toks2 = run()
        assert toks2 == toks, arm


def test_program_without_verify_step_surfaces_spec_disabled(obs_on):
    """ISSUE 16 bugfix: a program that cannot verify used to degrade
    speculation to d=0 with only a log line — now it lands a
    {reason=}-labelled counter and a flight event."""
    cfg = DecodeConfig(vocab_size=17, d_model=16, n_head=2, n_layer=1,
                       d_inner=16, max_length=16)
    pool = KVCachePool(num_pages=4, page_size=4, num_layers=1,
                       num_heads=2, head_dim=8)

    class _NoVerify:
        def __init__(self, cfg):
            self.cfg = cfg

        def resolve_impl(self, pool):
            return "reference"

    loop = ContinuousBatchingLoop(None, None, pool,
                                  program=_NoVerify(cfg), speculate=2)
    assert loop._speculate == 0 and loop.drafter is None
    snap = obs.default_registry().to_prometheus()
    assert "paddle_tpu_serving_spec_disabled_total" in snap
    assert 'reason="program_no_verify"' in snap
    ev = [e for e in obs.default_flight().events()
          if e["kind"] == "spec_disabled"]
    assert ev and ev[0]["reason"] == "program_no_verify"
    assert ev[0]["program"] == "_NoVerify"


def _corpus_cache(chains):
    """A PrefixCache primed the production way: each chain is a
    finished prefill whose prompt pages were inserted into the trie."""
    pool = KVCachePool(num_pages=64, page_size=4, num_layers=1,
                       num_heads=2, head_dim=8)
    cache = PrefixCache(pool)
    for sid, chain in enumerate(chains):
        pool.allocate(sid)
        pool.append_tokens([sid], [len(chain)])
        cache.insert(sid, chain)
    return pool, cache


def test_ngram_continuation_decision_rule():
    pool, cache = _corpus_cache([
        [1, 2, 3, 4, 5, 6, 7, 8],   # older chain, longer follow-up
        [9, 9, 1, 2, 3, 7, 7, 7],   # newer chain, shorter follow-up
    ])
    # the longer continuation wins across chains
    assert cache.ngram_continuation([1, 2, 3], 4) == [4, 5, 6, 7]
    # at equal (full) length the more recently used chain wins the tie
    assert cache.ngram_continuation([1, 2, 3], 3) == [7, 7, 7]
    # a miss returns [] — the drafter falls back to own history
    assert cache.ngram_continuation([5, 9], 4) == []
    assert cache.ngram_continuation([], 4) == []
    assert cache.ngram_continuation([1, 2, 3], 0) == []
    # the corpus walk is pure host bookkeeping: no pool state moved
    assert pool.check_invariants()["ok"]


def test_ngram_continuation_newest_position_wins_within_chain():
    _, cache = _corpus_cache([[1, 2, 5, 1, 2, 6, 1, 2]])
    # [1, 2] occurs at 0, 3 and 6; the newest occurrence with a
    # full-length continuation (position 3) wins over the older one
    assert cache.ngram_continuation([1, 2], 2) == [6, 1]


def test_drafter_corpus_decision_rule_and_type_check():
    _, cache = _corpus_cache([[3, 4, 50, 51, 52, 53, 54, 55]])
    d = PromptLookupDrafter(max_draft=4, max_ngram=3, corpus=cache)
    ctx = [3, 4, 8, 3, 4]
    # own history fills the limit → the corpus is never consulted
    assert d.draft(ctx, 3) == [8, 3, 4]
    # own comes up short → a STRICTLY longer corpus continuation wins
    assert d.draft(ctx, 4) == [50, 51, 52, 53]
    # an equal-length corpus match does NOT displace own history
    d2 = PromptLookupDrafter(
        max_draft=4, max_ngram=3,
        corpus=_corpus_cache([[3, 4, 60, 61, 62]])[1])
    assert d2.draft(ctx, 4) == [8, 3, 4]
    with pytest.raises(TypeError, match="ngram_continuation"):
        PromptLookupDrafter(corpus=object())


def test_loop_wires_prefix_cache_as_drafter_corpus():
    cfg0, params, _, _ = _oracle_setup()

    def pool():
        return KVCachePool(num_pages=64, page_size=4,
                           num_layers=cfg0.n_layer,
                           num_heads=cfg0.n_head,
                           head_dim=cfg0.head_dim)

    p1 = pool()
    cache = PrefixCache(p1)
    loop = ContinuousBatchingLoop(params, cfg0, p1, speculate=3,
                                  prefix_cache=cache)
    assert loop.drafter is not None and loop.drafter.corpus is cache
    # no prefix cache → no corpus, plain own-history drafting
    loop2 = ContinuousBatchingLoop(params, cfg0, pool(), speculate=3)
    assert loop2.drafter is not None and loop2.drafter.corpus is None


def test_sampled_request_rides_spec_batch_and_replays_identically():
    """A non-greedy request decodes alongside speculating batch-mates
    without breaking the greedy mate's oracle parity, and an identical
    replay regenerates the identical stream (the (seed, token-index)
    RNG key contract; exact cross-composition identity is NOT promised
    — fp32 reduction order differs between step shapes).  ISSUE 16:
    the sampled row itself DRAFTS now — the accept/resample epilogue
    verifies it — so a purely sampled run speculates too."""
    cfg0, params, prompt, want = _oracle_setup()
    sp = SamplingParams(temperature=0.9, seed=3)

    def run(reqs):
        pool = KVCachePool(num_pages=64, page_size=4,
                           num_layers=cfg0.n_layer, num_heads=cfg0.n_head,
                           head_dim=cfg0.head_dim)
        loop = ContinuousBatchingLoop(params, cfg0, pool, max_batch=4,
                                      speculate=3)
        out = loop.run(reqs)
        assert pool.free_pages == pool.num_pages
        return loop, out

    loop, mixed = run([DecodeRequest(prompt, 10),
                       DecodeRequest(prompt, 10, sampling=sp)])
    assert mixed[0].tokens == want            # greedy mate: oracle-exact
    assert len(mixed[1].tokens) == 10
    assert mixed[1].tokens != want            # genuinely sampled
    assert loop.drafted_tokens > 0            # the greedy mate drafted
    _, replay = run([DecodeRequest(prompt, 10),
                     DecodeRequest(prompt, 10, sampling=sp)])
    assert replay[1].tokens == mixed[1].tokens  # identical replay
    # a different seed is a different stream
    _, other = run([DecodeRequest(prompt, 10),
                    DecodeRequest(prompt, 10,
                                  sampling=SamplingParams(
                                      temperature=0.9, seed=4))])
    assert other[1].tokens != mixed[1].tokens
    # a purely sampled run drafts too (ISSUE 16 — no per-sequence
    # auto-disable anymore) and its replay is still exact
    loop2, out2 = run([DecodeRequest(prompt, 6, sampling=sp),
                       DecodeRequest(prompt, 6,
                                     sampling=SamplingParams(
                                         temperature=0.5, seed=1))])
    assert loop2.drafted_tokens > 0 and loop2.spec_steps > 0
    _, out3 = run([DecodeRequest(prompt, 6, sampling=sp),
                   DecodeRequest(prompt, 6,
                                 sampling=SamplingParams(
                                     temperature=0.5, seed=1))])
    assert [o.tokens for o in out3] == [o.tokens for o in out2]


def test_logit_bias_shifts_greedy_argmax_and_keeps_speculation():
    cfg0, params, prompt, want = _oracle_setup()
    forced = (want[0] + 1) % 61 or 1
    sp = SamplingParams(logit_bias={forced: 1e3})
    assert sp.greedy  # biased greedy is deterministic: speculation on
    pool = KVCachePool(num_pages=64, page_size=4, num_layers=cfg0.n_layer,
                       num_heads=cfg0.n_head, head_dim=cfg0.head_dim)
    loop = ContinuousBatchingLoop(params, cfg0, pool, max_batch=2,
                                  speculate=3)
    res = loop.run([DecodeRequest(prompt, 5, sampling=sp)])[0]
    assert all(t == forced for t in res.tokens)  # the bias wins each step
    assert pool.free_pages == pool.num_pages


def test_engine_submit_threads_sampling_passthrough_only():
    from paddle_tpu import serving

    captured = {}

    class _Backend:
        feed_names = None

        def __call__(self, feed, **kw):
            captured.update(kw)
            return [np.zeros((1, 1), np.float32)]

    eng = serving.Engine(_Backend(),
                         config=serving.EngineConfig(buckets=()))
    sp = SamplingParams(temperature=0.5, seed=9)
    fut = eng.submit({"x": np.zeros((1, 2), np.float32)}, sampling=sp)
    fut.result(timeout=10)
    assert captured["sampling"] is sp
    with pytest.raises(TypeError, match="SamplingParams"):
        eng.submit({"x": np.zeros((1, 2), np.float32)},
                   sampling={"temperature": 1.0})
    eng.close()
    bucketed = serving.Engine(_Backend(),
                              config=serving.EngineConfig(buckets=(1, 2)))
    with pytest.raises(ValueError, match="pass-through"):
        bucketed.submit({"x": np.zeros((1, 2), np.float32)}, sampling=sp)
    bucketed.close()


def test_loop_rejects_bad_speculate_and_degrades_for_program():
    cfg = DecodeConfig(vocab_size=17, d_model=16, n_head=2, n_layer=1,
                       d_inner=16, max_length=16)
    pool = KVCachePool(num_pages=4, page_size=4, num_layers=1,
                       num_heads=2, head_dim=8)
    with pytest.raises(ValueError, match="speculate"):
        ContinuousBatchingLoop(init_decode_params(cfg), cfg, pool,
                               speculate=-1)
    # FLAGS default keeps speculation off
    loop = ContinuousBatchingLoop(init_decode_params(cfg), cfg, pool)
    assert loop._speculate == 0 and loop.drafter is None


# ---------------------------------------------------------------------------
# (h) observability: flight events + span annotations


@pytest.fixture
def obs_on(tmp_path):
    fluid.set_flags({"FLAGS_observability": True,
                     "FLAGS_flight_dir": str(tmp_path / "flight")})
    obs.reset()
    yield
    obs.reset()
    fluid.set_flags({"FLAGS_observability": False,
                     "FLAGS_flight_dir": ""})


def test_flight_events_and_span_annotations(obs_on):
    cfg0, params, prompt, want = _oracle_setup()
    pool = KVCachePool(num_pages=80, page_size=4, num_layers=cfg0.n_layer,
                       num_heads=cfg0.n_head, head_dim=cfg0.head_dim)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 61, size=n).tolist() for n in (6, 9, 4, 11)]
    loop = ContinuousBatchingLoop(params, cfg0, pool, max_batch=4,
                                  speculate=3)
    results = loop.run([DecodeRequest(p, 10) for p in prompts])
    assert loop.rolled_back_tokens > 0  # this seed rolls back (pinned)
    kinds = [e["kind"] for e in obs.default_flight().events()]
    for kind in ("draft", "verify", "rollback"):
        assert kind in kinds, kinds
    ev = [e for e in obs.default_flight().events() if e["kind"] == "verify"]
    assert all("accepted" in e and "rejected" in e and "trace_id" in e
               for e in ev)
    # the sequence span carries the accepted/rejected annotation
    spans = [s for s in obs.default_tracer().spans()
             if s.name == "sequence"]
    annotated = [s for s in spans if "drafted" in s.args]
    assert annotated
    for s in annotated:
        assert s.args["drafted"] == s.args["accepted"] + s.args["rejected"]
    # the spec counter landed
    snap = obs.default_registry().to_prometheus()
    assert "paddle_tpu_serving_spec_tokens_total" in snap
    # every sequence still oracle-exact with the flag on
    for p, r in zip(prompts, results):
        assert r.tokens == full_decode(params, cfg0, p, 10)[0]


# ---------------------------------------------------------------------------
# (f) the incremental n-gram index (ROADMAP speculative item 3)


def test_drafter_incremental_index_parity_over_random_histories():
    """The per-sequence suffix index must answer EXACTLY like the
    stateless reversed scan at every point of a random commit/rollback
    history — the index is an accelerator, never a different oracle."""
    rng = np.random.RandomState(7)
    for trial in range(8):
        d = PromptLookupDrafter(max_draft=4, max_ngram=3)
        oracle = PromptLookupDrafter(max_draft=4, max_ngram=3)
        ctx = rng.randint(0, 5, size=rng.randint(2, 8)).tolist()
        for step in range(60):
            op = rng.rand()
            if op < 0.2 and len(ctx) > 3:
                # rollback: a verify step rejected some draft tokens
                ctx = ctx[:rng.randint(2, len(ctx))]
            else:
                ctx = ctx + rng.randint(0, 5,
                                        size=rng.randint(1, 4)).tolist()
            limit = int(rng.randint(1, 5))
            got = d.draft(ctx, limit, seq_id=trial)
            want = oracle.draft(ctx, limit)  # stateless scan
            assert got == want, (trial, step, ctx, limit, got, want)
            # the index re-synced to exactly the visible context
            assert d._index[trial].tokens == ctx


def test_drafter_rollback_rewinds_index_exactly():
    """truncate_seq rollbacks reach the drafter as a shorter/diverged
    context: the index must pop every n-gram the dead tokens registered
    (a stale occurrence would propose continuations from rolled-back
    text)."""
    d = PromptLookupDrafter(max_draft=4, max_ngram=3)
    # commit a history whose tail will be rolled back
    full = [1, 2, 3, 9, 9, 9, 1, 2, 3]
    assert d.draft(full, 4, seq_id=0) == [9, 9, 9, 1]
    idx = d._index[0]
    n_keys_full = len(idx.occ)
    # the verifier rejected everything after position 4, then committed
    # a different token — the next call's context diverges at 4
    rolled = full[:4] + [7]
    assert d.draft(rolled, 4, seq_id=0) == \
        PromptLookupDrafter(max_draft=4, max_ngram=3).draft(rolled, 4)
    assert idx.tokens == rolled
    assert len(idx.occ) < n_keys_full
    # no surviving occurrence may end past the new length
    for key, positions in idx.occ.items():
        for i in positions:
            assert i + len(key) <= len(rolled)
    # growing again after the rewind stays consistent
    grown = rolled + [1, 2, 3]
    assert d.draft(grown, 4, seq_id=0) == \
        PromptLookupDrafter(max_draft=4, max_ngram=3).draft(grown, 4)


def test_drafter_release_and_lru_cap_bound_host_memory():
    d = PromptLookupDrafter(max_draft=2, max_sequences=2)
    assert d.stateful  # the loop's seq_id/release protocol marker
    for sid in (10, 11, 12):
        d.draft([1, 2, 1, 2], 2, seq_id=sid)
    assert d.tracked_sequences() == 2  # LRU evicted the oldest
    assert 10 not in d._index and 12 in d._index
    d.release(11)
    assert d.tracked_sequences() == 1
    d.release(99)  # releasing an untracked id is a no-op
    # stateless calls never touch the index
    d.draft([1, 2, 1, 2], 2)
    assert d.tracked_sequences() == 1


def test_loop_releases_drafter_index_on_retirement():
    """The serving loop passes seq_id (the incremental path) and drops
    the index when a sequence retires — a long-lived engine must not
    grow one suffix map per request forever."""
    cfg = DecodeConfig(vocab_size=61, d_model=16, n_head=2, n_layer=1,
                       d_inner=32, max_length=64)
    params = init_decode_params(cfg, seed=2)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in (6, 9, 4)]
    pool = KVCachePool(num_pages=80, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3,
                                  speculate=3, check_every=1)
    assert loop.drafter.stateful
    results = loop.run([DecodeRequest(p, 10) for p in prompts])
    for p, res in zip(prompts, results):
        assert res.tokens == full_decode(params, cfg, p, 10)[0]
    assert loop.drafted_tokens > 0  # the indexed path actually drafted
    assert loop.drafter.tracked_sequences() == 0  # released on retire
