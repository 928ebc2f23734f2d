"""Grouped-query paged attention + int8 KV pages (ISSUE 12).

Acceptance pinned here:
(a) interpret-tier parity: continuous-batching decode with H_q=8 over
    H_kv in {8, 4, 2, 1}, at fp32 AND int8 pages, is token-identical to
    the ``full_decode`` oracle on >= 3 overlapping ragged sequences
    (logits at fp32 tolerance; int8 at the stated 2e-2 tolerance), with
    zero leaked pages;
(b) the grouped pallas kernel (interpret mode) matches the reference
    gather token-for-token over a ragged multi-step decode, grouped and
    quantized arms both;
(c) the per-page scale table stays consistent through copy-on-write,
    defrag, scrub, free, and reclaim_orphans — ``check_invariants``
    audits it (live written pages have entries, freed pages must not) —
    and FAULT_SERVE_PREFIX_CORRUPT against an INT8 pool quarantines the
    poisoned-prefix reader while batch-mates survive oracle-identical;
(d) envelope/typing: H_q % H_kv != 0 raises the typed
    ``GroupedHeadsError`` everywhere (kernel, pool, config); int8 joins
    the Mosaic envelope at sublane 32; an out-of-envelope explicit
    ``pallas`` falls back to reference with a ``fallback_count()``
    increment; the analytic byte model prices H_kv and dtype arms;
(e) serving observability: the attention-bytes gauge carries
    ``kv_dtype=`` next to ``impl=``, and the disabled path stays
    zero-work (no metrics recorded with FLAGS_observability off);
(f) serve_bench decode mode banks kv_heads / kv_dtype /
    kv_bytes_per_token on the shared 0/2/3 gate contract.
"""

import functools
import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.kernels.paged_attention import (
    GroupedHeadsError,
    attention_bytes_per_step,
    fallback_count,
    gather_kv_pages,
    paged_decode_attention,
    pallas_paged_viable,
    resolve_paged_impl,
)
from paddle_tpu.serving import (
    ContinuousBatchingLoop,
    DecodeConfig,
    DecodeRequest,
    KVCachePool,
    PrefixCache,
    full_decode,
    init_decode_params,
)
from paddle_tpu.serving.generate import NonFiniteSequenceError


def _write_random(pool, rng, seq_ids, layers=1):
    """Append one token per sequence and write random K/V rows on every
    layer; returns the per-layer K rows for layer 0."""
    B = len(seq_ids)
    pages, slots = pool.append_token(seq_ids)
    rows = None
    for li in range(layers):
        k = rng.standard_normal(
            (B, pool.num_kv_heads, pool.head_dim)).astype(np.float32)
        v = rng.standard_normal(
            (B, pool.num_kv_heads, pool.head_dim)).astype(np.float32)
        pool.write_kv(li, pages, slots, k, v)
        if li == 0:
            rows = k
    return rows


# -- (a) the acceptance matrix: loop vs oracle ---------------------------

def _matrix_model(h_kv):
    cfg = DecodeConfig(vocab_size=61, d_model=32, n_head=8, n_layer=2,
                       d_inner=48, max_length=40, n_kv_head=h_kv)
    rng = np.random.RandomState(h_kv)
    # 7 sequence lengths for the oracle to compile (4 to 10), 10 before
    # PR 46 (prompts of 5, 2, 7, 3 and 5 new tokens)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in (5, 4, 7, 6)]
    return cfg, init_decode_params(cfg, seed=h_kv), prompts


@functools.lru_cache(maxsize=None)
def _matrix_oracle(h_kv):
    """full_decode of the matrix's prompts, once a K/V head count: the
    fp32 and the int8 case of one model are held to one answer."""
    cfg, params, prompts = _matrix_model(h_kv)
    return [full_decode(params, cfg, p, 4) for p in prompts]


@pytest.mark.parametrize("h_kv", [8, 4, 2, 1])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_loop_parity_matrix_vs_full_decode(h_kv, dtype):
    """H_q=8 over every banked H_kv, fp32 and int8 pages, through the
    REAL grouped pallas kernel (interpret mode): tokens exactly match
    the full-recompute oracle on overlapping ragged sequences, logits
    within tolerance (int8: the stated 2e-2 — amax per-page quant), and
    every page returns to the pool."""
    cfg, params, prompts = _matrix_model(h_kv)
    assert cfg.num_kv_heads == h_kv and cfg.group_size == 8 // h_kv
    pool = KVCachePool(num_pages=36, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim,
                       num_kv_heads=h_kv, dtype=dtype)
    assert pool.quantized == (dtype == "int8")
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3,
                                  paged_impl="interpret", check_every=1)
    results = loop.run([DecodeRequest(p, 4) for p in prompts])
    tol = 2e-2 if dtype == "int8" else 1e-4
    for res, (want_tokens, want_logits) in zip(results,
                                               _matrix_oracle(h_kv)):
        assert res.tokens == want_tokens  # greedy tokens EXACT
        for got, want in zip(res.logits, want_logits):
            np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert pool.free_pages == pool.num_pages
    assert loop.invariant_violations == 0
    assert pool.check_invariants()["ok"]


# -- (b) kernel-level grouped/quantized parity ---------------------------

@pytest.mark.parametrize("h_kv,dtype", [(2, "float32"), (1, "float32"),
                                        (2, "int8")])
def test_grouped_kernel_interpret_matches_reference_multistep(h_kv, dtype):
    """The grouped page-walk kernel vs the reference gather+repeat over
    a ragged multi-step simulated decode — the ISSUE 5 parity contract,
    grouped and int8 arms."""
    Hq, Dh, page_size = 4, 8, 3  # odd page size: deliberately unaligned
    pool = KVCachePool(num_pages=32, page_size=page_size, num_layers=1,
                       num_heads=Hq, head_dim=Dh, num_kv_heads=h_kv,
                       dtype=dtype)
    rng = np.random.RandomState(12)
    seq_ids = [0, 1, 2, 3]
    for s in seq_ids:
        pool.allocate(s)
    for s, prefix in zip(seq_ids, (5, 1, 9, 3)):
        for _ in range(prefix):
            _write_random(pool, rng, [s])
    tol = dict(rtol=2e-5, atol=2e-6)
    for step in range(10):
        _write_random(pool, rng, seq_ids)
        tables, lengths = pool.page_table_batch(seq_ids)
        ks, vs = pool.layer_scales(0)
        q = rng.standard_normal((4, Hq, 1, Dh)).astype(np.float32)
        want = np.asarray(paged_decode_attention(
            q, pool.k_pages[0], pool.v_pages[0], tables, lengths,
            impl="reference", k_scales=ks, v_scales=vs))
        got = np.asarray(paged_decode_attention(
            q, pool.k_pages[0], pool.v_pages[0], tables, lengths,
            impl="interpret", k_scales=ks, v_scales=vs))
        np.testing.assert_allclose(got, want, err_msg=f"step {step}",
                                   **tol)


def test_int8_dequant_error_bounded_by_page_amax():
    """amax per-page quantization: every dequantized value sits within
    half an int8 LSB of its page's largest magnitude — including after
    later writes GREW the page's amax (the requantize arm)."""
    pool = KVCachePool(num_pages=4, page_size=4, num_layers=1,
                       num_heads=2, head_dim=4, dtype="int8")
    pool.allocate(0)
    rng = np.random.RandomState(3)
    written = []
    for step in range(4):
        pages, slots = pool.append_token([0])
        # growing magnitudes force scale growth + requantization
        k = (rng.standard_normal((1, 2, 4)) * (1 + 3 * step)).astype(
            np.float32)
        pool.write_kv(0, pages, slots, k, k)
        written.append(k[0])
    tables, _ = pool.page_table_batch([0])
    ks, _ = pool.layer_scales(0)
    got = np.asarray(gather_kv_pages(pool.k_pages[0], tables, scales=ks))
    want = np.stack(written, axis=1)  # [H, S, D]
    amax = np.abs(want).max()
    # one page here: bound is half an LSB of the page amax
    assert np.abs(got[0, :, :4] - want).max() <= amax / 127.0


# -- (c) scale-table consistency -----------------------------------------

def test_scale_audit_live_and_freed_pages():
    pool = KVCachePool(num_pages=6, page_size=2, num_layers=2,
                       num_heads=2, head_dim=4, dtype="int8")
    pool.allocate(0)
    rng = np.random.RandomState(5)
    _write_random(pool, rng, [0], layers=2)
    assert pool.check_invariants()["ok"]
    page = pool.table_snapshot(0)[0][0]
    # a live written page missing its scale entry is flagged
    saved = pool.k_scales[1, page]
    pool.k_scales[1, page] = 0.0
    rep = pool.check_invariants()
    assert not rep["ok"] and page in rep["scale_errors"]
    pool.k_scales[1, page] = saved
    # scrubbing a LIVE sequence (the pre-quarantine path) zeroes scales
    # WITH the content — all-zero is consistent, not corruption
    pool.scrub_seq_pages(0)
    assert pool.check_invariants()["ok"]
    _write_random(pool, rng, [0], layers=2)
    # a freed page keeping a stale entry is flagged...
    pool.free_seq(0)
    rep = pool.check_invariants()
    assert rep["ok"] and rep["scale_errors"] == []
    pool.k_scales[0, page] = 0.25
    rep = pool.check_invariants()
    assert not rep["ok"] and page in rep["scale_errors"]
    # ...and reclaim_orphans re-trues it with the refcounts
    pool.reclaim_orphans()
    assert pool.check_invariants()["ok"]


def test_scales_travel_through_cow_defrag_scrub():
    """CoW copies the shared tail's scales to the fresh page; defrag
    permutes scale columns with their pages (gather parity holds); the
    quarantine scrub zeroes content AND scales."""
    pool = KVCachePool(num_pages=8, page_size=4, num_layers=1,
                       num_heads=2, head_dim=4, dtype="int8")
    rng = np.random.RandomState(9)
    for s in (0, 1):
        pool.allocate(s)
    pages, slots = pool.append_tokens([0], [2])  # partial tail page
    k = rng.standard_normal((2, 2, 4)).astype(np.float32)
    pool.write_kv(0, pages, slots, k, k)
    tail = pool.table_snapshot(0)[0][-1]
    # share the tail read-only, then diverge: append_tokens must CoW
    pool.attach_prefix(1, [tail], 2)
    p2, s2 = pool.append_token([0])
    pool.write_kv(0, p2, s2, np.ones((1, 2, 4), np.float32),
                  np.ones((1, 2, 4), np.float32))
    new_tail = pool.table_snapshot(0)[0][-1]
    assert new_tail != tail and pool.stats()["cow_copies"] == 1
    np.testing.assert_array_equal(pool.k_scales[:, new_tail],
                                  pool.k_scales[:, tail])
    assert pool.check_invariants()["ok"]
    # defrag: punch a hole, compact, dequantized gather identical
    pool.free_seq(1)
    tables, _ = pool.page_table_batch([0])
    ks, _ = pool.layer_scales(0)
    before = np.asarray(gather_kv_pages(pool.k_pages[0], tables,
                                        scales=ks))
    pool.defrag()
    tables2, _ = pool.page_table_batch([0])
    ks2, _ = pool.layer_scales(0)
    after = np.asarray(gather_kv_pages(pool.k_pages[0], tables2,
                                       scales=ks2))
    np.testing.assert_array_equal(before, after)
    assert pool.check_invariants()["ok"]
    # scrub zeroes scales with the content
    own = pool.table_snapshot(0)[0]
    pool.scrub_seq_pages(0)
    assert pool.k_scales[:, own].sum() == 0
    pool.free_seq(0)
    assert pool.check_invariants()["ok"]


def test_prefix_corrupt_chaos_against_int8_pool():
    """FAULT_SERVE_PREFIX_CORRUPT with int8 pages: the poison lands on
    the cached page's K SCALE (int8 content cannot hold NaN), the hit
    sequence quarantines, batch-mates survive oracle-identical, the
    chain is invalidated + scrubbed, and the scale audit stays green
    with zero leaked pages."""
    cfg = DecodeConfig(vocab_size=41, d_model=16, n_head=4, n_layer=2,
                       d_inner=32, max_length=48, n_kv_head=2)
    params = init_decode_params(cfg, seed=21)
    rng = np.random.RandomState(21)
    # two cached pages and two tokens decoded a request (PR 46; three pages
    # and three before): every sequence length the oracle meets and every
    # step shape of the loop is ~2 s of op-by-op compiles on the CPU
    shared = rng.randint(1, cfg.vocab_size, size=8).tolist()
    owner = shared + rng.randint(1, cfg.vocab_size, size=2).tolist()
    victim = shared + rng.randint(1, cfg.vocab_size, size=3).tolist()
    bystander = rng.randint(1, cfg.vocab_size, size=5).tolist()
    pool = KVCachePool(num_pages=48, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim,
                       num_kv_heads=2, dtype="int8")
    cache = PrefixCache(pool)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=2,
                                  prefix_cache=cache, check_every=1)
    assert loop.run([DecodeRequest(owner, 2)])[0].error is None
    os.environ["FAULT_SERVE_PREFIX_CORRUPT"] = "1"
    try:
        res = loop.run([DecodeRequest(victim, 2),
                        DecodeRequest(bystander, 2)])
    finally:
        os.environ.pop("FAULT_SERVE_PREFIX_CORRUPT", None)
        from paddle_tpu.resilience import faultinject

        faultinject.reset()
    assert loop.quarantined == 1
    assert isinstance(res[0].error, NonFiniteSequenceError)
    want_b, _ = full_decode(params, cfg, bystander, 2)
    assert res[1].error is None and res[1].tokens == want_b
    assert cache.stats()["invalidations"] >= 1
    # re-request re-prefills clean and matches the oracle (NaN scale
    # was scrubbed with the invalidated chain, not recycled)
    res3 = loop.run([DecodeRequest(list(victim), 2)])
    want_v, _ = full_decode(params, cfg, victim, 2)
    assert res3[0].error is None and res3[0].tokens == want_v
    cache.clear()
    assert pool.used_pages == 0
    rep = pool.check_invariants()
    assert rep["ok"] and rep["scale_errors"] == []
    assert np.isfinite(pool.k_scales).all()


# -- prefix sharing + GQA + int8 compose ---------------------------------

def test_prefix_cache_hits_compose_with_gqa_int8():
    """The ISSUE 11 prefix cache over an int8 GQA pool: second
    same-prefix request HITS, attaches quantized pages read-only, and
    both generations match the oracle exactly."""
    cfg = DecodeConfig(vocab_size=53, d_model=32, n_head=8, n_layer=2,
                       d_inner=48, max_length=48, n_kv_head=2)
    params = init_decode_params(cfg, seed=4)
    rng = np.random.RandomState(4)
    shared = rng.randint(1, cfg.vocab_size, size=9).tolist()
    a = shared + rng.randint(1, cfg.vocab_size, size=3).tolist()
    b = shared + rng.randint(1, cfg.vocab_size, size=2).tolist()
    pool = KVCachePool(num_pages=32, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim,
                       num_kv_heads=2, dtype="int8")
    cache = PrefixCache(pool)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=2,
                                  prefix_cache=cache, check_every=1)
    res = loop.run([DecodeRequest(a, 4)])
    res2 = loop.run([DecodeRequest(b, 4)])
    assert loop.prefix_hits == 1 and loop.cached_prefill_tokens >= 8
    for prompt, r in ((a, res[0]), (b, res2[0])):
        want_tokens, want_logits = full_decode(params, cfg, prompt, 4)
        assert r.tokens == want_tokens
        for got, want in zip(r.logits, want_logits):
            np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    cache.clear()
    assert pool.used_pages == 0 and pool.check_invariants()["ok"]
    assert loop.invariant_violations == 0


# -- (d) envelope, typed errors, byte model ------------------------------

def test_grouped_envelope_typed_errors_and_fallback_count():
    # int8 joins the envelope at sublane 32
    assert pallas_paged_viable(32, 128, "int8")
    assert not pallas_paged_viable(16, 128, "int8")
    assert pallas_paged_viable(16, 128)  # fp32 arms unchanged
    assert not pallas_paged_viable(16, 128, "float64")
    # H_q % H_kv != 0: the TYPED error, never a silent fallback
    rng = np.random.RandomState(0)
    kp = rng.standard_normal((3, 4, 4, 8)).astype(np.float32)
    q = rng.standard_normal((1, 4, 1, 8)).astype(np.float32)
    tb = np.zeros((1, 2), np.int32)
    ln = np.ones((1,), np.int32)
    with pytest.raises(GroupedHeadsError):
        paged_decode_attention(q, kp, kp, tb, ln, impl="reference")
    with pytest.raises(GroupedHeadsError):
        KVCachePool(4, 4, 1, num_heads=4, head_dim=8, num_kv_heads=3)
    with pytest.raises(GroupedHeadsError):
        DecodeConfig(n_head=4, n_kv_head=3).num_kv_heads
    with pytest.raises(GroupedHeadsError):
        attention_bytes_per_step("pallas", 1, 2, 4, 4, 8, num_kv_heads=3)
    # int8 pool content without its scales is meaningless: rejected
    with pytest.raises(ValueError, match="scales"):
        paged_decode_attention(
            q[:, :3], kp.astype(np.int8), kp.astype(np.int8), tb, ln,
            impl="reference")
    # out-of-envelope explicit pallas on an int8 geometry: reference
    # fallback with the counter increment (the gate's signal)
    before = fallback_count()
    assert resolve_paged_impl("pallas", 16, 128, "int8") == "reference"
    assert fallback_count() == before + 1
    # in-envelope int8 passes through untouched
    assert resolve_paged_impl("pallas", 32, 128, "int8") == "pallas"
    assert resolve_paged_impl("interpret", 16, 128, "int8") == "interpret"
    assert fallback_count() == before + 1


def test_attention_bytes_model_gqa_and_dtype_arms():
    """The fixed byte model: explicit dtype overrides the fp32-itemsize
    default, KV traffic scales with num_kv_heads, int8 charges the
    per-page scale reads, and the reference arm prices its dequantized
    fp32 copy."""
    kw = dict(batch=4, max_pages=32, page_size=16, num_heads=8,
              head_dim=128, num_layers=2)
    elems = 4 * 32 * 16 * 8 * 128
    # legacy arms unchanged (itemsize default 4)
    assert attention_bytes_per_step("pallas", **kw) == 2 * elems * 4 * 2
    assert attention_bytes_per_step("reference", **kw) == 6 * elems * 4 * 2
    # explicit dtype wins over the itemsize default
    assert attention_bytes_per_step("pallas", dtype="bfloat16", **kw) \
        == 2 * elems * 2 * 2
    # GQA: H_kv/H_q x on the page stream (the pallas arm)
    full = attention_bytes_per_step("pallas", **kw)
    quarter = attention_bytes_per_step("pallas", num_kv_heads=2, **kw)
    assert quarter == full // 4
    # the reference arm under GQA pays its materialized group
    # broadcast: pages + gather copy at H_kv, repeat write + attention
    # read at H_q — NOT the naive H_kv-scaled 6x
    e_kv, e_q = elems // 4, elems
    assert attention_bytes_per_step("reference", num_kv_heads=2, **kw) \
        == 2 * 2 * (e_kv * 4 + e_kv * 4 + e_q * 4 + e_q * 4)
    # int8: elements at 1 byte + 2 fp32 scales per page walked; the
    # reference arm's materialized copy is the DEQUANTIZED fp32 one
    scale_bytes = 2 * 4 * 32 * 4 * 2  # 2 scales * B * maxp * 4B * L
    assert attention_bytes_per_step("pallas", dtype="int8", **kw) \
        == 2 * elems * 1 * 2 + scale_bytes
    assert attention_bytes_per_step("reference", dtype="int8", **kw) \
        == (2 * elems * 1 + 4 * elems * 4) * 2 + scale_bytes


# -- (e) observability: kv_dtype label + zero-work disabled path ---------

def test_attention_bytes_gauge_labeled_with_kv_dtype():
    from paddle_tpu import observability as obs

    cfg = DecodeConfig(vocab_size=17, d_model=16, n_head=4, n_layer=1,
                       d_inner=16, max_length=16, n_kv_head=2)
    params = init_decode_params(cfg, seed=0)

    def run_once():
        pool = KVCachePool(num_pages=8, page_size=4, num_layers=1,
                           num_heads=4, head_dim=4, num_kv_heads=2,
                           dtype="int8")
        ContinuousBatchingLoop(params, cfg, pool, max_batch=2).run(
            [DecodeRequest([1, 2], 2)])

    # disabled path first: ZERO series recorded (the zero-work contract)
    obs.reset()
    assert not fluid.flags.flag("FLAGS_observability")
    run_once()
    assert obs.default_registry().snapshot()["metrics"] == []
    # enabled: the gauge carries impl AND kv_dtype
    fluid.set_flags({"FLAGS_observability": True})
    try:
        run_once()
        snap = obs.default_registry().snapshot()["metrics"]
        by_name = {m["name"]: m for m in snap}
        series = by_name[
            "paddle_tpu_serving_attention_bytes_per_step"]["series"]
        assert series and all(
            s["labels"] == {"impl": "reference", "kv_dtype": "int8"}
            and s["value"] > 0 for s in series)
    finally:
        fluid.set_flags({"FLAGS_observability": False})
        obs.reset()


# -- (f) serve_bench kv knobs -------------------------------------------

def test_serve_bench_kv_knobs_bank_and_gate(tmp_path, capsys):
    from tools.serve_bench import main as bench_main

    out = tmp_path / "gqa.json"
    argv = ["--mode", "decode", "--sequences", "3", "--max-new", "4",
            "--d-model", "32", "--n-head", "8", "--kv-heads", "2",
            "--kv-dtype", "int8", "--vocab", "31", "--max-len", "32",
            "--pages", "32", "--page-size", "4"]
    rc = bench_main(argv + ["--json", str(out)])
    assert rc == 0
    r = json.loads(out.read_text())
    assert r["kv_heads"] == 2 and r["kv_dtype"] == "int8"
    assert r["pages_leaked"] == 0 and r["paged_fallbacks"] == 0
    # kv_bytes_per_token = bytes_per_page / page_size: H_kv heads at 1
    # byte + amortized fp32 scales — 2*2L*4ps*2H*4D*1B/4 + 2*2L*4B/4
    assert r["kv_bytes_per_token"] == (2 * 2 * 4 * 2 * 4 * 1
                                       + 2 * 2 * 4) / 4.0
    # bank the capacity numbers, re-gate: kv_bytes_per_token gates
    # lower-is-better, so an fp32 full-head run against the int8 GQA
    # bank must FAIL (16x the bytes/token)
    bank = tmp_path / "bank.json"
    bank.write_text(json.dumps({
        "kv_bytes_per_token": r["kv_bytes_per_token"],
        "pages_leaked": 0, "paged_fallbacks": 0}))
    assert bench_main(argv + ["--baseline", str(bank), "--gate"]) == 0
    rc = bench_main([
        "--mode", "decode", "--sequences", "3", "--max-new", "4",
        "--d-model", "32", "--n-head", "8", "--vocab", "31",
        "--max-len", "32", "--pages", "32", "--page-size", "4",
        "--baseline", str(bank), "--gate"])
    assert rc == 3
    capsys.readouterr()


def test_serve_bench_kv_usage_errors(capsys):
    from tools.serve_bench import main as bench_main

    # engine mode: exit 2
    assert bench_main(["--kv-dtype", "int8"]) == 2
    assert bench_main(["--mode", "engine", "--kv-heads", "2"]) == 2
    # non-divisor kv-heads: exit 2
    assert bench_main(["--mode", "decode", "--n-head", "4",
                       "--kv-heads", "3"]) == 2
    # int8 / non-mesh-dividing KV heads cannot shard: exit 2, not a
    # ValueError traceback (the shared 0/2/3 gate contract)
    assert bench_main(["--mode", "decode", "--mesh", "2",
                       "--kv-dtype", "int8"]) == 2
    assert bench_main(["--mode", "decode", "--mesh", "4",
                       "--n-head", "8", "--kv-heads", "2"]) == 2
    capsys.readouterr()
