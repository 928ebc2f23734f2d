"""Tiered KV cache (ISSUE 18): spill idle sessions to host RAM.

Acceptance pinned here:
(a) a session that spills and resumes between EVERY turn is
    token-identical to a never-spilled resident oracle across
    H_kv ∈ {8, 2} × {fp32, int8} × prefix-cache hit/miss, with zero
    pages leaked in either tier and invariants green mid-park;
(b) admission reserves against the COMBINED tier: more concurrent
    sessions than HBM fits stay resumable (``make_room`` spills on
    demand), every turn still token-identical to ``full_decode``;
(c) victim policy: idle sessions spill LRU-first; a bounded host tier
    LRU-evicts parked payloads (their next turn re-prefills, counted);
(d) pool pressure (the reclaimer hook inside ``append_tokens``)
    proactively spills idle sessions inline;
(e) tier-aware audits: a parked session's pinned prefix pages are
    OWNED (``check_invariants`` ok, ``reclaim_orphans`` repairs
    nothing), and a corrupted host payload fails the tier audit;
(f) int8 exports round-trip the host tier byte-identical, scales
    included;
(g) a retained-history mismatch resets the session typed (resident and
    parked arms) instead of resuming the wrong KV;
(h) tier observability is gated: FLAGS_observability off mints NO tier
    metrics; on, the spill/resume counters, transfer bytes, and
    occupancy gauges appear.
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.serving import (
    ContinuousBatchingLoop,
    DecodeConfig,
    DecodeRequest,
    HostKVTier,
    HostTierFullError,
    KVCachePool,
    PrefixCache,
    TieredSessionManager,
    full_decode,
    init_decode_params,
)


def _cfg(**kw):
    base = dict(vocab_size=61, d_model=16, n_head=2, n_layer=2,
                d_inner=32, max_length=64)
    base.update(kw)
    return DecodeConfig(**base)


def _pool(cfg, num_pages=64, page_size=4, dtype="float32"):
    return KVCachePool(num_pages=num_pages, page_size=page_size,
                       num_layers=cfg.n_layer, num_heads=cfg.n_head,
                       head_dim=cfg.head_dim, dtype=dtype)


def _multi_turn(loop, mgr, first_prompt, extras, max_new,
                spill_each=False):
    """Drive one chat session: each turn's prompt is the full
    transcript (previous prompt + generated + the user's new tokens).
    With ``spill_each`` the session round-trips the host tier between
    every turn, auditing both tiers mid-park."""
    sess = mgr.open_session()
    outs = []
    p = list(first_prompt)
    for i in range(len(extras) + 1):
        if i:
            p = p + outs[-1] + list(extras[i - 1])
        (res,) = loop.run([DecodeRequest(prompt=list(p),
                                         max_new_tokens=max_new,
                                         session=sess)])
        assert res.error is None, res.error
        outs.append(res.tokens)
        if spill_each:
            assert mgr.spill(sess, wait=True), sess.state
            assert sess.state == "parked"
            rep = mgr.check_invariants()
            assert rep["ok"], rep
    return sess, outs


# -- (a) the headline parity matrix --------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("n_head", [2, 8])
@pytest.mark.parametrize("with_cache", [True, False])
def test_spill_resume_parity_matrix(dtype, n_head, with_cache):
    cfg = _cfg(n_head=n_head, d_model=8 * n_head)
    params = init_decode_params(cfg, seed=5)
    rng = np.random.RandomState(5)
    # three turns of 9, 13 and 17 prompt tokens, two decoded a turn: the
    # fp32 oracle compiles each sequence length it meets op by op, six
    # here (12 at four new tokens and extras of three), and every turn
    # still spills, resumes from the host and pins its cached pages
    ps, max_new = 4, 2
    prompt1 = rng.randint(1, cfg.vocab_size, size=9).tolist()
    extras = [rng.randint(1, cfg.vocab_size, size=2).tolist()
              for _ in range(2)]

    def run(spill_each):
        pool = _pool(cfg, num_pages=64, page_size=ps, dtype=dtype)
        cache = PrefixCache(pool) if with_cache else None
        mgr = TieredSessionManager(pool, prefix_cache=cache,
                                   host_bytes=1 << 26)
        loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=2,
                                      prefix_cache=cache,
                                      session_manager=mgr)
        sess, outs = _multi_turn(loop, mgr, prompt1, extras, max_new,
                                 spill_each=spill_each)
        if spill_each and with_cache:
            # the spill pinned the cached full-page prefix and shipped
            # only the unshared tail host-side
            assert sess.pinned_tokens > 0
        st = mgr.stats()
        mgr.close()
        if cache is not None:
            cache.clear()
        # zero pages leaked in EITHER tier
        assert pool.used_pages == 0, pool.used_pages
        assert pool.check_invariants()["ok"]
        assert len(mgr.tier) == 0
        return outs, st, loop

    outs_resident, st_res, _ = run(spill_each=False)
    outs_spilled, st_sp, loop_sp = run(spill_each=True)

    # token-identical to the never-spilled oracle, every turn
    assert outs_spilled == outs_resident
    assert st_sp["spills"] == 3 and st_sp["resumed_host"] == 2
    assert st_sp["re_prefills"] == 0
    assert st_res["spills"] == 0 and st_res["resumed_resident"] == 2
    assert loop_sp.session_resumes == 2
    assert loop_sp.session_resumed_tokens > 0
    if dtype == "float32":
        # fp32 also matches the full-recompute transcript oracle
        p = list(prompt1)
        for i, out in enumerate(outs_spilled):
            if i:
                p = p + outs_spilled[i - 1] + extras[i - 1]
            assert out == full_decode(params, cfg, p, max_new)[0]


# -- (b) combined-tier admission -----------------------------------------

def test_combined_tier_admits_more_sessions_than_hbm_fits():
    cfg = _cfg()
    params = init_decode_params(cfg, seed=7)
    rng = np.random.RandomState(7)
    ps, max_new = 4, 4
    # a retired turn retains 12 tokens (9 prompt + 3 appended) = 3
    # pages, so 12 pages = at most 4 resident sessions; we keep 6 open
    pool = _pool(cfg, num_pages=12, page_size=ps)
    mgr = TieredSessionManager(pool, host_bytes=1 << 26)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=1,
                                  session_manager=mgr)
    sessions = [mgr.open_session() for _ in range(6)]
    prompts = [rng.randint(1, cfg.vocab_size, size=9).tolist()
               for _ in range(6)]
    extras = [rng.randint(1, cfg.vocab_size, size=3).tolist()
              for _ in range(6)]

    transcripts = []
    for s, p in zip(sessions, prompts):
        (r,) = loop.run([DecodeRequest(prompt=list(p),
                                       max_new_tokens=max_new,
                                       session=s)])
        assert r.error is None, r.error
        assert r.tokens == full_decode(params, cfg, p, max_new)[0]
        transcripts.append(list(p) + r.tokens)
    st = mgr.stats()
    # all 6 sessions are retained although HBM only fits 4: admission
    # spilled idle victims through make_room
    assert st["sessions"] == 6
    assert st["spills"] >= 2 and st["parked_sessions"] >= 2
    retained = sum(len(t) for t in transcripts)
    assert retained > pool.num_pages * ps  # > no-tier session capacity

    # turn 2 on every session, oldest (certainly parked) first
    for s, t, ext in zip(sessions, transcripts, extras):
        p2 = t + list(ext)
        (r,) = loop.run([DecodeRequest(prompt=list(p2),
                                       max_new_tokens=max_new,
                                       session=s)])
        assert r.error is None, r.error
        assert r.tokens == full_decode(params, cfg, p2, max_new)[0]
    st = mgr.stats()
    assert st["resumes"] == 6 and st["resumed_host"] >= 1
    assert st["re_prefills"] == 0

    rep = mgr.check_invariants()
    assert rep["ok"], rep
    mgr.close()
    assert pool.used_pages == 0
    assert pool.check_invariants()["ok"]
    assert len(mgr.tier) == 0


# -- (c) victim policy ----------------------------------------------------

def _idle_sessions(mgr, loop, params, cfg, rng, n, max_new=3):
    sessions = []
    for _ in range(n):
        s = mgr.open_session()
        p = rng.randint(1, cfg.vocab_size, size=9).tolist()
        (r,) = loop.run([DecodeRequest(prompt=p, max_new_tokens=max_new,
                                       session=s)])
        assert r.error is None, r.error
        sessions.append(s)
    return sessions


def test_idle_victims_spill_lru_first():
    cfg = _cfg()
    params = init_decode_params(cfg, seed=3)
    rng = np.random.RandomState(3)
    pool = _pool(cfg, num_pages=32, page_size=4)
    mgr = TieredSessionManager(pool, host_bytes=1 << 26)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=1,
                                  session_manager=mgr)
    s0, s1, s2 = _idle_sessions(mgr, loop, params, cfg, rng, 3)
    s0.last_used, s1.last_used, s2.last_used = 0.0, 1.0, 2.0
    # one session's worth of pressure: only the LRU victim spills
    freed = mgr.make_room(3)
    assert freed >= 3
    assert s0.state == "parked"
    assert s1.state == "idle" and s2.state == "idle"
    mgr.close()
    assert pool.used_pages == 0 and len(mgr.tier) == 0


def test_bounded_host_tier_evicts_lru_parked():
    cfg = _cfg()
    params = init_decode_params(cfg, seed=4)
    rng = np.random.RandomState(4)

    # phase 1: measure one parked payload's size, unbounded
    pool = _pool(cfg, num_pages=32, page_size=4)
    mgr = TieredSessionManager(pool, host_bytes=1 << 26)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=1,
                                  session_manager=mgr)
    (s,) = _idle_sessions(mgr, loop, params, cfg,
                          np.random.RandomState(4), 1)
    assert mgr.spill(s, wait=True)
    one = s.parked_bytes
    assert one > 0
    mgr.close()

    # phase 2: a host tier that fits ONE payload; parking the second
    # LRU-evicts the first (its session resets, next turn re-prefills)
    pool = _pool(cfg, num_pages=32, page_size=4)
    mgr = TieredSessionManager(pool, host_bytes=int(1.5 * one))
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=1,
                                  session_manager=mgr)
    s0, s1 = _idle_sessions(mgr, loop, params, cfg, rng, 2)
    assert mgr.spill(s0, wait=True) and s0.state == "parked"
    assert mgr.spill(s1, wait=True) and s1.state == "parked"
    assert s0.state == "fresh"  # LRU-evicted to make room, not lost
    st = mgr.stats()
    assert st["evictions"] >= 1
    assert len(mgr.tier) == 1
    mgr.close()
    assert pool.used_pages == 0 and len(mgr.tier) == 0


def test_host_tier_park_raises_typed_when_unevictable():
    cfg = _cfg()
    pool = _pool(cfg, num_pages=8, page_size=4)
    pool.allocate(7)
    pool.append_tokens([7], [8])
    exp = pool.export_seq(7)
    tier = HostKVTier(capacity_bytes=max(1, exp.nbytes() - 1))
    with pytest.raises(HostTierFullError):
        tier.park("a", exp)
    assert len(tier) == 0 and tier.bytes_used == 0


# -- (d) pool pressure spills proactively --------------------------------

def test_pool_pressure_reclaimer_spills_idle_sessions():
    cfg = _cfg()
    params = init_decode_params(cfg, seed=9)
    rng = np.random.RandomState(9)
    pool = _pool(cfg, num_pages=12, page_size=4)
    mgr = TieredSessionManager(pool, host_bytes=1 << 26)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=1,
                                  session_manager=mgr)
    (s,) = _idle_sessions(mgr, loop, params, cfg, rng, 1)
    assert s.state == "idle"
    used = pool.used_pages
    # claim more pages than are free: append_tokens runs the
    # registered reclaimer mid-claim, which spills the idle session
    # INLINE (under the pool lock) instead of failing the claim
    pool.allocate(99)
    need_tokens = (pool.num_pages - used + 1) * pool.page_size
    pool.append_tokens([99], [need_tokens])
    assert s.state == "parked"
    assert mgr.stats()["pressure_spills"] >= 1
    pool.free_seq(99)
    rep = mgr.check_invariants()
    assert rep["ok"], rep
    mgr.close()
    assert pool.used_pages == 0 and len(mgr.tier) == 0


# -- (e) tier-aware audits mid-park --------------------------------------

def test_invariants_and_orphan_repair_mid_park():
    cfg = _cfg()
    params = init_decode_params(cfg, seed=6)
    rng = np.random.RandomState(6)
    ps = 4
    pool = _pool(cfg, num_pages=32, page_size=ps)
    cache = PrefixCache(pool)
    mgr = TieredSessionManager(pool, prefix_cache=cache,
                               host_bytes=1 << 26)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=1,
                                  prefix_cache=cache,
                                  session_manager=mgr)
    s = mgr.open_session()
    p = rng.randint(1, cfg.vocab_size, size=9).tolist()
    (r,) = loop.run([DecodeRequest(prompt=p, max_new_tokens=4,
                                   session=s)])
    assert r.error is None
    assert mgr.spill(s, wait=True) and s.state == "parked"
    assert s.pinned_pages, "prefix pages should stay pinned mid-park"

    # a parked session's pinned pages are OWNED, not orphaned: the
    # audit is green and the repair arm must not free them
    assert pool.check_invariants()["ok"]
    used_before = pool.used_pages
    assert pool.reclaim_orphans() == 0
    assert pool.used_pages == used_before
    rep = mgr.check_invariants()
    assert rep["ok"] and rep["pool"]["ok"] and rep["tier"]["ok"]

    # teeth: a flipped payload byte fails the HOST tier audit
    entry = next(iter(mgr.tier._entries.values()))
    entry.export.k = entry.export.k.copy()  # exports of jax pools are RO
    entry.export.k.reshape(-1).view(np.uint8)[0] ^= 0xFF
    rep = mgr.check_invariants()
    assert not rep["ok"] and not rep["tier"]["ok"]
    assert rep["tier"]["errors"]
    entry.export.k.reshape(-1).view(np.uint8)[0] ^= 0xFF  # restore
    assert mgr.check_invariants()["ok"]
    mgr.close()
    cache.clear()
    assert pool.used_pages == 0 and len(mgr.tier) == 0


# -- (f) int8 payloads round-trip the host tier byte-identical -----------

def test_int8_export_roundtrips_host_tier_with_scales():
    cfg = _cfg()
    pool = _pool(cfg, num_pages=8, page_size=4, dtype="int8")
    pool.allocate(7)
    pool.append_tokens([7], [10])
    rng = np.random.RandomState(0)
    import jax.numpy as jnp

    pool.k_pages = jnp.asarray(rng.randint(
        -128, 128, size=pool.k_pages.shape).astype(np.int8))
    pool.v_pages = jnp.asarray(rng.randint(
        -128, 128, size=pool.v_pages.shape).astype(np.int8))
    pool.k_scales[:] = rng.rand(*pool.k_scales.shape)
    pool.v_scales[:] = rng.rand(*pool.v_scales.shape)
    exp = pool.export_seq(7)
    tier = HostKVTier(capacity_bytes=1 << 24)
    tier.park("s", exp)
    assert tier.check_invariants()["ok"]
    back = tier.fetch("s")
    assert back.k.tobytes() == exp.k.tobytes()
    assert back.v.tobytes() == exp.v.tobytes()
    assert back.k_scales.tobytes() == exp.k_scales.tobytes()
    assert back.v_scales.tobytes() == exp.v_scales.tobytes()
    assert len(tier) == 0 and tier.bytes_used == 0


# -- (g) history mismatch degrades typed ---------------------------------

def test_history_mismatch_resets_instead_of_resuming_wrong_kv():
    cfg = _cfg()
    params = init_decode_params(cfg, seed=8)
    pool = _pool(cfg, num_pages=32, page_size=4)
    mgr = TieredSessionManager(pool, host_bytes=1 << 26)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=1,
                                  session_manager=mgr)

    # parked arm: a next-turn prompt unrelated to the parked history
    # discards the payload and prefills fresh — still correct
    s = mgr.open_session()
    p1 = [5, 1, 2, 3, 4, 5, 6, 7, 8]
    (r,) = loop.run([DecodeRequest(prompt=list(p1), max_new_tokens=3,
                                   session=s)])
    assert r.error is None
    assert mgr.spill(s, wait=True)
    p_other = [7, 9, 11, 13, 15, 17, 19]
    (r,) = loop.run([DecodeRequest(prompt=list(p_other),
                                   max_new_tokens=3, session=s)])
    assert r.error is None
    assert r.tokens == full_decode(params, cfg, p_other, 3)[0]
    st = mgr.stats()
    assert st["mismatch_resets"] >= 1 and st["evictions"] >= 1

    # resident arm: a first-token divergence against resident KV
    # resets too (common prefix 0 — nothing worth keeping)
    p_other2 = [11, 2, 4, 6, 8, 10, 12, 14]
    (r,) = loop.run([DecodeRequest(prompt=list(p_other2),
                                   max_new_tokens=3, session=s)])
    assert r.error is None
    assert r.tokens == full_decode(params, cfg, p_other2, 3)[0]
    assert mgr.stats()["mismatch_resets"] >= 2
    mgr.close()
    assert pool.used_pages == 0 and len(mgr.tier) == 0


# -- (h) observability is gated ------------------------------------------

def _tiered_turns():
    cfg = _cfg()
    params = init_decode_params(cfg, seed=2)
    rng = np.random.RandomState(2)
    pool = _pool(cfg, num_pages=32, page_size=4)
    mgr = TieredSessionManager(pool, host_bytes=1 << 26)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=1,
                                  session_manager=mgr)
    p1 = rng.randint(1, cfg.vocab_size, size=9).tolist()
    _, outs = _multi_turn(loop, mgr, p1,
                          [rng.randint(1, cfg.vocab_size,
                                       size=3).tolist()],
                          3, spill_each=True)
    mgr.close()
    assert pool.used_pages == 0


def test_tier_metrics_disabled_path_mints_nothing():
    obs.reset()
    try:
        _tiered_turns()  # FLAGS_observability defaults off
        names = {m.name for m in obs.default_registry().metrics()}
        assert not any("kvtier" in n or "host_tier" in n
                       for n in names), names
    finally:
        obs.reset()


def test_tier_metrics_enabled_records_events_and_gauges():
    fluid.set_flags({"FLAGS_observability": True})
    obs.reset()
    try:
        _tiered_turns()
        reg = obs.default_registry()
        ev = reg.counter("paddle_tpu_serving_kvtier_events", "")
        assert ev.value(event="spill") == 2
        assert ev.value(event="resume_host") == 1
        tx = reg.counter("paddle_tpu_serving_kvtier_transfer_bytes", "")
        assert tx.value(direction="spill") > 0
        assert tx.value(direction="resume") > 0
        names = {m.name for m in reg.metrics()}
        assert "paddle_tpu_serving_host_tier_bytes" in names
        assert "paddle_tpu_serving_parked_sessions" in names
    finally:
        obs.reset()
        fluid.set_flags({"FLAGS_observability": False})
