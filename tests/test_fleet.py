"""Disaggregated prefill/decode fleet (paddle_tpu/serving/fleet/):
KV handoff, elastic autoscaling, rolling upgrades, chaos degradation.

Acceptance criteria pinned here (ISSUE 15):
(a) disaggregated prefill→handoff→decode output is TOKEN-IDENTICAL to
    the monolithic ContinuousBatchingLoop oracle across the
    H_kv∈{8,2} × {fp32,int8} × prefix-cache hit/miss matrix, with zero
    leaked pages and check_invariants green on BOTH pools;
(b) prefix-cache composition ships only the unshared tail (the
    destination re-attaches shared pages from its own cache, pinned by
    a transfer reservation);
(c) the autoscaler scales each class between min/max on queue/shed
    signals read from heartbeat payloads (in-process AND over the
    RemoteMaster RPC plane), with scale decisions visible in flight
    events;
(d) replica kill mid-traffic and a rolling weight upgrade both finish
    with lost_requests=0 (failover / zero-loss drain handoff);
(e) ghost leases are fixed: ReplicaDirectory.deregister (wired into
    Router.remove_replica and Fleet.remove_replica) stops a removed
    replica from haunting every later expired() poll;
(f) Router routing tables survive a concurrent submit-vs-membership
    storm with no request lost, misrouted, or double-dispatched.
"""

import os
import threading
import time

import numpy as np
import pytest

from paddle_tpu import flags as pflags
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.elastic.master import InMemStore, MasterService
from paddle_tpu.elastic.rpc import RemoteMaster, serve_master
from paddle_tpu.resilience import faultinject
from paddle_tpu.serving import (
    ContinuousBatchingLoop,
    DecodeConfig,
    DecodeRequest,
    Engine,
    EngineConfig,
    KVCachePool,
)
from paddle_tpu.serving.distributed import (
    ReplicaDirectory,
    ReplicaUnavailableError,
    Router,
)
from paddle_tpu.serving.fleet import (
    AutoscalePolicy,
    DecodeReplica,
    Fleet,
    FleetController,
    FleetReplica,
    PrefillReplica,
    ReplicaKilledError,
)


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_head=4, n_layer=2,
                d_inner=64, max_length=48)
    base.update(kw)
    return DecodeConfig(**base)


def _mk_fleet(params, cfg, n_prefill=1, n_decode=1, dtype="float32",
              pages=64, page_size=4, max_batch=4, directory=None,
              prefix_cache=True, beat_every_s=0.05, **fleet_kw):
    return Fleet(
        lambda n: PrefillReplica(
            n, params, cfg, num_pages=pages, page_size=page_size,
            dtype=dtype, max_batch=max_batch,
            prefix_cache=prefix_cache, beat_every_s=beat_every_s),
        lambda n: DecodeReplica(
            n, params, cfg, num_pages=pages, page_size=page_size,
            dtype=dtype, max_batch=max_batch,
            prefix_cache=prefix_cache, beat_every_s=beat_every_s),
        n_prefill=n_prefill, n_decode=n_decode, directory=directory,
        **fleet_kw)


# ---------------------------------------------------------------------------
# export_seq / import_seq: the KV handoff substrate


def _write_random(pool, seq_id, tokens, seed=0):
    rng = np.random.RandomState(seed)
    pages, slots = pool.append_tokens([seq_id], [tokens])
    for li in range(pool.num_layers):
        pool.write_kv(
            li, pages, slots,
            rng.rand(tokens, pool.num_kv_heads,
                     pool.head_dim).astype(np.float32),
            rng.rand(tokens, pool.num_kv_heads,
                     pool.head_dim).astype(np.float32))


def _gathered(pool, seq_id):
    tables, lengths = pool.page_table_batch([seq_id])
    return (np.asarray(pool.k_pages[:, :, tables[0]]),
            np.asarray(pool.v_pages[:, :, tables[0]]), int(lengths[0]))


def test_export_import_roundtrip_fp32():
    a = KVCachePool(16, 4, 2, 4, 8, name="src")
    b = KVCachePool(16, 4, 2, 4, 8, name="dst")
    a.allocate(0)
    _write_random(a, 0, 10)
    ex = a.export_seq(0)
    assert ex.length == 10 and ex.skip_tokens == 0
    assert ex.k.shape == (2, 4, 3, 4, 8)
    assert ex.nbytes() == 2 * ex.k.nbytes
    b.allocate(7)
    pages, tokens = b.import_seq(ex, 7)
    assert (pages, tokens) == (3, 10)
    ka, va, la = _gathered(a, 0)
    kb, vb, lb = _gathered(b, 7)
    assert la == lb == 10
    np.testing.assert_array_equal(ka, kb)
    np.testing.assert_array_equal(va, vb)
    # export leaves the source untouched; both pools audit green
    assert a.check_invariants()["ok"] and b.check_invariants()["ok"]
    assert a.stats()["seqs_exported"] == 1
    assert b.stats()["seqs_imported"] == 1
    a.free_seq(0)
    b.free_seq(7)
    assert a.used_pages == 0 and b.used_pages == 0


def test_export_import_int8_scales_travel():
    a = KVCachePool(16, 4, 2, 4, 8, dtype="int8", name="src8")
    b = KVCachePool(16, 4, 2, 4, 8, dtype="int8", name="dst8")
    a.allocate(0)
    _write_random(a, 0, 9, seed=3)
    ex = a.export_seq(0)
    assert ex.k_scales is not None and ex.k_scales.shape == (2, 3)
    b.allocate(1)
    b.import_seq(ex, 1)
    ka, va, _ = _gathered(a, 0)
    kb, vb, _ = _gathered(b, 1)
    np.testing.assert_array_equal(ka, kb)  # int8 content verbatim
    ta, _ = a.page_table_batch([0])
    tb, _ = b.page_table_batch([1])
    np.testing.assert_array_equal(a.k_scales[:, ta[0]],
                                  b.k_scales[:, tb[0]])
    # the freed-pages-carry-no-scale / live-pages-have-scales audit
    assert b.check_invariants()["scale_errors"] == []
    assert b.check_invariants()["ok"]
    a.free_seq(0)
    b.free_seq(1)
    assert b.check_invariants()["ok"]


def test_export_import_validation_and_atomicity():
    a = KVCachePool(16, 4, 2, 4, 8)
    a.allocate(0)
    _write_random(a, 0, 10)
    with pytest.raises(ValueError, match="page boundary|multiple"):
        a.export_seq(0, skip_tokens=3)  # not page-aligned
    with pytest.raises(ValueError, match="multiple|page boundary"):
        a.export_seq(0, skip_tokens=12)  # >= length
    ex = a.export_seq(0)
    # geometry mismatches are loud
    wrong = KVCachePool(16, 8, 2, 4, 8)
    wrong.allocate(0)
    with pytest.raises(ValueError, match="page_size"):
        wrong.import_seq(ex, 0)
    wrong_dtype = KVCachePool(16, 4, 2, 4, 8, dtype="int8")
    wrong_dtype.allocate(0)
    with pytest.raises(ValueError, match="dtype"):
        wrong_dtype.import_seq(ex, 0)
    # the destination must hold exactly the skipped prefix
    b = KVCachePool(16, 4, 2, 4, 8)
    b.allocate(5)
    b.append_tokens([5], [2])
    with pytest.raises(ValueError, match="re-attach"):
        b.import_seq(ex, 5)
    # exhaustion raises BEFORE any table mutates (atomic claim)
    tiny = KVCachePool(2, 4, 2, 4, 8)
    tiny.allocate(9)
    from paddle_tpu.serving import PagePoolExhausted

    with pytest.raises(PagePoolExhausted):
        tiny.import_seq(ex, 9)
    assert tiny.length(9) == 0 and tiny.used_pages == 0
    assert tiny.check_invariants()["ok"]


def test_export_skip_tokens_ships_only_tail():
    a = KVCachePool(16, 4, 2, 4, 8)
    a.allocate(0)
    _write_random(a, 0, 10)
    full = a.export_seq(0)
    tail = a.export_seq(0, skip_tokens=8)
    assert tail.skip_tokens == 8 and tail.k.shape[2] == 1
    assert tail.nbytes() < full.nbytes()
    np.testing.assert_array_equal(tail.k, full.k[:, :, 2:])


# ---------------------------------------------------------------------------
# (a) disaggregated output == monolithic oracle, across the matrix


@pytest.mark.parametrize("n_kv_head", [8, 2])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
@pytest.mark.parametrize("prefix", ["hit", "miss"])
def test_disagg_token_identical_to_monolithic(n_kv_head, dtype, prefix):
    cfg = _cfg(n_head=8, n_kv_head=n_kv_head, n_layer=1)
    params = serving.init_decode_params(cfg, seed=11)
    rng = np.random.RandomState(11)
    if prefix == "hit":
        shared = rng.randint(1, cfg.vocab_size, size=13).tolist()
        prompts = [shared + rng.randint(1, cfg.vocab_size,
                                        size=3).tolist()
                   for _ in range(4)]
    else:
        prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
                   for n in (5, 9, 4, 7)]

    def reqs():
        return [DecodeRequest(prompt=list(p), max_new_tokens=5)
                for p in prompts]

    # monolithic oracle, SAME submission discipline (first request
    # warms its prefix cache, the rest hit)
    mpool = KVCachePool(64, 4, cfg.n_layer, cfg.n_head, cfg.head_dim,
                        num_kv_heads=cfg.num_kv_heads, dtype=dtype)
    mcache = serving.PrefixCache(mpool)
    mono = ContinuousBatchingLoop(params, cfg, mpool, max_batch=4,
                                  prefix_cache=mcache)
    want = mono.run(reqs()[:1]) + mono.run(reqs()[1:])

    fleet = _mk_fleet(params, cfg, dtype=dtype)
    try:
        r = reqs()
        first = fleet.submit(r[0]).result(120)
        rest = [f.result(120) for f in
                [fleet.submit(q) for q in r[1:]]]
        got = [first] + rest
        for w, g in zip(want, got):
            assert g.error is None
            assert g.tokens == w.tokens
        st = fleet.stats()
        assert st["handoffs"] == 4 and st["lost_requests"] == 0
        if prefix == "hit":
            # both sides actually shared: the oracle hit its cache and
            # the handoffs shipped only the unshared tail
            assert mono.prefix_hits >= 1
            assert st["skipped_tokens"] > 0
        audit = fleet.audit()
        assert audit["pages_leaked"] == 0 and audit["invariants_ok"]
    finally:
        fleet.close()
    mcache.clear()
    assert mpool.used_pages == 0 and mpool.check_invariants()["ok"]


def test_handoff_prefix_reuse_shrinks_payload():
    cfg = _cfg()
    params = serving.init_decode_params(cfg, seed=2)
    rng = np.random.RandomState(2)
    shared = rng.randint(1, cfg.vocab_size, size=12).tolist()
    fleet = _mk_fleet(params, cfg)
    try:
        sizes = []
        orig = Fleet._dispatch_decode

        def spy(self, hd, *a, **kw):
            sizes.append((hd.payload.skip_tokens, hd.nbytes()))
            return orig(self, hd, *a, **kw)

        Fleet._dispatch_decode = spy
        try:
            for k in range(3):
                tail = rng.randint(1, cfg.vocab_size, size=3).tolist()
                fleet.infer(DecodeRequest(prompt=shared + tail,
                                          max_new_tokens=4),
                            timeout=120)
        finally:
            Fleet._dispatch_decode = orig
        # first handoff ships everything; later ones skip the shared
        # full pages and ship strictly less
        assert sizes[0][0] == 0
        assert sizes[1][0] >= 8 and sizes[2][0] >= 8
        assert sizes[1][1] < sizes[0][1]
        audit = fleet.audit()
        assert audit["pages_leaked"] == 0 and audit["invariants_ok"]
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# chaos: quarantine-not-crash degradation


def test_prefill_quarantine_not_crash():
    cfg = _cfg()
    params = serving.init_decode_params(cfg, seed=5)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in (4, 6, 5)]
    fleet = _mk_fleet(params, cfg)
    os.environ["FAULT_SERVE_NAN_SEQ"] = "0@0"  # first prefill step
    try:
        futs = [fleet.submit(DecodeRequest(prompt=list(p),
                                           max_new_tokens=4))
                for p in prompts]
        results = [f.result(120) for f in futs]
    finally:
        os.environ.pop("FAULT_SERVE_NAN_SEQ", None)
        faultinject.reset()
    errs = [r for r in results if r.error is not None]
    assert len(errs) == 1
    assert isinstance(errs[0].error, serving.NonFiniteSequenceError)
    ok = [r for r in results if r.error is None]
    assert all(len(r.tokens) == 4 for r in ok)
    pre = fleet.replicas("prefill")["prefill0"]
    assert pre.alive and pre.quarantined == 1
    st = fleet.stats()
    assert st["lost_requests"] == 0
    audit = fleet.audit()
    assert audit["pages_leaked"] == 0 and audit["invariants_ok"]
    fleet.close()


def test_replica_kill_failover_zero_lost():
    cfg = _cfg()
    params = serving.init_decode_params(cfg, seed=7)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, size=4 + n % 4).tolist()
               for n in range(10)]
    fleet = _mk_fleet(params, cfg, n_decode=2)
    ctl = FleetController(fleet, min_replicas={"decode": 2})
    os.environ["FAULT_SERVE_REPLICA_KILL"] = "decode0"
    try:
        futs = [fleet.submit(DecodeRequest(prompt=list(p),
                                           max_new_tokens=4))
                for p in prompts]
        results = [f.result(120) for f in futs]
        assert all(r.error is None for r in results)
        # the victim is dead; the controller quarantines and replaces
        deadline = time.perf_counter() + 5.0
        while fleet.replicas("decode")["decode0"].alive \
                and time.perf_counter() < deadline:
            time.sleep(0.02)
        assert not fleet.replicas("decode")["decode0"].alive
        ctl.step()
        st = fleet.stats()
        assert st["lost_requests"] == 0
        assert st["replica_deaths"] == 1
        assert "decode2" in fleet.replicas("decode")  # replacement
        assert any(d["action"] == "replica_dead"
                   for d in ctl.decisions)
    finally:
        os.environ.pop("FAULT_SERVE_REPLICA_KILL", None)
        faultinject.reset()
        fleet.close()


def test_handoff_drop_requeues_zero_lost():
    cfg = _cfg()
    params = serving.init_decode_params(cfg, seed=9)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in (5, 7, 4)]
    want = [serving.full_decode(params, cfg, p, 4)[0] for p in prompts]
    fleet = _mk_fleet(params, cfg, prefix_cache=False)
    os.environ["FAULT_SERVE_HANDOFF_DROP"] = "1"
    try:
        futs = [fleet.submit(DecodeRequest(prompt=list(p),
                                           max_new_tokens=4))
                for p in prompts]
        results = [f.result(120) for f in futs]
        for w, g in zip(want, results):
            assert g.error is None and g.tokens == w
        st = fleet.stats()
        assert st["handoff_drops"] == 1
        assert st["re_prefills"] == 1
        assert st["lost_requests"] == 0
    finally:
        os.environ.pop("FAULT_SERVE_HANDOFF_DROP", None)
        faultinject.reset()
        fleet.close()


def test_engine_replica_kill_goes_broken_without_restart():
    """The Engine-level arm of FAULT_SERVE_REPLICA_KILL (serve_bench
    --chaos --replicas): the dispatcher dies WITHOUT supervisor
    restart, queued futures fail typed, health goes BROKEN."""

    class _Slow:
        feed_names = ["x"]
        fetch_names = ["y"]
        meta: dict = {}

        def __call__(self, feed):
            time.sleep(0.05)
            return [np.asarray(feed["x"]) * 2.0]

    eng = Engine(_Slow(), config=EngineConfig(
        buckets=(1,), max_wait_s=0.0), name="victim")
    try:
        eng.infer({"x": np.ones((1, 2), np.float32)})
        os.environ["FAULT_SERVE_REPLICA_KILL"] = "victim"
        futs = [eng.submit({"x": np.ones((1, 2), np.float32)})
                for _ in range(4)]
        failed = 0
        for f in futs:
            try:
                f.result(timeout=10)
            except Exception:
                failed += 1
        assert failed >= 1  # queued requests failed typed, none hang
        deadline = time.perf_counter() + 5.0
        while eng.health()["state"] != "BROKEN" \
                and time.perf_counter() < deadline:
            time.sleep(0.02)
        assert eng.health()["state"] == "BROKEN"
        st = eng.stats()
        assert st["replica_killed"] is True
        assert st["dispatcher_restarts"] == 0
        # a dead engine must REJECT new submits typed, not strand them
        # in a queue nothing drains (the router falls over on this even
        # when its cached health snapshot predates the kill)
        with pytest.raises(serving.EngineClosedError):
            eng.submit({"x": np.ones((1, 2), np.float32)})
    finally:
        os.environ.pop("FAULT_SERVE_REPLICA_KILL", None)
        faultinject.reset()


# ---------------------------------------------------------------------------
# (c) autoscaler: policy units + e2e with flight events


class _StubFleet:
    directory = None
    name = "stub"

    def replicas(self, role=None):
        return {}


def _sig(replicas=1, queue=0, shed=0):
    return {"replicas": replicas, "queue_depth": queue, "shed": shed,
            "dead": []}


def test_autoscale_policy_units():
    ctl = FleetController(
        _StubFleet(),
        policy=AutoscalePolicy(queue_high=4, sustain=2, idle_sustain=3,
                               cooldown=1),
        min_replicas={"decode": 1}, max_replicas={"decode": 3})
    # queue pressure must SUSTAIN before scale-up
    assert ctl._decide("decode", _sig(queue=10)) is None  # streak 1
    assert ctl._decide("decode", _sig(queue=10)) == "scale_up"
    # cooldown holds the very next step even under pressure
    assert ctl._decide("decode", _sig(replicas=2, queue=20)) is None
    # shed delta alone is pressure (queue empty): streak reaches 2
    assert ctl._decide("decode", _sig(replicas=2,
                                      shed=3)) == "scale_up"
    # cooldown again, then the MAX clamp: pressured at max never
    # scales up
    assert ctl._decide("decode", _sig(replicas=3, queue=99,
                                      shed=3)) is None  # cooldown
    assert ctl._decide("decode", _sig(replicas=3, queue=99,
                                      shed=3)) is None  # at max
    assert ctl._decide("decode", _sig(replicas=3, queue=99,
                                      shed=3)) is None  # still at max
    # idleness must sustain before scale-down (queue 0, no new shed)
    assert ctl._decide("decode", _sig(replicas=3, shed=3)) is None
    assert ctl._decide("decode", _sig(replicas=3, shed=3)) is None
    assert ctl._decide("decode", _sig(replicas=3,
                                      shed=3)) == "scale_down"
    # min clamp: idle at min never scales down
    ctl2 = FleetController(
        _StubFleet(),
        policy=AutoscalePolicy(idle_sustain=1, cooldown=0))
    for _ in range(4):
        assert ctl2._decide("decode", _sig(replicas=1)) is None


def test_controller_scale_up_down_e2e_flight_events():
    pflags.set_flags({"FLAGS_observability": True})
    obs.reset()
    cfg = _cfg()
    params = serving.init_decode_params(cfg, seed=13)
    rng = np.random.RandomState(13)
    fleet = _mk_fleet(params, cfg)
    ctl = FleetController(
        fleet,
        policy=AutoscalePolicy(queue_high=2, sustain=2, idle_sustain=2,
                               cooldown=0),
        max_replicas={"prefill": 2, "decode": 2})
    try:
        futs = [fleet.submit(DecodeRequest(
            prompt=rng.randint(1, cfg.vocab_size, size=5).tolist(),
            max_new_tokens=4)) for _ in range(10)]
        # burst: back-to-back steps see the sustained queue
        ctl.step()
        ctl.step()
        assert fleet.stats()["scale_ups"] >= 1
        [f.result(120) for f in futs]
        for _ in range(3):
            ctl.step()
        st = fleet.stats()
        assert st["scale_downs"] >= 1
        assert st["lost_requests"] == 0
        kinds = [e["kind"] for e in obs.default_flight().events()]
        assert "scale_up" in kinds and "scale_down" in kinds
        assert "handoff" in kinds
    finally:
        fleet.close()
        pflags.set_flags({"FLAGS_observability": False})
        obs.reset()


# ---------------------------------------------------------------------------
# (d) rolling upgrade under live traffic


def test_rolling_upgrade_zero_lost_and_new_params_serve():
    """The prefill steps run op by op, and on the CPU every new (rows,
    tokens) shape of a co-admitted group costs ~1.5 s of small compiles
    (measured by PR 46: draining the only prefill replica took 12 s with
    four prompt lengths and groups of up to four, past the fleet's 10 s
    placement budget, so the traffic that waited out the drain failed).
    The fleet is right to make it wait; the test keeps the shapes few
    (one prompt length, groups of at most two), warms them, bounds what
    its client has outstanding, and gives the placement wait the 120 s
    its other waits have."""
    cfg = _cfg()
    p_old = serving.init_decode_params(cfg, seed=1)
    p_new = serving.init_decode_params(cfg, seed=2)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, cfg.vocab_size, size=4).tolist()
               for _ in range(4)]
    fleet = _mk_fleet(p_old, cfg, n_decode=2, max_batch=2,
                      place_timeout_s=120.0)
    ctl = FleetController(fleet, min_replicas={"decode": 2})
    try:
        # warm the step shapes so drains are fast: uncached, then from
        # the prefix cache, alone and in pairs
        for _ in range(2):
            [f.result(120) for f in
             [fleet.submit(DecodeRequest(prompt=list(p), max_new_tokens=4))
              for p in prompts]]
            for p in prompts[:2]:
                fleet.infer(DecodeRequest(prompt=list(p), max_new_tokens=4),
                            timeout=120)
        stop = threading.Event()
        futs, lock = [], threading.Lock()
        # a client with four requests outstanding: an open loop at 50 a
        # second outruns the CPU's op-by-op steps, and every drain (and
        # the test) then lasts as long as the backlog
        window = threading.Semaphore(4)

        def traffic():
            i = 0
            while not stop.is_set():
                if not window.acquire(timeout=0.05):
                    continue
                f = fleet.submit(DecodeRequest(
                    prompt=list(prompts[i % len(prompts)]),
                    max_new_tokens=4))
                f.add_done_callback(lambda _: window.release())
                with lock:
                    futs.append(f)
                i += 1
                time.sleep(0.02)

        t = threading.Thread(target=traffic)
        t.start()
        time.sleep(0.1)
        upgraded = ctl.rolling_upgrade(p_new, timeout=60.0)
        stop.set()
        t.join()
        assert upgraded == ["prefill0", "decode0", "decode1"]
        results = [f.result(120) for f in futs]
        assert all(r.error is None for r in results)
        st = fleet.stats()
        # zero lost, zero duplicated: every submit resolved exactly
        # once and nothing failed
        assert st["lost_requests"] == 0 and st["failed"] == 0
        assert st["upgrades"] == 3
        # the upgraded fleet serves the NEW weights
        want, _ = serving.full_decode(p_new, cfg, prompts[0], 4)
        got = fleet.infer(DecodeRequest(prompt=list(prompts[0]),
                                        max_new_tokens=4), timeout=120)
        assert got.tokens == want
        audit = fleet.audit()
        assert audit["pages_leaked"] == 0 and audit["invariants_ok"]
    finally:
        fleet.close()


def test_a_request_waits_out_the_drain_of_the_only_prefill_replica():
    """The order a rolling upgrade leans on: while the only prefill
    replica is drained nothing is placeable, and a request that arrives
    then is neither failed nor failed over: it waits inside the placement
    budget and is served, with the right tokens, once the replica is
    resumed."""
    cfg = _cfg()
    params = serving.init_decode_params(cfg, seed=1)
    prompt = np.random.RandomState(1).randint(
        1, cfg.vocab_size, size=4).tolist()
    fleet = _mk_fleet(params, cfg, place_timeout_s=120.0)
    try:
        assert fleet.drain_replica("prefill0", timeout=60.0)
        fut = fleet.submit(DecodeRequest(prompt=list(prompt),
                                         max_new_tokens=4))
        assert not fut.done() and fleet.stats()["failed"] == 0
        fleet.resume_replica("prefill0")
        got = fut.result(120)
        want, _ = serving.full_decode(params, cfg, prompt, 4)
        assert got.error is None and got.tokens == want
        st = fleet.stats()
        assert st["failovers"] == 0 and st["re_prefills"] == 0
        assert st["lost_requests"] == 0 and st["failed"] == 0
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# heartbeat payloads + controller signals over the RPC plane


def test_heartbeat_payloads_and_signals_over_remote_master():
    master = MasterService(InMemStore(), timeout_dur=60.0)
    server = serve_master(master)
    remote = RemoteMaster(server.endpoint)
    directory = ReplicaDirectory(remote, max_silence_s=2.0)
    cfg = _cfg()
    params = serving.init_decode_params(cfg, seed=3)
    fleet = _mk_fleet(params, cfg, n_decode=2, directory=directory)
    ctl = FleetController(fleet)
    try:
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline:
            st = directory.status()
            if set(st) == {"prefill0", "decode0", "decode1"} and all(
                    v["payload"] for v in st.values()):
                break
            time.sleep(0.05)
        st = directory.status()
        assert set(st) == {"prefill0", "decode0", "decode1"}
        assert st["decode0"]["payload"]["role"] == "decode"
        assert st["prefill0"]["payload"]["state"] == "SERVING"
        assert "queue_depth" in st["decode1"]["payload"]
        # the controller reads the SAME signals through the RPC plane
        sigs = ctl.signals()
        assert sigs["decode"]["replicas"] == 2
        assert sigs["prefill"]["replicas"] == 1
        # deregistration over RPC: no ghost lease after removal
        fleet.drain_replica("decode1", timeout=30)
        fleet.remove_replica("decode1")
        assert "decode1" not in directory.status()
        time.sleep(0.1)
        assert "decode1" not in directory.expired()
    finally:
        fleet.close()
        remote.shutdown_server()


# ---------------------------------------------------------------------------
# (e) ghost leases: deregister on removal


def test_replica_directory_deregister_fixes_ghost_lease():
    master = MasterService(InMemStore(), timeout_dur=60.0)
    directory = ReplicaDirectory(master, max_silence_s=0.1)
    directory.register("gone")
    directory.register("alive")
    time.sleep(0.15)
    directory.beat("alive")
    # without deregistration the silent replica haunts every poll
    assert "gone" in directory.expired()
    directory.deregister("gone")
    assert "gone" not in directory.expired()
    assert "gone" not in directory.status()
    time.sleep(0.15)
    assert directory.expired() == ["alive"]  # real expiry still works


def test_router_remove_replica_deregisters_lease():
    class _Noop:
        feed_names = ["x"]
        fetch_names = ["y"]
        meta: dict = {}

        def __call__(self, feed):
            return [np.asarray(feed["x"])]

    master = MasterService(InMemStore(), timeout_dur=60.0)
    directory = ReplicaDirectory(master, max_silence_s=0.1)
    e0 = Engine(_Noop(), config=EngineConfig(buckets=(1,)), name="r0")
    e1 = Engine(_Noop(), config=EngineConfig(buckets=(1,)), name="r1")
    router = Router([e0, e1], directory=directory,
                    health_cache_s=0.0)
    router.drain_replica("r0", timeout=10)
    router.remove_replica("r0")
    time.sleep(0.15)
    directory.beat("r1")
    # the REGRESSION: before deregister-on-removal, r0 reported
    # lease-expired in every later poll forever
    assert "r0" not in directory.expired()
    router.close()
    e0.close()


def test_prefill_batch_failure_frees_pages_and_replica_recovers():
    """A mid-group prefill raise (pool exhausted under pressure) must
    fail the batch's futures typed and free every allocated sequence —
    leaked pages would shrink the pool forever and wedge swap_params."""
    cfg = _cfg()
    params = serving.init_decode_params(cfg, seed=0)
    rep = PrefillReplica("p0", params, cfg, num_pages=8, page_size=4,
                         prefix_cache=False)
    try:
        # eat most of the pool so the head request passes submit's
        # whole-pool check but cannot claim its pages at process time
        rep.pool.allocate(999)
        rep.pool.append_tokens([999], [24])  # 6 of 8 pages
        req = DecodeRequest(prompt=list(range(1, 17)),
                            max_new_tokens=2)  # needs 4 pages, 2 free
        with pytest.raises(Exception) as ei:
            rep.submit(req).result(timeout=30)
        assert "pool" in str(ei.value).lower()
        # the REGRESSION: the failed group's sequence stayed allocated
        assert rep.pool.used_pages == 6  # only the blocker remains
        rep.pool.free_seq(999)
        assert rep.pool.used_pages == 0
        assert rep.pool.check_invariants()["ok"]
        # and the replica still serves: same request now prefills fine
        hd = rep.submit(req).result(timeout=30)
        assert hd.payload.length == 16
        assert rep.pool.used_pages == 0  # exported then freed
    finally:
        rep.close(timeout=10)


def test_quarantine_silences_flapping_replica_and_fails_over_queue():
    """Quarantining an ALIVE-but-flapping replica (lease lapsed while
    its worker lives on) must stop its heartbeats for good — a
    quarantined worker that kept beating re-registered the ghost lease
    the controller just deregistered, was counted live forever with
    routing off, and the class never got its replacement."""

    class _Slow(FleetReplica):
        role = "decode"

        def _process(self, batch):
            time.sleep(0.2)
            for item, fut in batch:
                fut.set_result(item)

    master = MasterService(InMemStore(), timeout_dur=60.0)
    directory = ReplicaDirectory(master, max_silence_s=10.0)
    rep = _Slow("flappy", max_batch=1, beat_every_s=0.01)
    rep.join_directory(directory)
    f1 = rep._submit_item("a")
    f2 = rep._submit_item("b")
    time.sleep(0.05)  # worker is mid-batch on "a"; "b" still queued
    rep.quarantine()
    directory.deregister("flappy")
    # queued work fails over typed; the in-flight batch still resolves
    with pytest.raises(ReplicaKilledError):
        f2.result(timeout=5)
    assert f1.result(timeout=5) == "a"
    assert not rep.alive and not rep.routing
    rep._thread.join(5.0)
    assert not rep._thread.is_alive()
    # the REGRESSION: no post-quarantine beat resurrected the lease
    time.sleep(0.1)
    assert "flappy" not in directory.status()
    assert "flappy" not in directory.expired()


# ---------------------------------------------------------------------------
# (f) routing-table races: submit vs drain/remove/add storm


def test_router_membership_storm_no_lost_misrouted_or_doubled():
    class _Echo:
        feed_names = ["x"]
        fetch_names = ["y"]
        meta: dict = {}

        def __call__(self, feed):
            time.sleep(0.001)
            return [np.asarray(feed["x"]) * 2.0]

    def _mk(name):
        return Engine(_Echo(), config=EngineConfig(
            buckets=(1, 2, 4), max_wait_s=0.001, queue_depth=512),
            name=name)

    router = Router([_mk("churn0"), _mk("stable")])
    n = 120
    feeds = [np.full((1, 4), i, np.float32) for i in range(n)]
    results: dict = {}
    lock = threading.Lock()
    errors: list = []
    stop_churn = threading.Event()

    def submitter(lo, hi):
        for i in range(lo, hi):
            for _ in range(200):
                try:
                    out = router.submit({"x": feeds[i]}).result(30)
                    break
                except ReplicaUnavailableError:
                    time.sleep(0.002)  # membership mid-swap
            else:
                errors.append(f"request {i} never placed")
                continue
            with lock:
                if i in results:
                    errors.append(f"request {i} resolved twice")
                results[i] = out[0]

    def churner():
        gen = 0
        while not stop_churn.is_set():
            name = f"churn{gen}"
            try:
                # zero-loss removal discipline: drain fully first
                router.drain_replica(name, timeout=10)
                old = router.remove_replica(name)
                old.close()
                gen += 1
                router.add_replica(_mk(f"churn{gen}"))
            except KeyError:
                break
            time.sleep(0.005)

    threads = [threading.Thread(target=submitter,
                                args=(k * 30, (k + 1) * 30))
               for k in range(4)]
    ct = threading.Thread(target=churner)
    [t.start() for t in threads]
    ct.start()
    [t.join(60) for t in threads]
    stop_churn.set()
    ct.join(30)
    assert not errors, errors
    # no lost: every request resolved; no misrouted/cross-wired: each
    # got ITS OWN payload back exactly
    assert len(results) == n
    for i in range(n):
        np.testing.assert_array_equal(results[i], feeds[i] * 2.0)
    st = router.stats()
    # counters consistent after the storm: the surviving members'
    # routed counts are sane and nothing negative/corrupt
    assert st["routed"] >= 1
    assert all(v["routed"] >= 0 and v["skipped"] >= 0
               for v in st["replicas"].values())
    assert "stable" in st["replicas"]
    router.close()


# ---------------------------------------------------------------------------
# serve_bench wiring: --disagg / --fleet / --chaos --replicas


def test_serve_bench_disagg_gate_roundtrip(tmp_path, capsys):
    import json

    from tools.serve_bench import main as bench_main

    bank = tmp_path / "bank.json"
    bank.write_text(json.dumps({
        "lost_requests": 0, "pages_leaked": 0, "invariants_ok": 1,
        "handoff_drops": 0,
    }))
    out_json = tmp_path / "out.json"
    rc = bench_main([
        "--mode", "decode", "--disagg", "--sequences", "5",
        "--max-new", "5", "--pages", "64", "--page-size", "4",
        "--d-model", "32", "--max-len", "48", "--json", str(out_json),
        "--baseline", str(bank), "--gate",
    ])
    capsys.readouterr()
    assert rc == 0
    result = json.loads(out_json.read_text())
    assert result["mode"] == "disagg"
    assert result["handoffs"] == 5
    assert result["handoff_bytes_per_seq"] > 0
    assert result["lost_requests"] == 0
    assert result["pages_leaked"] == 0
    assert result["ttft_p50_ms"] is not None


def test_serve_bench_disagg_gate_teeth_on_handoff_drop(tmp_path,
                                                       capsys):
    """The fleet gate's teeth: an armed FAULT_SERVE_HANDOFF_DROP is
    absorbed (lost_requests still 0) but the banked handoff_drops=0
    regresses — the gate must exit 3."""
    import json

    from tools.serve_bench import main as bench_main

    bank = tmp_path / "bank.json"
    bank.write_text(json.dumps({"lost_requests": 0,
                                "handoff_drops": 0}))
    os.environ["FAULT_SERVE_HANDOFF_DROP"] = "1"
    try:
        rc = bench_main([
            "--mode", "decode", "--disagg", "--sequences", "4",
            "--max-new", "4", "--pages", "64", "--page-size", "4",
            "--d-model", "32", "--max-len", "48",
            "--baseline", str(bank), "--gate",
        ])
    finally:
        os.environ.pop("FAULT_SERVE_HANDOFF_DROP", None)
        faultinject.reset()
    capsys.readouterr()
    assert rc == 3


def test_serve_bench_fleet_elastic_smoke(tmp_path, capsys):
    import json

    from tools.serve_bench import main as bench_main

    out_json = tmp_path / "out.json"
    rc = bench_main([
        "--mode", "decode", "--fleet", "--sequences", "8",
        "--max-new", "5", "--pages", "64", "--page-size", "4",
        "--d-model", "32", "--max-len", "48", "--json", str(out_json),
    ])
    capsys.readouterr()
    assert rc == 0
    result = json.loads(out_json.read_text())
    assert result["mode"] == "fleet"
    assert result["scale_ups"] >= 1
    assert result["scale_downs"] >= 1
    assert result["lost_requests"] == 0
    assert result["invariants_ok"] == 1


def test_serve_bench_chaos_replicas_failover(tmp_path, capsys):
    import json

    from tools.serve_bench import main as bench_main

    bank = tmp_path / "bank.json"
    bank.write_text(json.dumps({"lost_requests": 0,
                                "replica_kills": 1}))
    out_json = tmp_path / "out.json"
    rc = bench_main([
        "--replicas", "2", "--model", "tiny", "--requests", "18",
        "--rate", "400", "--no-warmup", "--chaos",
        "--json", str(out_json), "--baseline", str(bank), "--gate",
    ])
    capsys.readouterr()
    assert rc == 0
    result = json.loads(out_json.read_text())
    assert result["killed_replica"] == "replica1"
    assert result["replica_kills"] == 1
    assert result["lost_requests"] == 0


def test_serve_bench_fleet_usage_errors(capsys):
    from tools.serve_bench import main as bench_main

    # --disagg/--fleet need decode mode and exclude mesh/spec/chaos
    assert bench_main(["--disagg"]) == 2
    assert bench_main(["--fleet"]) == 2
    assert bench_main(["--mode", "decode", "--disagg",
                       "--mesh", "4"]) == 2
    assert bench_main(["--mode", "decode", "--fleet",
                       "--chaos"]) == 2
    assert bench_main(["--mode", "decode", "--disagg",
                       "--sampling", "temp"]) == 2
    capsys.readouterr()
