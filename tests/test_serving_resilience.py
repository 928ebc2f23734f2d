"""Serving-tier fault isolation (ISSUE 6): dispatcher supervision,
circuit breaker, per-sequence quarantine, KV-pool integrity watchdog,
deadline-aware shedding, and the FAULT_SERVE_* chaos suite.

Acceptance pinned here:
(a) a dispatch raise fails ONLY that batch's futures (typed
    EngineInternalError naming the cause) while the dispatcher survives:
    the chaos run's pass count is the fault-free count minus the
    poisoned batch;
(b) a dispatcher thread that dies outside the protected region is
    restarted by the supervisor with the queue preserved;
(c) breaker_threshold consecutive internal errors open the circuit
    breaker (submit fails fast with EngineUnhealthyError) until a
    cool-down probe succeeds;
(d) FAULT_SERVE_NAN_SEQ evicts exactly the poisoned sequence
    (NonFiniteSequenceError, pages freed) while survivors stay
    token-identical to the full_decode oracle — and the per-step finite
    check is ONE fused jit call per step, never per sequence;
(e) any exception out of a prefill/decode step frees the stepping
    sequences' pages before propagating (zero net page delta);
(f) FAULT_SERVE_LEAK_PAGES is detected by check_invariants() and
    repaired by reclaim_orphans() via the loop's check_every watchdog;
(g) a queue saturated with slow requests sheds a tight-deadline submit
    immediately (no queue wait) and accepts it again once drained;
(h) close() surfaces a dispatcher that outlived its join as
    stats()["close_timed_out"] instead of returning silently.
"""

import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.resilience import faultinject
from paddle_tpu.serving import (
    ContinuousBatchingLoop,
    DecodeConfig,
    DecodeRequest,
    Engine,
    EngineConfig,
    EngineInternalError,
    EngineUnhealthyError,
    KVCachePool,
    NonFiniteSequenceError,
    RequestTimeoutError,
    full_decode,
    init_decode_params,
)

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _disarm_faults():
    """Every test starts and ends with no armed serving faults."""
    faultinject.reset()
    yield
    for k in ("FAULT_SERVE_DISPATCH_RAISE", "FAULT_SERVE_NAN_SEQ",
              "FAULT_SERVE_LEAK_PAGES", "FAULT_SERVE_SLOW_STEP_MS",
              "FAULT_SERVE_PREFIX_CORRUPT", "FAULT_SERVE_SPILL_CORRUPT",
              "FAULT_SERVE_SPILL_DROP"):
        os.environ.pop(k, None)
    faultinject.reset()


def _wait_until(pred, timeout=5.0):
    t0 = time.perf_counter()
    while not pred():
        if time.perf_counter() - t0 > timeout:
            raise AssertionError("condition not reached in time")
        time.sleep(0.005)


class _EchoBackend:
    """Fast backend: y = 2x, optional per-call delay/failure toggle."""

    feed_names = ["x"]
    fetch_names = ["y"]
    meta: dict = {}

    def __init__(self, delay_s: float = 0.0):
        self.delay_s = delay_s
        self.fail = False
        self.calls = 0

    def __call__(self, feed):
        self.calls += 1
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.fail:
            raise RuntimeError("backend exploded")
        return [np.asarray(feed["x"]) * 2.0]


class _GatedBackend:
    """Backend whose dispatch blocks until released."""

    feed_names = ["x"]
    fetch_names = ["y"]
    meta: dict = {}

    def __init__(self):
        self.gate = threading.Event()
        self.calls = 0

    def __call__(self, feed):
        self.calls += 1
        assert self.gate.wait(10.0), "test gate never released"
        return [np.asarray(feed["x"]) * 2.0]


def _feed(v=1.0, rows=1):
    return {"x": np.full((rows, 2), v, np.float32)}


# -- (a) dispatch raise: batch-level blast radius -----------------------

def test_dispatch_raise_fails_only_poisoned_batch():
    def run_workload():
        eng = Engine(_EchoBackend(),
                     config=EngineConfig(buckets=(1,), max_wait_s=0.0))
        futs = [eng.submit(_feed(i)) for i in range(8)]
        passed, errors = 0, []
        for f in futs:
            try:
                f.result(timeout=10)
                passed += 1
            except Exception as e:  # noqa: BLE001
                errors.append(e)
        stats = eng.stats()
        alive = eng._thread.is_alive()
        eng.close()
        return passed, errors, stats, alive

    fault_free, errors, _, _ = run_workload()
    assert fault_free == 8 and not errors

    faultinject.reset()
    os.environ["FAULT_SERVE_DISPATCH_RAISE"] = "1"
    passed, errors, stats, alive = run_workload()
    # pass count == fault-free minus ONLY the poisoned batch (1-bucket
    # ladder: one batch = one request)
    assert passed == fault_free - 1
    assert len(errors) == 1
    assert isinstance(errors[0], EngineInternalError)
    assert "dispatch raise" in str(errors[0])  # names the cause
    assert isinstance(errors[0].cause, RuntimeError)
    assert stats["internal_errors"] == 1
    assert stats["breaker_trips"] == 0  # one error: below the threshold
    assert alive  # the dispatcher survived the poisoned batch


# -- (b) dispatcher thread death: supervisor restart --------------------

def test_dispatcher_death_restarts_with_queue_preserved():
    os.environ["FAULT_SERVE_DISPATCH_RAISE"] = "thread"
    eng = Engine(_EchoBackend(),
                 config=EngineConfig(buckets=(1,), max_wait_s=0.0))
    futs = [eng.submit(_feed(i)) for i in range(4)]
    # the thread died at some cycle boundary; the supervisor restarted
    # it and every queued request still completes (generous timeout: it
    # only guards against deadlock, and a loaded CI box can starve the
    # restarted dispatcher for seconds)
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(
            f.result(timeout=30)[0], np.full((1, 2), 2.0 * i, np.float32))
    stats = eng.stats()
    assert stats["dispatcher_restarts"] == 1
    assert eng._thread.is_alive()
    assert eng.health()["dispatcher_alive"]
    eng.close()


def test_a_submit_inside_the_supervisors_restart_restarts_nothing(monkeypatch):
    """One dead dispatcher is one restart, whoever else looks while the
    supervisor replaces it: a submit() is driven from inside the new
    thread's own start(), the moment at which the engine once published a
    thread that had yet to start (is_alive() false) outside its lock, so
    that submit() spawned a second dispatcher and counted a second
    restart.  No sleep: where the restart holds the engine's lock nothing
    can look, and the late submit is made after it."""
    eng = Engine(_EchoBackend(),
                 config=EngineConfig(buckets=(1,), max_wait_s=0.0))
    real_start = threading.Thread.start
    seen, late = [], []

    def start(thread):
        supervisor = threading.current_thread().name == f"serving-{eng.name}"
        if supervisor and not seen:
            seen.append(eng._lock.locked())
            if not seen[0]:  # nothing keeps a submit() out: make one
                late.append(eng.submit(_feed(9)))
        real_start(thread)

    monkeypatch.setattr(threading.Thread, "start", start)
    os.environ["FAULT_SERVE_DISPATCH_RAISE"] = "thread"
    futs = [eng.submit(_feed(i)) for i in range(3)]
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(
            f.result(timeout=30)[0], np.full((1, 2), 2.0 * i, np.float32))
    late.append(eng.submit(_feed(9)))
    for f in late:
        np.testing.assert_array_equal(
            f.result(timeout=30)[0], np.full((1, 2), 18.0, np.float32))
    assert seen == [True]  # the supervisor ran, and restarted under the lock
    assert eng.stats()["dispatcher_restarts"] == 1
    assert sum(t.name == f"serving-{eng.name}" and t.is_alive()
               for t in threading.enumerate()) == 1
    eng.close()


def test_a_dispatcher_gone_without_its_supervisor_is_restarted_by_submit(
        monkeypatch):
    """The other finder of a dead dispatcher: the thread is gone and no
    supervisor ran (here: the hook is stubbed out), so the next submit()
    restarts it, once, counts it, and the request is served; a second
    submit() finds the new thread alive and restarts nothing."""
    eng = Engine(_EchoBackend(),
                 config=EngineConfig(buckets=(1,), max_wait_s=0.0))
    monkeypatch.setattr(Engine, "_on_dispatcher_death",
                        lambda self, exc: None)
    os.environ["FAULT_SERVE_DISPATCH_RAISE"] = "thread"
    dead = eng._thread
    with eng._cond:
        eng._cond.notify_all()  # the parked dispatcher cycles, and dies
    dead.join(timeout=30)
    assert not dead.is_alive()
    assert eng.stats()["dispatcher_restarts"] == 0
    for i in (3, 4):
        np.testing.assert_array_equal(
            eng.submit(_feed(i)).result(timeout=30)[0],
            np.full((1, 2), 2.0 * i, np.float32))
        assert eng.stats()["dispatcher_restarts"] == 1
    assert eng._thread is not dead and eng._thread.is_alive()
    eng.close()


# -- (c) circuit breaker ------------------------------------------------

def test_circuit_breaker_trips_fast_fails_and_recovers():
    backend = _EchoBackend()
    backend.fail = True
    eng = Engine(backend, config=EngineConfig(
        buckets=(1,), max_wait_s=0.0,
        breaker_threshold=2, breaker_cooldown_s=0.25))
    f1 = eng.submit(_feed())
    f2 = eng.submit(_feed())
    for f in (f1, f2):
        with pytest.raises(EngineInternalError, match="exploded"):
            f.result(timeout=10)
    # 2 consecutive failures == threshold: the breaker is OPEN
    h = eng.health()
    assert h["state"] == "BROKEN"
    assert h["breaker"]["state"] == "open"
    assert h["breaker"]["last_error"] and "exploded" in h["breaker"]["last_error"]
    with pytest.raises(EngineUnhealthyError, match="breaker"):
        eng.submit(_feed())
    # cool-down: half-open, a probe is admitted; a healthy backend
    # closes the breaker
    time.sleep(0.3)
    assert eng.health()["breaker"]["state"] == "half_open"
    backend.fail = False
    out = eng.infer(_feed(3.0), timeout=None)
    np.testing.assert_array_equal(out[0], np.full((1, 2), 6.0, np.float32))
    h = eng.health()
    assert h["state"] == "SERVING"
    assert h["breaker"]["state"] == "closed"
    assert h["breaker"]["consecutive_errors"] == 0
    assert eng.stats()["breaker_trips"] == 1
    assert h["last_dispatch_age_s"] is not None
    eng.close()


def test_breaker_reprobe_failure_retrips():
    backend = _EchoBackend()
    backend.fail = True
    eng = Engine(backend, config=EngineConfig(
        buckets=(1,), max_wait_s=0.0,
        breaker_threshold=1, breaker_cooldown_s=0.2))
    with pytest.raises(EngineInternalError):
        eng.infer(_feed())
    with pytest.raises(EngineUnhealthyError):
        eng.submit(_feed())
    time.sleep(0.25)  # half-open; the probe fails -> re-trip
    with pytest.raises(EngineInternalError):
        eng.infer(_feed())
    with pytest.raises(EngineUnhealthyError):
        eng.submit(_feed())
    assert eng.stats()["breaker_trips"] == 2
    eng.close()


# -- health() -----------------------------------------------------------

def test_health_states_and_snapshot():
    backend = _GatedBackend()
    eng = Engine(backend, config=EngineConfig(
        buckets=(1,), max_wait_s=0.0, queue_depth=5))
    h = eng.health()
    assert h["state"] == "SERVING"
    assert h["queue_depth"] == 0 and h["queue_capacity"] == 5
    assert h["dispatcher_alive"] and not h["close_timed_out"]
    assert h["pool"] is None
    # saturate the queue to >= 80%: DEGRADED (still admitting)
    eng.submit(_feed())
    _wait_until(lambda: backend.calls == 1)  # in-flight, queue empty
    for _ in range(4):
        eng.submit(_feed())
    assert eng.health()["state"] == "DEGRADED"
    backend.gate.set()
    assert eng.drain(timeout=10.0)
    assert eng.health()["state"] == "DRAINING"
    eng.close()

    # a pool attached for utilization reporting
    pool = KVCachePool(num_pages=4, page_size=2, num_layers=1,
                       num_heads=1, head_dim=4)
    pool.allocate(0)
    pool.append_token([0])
    eng2 = Engine(_EchoBackend(), config=EngineConfig(buckets=(1,)))
    eng2.attach_pool(pool)
    assert eng2.health()["pool"]["used_pages"] == 1
    assert eng2.health()["pool"]["utilization"] == 0.25
    eng2.close()


def test_health_exported_through_observability_gauges():
    from paddle_tpu import observability as obs

    obs.reset()
    fluid.set_flags({"FLAGS_observability": True})
    try:
        eng = Engine(_EchoBackend(), config=EngineConfig(buckets=(1,)))
        eng.infer(_feed())
        assert eng.health()["state"] == "SERVING"
        eng.close()
        snap = obs.default_registry().snapshot()["metrics"]
        by_name = {m["name"]: m for m in snap}
        assert "paddle_tpu_serving_health_state" in by_name
        assert by_name["paddle_tpu_serving_health_state"]["series"][0][
            "value"] == 0  # SERVING
        assert "paddle_tpu_serving_breaker_open" in by_name
    finally:
        fluid.set_flags({"FLAGS_observability": False})
        obs.reset()


# -- (g) deadline-aware shedding (satellite) ----------------------------

def test_deadline_shedding_rejects_immediately_then_readmits():
    backend = _EchoBackend(delay_s=0.05)
    eng = Engine(backend, config=EngineConfig(buckets=(1,), max_wait_s=0.0))
    eng.infer(_feed())  # warm: one observed batch latency (~50ms)
    # saturate: 6 slow requests ahead -> ~0.3s of queued work
    futs = [eng.submit(_feed()) for _ in range(6)]
    t0 = time.perf_counter()
    with pytest.raises(RequestTimeoutError, match="shed"):
        eng.submit(_feed(), timeout=0.01)
    elapsed = time.perf_counter() - t0
    assert elapsed < 0.04, f"shed decision took {elapsed:.3f}s (queue wait?)"
    assert eng.stats()["shed"] == 1
    for f in futs:
        f.result(timeout=30)
    # drained: the same tight-ish deadline is admitted again
    _wait_until(lambda: eng.queue_depth() == 0)
    out = eng.infer(_feed(5.0), timeout=5.0)
    np.testing.assert_array_equal(out[0], np.full((1, 2), 10.0, np.float32))
    assert eng.stats()["shed"] == 1  # no new shed
    eng.close()


def test_no_shedding_without_deadline_or_evidence():
    backend = _EchoBackend(delay_s=0.02)
    eng = Engine(backend, config=EngineConfig(buckets=(1,), max_wait_s=0.0))
    # no latency observed yet: even a tight deadline is admitted (it
    # may expire in queue, but it is never shed on a guess)
    f = eng.submit(_feed(), timeout=5.0)
    f.result(timeout=10)
    # deadline-less requests are never shed no matter the queue
    futs = [eng.submit(_feed()) for _ in range(5)]
    for f in futs:
        f.result(timeout=30)
    assert eng.stats()["shed"] == 0
    eng.close()


# -- (h) close timeout surfaces (satellite) -----------------------------

def test_close_timed_out_flag(monkeypatch):
    monkeypatch.setattr(Engine, "_JOIN_TIMEOUT_S", 0.2)
    backend = _GatedBackend()  # never released before close
    eng = Engine(backend, config=EngineConfig(buckets=(1,), max_wait_s=0.0))
    f = eng.submit(_feed())
    _wait_until(lambda: backend.calls == 1)
    eng.close(timeout=0.05)  # drain cannot finish: backend is stuck
    assert eng.stats()["close_timed_out"] is True
    assert eng.health()["close_timed_out"] is True
    backend.gate.set()  # release: the stuck batch still completes
    np.testing.assert_array_equal(
        f.result(timeout=5.0)[0], np.full((1, 2), 2.0, np.float32))
    eng._thread.join(timeout=5.0)
    assert not eng._thread.is_alive()


# -- decode: per-sequence quarantine ------------------------------------

def _decode_setup(seed=7, n_layer=2):
    cfg = DecodeConfig(vocab_size=41, d_model=16, n_head=2,
                       n_layer=n_layer, d_inner=32, max_length=32)
    params = init_decode_params(cfg, seed=seed)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in (4, 2, 3)]
    pool = KVCachePool(num_pages=24, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim)
    return cfg, params, prompts, pool


def test_nan_seq_quarantine_evicts_one_survivors_match_oracle():
    cfg, params, prompts, pool = _decode_setup()
    oracles = [full_decode(params, cfg, p, 4)[0] for p in prompts]
    os.environ["FAULT_SERVE_NAN_SEQ"] = "1@1"  # seq 1, first decode step
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3)
    results = loop.run([DecodeRequest(p, 4) for p in prompts])
    assert loop.quarantined == 1
    assert isinstance(results[1].error, NonFiniteSequenceError)
    assert results[1].error.seq_id == 1 and results[1].error.step == 1
    # survivors are token-identical to the per-sequence oracle
    for i in (0, 2):
        assert results[i].error is None
        assert results[i].tokens == oracles[i]
    # the evicted sequence's pages returned to the pool
    assert pool.free_pages == pool.num_pages
    assert pool.check_invariants()["ok"]


def test_prefix_corrupt_quarantined_evicted_batchmates_survive():
    """FAULT_SERVE_PREFIX_CORRUPT (ISSUE 11): a cached prefix page goes
    bad at reuse — the sequence served the poisoned prefix quarantines
    (NonFiniteSequenceError), the poisoned chain is INVALIDATED so it
    can never be served again, batch-mates decode on oracle-identical,
    and a later same-prefix request re-prefills clean."""
    from paddle_tpu.serving import PrefixCache

    cfg = DecodeConfig(vocab_size=41, d_model=16, n_head=2, n_layer=2,
                       d_inner=32, max_length=48)
    params = init_decode_params(cfg, seed=21)
    rng = np.random.RandomState(21)
    shared = rng.randint(1, cfg.vocab_size, size=12).tolist()
    owner = shared + rng.randint(1, cfg.vocab_size, size=2).tolist()
    victim = shared + rng.randint(1, cfg.vocab_size, size=3).tolist()
    # bystander: 5 prompt + 3 new = exactly 2 pages, all claimed at its
    # prefill — it never allocates after the quarantine frees pages
    bystander = rng.randint(1, cfg.vocab_size, size=5).tolist()
    pool = KVCachePool(num_pages=48, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim)
    cache = PrefixCache(pool)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=2,
                                  prefix_cache=cache, check_every=1)
    # warm the cache
    r0 = loop.run([DecodeRequest(owner, 3)])
    assert r0[0].error is None
    # arm: the victim's attach poisons the first matched page
    os.environ["FAULT_SERVE_PREFIX_CORRUPT"] = "1"
    res = loop.run([DecodeRequest(victim, 3),
                    DecodeRequest(bystander, 3)])
    assert loop.quarantined == 1
    assert isinstance(res[0].error, NonFiniteSequenceError)
    want_b, _ = full_decode(params, cfg, bystander, 3)
    assert res[1].error is None and res[1].tokens == want_b
    # the poisoned chain was evicted from the cache...
    assert cache.stats()["invalidations"] >= 1
    # ...so a fresh same-prefix request MISSES and re-prefills clean,
    # matching the oracle (the corruption is gone, not resident)
    hits_before = loop.prefix_hits
    res3 = loop.run([DecodeRequest(list(victim), 3)])
    assert loop.prefix_hits == hits_before
    want_v, _ = full_decode(params, cfg, victim, 3)
    assert res3[0].error is None and res3[0].tokens == want_v
    # zero leaked pages, refcount invariants green
    cache.clear()
    assert pool.used_pages == 0
    assert pool.check_invariants()["ok"]
    assert loop.invariant_violations == 0


def test_nan_at_prefill_quarantines_only_offender():
    cfg, params, prompts, pool = _decode_setup(seed=3)
    oracles = [full_decode(params, cfg, p, 3)[0] for p in prompts]
    os.environ["FAULT_SERVE_NAN_SEQ"] = "0@0"  # seq 0 at the prefill pass
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3)
    results = loop.run([DecodeRequest(p, 3) for p in prompts])
    assert loop.quarantined == 1
    assert isinstance(results[0].error, NonFiniteSequenceError)
    assert results[0].tokens == []  # evicted before its first token
    for i in (1, 2):
        assert results[i].error is None
        assert results[i].tokens == oracles[i]
    assert pool.free_pages == pool.num_pages


def test_finite_check_is_one_fused_call_per_step():
    """The quarantine scan must be ONE batched rows_finite call per loop
    step ([B, V] in, [B] bool out) — never a per-sequence check.  The
    scan lives in the shared prefill scheduler (prefill_sched) since the
    fleet's prefill replica runs the same blast radius."""
    import paddle_tpu.serving.prefill_sched as psched

    cfg, params, prompts, pool = _decode_setup(seed=5)
    calls = []
    real = psched.rows_finite

    def counting(x):
        calls.append(np.asarray(x).shape)
        return real(x)

    psched.rows_finite, orig = counting, psched.rows_finite
    try:
        loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3)
        loop.run([DecodeRequest(p, 3) for p in prompts])
    finally:
        psched.rows_finite = orig
    assert len(calls) == loop.steps  # exactly one scan per step
    assert all(len(s) == 2 and s[1] == cfg.vocab_size for s in calls), \
        "scan must see the whole [B, V] logits batch at once"


# -- decode: exception-safe page release (satellite) --------------------

def test_decode_step_exception_frees_pages_before_propagating():
    import paddle_tpu.serving.generate as gen

    cfg, params, prompts, pool = _decode_setup(seed=11)
    real = gen.decode_step
    calls = [0]

    def flaky(*a, **k):
        calls[0] += 1
        if calls[0] == 2:
            raise RuntimeError("decode step exploded")
        return real(*a, **k)

    gen.decode_step, orig = flaky, gen.decode_step
    try:
        loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3)
        with pytest.raises(RuntimeError, match="decode step exploded"):
            loop.run([DecodeRequest(p, 4) for p in prompts])
    finally:
        gen.decode_step = orig
    # zero net page delta: everything claimed before the raise was freed
    assert pool.used_pages == 0
    assert pool.check_invariants()["ok"]


def test_mid_prefill_raise_zero_net_page_delta(monkeypatch):
    """The acknowledged hazard: a raise inside the admission/prefill
    window (pages already claimed by append_tokens) must free them."""
    cfg, params, prompts, pool = _decode_setup(seed=13)
    real = pool.write_kv
    calls = [0]

    def flaky(layer, pages, slots, k, v):
        calls[0] += 1
        if calls[0] == 2:  # layer 1 of the first prefill pass
            raise RuntimeError("mid-prefill write failed")
        return real(layer, pages, slots, k, v)

    monkeypatch.setattr(pool, "write_kv", flaky)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3)
    assert pool.used_pages == 0
    with pytest.raises(RuntimeError, match="mid-prefill"):
        loop.run([DecodeRequest(p, 4) for p in prompts])
    assert pool.used_pages == 0  # zero net delta
    assert pool.check_invariants()["ok"]


# -- KV-pool integrity watchdog -----------------------------------------

def test_check_invariants_clean_and_orphan_detection():
    pool = KVCachePool(num_pages=6, page_size=2, num_layers=1,
                       num_heads=1, head_dim=4)
    assert pool.check_invariants()["ok"]
    pool.allocate(0)
    pool.append_token([0])
    assert pool.check_invariants()["ok"]
    # orphan a page: not free, owned by nobody
    leaked = pool._free.pop()
    rep = pool.check_invariants()
    assert not rep["ok"]
    assert rep["orphaned_pages"] == [leaked]
    assert pool.reclaim_orphans() == 1
    assert pool.check_invariants()["ok"]
    assert pool.stats()["orphans_reclaimed"] == 1
    # reclaim is idempotent
    assert pool.reclaim_orphans() == 0
    pool.free_seq(0)
    assert pool.free_pages == pool.num_pages


def test_check_invariants_detects_double_owned_and_mismatch():
    pool = KVCachePool(num_pages=6, page_size=2, num_layers=1,
                       num_heads=1, head_dim=4)
    pool.allocate(0)
    pool.allocate(1)
    pool.append_token([0])
    pool.append_token([1])
    shared = pool._tables[0].pages[0]
    pool._tables[1].pages.append(shared)  # corruption: two owners
    rep = pool.check_invariants()
    assert not rep["ok"]
    assert shared in rep["double_owned_pages"]
    assert 1 in rep["length_mismatches"]  # seq 1: a whole spare page
    pool._tables[1].pages.pop()
    pool._tables[0].length = 99  # length beyond capacity
    rep = pool.check_invariants()
    assert 0 in rep["length_mismatches"]


def test_leak_pages_detected_and_repaired_by_watchdog():
    cfg, params, prompts, pool = _decode_setup(seed=17)
    oracles = [full_decode(params, cfg, p, 4)[0] for p in prompts]
    os.environ["FAULT_SERVE_LEAK_PAGES"] = "2"
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3,
                                  check_every=1)
    results = loop.run([DecodeRequest(p, 4) for p in prompts])
    assert loop.invariant_violations == 1
    assert loop.reclaimed_pages == 2
    # the leak cost nothing: all sequences completed, oracle-identical,
    # and the run ends with a clean pool and zero orphans
    for r, want in zip(results, oracles):
        assert r.error is None and r.tokens == want
    rep = pool.check_invariants()
    assert rep["ok"] and rep["orphaned_pages"] == []
    assert pool.used_pages == 0
    assert pool.stats()["orphans_reclaimed"] == 2


def test_watchdog_off_by_default_leak_stays_visible():
    cfg, params, prompts, pool = _decode_setup(seed=19)
    os.environ["FAULT_SERVE_LEAK_PAGES"] = "2"
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3)
    loop.run([DecodeRequest(p, 3) for p in prompts])
    # no watchdog: the leak persists and check_invariants names it
    rep = pool.check_invariants()
    assert not rep["ok"] and len(rep["orphaned_pages"]) == 2
    assert pool.used_pages == 2  # the leak, visible in accounting
    assert pool.reclaim_orphans() == 2
    assert pool.used_pages == 0


# -- observability wiring ----------------------------------------------

def test_fault_isolation_metrics_emitted_when_enabled():
    from paddle_tpu import observability as obs

    obs.reset()
    fluid.set_flags({"FLAGS_observability": True})
    try:
        backend = _EchoBackend()
        backend.fail = True
        eng = Engine(backend, config=EngineConfig(
            buckets=(1,), max_wait_s=0.0,
            breaker_threshold=1, breaker_cooldown_s=5.0))
        with pytest.raises(EngineInternalError):
            eng.infer(_feed())
        with pytest.raises(EngineUnhealthyError):
            eng.submit(_feed())
        eng.health()
        eng.close()

        cfg, params, prompts, pool = _decode_setup(seed=23)
        os.environ["FAULT_SERVE_NAN_SEQ"] = "1@1"
        os.environ["FAULT_SERVE_LEAK_PAGES"] = "1"
        ContinuousBatchingLoop(params, cfg, pool, max_batch=3,
                               check_every=1).run(
            [DecodeRequest(p, 3) for p in prompts])

        snap = obs.default_registry().snapshot()["metrics"]
        by_name = {m["name"]: m for m in snap}
        assert "paddle_tpu_serving_breaker_trips" in by_name
        assert "paddle_tpu_serving_health_state" in by_name
        assert "paddle_tpu_serving_pool_orphans_reclaimed" in by_name
        outcomes = {s["labels"].get("outcome")
                    for s in by_name["paddle_tpu_serving_requests"]["series"]}
        assert "rejected_breaker_open" in outcomes
        events = {s["labels"].get("event")
                  for s in by_name["paddle_tpu_serving_sequences"]["series"]}
        assert "quarantined" in events
    finally:
        fluid.set_flags({"FLAGS_observability": False})
        obs.reset()


# -- serve_bench --chaos ------------------------------------------------

def test_serve_bench_chaos_decode_gate(tmp_path, capsys):
    import json

    from tools.serve_bench import main as bench_main

    out = tmp_path / "chaos.json"
    rc = bench_main([
        "--mode", "decode", "--chaos", "--sequences", "5", "--max-new", "4",
        "--d-model", "16", "--vocab", "31", "--max-len", "32",
        "--pages", "32", "--page-size", "4", "--json", str(out),
    ])
    assert rc == 0
    result = json.loads(out.read_text())
    assert result["quarantined"] == 1
    assert result["reclaimed_pages"] == 2
    assert result["pages_leaked"] == 0
    assert result["invariants_ok"] == 1
    # the CI contract: chaos runs gate on zero leaked pages
    bank = tmp_path / "bank.json"
    bank.write_text(json.dumps({"pages_leaked": 0, "invariants_ok": 1}))
    rc = bench_main([
        "--mode", "decode", "--chaos", "--sequences", "5", "--max-new", "4",
        "--d-model", "16", "--vocab", "31", "--max-len", "32",
        "--pages", "32", "--page-size", "4",
        "--baseline", str(bank), "--gate",
    ])
    assert rc == 0
    capsys.readouterr()


def test_serve_bench_chaos_engine_smoke(tmp_path, capsys):
    import json

    from paddle_tpu import observability as obs
    from tools.serve_bench import main as bench_main

    rc = bench_main([
        "--model", "tiny", "--requests", "18", "--rate", "400",
        "--buckets", "1,2", "--batch-range", "1,2", "--chaos",
        "--obs-dir", str(tmp_path / "obs"),
    ])
    assert rc == 0
    result = json.loads(capsys.readouterr().out)
    # breaker_threshold consecutive batches were poisoned — enough to
    # TRIP the breaker (ISSUE 8: the flight recorder's dump trigger)
    assert result["internal_errors"] == 3
    assert result["breaker_trips"] == 1
    assert 3 <= result["poisoned_requests"] <= 6
    assert result["recovered_requests"] >= 1
    assert (result["recovered_requests"] + result["poisoned_requests"]
            + result["timeout_requests"] + result["shed_requests"]
            + result["breaker_rejected_requests"]
            == result["requests"])
    assert result["dispatcher_restarts"] == 0
    # the induced trip left a black box, and it holds the transition
    assert result["flight_dumps"] >= 1
    dump = result["artifacts"]["flight_dumps"][0]
    with open(dump) as f:
        events = [json.loads(ln) for ln in f][1:]
    assert "breaker_open" in {e["kind"] for e in events}
    # banking {"flight_dumps": 1} gates future chaos runs on the
    # artifact existing (same 0/2/3 contract as pages_leaked)
    bank = tmp_path / "bank.json"
    bank.write_text(json.dumps({"flight_dumps": 1,
                                "dispatcher_restarts": 0}))
    rc = bench_main([
        "--model", "tiny", "--requests", "18", "--rate", "400",
        "--buckets", "1,2", "--batch-range", "1,2", "--chaos",
        "--baseline", str(bank), "--gate",
    ])
    capsys.readouterr()
    assert rc == 0
    # serve_bench restored the observability flag it flipped on
    assert not obs.enabled()
    obs.reset()


# -- host KV tier chaos (ISSUE 18) ---------------------------------------

def _tiered_two_turns(fault=None, arm_before_turn=None):
    """One session, two turns, spilled to host between them.  `fault`
    is armed before turn `arm_before_turn` (1 = before the spill's
    park, 2 = before the resume's fetch).  Returns (outputs, oracle
    outputs, manager) with the manager already closed and leak-audited."""
    from paddle_tpu.serving import TieredSessionManager

    cfg = DecodeConfig(vocab_size=61, d_model=16, n_head=2, n_layer=2,
                       d_inner=32, max_length=64)
    params = init_decode_params(cfg, seed=12)
    pool = KVCachePool(num_pages=32, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim)
    mgr = TieredSessionManager(pool, host_bytes=1 << 26)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=1,
                                  session_manager=mgr)
    s = mgr.open_session()
    p1 = [5, 1, 2, 3, 4, 5, 6, 7, 8]
    outs, want = [], []
    for turn, extra in enumerate(([], [9, 10, 11]), start=1):
        if fault and arm_before_turn == turn:
            os.environ[fault] = "1"
            faultinject.reset()
        p = p1 if turn == 1 else p1 + outs[0] + extra
        (r,) = loop.run([DecodeRequest(prompt=list(p), max_new_tokens=4,
                                       session=s)])
        assert r.error is None, r.error
        outs.append(r.tokens)
        want.append(full_decode(params, cfg, p, 4)[0])
        if turn == 1:
            assert mgr.spill(s, wait=True) and s.state == "parked"
    st = mgr.stats()
    mgr.close()
    assert pool.used_pages == 0
    assert pool.check_invariants()["ok"]
    assert len(mgr.tier) == 0
    return outs, want, st


def test_spill_corrupt_rejected_session_reprefills_correctly():
    """FAULT_SERVE_SPILL_CORRUPT: the parked payload rots in host RAM.
    The resume's CRC verify rejects it (never imports garbage), the
    session re-prefills, and turn 2 is still token-identical."""
    outs, want, st = _tiered_two_turns(
        fault="FAULT_SERVE_SPILL_CORRUPT", arm_before_turn=1)
    assert outs == want
    assert st["re_prefills"] == 1
    assert st["tier"]["corrupt_rejected"] == 1
    assert st["resumed_host"] == 0  # the one resume fell back


def test_spill_drop_lost_payload_session_reprefills_correctly():
    """FAULT_SERVE_SPILL_DROP: the parked payload vanishes before the
    resume fetches it — typed SpillMissingError fallback, counted,
    and turn 2 still matches the oracle."""
    outs, want, st = _tiered_two_turns(
        fault="FAULT_SERVE_SPILL_DROP", arm_before_turn=2)
    assert outs == want
    assert st["re_prefills"] == 1
    assert st["tier"]["lost"] == 1
    assert st["resumed_host"] == 0


def test_tiered_turns_clean_baseline_no_reprefill():
    """The same scenario unarmed: the resume comes back from host with
    no fallback — the teeth arms above fail without their knobs."""
    outs, want, st = _tiered_two_turns()
    assert outs == want
    assert st["re_prefills"] == 0 and st["resumed_host"] == 1
    assert st["tier"]["corrupt_rejected"] == 0 and st["tier"]["lost"] == 0
