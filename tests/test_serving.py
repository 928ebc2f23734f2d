"""Serving tier (paddle_tpu/serving/): batching engine acceptance,
paged KV-cache decode parity, drain/timeout semantics, serve_bench gate.

Acceptance criteria pinned here (ISSUE 4):
(a) concurrent mixed-shape submit()s == sequential predict(), bit-exact;
(b) a bucket-ladder engine dispatches at most len(buckets) distinct
    batch shapes across 100 mixed-size requests (compile counters);
(c) continuous-batching decode of overlapping sequences through the
    paged KV cache == per-sequence full-recompute decode (fp32 tol),
    and retired sequences' pages return to the free pool;
(d) deadline-expired requests fail with the named timeout error while
    in-flight batches complete during drain.
Plus the decode-shaped ragged-attention contract the KV loop relies on:
flash_attention at Sq=1 with growing k_lengths == _reference_attention
token-for-token.

ISSUE 5 additions (pallas ragged paged attention + batched prefill):
(e) interpret-mode pallas paged decode == the reference gather path
    token-for-token over a multi-step simulated decode with ragged
    lengths, mixed page counts, and >=3 overlapping sequences — and the
    whole continuous-batching loop under paged_impl="interpret" matches
    full_decode;
(f) batched whole-prompt prefill: prefill_step == full_forward's last
    row per sequence (the batched-reference oracle), batched-vs-token
    loops produce token-identical generations, and prefill model-steps
    drop from O(prompt_len) to O(1) per admission group (step counters);
(g) envelope/flag selection: pallas_paged_viable encodes the Mosaic
    tiling envelope, explicit pallas outside it falls back to reference
    (same numbers, no compile bomb), FLAGS_serving_paged_impl validates
    its choices.
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, serving
from paddle_tpu.core.framework import unique_name_guard
from paddle_tpu.inference import (
    load_compiled_inference_model,
    save_compiled_inference_model,
)
from paddle_tpu.kernels.flash_attention import (
    _reference_attention,
    flash_attention,
)
from paddle_tpu.kernels.paged_attention import (
    attention_bytes_per_step,
    gather_kv_pages,
    paged_decode_attention,
    pallas_paged_viable,
    resolve_paged_impl,
)
from paddle_tpu.resilience import PreemptionDrain
from paddle_tpu.serving import (
    ContinuousBatchingLoop,
    DecodeConfig,
    DecodeRequest,
    Engine,
    EngineClosedError,
    EngineConfig,
    KVCachePool,
    PagePoolExhausted,
    QueueFullError,
    RequestTimeoutError,
    full_decode,
    full_forward,
    init_decode_params,
    prefill_step,
)
from paddle_tpu.serving.generate import window_mask


def _export_small_cnn(dirname: str):
    """Conv->bn->pool->fc artifact in private programs/scope (reusable
    across tests regardless of the autouse fresh-program fixture)."""
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), unique_name_guard():
        img = layers.data("image", [1, 8, 8], dtype="float32")
        c = layers.conv2d(img, num_filters=4, filter_size=3, padding=1)
        b = layers.batch_norm(c, act="relu")
        p = layers.pool2d(b, pool_size=8, pool_type="avg")
        pred = layers.fc(p, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        save_compiled_inference_model(
            dirname, ["image"], [pred], exe, main_program=main, scope=scope)
    return load_compiled_inference_model(dirname)


@pytest.fixture(scope="module")
def cnn_predict(tmp_path_factory):
    return _export_small_cnn(str(tmp_path_factory.mktemp("serving_cnn")))


def _wait_until(pred, timeout=5.0):
    t0 = time.perf_counter()
    while not pred():
        if time.perf_counter() - t0 > timeout:
            raise AssertionError("condition not reached in time")
        time.sleep(0.005)


class _GatedBackend:
    """Backend whose dispatch blocks until released — stages the
    in-flight-during-drain scenarios deterministically."""

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


# -- (a) concurrent mixed shapes, bit-identical -------------------------

def test_concurrent_mixed_shapes_bit_identical_to_the_same_bucket_alone(
        cnn_predict):
    """What the engine promises a request under concurrent mixed traffic:
    the bits it would get alone in the bucket it was served in, whoever
    shares the batch and wherever its rows sit in it.  NOT the bits of the
    request run at its own row count: a bucket is another executable, and
    XLA's CPU matmul rounds one element of a 4-row request one ulp apart
    at batch 4 and at batch 8 (found by PR 46; the same rows at any offset
    of one bucket, beside any companions, agree to the bit)."""
    from paddle_tpu.serving.batching import pad_rows

    eng = Engine.from_artifact(
        cnn_predict,
        config=EngineConfig(buckets=(1, 2, 4, 8), max_wait_s=0.002))
    dispatched = []

    def recording(feed):
        dispatched.append(np.asarray(feed["image"]))
        return cnn_predict(feed)

    eng.backend.predict = recording
    rng = np.random.RandomState(7)
    feeds = [
        {"image": rng.rand(int(rng.randint(1, 5)), 1, 8, 8).astype(np.float32)}
        for _ in range(24)
    ]
    with ThreadPoolExecutor(max_workers=6) as tp:
        futs = list(tp.map(eng.submit, feeds))
    outs = [f.result(timeout=30) for f in futs]
    eng.close()
    for feed, got in zip(feeds, outs):
        x = feed["image"]
        # the one batch that carried this request's rows gives its bucket
        (bucket,) = [len(b) for b in dispatched
                     for off in range(len(b) - len(x) + 1)
                     if np.array_equal(b[off:off + len(x)], x)][:1]
        (alone,) = cnn_predict({"image": pad_rows(x, bucket)})
        assert got[0].shape == (len(x),) + alone.shape[1:]
        np.testing.assert_array_equal(got[0], alone[:len(x)])


# -- (b) bucket ladder bounds compiled shapes ---------------------------

def test_bucket_ladder_bounds_compiled_shapes(cnn_predict):
    buckets = (1, 2, 4, 8)
    eng = Engine.from_artifact(
        cnn_predict, config=EngineConfig(buckets=buckets, max_wait_s=0.001))
    rng = np.random.RandomState(3)
    futs = [
        eng.submit({"image": rng.rand(
            int(rng.randint(1, 9)), 1, 8, 8).astype(np.float32)})
        for _ in range(100)
    ]
    for f in futs:
        f.result(timeout=60)
    counters = eng.compile_counters()
    stats = eng.stats()
    eng.close()
    # 100 mixed-size requests, at most one first-seen shape per bucket
    assert counters["miss"] == counters["distinct_shapes"]
    assert counters["distinct_shapes"] <= len(buckets)
    assert counters["hit"] + counters["miss"] == stats["batches"]
    assert stats["rows"] == sum(int(f.result()[0].shape[0]) for f in futs)


def test_static_artifact_collapses_ladder(tmp_path, monkeypatch):
    """A static-batch artifact can only serve its exported size: the
    bucket planner collapses the ladder and records the export's
    symbolic_error as the reason."""
    import paddle_tpu.inference.aot  # noqa: F401 — jexport target below
    from jax import export as jexport

    real = jexport.export
    calls = {"n": 0}

    def flaky_export(fn, **kw):
        wrapped = real(fn, **kw)

        def call(*specs):
            calls["n"] += 1
            if calls["n"] == 1:
                raise ValueError("synthetic: polymorphism unsupported")
            return wrapped(*specs)

        return call

    monkeypatch.setattr(jexport, "export", flaky_export)
    predict = _export_small_cnn(str(tmp_path))
    assert predict.meta["batch"] == "static"
    eng = Engine.from_artifact(
        predict, config=EngineConfig(buckets=(1, 2, 4), max_wait_s=0.0))
    assert eng.ladder.buckets == (1,)
    assert "synthetic" in eng.bucket_reason
    (out,) = eng.infer({"image": np.zeros((1, 1, 8, 8), np.float32)})
    assert out.shape == (1, 3)
    with pytest.raises(ValueError, match="max_batch"):
        eng.submit({"image": np.zeros((2, 1, 8, 8), np.float32)})
    eng.close()


def test_engine_rejects_bad_feeds(cnn_predict):
    eng = Engine.from_artifact(
        cnn_predict, config=EngineConfig(buckets=(1, 2)))
    with pytest.raises(KeyError, match="missing"):
        eng.submit({})
    with pytest.raises(KeyError, match="unknown"):
        eng.submit({"image": np.zeros((1, 1, 8, 8), np.float32),
                    "oops": np.zeros((1,), np.float32)})
    eng.close()


# -- (d) deadlines, drain, backpressure ---------------------------------

def test_deadline_timeout_and_drain_semantics():
    backend = _GatedBackend()
    eng = Engine(backend, config=EngineConfig(buckets=(1,), max_wait_s=0.0))
    f_inflight = eng.submit({"x": np.ones((1, 2), np.float32)})
    _wait_until(lambda: backend.calls == 1)  # A is in-flight, queue empty
    f_b = eng.submit({"x": np.full((1, 2), 3.0, np.float32)}, timeout=0.01)
    f_c = eng.submit({"x": np.full((1, 2), 4.0, np.float32)}, timeout=0.01)
    time.sleep(0.05)  # let both deadlines lapse while A blocks the engine
    eng.begin_drain()
    with pytest.raises(EngineClosedError):
        eng.submit({"x": np.ones((1, 2), np.float32)})
    backend.gate.set()
    assert eng.drain(timeout=10.0)
    # the in-flight batch completed during drain...
    np.testing.assert_array_equal(
        f_inflight.result(timeout=1.0)[0], np.full((1, 2), 2.0, np.float32))
    # ...and the expired queued requests failed with the NAMED error
    for f in (f_b, f_c):
        with pytest.raises(RequestTimeoutError, match="expired"):
            f.result(timeout=1.0)
    eng.close()


def test_deadline_fires_without_traffic():
    """An expired request fails promptly even when nothing else arrives
    to tickle the dispatcher: a 1-row request under a batch-fill window
    of 5s must NOT wait the window out — the dispatcher's sleep tracks
    the earliest deadline."""
    backend = _GatedBackend()
    backend.gate.set()
    eng = Engine(backend, config=EngineConfig(buckets=(2,), max_wait_s=5.0))
    t0 = time.perf_counter()
    f = eng.submit({"x": np.ones((1, 2), np.float32)}, timeout=0.05)
    with pytest.raises(RequestTimeoutError):
        f.result(timeout=2.0)
    assert time.perf_counter() - t0 < 2.0  # not the 5s fill window
    eng.close()


def test_queue_backpressure():
    backend = _GatedBackend()
    eng = Engine(backend, config=EngineConfig(
        buckets=(1,), max_wait_s=0.0, queue_depth=2))
    f_a = eng.submit({"x": np.ones((1, 2), np.float32)})
    _wait_until(lambda: backend.calls == 1)
    eng.submit({"x": np.ones((1, 2), np.float32)})
    eng.submit({"x": np.ones((1, 2), np.float32)})
    with pytest.raises(QueueFullError):
        eng.submit({"x": np.ones((1, 2), np.float32)})
    backend.gate.set()
    eng.close()
    assert f_a.result(timeout=1.0)


def test_preemption_drain_wiring():
    """SIGTERM-path: PreemptionDrain.request() stops admissions via the
    listener hook while admitted work completes."""
    backend = _GatedBackend()
    backend.gate.set()  # fast backend
    eng = Engine(backend, config=EngineConfig(buckets=(1,), max_wait_s=0.0))
    drain = PreemptionDrain()
    eng.attach_drain(drain)
    f = eng.submit({"x": np.ones((1, 2), np.float32)})
    drain.request()
    assert eng.draining
    np.testing.assert_array_equal(
        f.result(timeout=5.0)[0], np.full((1, 2), 2.0, np.float32))
    with pytest.raises(EngineClosedError):
        eng.submit({"x": np.ones((1, 2), np.float32)})
    eng.close()
    # a listener attached AFTER the notice fires immediately
    late = Engine(backend, config=EngineConfig(buckets=(1,)))
    late.attach_drain(drain)
    assert late.draining
    late.close()


def test_begin_drain_is_nonblocking_under_contention():
    """begin_drain runs from SIGNAL context on the main thread — it must
    never block on the engine lock (a SIGTERM landing while that thread
    is inside submit() would self-deadlock), and the drain must still
    proceed via the dispatcher's bounded park."""
    backend = _GatedBackend()
    backend.gate.set()
    eng = Engine(backend, config=EngineConfig(buckets=(1,), max_wait_s=0.0))
    with eng._cond:  # simulate the interrupted thread holding the lock
        t0 = time.perf_counter()
        eng.begin_drain()  # must return immediately, no notify possible
        assert time.perf_counter() - t0 < 0.1
    assert eng.draining
    assert eng.drain(timeout=2 * Engine._IDLE_PARK_S + 1.0)
    eng.close()


def test_close_timeout_fails_stranded_requests():
    """A close() whose drain times out must FAIL whatever is still
    queued — a stopped dispatcher leaving futures pending would hang
    every caller blocked in .result()."""
    backend = _GatedBackend()  # gate closed: first dispatch blocks
    eng = Engine(backend, config=EngineConfig(buckets=(1,), max_wait_s=0.0))
    f_inflight = eng.submit({"x": np.ones((1, 2), np.float32)})
    _wait_until(lambda: backend.calls == 1)
    f_queued = eng.submit({"x": np.ones((1, 2), np.float32)})
    eng.close(timeout=0.1)  # cannot drain: the backend is blocked
    with pytest.raises(EngineClosedError, match="drain timed out"):
        f_queued.result(timeout=1.0)
    backend.gate.set()  # release the in-flight batch: it still completes
    np.testing.assert_array_equal(
        f_inflight.result(timeout=5.0)[0], np.full((1, 2), 2.0, np.float32))


def test_done_callback_touching_engine_does_not_deadlock():
    """Future.set_exception runs done-callbacks synchronously on the
    dispatcher thread; a callback that calls back into the engine must
    not deadlock it (expired futures complete OUTSIDE the lock)."""
    backend = _GatedBackend()
    backend.gate.set()
    eng = Engine(backend, config=EngineConfig(buckets=(2,), max_wait_s=5.0))
    seen = []
    f = eng.submit({"x": np.ones((1, 2), np.float32)}, timeout=0.05)
    f.add_done_callback(lambda fut: seen.append(eng.queue_depth()))
    with pytest.raises(RequestTimeoutError):
        f.result(timeout=2.0)
    _wait_until(lambda: len(seen) == 1)
    # the dispatcher survived the reentrant callback: it still serves
    ok = eng.submit({"x": np.ones((2, 2), np.float32)})
    np.testing.assert_array_equal(
        ok.result(timeout=5.0)[0], np.full((2, 2), 2.0, np.float32))
    eng.close()


def test_trailing_shape_mismatch_rejected_at_submit(cnn_predict):
    """One client's mis-shaped request must fail at submit(), not poison
    the batch-mates it would have coalesced with."""
    eng = Engine.from_artifact(
        cnn_predict, config=EngineConfig(buckets=(1, 2, 4)))
    with pytest.raises(ValueError, match="trailing shape"):
        eng.submit({"image": np.zeros((1, 1, 32, 32), np.float32)})
    (out,) = eng.infer({"image": np.zeros((1, 1, 8, 8), np.float32)})
    assert out.shape == (1, 3)
    eng.close()


def test_abandoned_engine_is_collected():
    """An Engine dropped without close() must be garbage-collectable
    (the dispatcher holds it via weakref between cycles) — otherwise
    every forgotten Inferencer leaks a thread + executor forever."""
    import gc
    import weakref

    backend = _GatedBackend()
    backend.gate.set()
    eng = Engine(backend, config=EngineConfig(buckets=(1,), max_wait_s=0.0))
    eng.infer({"x": np.ones((1, 2), np.float32)})
    thread = eng._thread
    ref = weakref.ref(eng)
    del eng
    t0 = time.perf_counter()
    while ref() is not None and time.perf_counter() - t0 < 5.0:
        gc.collect()
        time.sleep(0.05)
    assert ref() is None
    thread.join(timeout=2 * Engine._IDLE_PARK_S + 1.0)
    assert not thread.is_alive()


def test_backend_failure_fails_the_batch():
    class Boom:
        feed_names = ["x"]
        fetch_names = ["y"]
        meta: dict = {}

        def __call__(self, feed):
            raise RuntimeError("backend exploded")

    eng = Engine(Boom(), config=EngineConfig(buckets=(1, 2), max_wait_s=0.0))
    f = eng.submit({"x": np.ones((1, 2), np.float32)})
    with pytest.raises(RuntimeError, match="exploded"):
        f.result(timeout=5.0)
    eng.close()


# -- Inferencer rides the engine ---------------------------------------

def test_inferencer_routes_through_engine(tmp_path):
    from paddle_tpu.contrib.inferencer import Inferencer

    def net():
        x = layers.data("x", [4], dtype="float32")
        return layers.fc(x, size=2,
                         param_attr=fluid.ParamAttr(name="infer_w"),
                         bias_attr=fluid.ParamAttr(name="infer_b"))

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), unique_name_guard():
        net()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_persistables(exe, str(tmp_path), main_program=main)

    inf = Inferencer(net, str(tmp_path), place=fluid.CPUPlace())
    x = np.ones((3, 4), np.float32)
    (out1,) = inf.infer({"x": x})
    (out2,) = inf.infer({"x": x})
    np.testing.assert_array_equal(out1, out2)
    assert out1.shape == (3, 2)
    # both calls went through ONE engine sharing one executor cache
    stats = inf._engine.stats()
    assert stats["batches"] == 2
    assert stats["distinct_shapes"] == 1  # same feed shape counts once
    # a new feed shape is a fresh executor trace — the counter says so
    inf.infer({"x": np.ones((5, 4), np.float32)})
    assert inf._engine.stats()["distinct_shapes"] == 2
    inf.close()


# -- KV-cache pool ------------------------------------------------------

def test_kvcache_alloc_append_free_accounting():
    pool = KVCachePool(num_pages=4, page_size=2, num_layers=1,
                       num_heads=1, head_dim=4)
    pool.allocate(0)
    for step in range(4):  # 4 tokens -> 2 pages
        pages, slots = pool.append_token([0])
        pool.write_kv(0, pages, slots,
                      np.full((1, 1, 4), step, np.float32),
                      np.full((1, 1, 4), -step, np.float32))
    assert pool.used_pages == 2 and pool.length(0) == 4
    tables, lengths = pool.page_table_batch([0])
    k = np.asarray(gather_kv_pages(pool.k_pages[0], tables))  # [1,H,S,D]
    np.testing.assert_array_equal(k[0, 0, :, 0], [0, 1, 2, 3])
    assert pool.free_seq(0) == 2
    assert pool.free_pages == pool.num_pages
    st = pool.stats()
    assert st["page_allocs"] == 2 and st["page_frees"] == 2
    assert st["used_pages_high_water"] == 2


def test_kvcache_exhaustion_is_atomic():
    pool = KVCachePool(num_pages=2, page_size=2, num_layers=1,
                       num_heads=1, head_dim=4)
    pool.allocate(0)
    pool.allocate(1)
    pool.append_token([0])
    pool.append_token([1])  # both pages claimed
    pool.append_token([0])  # slot 1 of page A, no fresh page needed
    with pytest.raises(PagePoolExhausted):
        # 0 needs a fresh page (full) and 1 has a slot: the claim must
        # fail BEFORE advancing either sequence
        pool.append_token([0, 1])
    assert pool.length(0) == 2 and pool.length(1) == 1


def test_kvcache_defrag_preserves_contents():
    pool = KVCachePool(num_pages=6, page_size=2, num_layers=1,
                       num_heads=1, head_dim=2)
    for s in range(3):
        pool.allocate(s)
    for step in range(4):
        pages, slots = pool.append_token([0, 1, 2])
        k = np.stack([np.full((1, 2), 100 * s + step, np.float32)
                      for s in range(3)])
        pool.write_kv(0, pages, slots, k, k)
    pool.free_seq(1)  # punch a hole mid-pool
    before_tables, lengths = pool.page_table_batch([0, 2])
    before = np.asarray(gather_kv_pages(pool.k_pages[0], before_tables))
    moves = pool.defrag()
    assert moves > 0
    after_tables, lengths2 = pool.page_table_batch([0, 2])
    after = np.asarray(gather_kv_pages(pool.k_pages[0], after_tables))
    np.testing.assert_array_equal(before, after)
    np.testing.assert_array_equal(lengths, lengths2)
    # compacted: live pages occupy the lowest indices
    assert int(np.asarray(after_tables).max()) == pool.used_pages - 1


# -- decode-shaped ragged attention (the KV-loop contract) --------------

@pytest.mark.parametrize("force", ["interpret", "jax"])
def test_flash_decode_ragged_matches_reference_token_for_token(force):
    """Sq=1 queries against a fixed K/V buffer with growing k_lengths —
    exactly what the paged decode loop issues — must match dense
    reference attention over the true prefix at every step, through the
    REAL pallas kernel (interpret mode) and the jax path, a case each."""
    # a buffer of 16 keys (32 before PR 46): either is one key block of the
    # kernel, and the dense reference, sliced to the true prefix, costs
    # one set of op-by-op compiles a length
    B, H, S, D = 2, 2, 16, 8
    rng = np.random.RandomState(11)
    q_all = rng.standard_normal((B, H, S, D)).astype(np.float32)
    k_buf = rng.standard_normal((B, H, S, D)).astype(np.float32)
    v_buf = rng.standard_normal((B, H, S, D)).astype(np.float32)
    scale = D ** -0.5
    for t in range(1, S + 1):
        q = q_all[:, :, t - 1:t, :]
        got = np.asarray(flash_attention(
            q, k_buf, v_buf, causal=False, scale=scale,
            k_lengths=np.full((B,), t, np.int32), force=force))
        want = np.asarray(_reference_attention(
            q, k_buf[:, :, :t], v_buf[:, :, :t], causal=False,
            scale=scale))
        np.testing.assert_allclose(
            got, want, rtol=2e-5, atol=2e-6, err_msg=f"step {t}")


# -- (e) pallas ragged paged attention: interpret-mode parity ----------

def test_paged_pallas_interpret_matches_reference_multistep():
    """The REAL pallas page-walk kernel (interpret mode) vs the
    reference gather, token-for-token over a simulated multi-step decode:
    >=3 overlapping sequences, ragged lengths, mixed page counts — the
    pool-level mirror of the flash Sq=1 contract test."""
    H, Dh, page_size = 2, 8, 3  # odd page size: deliberately unaligned
    pool = KVCachePool(num_pages=32, page_size=page_size, num_layers=1,
                       num_heads=H, head_dim=Dh)
    rng = np.random.RandomState(23)
    seq_ids = [0, 1, 2, 3]
    for s in seq_ids:
        pool.allocate(s)
    # stagger the prefixes so lengths (and page counts) stay ragged
    for s, prefix in zip(seq_ids, (5, 1, 9, 3)):
        for _ in range(prefix):
            pages, slots = pool.append_token([s])
            pool.write_kv(0, pages, slots,
                          rng.standard_normal((1, H, Dh)).astype(np.float32),
                          rng.standard_normal((1, H, Dh)).astype(np.float32))
    for step in range(12):
        pages, slots = pool.append_token(seq_ids)
        B = len(seq_ids)
        pool.write_kv(0, pages, slots,
                      rng.standard_normal((B, H, Dh)).astype(np.float32),
                      rng.standard_normal((B, H, Dh)).astype(np.float32))
        tables, lengths = pool.page_table_batch(seq_ids)
        assert len(set(tables.shape[1] - (lengths - 1) // page_size)) > 1, \
            "page counts must stay mixed for the test to bite"
        q = rng.standard_normal((B, H, 1, Dh)).astype(np.float32)
        want = np.asarray(paged_decode_attention(
            q, pool.k_pages[0], pool.v_pages[0], tables, lengths,
            impl="reference"))
        got = np.asarray(paged_decode_attention(
            q, pool.k_pages[0], pool.v_pages[0], tables, lengths,
            impl="interpret"))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6,
                                   err_msg=f"step {step}")


def test_paged_envelope_and_flag_selection():
    """pallas_paged_viable encodes the Mosaic tiling envelope; explicit
    pallas OUTSIDE it falls back to the reference gather (identical
    numbers, never a compile failure); the flag validates its choices."""
    # in-envelope: lane-multiple head_dim, sublane-multiple page size
    assert pallas_paged_viable(16, 128)
    assert pallas_paged_viable(8, 256)
    assert pallas_paged_viable(16, 128, "bfloat16")
    # out: unaligned page size / head_dim / dtype
    assert not pallas_paged_viable(3, 128)
    assert not pallas_paged_viable(16, 64)
    assert not pallas_paged_viable(8, 128, "bfloat16")  # bf16 sublane=16
    assert not pallas_paged_viable(16, 128, "float64")
    # resolution: auto on CPU -> reference; explicit pallas out of
    # envelope -> reference fallback; interpret passes through
    assert resolve_paged_impl(None, 16, 128) == "reference"
    assert resolve_paged_impl("pallas", 3, 8) == "reference"
    assert resolve_paged_impl("interpret", 3, 8) == "interpret"
    with pytest.raises(ValueError, match="impl"):
        resolve_paged_impl("mosaic", 16, 128)
    with pytest.raises(ValueError):
        fluid.set_flags({"FLAGS_serving_paged_impl": "gather"})
    # the loop resolves the impl it will actually run (and labels
    # metrics with it)
    cfg = DecodeConfig(vocab_size=17, d_model=16, n_head=2, n_layer=1,
                       d_inner=16, max_length=16)
    pool = KVCachePool(num_pages=4, page_size=4, num_layers=1,
                       num_heads=2, head_dim=8)
    loop = ContinuousBatchingLoop(init_decode_params(cfg, seed=0), cfg,
                                  pool, paged_impl="pallas")
    assert loop.paged_impl == "reference"  # head_dim 8: out of envelope
    with pytest.raises(ValueError, match="prefill"):
        ContinuousBatchingLoop(init_decode_params(cfg, seed=0), cfg,
                               pool, prefill="speculative")


def test_attention_bytes_per_step_model():
    """The metrics gauge's analytic model: reference moves 3x the KV
    bytes of the pallas stream (pages + contiguous copy written + copy
    read back), scaled by layers."""
    kw = dict(batch=4, max_pages=32, page_size=16, num_heads=8,
              head_dim=128, itemsize=4, num_layers=2)
    s_kv = 4 * 32 * 16 * 8 * 128 * 4
    assert attention_bytes_per_step("pallas", **kw) == 2 * s_kv * 2
    assert attention_bytes_per_step("interpret", **kw) == 2 * s_kv * 2
    assert attention_bytes_per_step("reference", **kw) == 6 * s_kv * 2


# -- the oracle itself ---------------------------------------------------

def _oracle_written_out(params, cfg, tokens, mask=None):
    """``full_forward``'s arithmetic as it stood before it was one traced
    function (PR 54), in float32 numpy, op by op: what ten test files
    trust the oracle to be."""
    tokens = np.asarray(tokens, np.int32)
    S, d, H, Dh = len(tokens), cfg.d_model, cfg.n_head, cfg.head_dim
    Hkv, G = cfg.num_kv_heads, cfg.group_size
    f32 = np.float32

    def layernorm(x, g, b):
        mean = x.mean(-1, keepdims=True, dtype=f32)
        var = np.square(x - mean).mean(-1, keepdims=True, dtype=f32)
        return (x - mean) / np.sqrt(var + f32(1e-5)) * g + b

    vis = (np.tril(np.ones((S, S), bool)) if mask is None
           else np.asarray(mask, bool))
    h = params["embed"][tokens] * f32(np.sqrt(d)) + params["pos"][:S]
    for lp in params["layers"]:
        q = (h @ lp["wq"]).reshape(S, H, Dh).transpose(1, 0, 2)
        k = (h @ lp["wk"]).reshape(S, Hkv, Dh).transpose(1, 0, 2)
        v = (h @ lp["wv"]).reshape(S, Hkv, Dh).transpose(1, 0, 2)
        k, v = np.repeat(k, G, axis=0), np.repeat(v, G, axis=0)
        scores = np.einsum("hqd,hkd->hqk", q, k) * f32(Dh ** -0.5)
        scores = np.where(vis[None], scores, f32(-1e30))
        w = np.exp(scores - scores.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True, dtype=f32)
        attn = np.einsum("hqk,hkd->hqd", w, v)
        attn = attn.transpose(1, 0, 2).reshape(S, d)
        h = layernorm(h + attn @ lp["wo"], lp["ln1_g"], lp["ln1_b"])
        ff = np.maximum(h @ lp["w1"] + lp["b1"], 0) @ lp["w2"] + lp["b2"]
        h = layernorm(h + ff, lp["ln2_g"], lp["ln2_b"])
    return h @ params["embed"].T


@pytest.mark.parametrize("S", [3, 8, 13])
@pytest.mark.parametrize("windowed", [False, True],
                         ids=["causal", "window_mask"])
@pytest.mark.parametrize("n_kv_head", [None, 2], ids=["mha", "kv2"])
def test_full_forward_is_the_arithmetic_written_out(n_kv_head, windowed, S):
    """The jitted oracle against the same arithmetic in numpy: logits to
    1e-5 and every argmax equal, with and without grouped heads and the
    windowed-decode mask, at three lengths."""
    cfg = DecodeConfig(vocab_size=37, d_model=32, n_head=4, n_layer=2,
                       d_inner=64, max_length=16, n_kv_head=n_kv_head)
    params = init_decode_params(cfg, seed=5)
    tokens = np.random.RandomState(S).randint(1, cfg.vocab_size, size=S)
    mask = (window_mask(S, prompt_len=2, window=3, sinks=2, page_size=2)
            if windowed else None)
    got = full_forward(params, cfg, tokens, mask=mask)
    want = _oracle_written_out(params, cfg, tokens, mask)
    assert got.shape == (S, cfg.vocab_size) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


# -- (f) batched whole-prompt prefill ----------------------------------

def test_prefill_step_matches_full_forward_oracle():
    """ONE batched causal pass == the whole-sequence oracle: last-row
    logits per sequence at fp32 tolerance, the pool holding exactly the
    K/V token-by-token prefill would have written."""
    cfg = DecodeConfig(vocab_size=37, d_model=16, n_head=2, n_layer=2,
                       d_inner=32, max_length=32)
    params = init_decode_params(cfg, seed=9)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in (6, 2, 4)]
    pool = KVCachePool(num_pages=16, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim)
    for s in range(len(prompts)):
        pool.allocate(s)
    logits = prefill_step(params, cfg, pool, list(range(len(prompts))),
                          prompts)
    for i, p in enumerate(prompts):
        want = full_forward(params, cfg, p)[-1]
        np.testing.assert_allclose(logits[i], want, rtol=1e-4, atol=1e-4,
                                   err_msg=f"sequence {i}")
        assert pool.length(i) == len(p)
    # the cached K/V is the same content token-by-token would have
    # produced: a decode step on top must match full_decode's next token
    tokens = [int(row.argmax()) for row in logits]
    from paddle_tpu.serving.generate import decode_step

    step_logits = decode_step(params, cfg, pool, list(range(len(prompts))),
                              tokens, [len(p) for p in prompts])
    for i, p in enumerate(prompts):
        want_tokens, want_logits = full_decode(params, cfg, p, 2)
        assert tokens[i] == want_tokens[0]
        np.testing.assert_allclose(step_logits[i], want_logits[1],
                                   rtol=1e-4, atol=1e-4)


def test_batched_prefill_token_identical_and_o1_steps():
    """prefill='batched' vs prefill='token': token-identical
    generations, logits at fp32 tolerance — and prefill model-steps are
    O(1) per admission group instead of O(prompt_len)."""
    cfg = DecodeConfig(vocab_size=53, d_model=16, n_head=2, n_layer=2,
                       d_inner=32, max_length=48)
    params = init_decode_params(cfg, seed=3)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in (7, 3, 5)]
    max_new = 5

    def run(prefill):
        pool = KVCachePool(num_pages=24, page_size=4,
                           num_layers=cfg.n_layer, num_heads=cfg.n_head,
                           head_dim=cfg.head_dim)
        loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3,
                                      prefill=prefill)
        return loop, loop.run(
            [DecodeRequest(p, max_new) for p in prompts])

    tok_loop, tok_res = run("token")
    bat_loop, bat_res = run("batched")
    for t, b in zip(tok_res, bat_res):
        assert t.tokens == b.tokens
        for lt, lb in zip(t.logits, b.logits):
            np.testing.assert_allclose(lb, lt, rtol=1e-4, atol=1e-4)
    # token-by-token burns one model step per prompt token; batched
    # prefill is ONE step for the whole co-admitted group
    assert tok_loop.prefill_steps == 0
    assert bat_loop.prefill_steps == 1  # all 3 admit together
    assert bat_loop.steps == 1 + bat_loop.decode_steps
    assert bat_loop.steps <= tok_loop.steps - (max(len(p) for p in prompts) - 1)
    # both loops retire cleanly
    assert tok_loop.pool.free_pages == tok_loop.pool.num_pages
    assert bat_loop.pool.free_pages == bat_loop.pool.num_pages


def test_continuous_batching_pallas_interpret_end_to_end():
    """The whole loop — batched prefill + pallas (interpret) paged
    decode — against the full-recompute oracle."""
    cfg = DecodeConfig(vocab_size=41, d_model=16, n_head=2, n_layer=2,
                       d_inner=32, max_length=32)
    params = init_decode_params(cfg, seed=7)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in (4, 2, 3)]
    pool = KVCachePool(num_pages=18, page_size=4, num_layers=cfg.n_layer,
                       num_heads=cfg.n_head, head_dim=cfg.head_dim)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3,
                                  paged_impl="interpret")
    assert loop.paged_impl == "interpret"
    results = loop.run([DecodeRequest(p, 4) for p in prompts])
    for p, res in zip(prompts, results):
        want_tokens, want_logits = full_decode(params, cfg, p, 4)
        assert res.tokens == want_tokens
        for got, want in zip(res.logits, want_logits):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert pool.free_pages == pool.num_pages


# -- (c) continuous-batching decode parity ------------------------------

def test_continuous_batching_decode_matches_full_recompute():
    cfg = DecodeConfig(vocab_size=61, d_model=16, n_head=2, n_layer=2,
                       d_inner=32, max_length=48)
    params = init_decode_params(cfg, seed=5)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).tolist()
               for n in (3, 5, 2, 4)]
    max_new = 6
    reqs = [DecodeRequest(p, max_new) for p in prompts]

    # pool sized for 3 concurrent worst-case sequences but not 4: the
    # 4th admits only when a retirement frees pages (admit-as-retire)
    page_size = 4
    per_seq = KVCachePool.pages_needed(max(len(p) for p in prompts) + max_new,
                                       page_size)
    pool = KVCachePool(num_pages=3 * per_seq, page_size=page_size,
                       num_layers=cfg.n_layer, num_heads=cfg.n_head,
                       head_dim=cfg.head_dim)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=3)
    results = loop.run(reqs)

    # ≥3 sequences genuinely overlapped: strictly fewer steps than
    # serial execution, and mean occupancy shows real batching
    serial_steps = sum(len(p) + max_new - 1 for p in prompts)
    assert loop.steps < serial_steps
    assert loop.mean_occupancy() > 0.5

    for req, res in zip(reqs, results):
        want_tokens, want_logits = full_decode(
            params, cfg, req.prompt, req.max_new_tokens)
        assert res.tokens == want_tokens
        assert len(res.logits) == len(want_logits)
        for got, want in zip(res.logits, want_logits):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        assert res.ttft_s is not None

    # every retired sequence's pages are back in the free pool
    assert pool.free_pages == pool.num_pages
    assert pool.stats()["live_sequences"] == 0


def test_decode_pool_too_small_raises():
    cfg = DecodeConfig(vocab_size=31, d_model=16, n_head=2, n_layer=1,
                       d_inner=16, max_length=32)
    params = init_decode_params(cfg, seed=1)
    pool = KVCachePool(num_pages=1, page_size=2, num_layers=1,
                       num_heads=2, head_dim=8)
    loop = ContinuousBatchingLoop(params, cfg, pool, max_batch=1)
    with pytest.raises(PagePoolExhausted):
        loop.run([DecodeRequest([1, 2, 3], 4)])


# -- observability wiring ----------------------------------------------

def test_serving_metrics_emitted_when_enabled(cnn_predict):
    from paddle_tpu import observability as obs

    obs.reset()
    fluid.set_flags({"FLAGS_observability": True})
    try:
        eng = Engine.from_artifact(
            cnn_predict, config=EngineConfig(buckets=(1, 2), max_wait_s=0.0))
        eng.infer({"image": np.zeros((1, 1, 8, 8), np.float32)})
        eng.close()

        cfg = DecodeConfig(vocab_size=17, d_model=8, n_head=2, n_layer=1,
                           d_inner=16, max_length=16)
        pool = KVCachePool(num_pages=4, page_size=4, num_layers=1,
                           num_heads=2, head_dim=4)
        ContinuousBatchingLoop(
            init_decode_params(cfg, seed=0), cfg, pool, max_batch=2,
        ).run([DecodeRequest([1, 2], 2)])

        snap = obs.default_registry().snapshot()["metrics"]
        names = {m["name"] for m in snap}
        for want in (
            "paddle_tpu_serving_queue_depth",
            "paddle_tpu_serving_requests",
            "paddle_tpu_serving_batches",
            "paddle_tpu_serving_batch_occupancy",
            "paddle_tpu_serving_request_latency_seconds",
            "paddle_tpu_serving_ttft_seconds",
            "paddle_tpu_serving_token_seconds",
            "paddle_tpu_serving_attention_bytes_per_step",
            "paddle_tpu_serving_page_pool_utilization",
            "paddle_tpu_serving_sequences",
        ):
            assert want in names, f"missing {want} in {sorted(names)}"
        # decode-step instruments are labeled with the active impl
        by_name = {m["name"]: m for m in snap}
        tok_labels = {s["labels"].get("impl")
                      for s in by_name["paddle_tpu_serving_token_seconds"]
                      ["series"]}
        assert tok_labels == {"reference"}  # CPU auto-resolves reference
        bytes_series = by_name[
            "paddle_tpu_serving_attention_bytes_per_step"]["series"]
        assert bytes_series and all(
            s["labels"]["impl"] == "reference" and s["value"] > 0
            for s in bytes_series)
    finally:
        fluid.set_flags({"FLAGS_observability": False})
        obs.reset()


def test_serving_metrics_silent_when_disabled(cnn_predict):
    from paddle_tpu import observability as obs

    obs.reset()
    assert not obs.enabled()
    eng = Engine.from_artifact(
        cnn_predict, config=EngineConfig(buckets=(1, 2), max_wait_s=0.0))
    eng.infer({"image": np.zeros((1, 1, 8, 8), np.float32)})
    eng.close()
    assert obs.default_registry().snapshot()["metrics"] == []


# -- serve_bench --------------------------------------------------------

def test_serve_bench_engine_smoke_and_gate(tmp_path, capsys):
    import json

    from tools.serve_bench import main as bench_main

    out = tmp_path / "bench.json"
    rc = bench_main([
        "--model", "mnist", "--requests", "8", "--rate", "400",
        "--buckets", "1,2,4", "--batch-range", "1,4",
        "--json", str(out),
    ])
    assert rc == 0
    result = json.loads(out.read_text())
    assert result["mode"] == "engine"
    assert result["distinct_shapes"] <= 3
    assert result["throughput_rps"] > 0
    # bank this run, re-gate against itself: must pass
    bank = tmp_path / "bank.json"
    bank.write_text(json.dumps(
        {"p99_ms": result["p99_ms"] * 10, "distinct_shapes": 3}))
    rc = bench_main([
        "--model", "mnist", "--requests", "8", "--rate", "400",
        "--buckets", "1,2,4", "--batch-range", "1,4",
        "--baseline", str(bank), "--tol", "0.5", "--gate",
    ])
    assert rc == 0
    # an impossible baseline must fail the gate with exit 3
    bank.write_text(json.dumps({"p99_ms": 1e-9}))
    rc = bench_main([
        "--model", "tiny", "--requests", "4", "--rate", "400",
        "--buckets", "1,2", "--batch-range", "1,2",
        "--baseline", str(bank), "--gate",
    ])
    assert rc == 3
    capsys.readouterr()  # swallow the report text


def test_serve_bench_decode_smoke(capsys):
    from tools.serve_bench import main as bench_main

    rc = bench_main([
        "--mode", "decode", "--sequences", "3", "--max-new", "4",
        "--d-model", "16", "--vocab", "31", "--max-len", "32",
        "--pages", "32", "--page-size", "4",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert '"pages_leaked": 0' in out


@pytest.mark.slow
def test_serve_bench_decode_transformer_scale(capsys):
    """Transformer-shaped decode config (d_model 128, 4 layers) through
    the paged loop — the load-generator run banked for trend tracking."""
    from tools.serve_bench import main as bench_main

    rc = bench_main([
        "--mode", "decode", "--sequences", "8", "--max-new", "16",
        "--d-model", "128", "--n-head", "8", "--n-layer", "4",
        "--vocab", "512", "--max-len", "96", "--max-batch", "4",
        "--pages", "128", "--page-size", "8",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert '"pages_leaked": 0' in out
