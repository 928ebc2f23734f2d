"""Unified telemetry subsystem (paddle_tpu/observability/): metrics
registry, trace spans, step stats, regression gates, executor wiring, and
the zero-overhead-when-disabled contract."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability as obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def obs_on():
    """FLAGS_observability on with clean registry/tracer/stats, restored
    after the test."""
    fluid.set_flags({"FLAGS_observability": True})
    obs.reset()
    yield
    obs.reset()
    fluid.set_flags({"FLAGS_observability": False})


def _build_step(name="obs_w"):
    x = layers.data("x", [4], dtype="float32")
    y = layers.fc(x, size=2, param_attr=fluid.ParamAttr(name=name))
    loss = layers.reduce_mean(y)
    fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe, loss


def _feed(seed=0, bad=False):
    rng = np.random.RandomState(seed)
    x = rng.randn(2, 4).astype("float32")
    if bad:
        x[0, 0] = np.nan
    return {"x": x}


# -----------------------------------------------------------------------
# metrics registry
# -----------------------------------------------------------------------
def test_counter_gauge_histogram_with_labels(obs_on):
    reg = obs.MetricsRegistry()
    c = reg.counter("requests", "requests served")
    c.inc(model="resnet50")
    c.inc(2.0, model="resnet50")
    c.inc(model="transformer")
    assert c.value(model="resnet50") == 3.0
    assert c.value(model="transformer") == 1.0
    assert c.value(model="absent") == 0.0

    g = reg.gauge("capacity", "")
    g.set(5.0, host="a")
    g.inc(2.0, host="a")
    g.dec(1.0, host="a")
    assert g.value(host="a") == 6.0
    assert g.value(host="b") is None
    # monotonic watermark: set_max never moves backwards
    g.set_max(10.0, host="a")
    g.set_max(3.0, host="a")
    assert g.value(host="a") == 10.0

    h = reg.histogram("lat", "", buckets=[0.01, 0.1, 1.0])
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    s = h.series_summary()
    assert s["count"] == 4
    assert s["min"] == 0.005 and s["max"] == 5.0
    # non-cumulative per-bucket counts: one obs each in 0.01/0.1/1.0/+Inf
    assert [c for _, c in s["buckets"]] == [1, 1, 1, 1]


def test_metric_type_conflict_raises(obs_on):
    reg = obs.MetricsRegistry()
    reg.counter("m", "")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("m", "")


def test_prometheus_text_format(obs_on):
    reg = obs.MetricsRegistry()
    reg.counter("steps", "steps run").inc(3, model="lenet")
    reg.gauge("hbm_bytes", "").set(1024)
    reg.histogram("step_s", "", buckets=[0.1, 1.0]).observe(0.05)
    text = reg.to_prometheus()
    assert "# TYPE steps_total counter" in text
    assert 'steps_total{model="lenet"} 3' in text
    assert "# TYPE hbm_bytes gauge" in text
    assert "hbm_bytes 1024" in text
    # histogram: cumulative buckets + sum + count
    assert 'step_s_bucket{le="0.1"} 1' in text
    assert 'step_s_bucket{le="1"} 1' in text
    assert 'step_s_bucket{le="+Inf"} 1' in text
    assert "step_s_count 1" in text


def test_snapshot_merge_adds_counters_and_histograms(obs_on):
    a, b = obs.MetricsRegistry(), obs.MetricsRegistry()
    a.counter("c", "").inc(2, k="x")
    b.counter("c", "").inc(3, k="x")
    a.histogram("h", "", buckets=[1.0]).observe(0.5)
    b.histogram("h", "", buckets=[1.0]).observe(2.0)
    a.gauge("g", "").set(1.0)
    time.sleep(0.01)
    b.gauge("g", "").set(9.0)  # newer write wins on merge

    merged = obs.MetricsRegistry()
    merged.merge(a.snapshot())
    merged.merge(b.snapshot())
    assert merged.counter("c", "").value(k="x") == 5.0
    hs = merged.histogram("h", "").series_summary()
    assert hs["count"] == 2 and hs["min"] == 0.5 and hs["max"] == 2.0
    assert merged.gauge("g", "").value() == 9.0


def test_process_dump_and_aggregate_dir(obs_on, tmp_path):
    """The multi-host story: one atomic snapshot file per process, any
    host merges the directory."""
    for p in (0, 1):
        reg = obs.MetricsRegistry()
        reg.counter("paddle_tpu_steps", "").inc(10, process=str(p))
        reg.counter("shared", "").inc(1)
        reg.dump(str(tmp_path / f"metrics_{p}.json"))
    agg = obs.MetricsRegistry.aggregate_dir(str(tmp_path))
    assert agg.counter("shared", "").value() == 2.0
    assert agg.counter("paddle_tpu_steps", "").value(process="0") == 10.0
    assert agg.counter("paddle_tpu_steps", "").value(process="1") == 10.0


def test_metrics_noop_when_disabled():
    assert not obs.enabled()
    reg = obs.MetricsRegistry()
    reg.counter("dead", "").inc(5)
    reg.gauge("dead_g", "").set(1)
    reg.histogram("dead_h", "").observe(1)
    assert reg.counter("dead", "").value() == 0.0
    assert reg.gauge("dead_g", "").value() is None
    assert reg.histogram("dead_h", "").series_summary() is None


# -----------------------------------------------------------------------
# spans + chrome trace
# -----------------------------------------------------------------------
def test_spans_nest_on_one_thread(obs_on):
    with obs.span("step", step=7):
        with obs.span("forward"):
            pass
        with obs.span("backward"):
            pass
    spans = {s.name: s for s in obs.default_tracer().spans()}
    assert set(spans) == {"step", "forward", "backward"}
    assert spans["forward"].parent == "step"
    assert spans["backward"].parent == "step"
    assert spans["step"].parent is None
    assert spans["step"].args == {"step": 7}
    # time containment
    assert spans["step"].t0 <= spans["forward"].t0
    assert spans["forward"].t1 <= spans["step"].t1


def test_spans_nest_independently_across_threads(obs_on):
    """A worker thread's spans must not adopt the main thread's open span
    as parent (per-thread stacks)."""
    def worker():
        with obs.span("io.write"):
            time.sleep(0.002)

    with obs.span("step"):
        t = threading.Thread(target=worker, name="ckpt-writer")
        t.start()
        t.join()
    spans = {s.name: s for s in obs.default_tracer().spans()}
    assert spans["io.write"].parent is None
    assert spans["io.write"].thread_name == "ckpt-writer"
    assert spans["io.write"].tid != spans["step"].tid


def test_chrome_trace_named_threads_stable_tids(obs_on, tmp_path):
    def worker(i):
        with obs.span(f"w{i}"):
            time.sleep(0.002)

    with obs.span("main_span"):
        ts = [threading.Thread(target=worker, args=(i,), name=f"worker-{i}")
              for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    path = str(tmp_path / "trace.json")
    n = obs.write_chrome_trace(path, obs.default_tracer().spans())
    assert n == 3
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    metas = [e for e in evs if e["ph"] == "M" and e["name"] == "thread_name"]
    assert len(xs) == 3
    for e in xs:
        assert e["dur"] >= 0
    # main thread pinned to tid 0; workers named
    by_name = {e["name"]: e for e in xs}
    assert by_name["main_span"]["tid"] == 0
    tid_names = {e["tid"]: e["args"]["name"] for e in metas}
    assert tid_names[0] == threading.main_thread().name
    assert {"worker-0", "worker-1"} <= set(tid_names.values())
    assert by_name["w0"]["tid"] != by_name["w1"]["tid"] != 0


def test_chrome_trace_separates_reused_thread_idents(obs_on, tmp_path):
    """CPython reuses thread idents after join; rows are keyed on
    (ident, name) so a stream of short-lived writer threads doesn't
    collapse onto one mislabeled row."""
    spans = [obs.Span("save1", 0.0, 1.0, 12345, "ckpt_finalize_1"),
             obs.Span("save2", 2.0, 3.0, 12345, "ckpt_finalize_2")]
    path = str(tmp_path / "t.json")
    obs.write_chrome_trace(path, spans)
    doc = json.load(open(path))
    xs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    metas = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert xs["save1"]["tid"] != xs["save2"]["tid"]
    assert metas[xs["save1"]["tid"]] == "ckpt_finalize_1"
    assert metas[xs["save2"]["tid"]] == "ckpt_finalize_2"


def test_histogram_merge_rejects_mismatched_buckets(obs_on):
    a, b = obs.MetricsRegistry(), obs.MetricsRegistry()
    a.histogram("h", "", buckets=[0.1, 1.0]).observe(0.05)
    b.histogram("h", "", buckets=[1.0, 10.0]).observe(5.0)
    merged = obs.MetricsRegistry()
    merged.merge(a.snapshot())
    with pytest.raises(ValueError, match="buckets"):
        merged.merge(b.snapshot())


def test_span_disabled_records_nothing():
    assert not obs.enabled()
    with obs.span("ghost"):
        pass
    assert obs.default_tracer().spans() == []


# -----------------------------------------------------------------------
# step stats + regression gate
# -----------------------------------------------------------------------
def test_stepstats_ring_and_percentiles():
    st = obs.StepStats(capacity=100)
    for v in range(1, 101):
        st.add(7.0, 7.0 + v / 1000.0)
    assert st.count == 100
    assert st.p50() == pytest.approx(0.050)
    assert st.p99() == pytest.approx(0.099)
    # rollover: 50 more samples push the window past capacity
    for v in range(101, 151):
        st.add(7.0, 7.0 + v / 1000.0)
    w = st.window()
    assert len(w) == 100 and st.count == 150
    assert min(w) == pytest.approx(0.051)  # oldest 50 rotated out
    s = st.summary()
    assert s["count"] == 150 and s["window"] == 100
    assert s["max_s"] == pytest.approx(0.150)
    assert s["last_s"] == pytest.approx(0.150)
    # the same store as plain values: what rotated out is counted
    snap = st.snapshot()
    assert snap["count"] == 150 and snap["dropped"] == 50
    assert len(snap["records"]) == 100 and snap["stalls"] == []
    newest = dict(zip(snap["fields"], snap["records"][-1]))
    assert (newest["kind"], newest["t_start"]) == (-1.0, 7.0)
    assert newest["t_end"] == pytest.approx(7.150)
    assert newest["t_ready"] is None and newest["seq"] is None
    st.reset()
    assert st.count == 0 and st.window() == []
    assert st.summary() == {"count": 0, "window": 0}


def test_regression_verdicts():
    v = obs.regression_verdict("m", baseline=100.0, current=99.0)
    assert v["verdict"] == "pass"  # within 5%
    v = obs.regression_verdict("m", baseline=100.0, current=90.0)
    assert v["verdict"] == "fail" and v["delta_pct"] == pytest.approx(-10.0)
    # lower-is-better (step time): +10% is a fail
    v = obs.regression_verdict("t", 1.0, 1.1, higher_is_better=False,
                               tolerance=0.05)
    assert v["verdict"] == "fail"
    v = obs.regression_verdict("t", 1.0, 1.02, higher_is_better=False)
    assert v["verdict"] == "pass"
    assert obs.regression_verdict("m", None, 1.0)["verdict"] == "no_baseline"


def test_gate_results_direction_follows_metric_name(tmp_path):
    """bytes/step (BENCH_COST_ONLY) and duration metrics gate on RISING
    above baseline, not falling below it."""
    p = str(tmp_path / "base.json")
    json.dump({"resnet50_bytes_per_step": 100.0}, open(p, "w"))
    worse = obs.gate_results(
        [{"metric": "resnet50_bytes_per_step", "value": 120.0}], p)
    better = obs.gate_results(
        [{"metric": "resnet50_bytes_per_step", "value": 80.0}], p)
    assert worse[0]["verdict"] == "fail"
    assert better[0]["verdict"] == "pass"


def test_tracer_is_bounded(obs_on):
    t = obs.Tracer(capacity=4)
    for i in range(6):
        with t.span(f"s{i}"):
            pass
    spans = t.spans()
    assert len(spans) == 4 and t.dropped == 2
    assert [s.name for s in spans] == ["s2", "s3", "s4", "s5"]  # newest kept
    t.clear()
    assert t.spans() == [] and t.dropped == 0


def test_gate_results_against_bench_artifact(tmp_path):
    baseline = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": 2000.0, "unit": "images/sec",
        "extra_metrics": [
            {"metric": "transformer_train_tokens_per_sec_per_chip",
             "value": 100000.0}],
    }
    p = str(tmp_path / "base.json")
    json.dump(baseline, open(p, "w"))
    results = [
        {"metric": "resnet50_train_images_per_sec_per_chip", "value": 2100.0},
        {"metric": "transformer_train_tokens_per_sec_per_chip",
         "value": 80000.0},
        {"metric": "unbaselined_metric", "value": 1.0},
    ]
    verdicts = obs.gate_results(results, p)
    by = {v["metric"]: v for v in verdicts}
    assert len(verdicts) == 2
    assert by["resnet50_train_images_per_sec_per_chip"]["verdict"] == "pass"
    assert by["transformer_train_tokens_per_sec_per_chip"]["verdict"] == "fail"


# -----------------------------------------------------------------------
# executor wiring
# -----------------------------------------------------------------------
def test_executor_step_telemetry(obs_on):
    exe, loss = _build_step()
    obs.reset()  # drop the startup-program run's records
    for i in range(3):
        exe.run(feed=_feed(i), fetch_list=[loss])
    reg = obs.default_registry()
    h = reg.histogram("paddle_tpu_executor_step_seconds", "")
    assert h.series_summary()["count"] == 3
    # first post-reset run compiled fresh (miss), then cache hits
    cc = reg.counter("paddle_tpu_compile_cache", "")
    assert cc.value(result="miss") == 1
    assert cc.value(result="hit") == 2
    # donation is the serial executor default
    assert reg.counter("paddle_tpu_executor_steps", "").value(
        donated="1") == 3
    assert obs.step_stats().count == 3
    assert obs.step_stats().p50() > 0
    names = [s.name for s in obs.default_tracer().spans()]
    assert names.count("executor.step") == 3
    assert "compile" in names  # the fresh compile rode in a span


def test_executor_sentinel_skip_metrics(obs_on):
    exe, loss = _build_step(name="obs_nan_w")
    fluid.set_flags({"FLAGS_check_numerics": True,
                     "FLAGS_check_numerics_max_consecutive": 5})
    try:
        obs.reset()
        exe.run(feed=_feed(0), fetch_list=[loss])
        exe.run(feed=_feed(1, bad=True), fetch_list=[loss])  # skipped
        exe.run(feed=_feed(2), fetch_list=[loss])
    finally:
        fluid.set_flags({"FLAGS_check_numerics": False,
                         "FLAGS_check_numerics_max_consecutive": 3})
    reg = obs.default_registry()
    assert reg.counter("paddle_tpu_executor_skipped_steps", "").value() == 1
    assert reg.counter("paddle_tpu_sentinel_trips", "").value(
        var="loss_mean") >= 0  # labeled by offending var; total below
    total = sum(
        s["value"] for s in reg.counter(
            "paddle_tpu_sentinel_trips", "").snapshot()["series"])
    assert total == 1
    # the skipped step still landed in the step histogram
    assert reg.histogram("paddle_tpu_executor_step_seconds",
                         "").series_summary()["count"] == 3


def test_executor_cost_attribution_native(obs_on):
    exe, loss = _build_step(name="obs_cost_w")
    fluid.set_flags({"FLAGS_observability_cost": "native"})
    try:
        obs.reset()
        exe.run(feed=_feed(0), fetch_list=[loss])
        exe.run(feed=_feed(1), fetch_list=[loss])  # same entry: no re-cost
    finally:
        fluid.set_flags({"FLAGS_observability_cost": "off"})
    g = obs.default_registry().gauge("paddle_tpu_cost_bytes_per_step", "")
    series = g.snapshot()["series"]
    assert len(series) == 1  # once per compiled entry
    assert series[0]["value"] > 0
    assert series[0]["labels"]["platform"] == "native"
    assert set(series[0]["labels"]) == {"program", "platform"}


def test_device_memory_watermarks(obs_on):
    class FakeDev:
        id = 3

        def __init__(self):
            self.stats = {"bytes_in_use": 100.0}

        def memory_stats(self):
            return self.stats

    dev = FakeDev()
    obs.record_device_memory(dev)
    reg = obs.default_registry()
    in_use = reg.gauge("paddle_tpu_device_bytes_in_use", "")
    peak = reg.gauge("paddle_tpu_device_peak_bytes_in_use", "")
    assert in_use.value(device="3") == 100.0
    # no allocator peak -> monotonic max of samples
    assert peak.value(device="3") == 100.0
    dev.stats = {"bytes_in_use": 60.0}
    obs.record_device_memory(dev)
    assert in_use.value(device="3") == 60.0
    assert peak.value(device="3") == 100.0  # watermark holds
    # allocator-reported peak wins when present (TPU backends)
    dev.stats = {"bytes_in_use": 80.0, "peak_bytes_in_use": 500.0}
    obs.record_device_memory(dev)
    assert peak.value(device="3") == 500.0
    # stats-less backends (CPU jax) are silently skipped
    class NoStats:
        def memory_stats(self):
            return None

    obs.record_device_memory(NoStats())


def test_histogram_rejects_conflicting_buckets(obs_on):
    reg = obs.MetricsRegistry()
    reg.histogram("h", "", buckets=[1.0, 10.0]).observe(5.0)
    reg.histogram("h", "")  # no buckets requested: fine
    with pytest.raises(ValueError, match="already registered"):
        reg.histogram("h", "", buckets=[0.1, 1.0])


@pytest.mark.parametrize("kind", ["serial", "spmd"])
def test_disabled_path_zero_observability_overhead(kind, monkeypatch):
    """The contract with the flag off and no profiler session, for either
    executor: a span is its inert TraceAnnotation and nothing else.  No
    instrument of the registry is reached, nothing is appended to the ring,
    `tracing` and `compiles` read no clock, and nothing the package
    allocated outlives the step.  Two instruments are on all the same.  The
    set-up log has the first runs and their executables, and a steady step
    adds nothing to it.  The step log gets exactly one record a step, from
    exactly eight clock reads in its module: six of `perf_counter`, two of
    `process_time`."""
    import gc
    import tracemalloc

    from paddle_tpu.observability import compiles, stepstats, tracing

    assert not obs.enabled()
    obs.reset()
    step = _stepper(kind)
    for i in range(2):  # warm the compile + caches
        step()
    log = obs.default_compile_log()
    before = log.snapshot()
    assert len(before["runs"]) == 2  # the start-up program, the step
    assert sum(r["fun"].startswith("jit(") and r["run"] is not None
               for r in before["records"]) >= 2
    steps = obs.step_stats()
    assert steps.count == 3  # the start-up program's run, the two steps

    calls = []
    for name in ("record_executor_step", "record_compile_cache",
                 "record_device_memory", "default_registry"):
        monkeypatch.setattr(obs, name,
                            lambda *a, _n=name, **k: calls.append(_n))
    monkeypatch.setattr(tracing.Tracer, "_append",
                        lambda self, s: calls.append("ring"))

    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read with the flag off")

    monkeypatch.setattr(tracing, "time", NoClock())
    monkeypatch.setattr(compiles, "time", NoClock())
    # the step log's module reads its two clocks through these two names
    # and through nothing else
    monkeypatch.setattr(stepstats, "time", NoClock())
    reads = {"wall": 0, "cpu": 0}
    wall, cpu = stepstats._wall, stepstats._cpu

    def counted(clock, name):
        def read():
            reads[name] += 1
            return clock()
        return read

    monkeypatch.setattr(stepstats, "_wall", counted(wall, "wall"))
    monkeypatch.setattr(stepstats, "_cpu", counted(cpu, "cpu"))
    obs_pkg_dir = os.path.dirname(os.path.abspath(obs.__file__))
    tracemalloc.start()
    try:
        for i in range(3):
            step()
        # an annotation's memory goes back at the next collection, not at
        # the with-block's end: live is what a collection leaves
        gc.collect()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    assert calls == []  # no instrument reached, nothing appended
    assert obs.default_tracer().spans() == []
    hits = snap.filter_traces(
        [tracemalloc.Filter(True, os.path.join(obs_pkg_dir, "*"))]
    ).statistics("filename")
    assert hits == [], f"observability allocated while disabled: {hits}"
    assert log.count == len(before["records"])
    assert reads == {"wall": 18, "cpu": 6}  # eight a step
    assert steps.count == 6  # one record a step
    monkeypatch.undo()
    assert log.snapshot() == before
    rows = _log_rows()
    assert [r["fresh"] for r in rows] == [1.0, 1.0, 0.0, 0.0, 0.0, 0.0]
    assert all(r["kind"] == stepstats.KINDS.index(kind) for r in rows[1:])
    # control: the SAME steps with the flag on do reach the instruments
    calls = []
    monkeypatch.setattr(obs, "record_executor_step",
                        lambda *a, **k: calls.append(1))
    fluid.set_flags({"FLAGS_observability": True})
    try:
        step()
    finally:
        fluid.set_flags({"FLAGS_observability": False})
        obs.reset()
    assert calls


# -----------------------------------------------------------------------
# the step's phases as spans: both executors, both sinks
# -----------------------------------------------------------------------
PHASES = ["executor.plan", "executor.stage", "executor.dispatch",
          "executor.commit", "executor.fetch"]
KINDS = ["serial", "spmd"]


def _stepper(kind):
    """A callable that makes one call of `run` into the executor of `kind`;
    the feed is staged on the device(s) once, as a training loop stages
    it."""
    import jax

    _, loss = _build_step(name=f"obs_{kind}_w")
    host = {"x": np.ones((4, 4), "float32")}
    if kind == "serial":
        exe = fluid.Executor(fluid.CPUPlace())
        feed = jax.device_put(host, exe.place.jax_device())
        return lambda: exe.run(feed=feed, fetch_list=[loss])
    from paddle_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    pe = fluid.ParallelExecutor(loss_name=loss.name, mesh=mesh)
    feed = jax.device_put(host, mesh.batch_sharding())
    return lambda: pe.run(feed=feed, fetch_list=[loss])


def _host_events(logdir):
    """[(name, start_ns, end_ns, counts)] of the program's spans on the
    host planes of the trace under `logdir`, by start."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb"))
    evs = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("executor.", "compile")):
                    evs.append((e.name.split("#")[0], e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(evs, key=lambda e: (e[1], -e[2]))


@pytest.mark.parametrize("kind", KINDS)
def test_step_phases_land_in_a_plain_profiler_session(kind, tmp_path):
    """Sink A: a session started with jax.profiler.start_trace itself, the
    flag off, holds executor.step over its five phases in order, with
    their counts, for both executors."""
    import jax

    assert not obs.enabled()
    step = _stepper(kind)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as benchmark/harness/trace.py starts it
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        step()  # compiles; the state comes from the startup program's run
        step()
    finally:
        jax.profiler.stop_trace()
    assert obs.default_tracer().spans() == []  # sink B stayed shut
    evs = _host_events(str(tmp_path))
    steps = [e for e in evs if e[0] == "executor.step"]
    assert len(steps) == 2
    for name, s0, e0, counts in steps:
        # everything inside the step but the fetch's two children, which
        # tests/test_executor_turnaround_spans.py holds
        inner = [e for e in evs if e[0] not in (
                     "executor.step", "compile", "executor.wait",
                     "executor.copy") and s0 <= e[1] and e[2] <= e0]
        assert [e[0] for e in inner] == PHASES
        # in that order, one after the other, none outside its step
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
        assert counts["kind"] == kind and counts["n_feed"] == 1
        assert counts["n_state"] >= 2  # the weight, the bias, ...
        assert "steps" not in counts
    first, second = ([e for e in evs if e[0] == name]
                     for name in ("executor.plan", "executor.stage"))
    assert [e[3]["cache"] for e in first] == ["miss", "hit"]
    # the miss built under `compile`, nested in the plan span
    (comp,) = [e for e in evs if e[0] == "compile"]
    assert first[0][1] <= comp[1] and comp[2] <= first[0][2]
    # steady state: everything the second step stages is in place already
    assert second[1][3]["moved"] == 0
    assert second[1][3]["n"] == steps[1][3]["n_state"] + 1 + 1
    (fetch, _) = [e for e in evs if e[0] == "executor.fetch"]
    assert fetch[3]["n"] == 1


@pytest.mark.parametrize("kind", KINDS)
def test_step_phases_land_in_the_ring_with_parents(kind, obs_on):
    """Sink B: the flag on and no profiler session, the ring holds the same
    tree, each phase with executor.step as its parent, and the step is
    counted as an Executor's is, whichever executor made it."""
    step = _stepper(kind)
    obs.reset()
    step()
    step()
    spans = obs.default_tracer().spans()
    steps = [s for s in spans if s.name == "executor.step"]
    assert len(steps) == 2
    assert all(s.parent == "executor.run" for s in steps)
    for st in steps:
        inner = sorted((s for s in spans
                        if s.name in PHASES and st.t0 <= s.t0
                        and s.t1 <= st.t1), key=lambda s: s.t0)
        assert [s.name for s in inner] == PHASES
        assert all(s.parent == "executor.step" for s in inner)
        assert st.args["kind"] == kind
    stage = [s for s in spans if s.name == "executor.stage"]
    assert stage[1].args["moved"] == 0
    (comp,) = [s for s in spans if s.name == "compile"]
    assert comp.parent == "executor.plan"
    reg = obs.default_registry()
    cc = reg.counter("paddle_tpu_compile_cache", "")
    assert (cc.value(result="miss"), cc.value(result="hit")) == (1, 1)
    assert obs.step_stats().count == 2
    assert reg.counter("paddle_tpu_executor_steps", "").value(
        donated="1") == 2
    assert reg.histogram("paddle_tpu_executor_step_seconds",
                         "").series_summary()["count"] == 2
    # the step's time is the span's, to the fetched value on the host: the
    # log reads the same clock just inside the span's two ends
    assert obs.step_stats().summary()["max_s"] == pytest.approx(
        max(s.duration for s in steps), abs=200e-6)


# -----------------------------------------------------------------------
# the step log: always on, one record a step, both executors
# -----------------------------------------------------------------------
def _log_rows():
    from paddle_tpu.observability import stepstats

    return [dict(zip(stepstats.FIELDS, r))
            for r in obs.step_stats().snapshot()["records"]]


ORDER = ["t_start", "t_dispatch", "t_dispatched", "t_fetch", "t_ready",
         "t_end"]


@pytest.mark.parametrize("kind", KINDS)
def test_step_log_one_record_a_step(kind):
    """The flag off, no profiler: N steps leave N records with consecutive
    `seq`, their boundaries in order on one clock, and periods that add up
    to the run (a step's period is the next step's start less its own)."""
    assert not obs.enabled()
    step = _stepper(kind)
    step()  # compiles
    obs.reset()
    t0 = time.perf_counter()
    for _ in range(5):
        step()
    t1 = time.perf_counter()
    rows = _log_rows()
    assert len(rows) == 5 and obs.step_stats().count == 5
    seqs = [r["seq"] for r in rows]
    assert seqs == [seqs[0] + i for i in range(5)]
    for r in rows:
        marks = [r[k] for k in ORDER]
        assert marks == sorted(marks) and t0 <= marks[0] and marks[-1] <= t1
        assert 0.0 <= r["cpu_ready"] - r["cpu_fetch"]
        assert (r["kind"], r["fresh"]) == (KINDS.index(kind), 0.0)
    starts = [r["t_start"] for r in rows] + [t1]
    periods = [b - a for a, b in zip(starts, starts[1:])]
    assert all(p > 0 for p in periods)
    assert sum(periods) == pytest.approx(t1 - rows[0]["t_start"])
    assert sum(periods) == pytest.approx(t1 - t0, abs=1e-3)
    # the summary is of the records: the step's duration
    summ = obs.step_stats().summary()
    assert summ["count"] == summ["window"] == 5
    assert summ["max_s"] == pytest.approx(
        max(r["t_end"] - r["t_start"] for r in rows))
    obs.reset()
    assert obs.step_stats().count == 0


def test_step_log_without_a_wait_has_no_ready():
    """`return_numpy=False` does not wait: the record says so with NaN
    (None as plain values), and still ends."""
    exe, loss = _build_step(name="obs_nowait_w")
    obs.reset()
    exe.run(feed=_feed(0), fetch_list=[loss], return_numpy=False)
    (row,) = _log_rows()
    assert row["t_ready"] is None
    assert row["cpu_fetch"] is None and row["cpu_ready"] is None
    assert row["t_start"] <= row["t_fetch"] <= row["t_end"]
    assert row["fresh"] == 1.0  # its first run
    obs.reset()


def test_step_log_seq_is_the_spans_seq(tmp_path):
    """One clock with the device trace, by `seq`: inside a plain profiler
    session a record's step and wait are the host plane's `executor.step`
    and `executor.wait` of that `seq`, to 200 us."""
    import jax

    step = _stepper("serial")
    step()
    obs.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            step()
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(str(tmp_path))
    spans = [e for e in evs if e[0] == "executor.step"]
    rows = {r["seq"]: r for r in _log_rows()}
    assert sorted(rows) == sorted(e[3]["seq"] for e in spans)
    for _, s0, e0, counts in spans:
        r = rows[counts["seq"]]
        (wait,) = [e for e in evs if e[0] == "executor.wait"
                   and s0 <= e[1] and e[2] <= e0]
        assert r["t_end"] - r["t_start"] == pytest.approx(
            (e0 - s0) / 1e9, abs=200e-6)
        assert r["t_ready"] - r["t_fetch"] == pytest.approx(
            (wait[2] - wait[1]) / 1e9, abs=200e-6)
    obs.reset()


def test_step_log_keeps_every_step_of_concurrent_threads():
    """Hogwild threads share the one log: each step gets a record of its
    own (a lost update of the count would hand two steps one record and
    leave marks of two steps in it)."""
    from paddle_tpu.observability import stepstats as ss

    threads, steps = 8, 400
    log = ss.StepStats(capacity=threads * steps)

    def work(t):
        for i in range(steps):
            rec = log.begin(t * steps + i, "serial")
            log.mark(rec + ss.DISPATCH)
            log.mark(rec + ss.DISPATCHED)
            log.mark_cpu(rec + ss.FETCH)
            log.mark_cpu(rec + ss.READY)
            log.end(rec, False)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=work, args=(t,))
                for t in range(threads)]
        for th in pool:
            th.start()
        for th in pool:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(th.is_alive() for th in pool)
    assert log.count == threads * steps
    snap = log.snapshot()
    rows = [dict(zip(snap["fields"], r)) for r in snap["records"]]
    assert sorted(r["seq"] for r in rows) == list(range(threads * steps))
    for r in rows:
        marks = [r[k] for k in ORDER]
        assert marks == sorted(marks)


class _Clock:
    """The step log's two clocks by hand: a read of the wall clock takes
    0.1 ms, and the process is on a CPU for a tenth of that."""

    def __init__(self):
        self.wall = self.cpu = 100.0

    def read_wall(self):
        self.wall += 1e-4
        self.cpu += 1e-5
        return self.wall

    def read_cpu(self):
        return self.cpu

    def sleep(self, s):
        self.wall += s

    def spin(self, s):
        self.wall += s
        self.cpu += s


@pytest.fixture
def by_hand(monkeypatch):
    """A toy step whose wait can be made long: (step, clock, hook); the
    step log reads `clock`, and `hook["wait"]` runs inside `executor.wait`
    after the values are ready."""
    import jax

    from paddle_tpu.observability import stepstats

    exe, loss = _build_step(name="obs_stall_w")
    feed = jax.device_put(_feed(0), exe.place.jax_device())
    exe.run(feed=feed, fetch_list=[loss])
    clock, hook = _Clock(), {"wait": None}
    ready = jax.block_until_ready

    def waited(x):
        out = ready(x)
        if hook["wait"] is not None:
            hook["wait"]()
        return out

    monkeypatch.setattr(jax, "block_until_ready", waited)
    monkeypatch.setattr(stepstats, "_wall", clock.read_wall)
    monkeypatch.setattr(stepstats, "_cpu", clock.read_cpu)
    obs.reset()
    yield (lambda: exe.run(feed=feed, fetch_list=[loss])), clock, hook
    obs.reset()


@pytest.mark.parametrize("how", ["asleep", "spinning"])
def test_a_stalled_step_says_so_once(how, by_hand, caplog):
    """After 64 plain steps a step whose fetch takes 50 ms longer is the one
    stalled record, its excess in the wait; the process slept through it
    or was on a CPU, and the record tells which.  It is logged as it ends,
    once; a caller that pauses between two steps has stalled nothing."""
    import logging

    from paddle_tpu.observability.stepstats import BLOCK

    step, clock, hook = by_hand
    with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
        for _ in range(BLOCK - 1):
            step()
        hook["wait"] = lambda: clock.sleep(0.05)
        step()  # the 64th: no reference yet, a long step says nothing
        hook["wait"] = None
        for _ in range(BLOCK + 3):
            step()
        clock.sleep(0.5)  # the caller evaluates, checkpoints, ...
        step()
        assert caplog.records == []
        hook["wait"] = lambda: (clock.sleep if how == "asleep"
                                else clock.spin)(0.05)
        step()
        hook["wait"] = None
        assert len(caplog.records) == 1  # as it ends
        step()
        step()
    (line,) = [r.getMessage() for r in caplog.records]
    snap = obs.step_stats().snapshot()
    (stall,) = snap["stalls"]
    rows = _log_rows()
    assert stall["seq"] == rows[2 * BLOCK + 4]["seq"]
    assert line.startswith(f"step {stall['seq']} stalled: 50.50 ms "
                           "against a median of 0.50; ")
    assert stall["kind"] == "serial"
    assert stall["median_s"] == pytest.approx(5e-4)  # five reads apart
    assert stall["step_s"] == pytest.approx(0.05 + 5e-4)
    assert stall["wait_s"] == pytest.approx(0.05 + 1e-4)
    parts = ("plan_s", "dispatch_s", "commit_s", "wait_s", "copy_s")
    assert sum(stall[k] for k in parts) == pytest.approx(stall["step_s"])
    assert stall["wait_cpu_s"] == pytest.approx(
        1e-5 + (0.0 if how == "asleep" else 0.05))
    assert obs.step_stats().stalls_seen == 1


def test_stall_lines_are_capped_and_export_run_says_the_rest(
        by_hand, caplog, tmp_path):
    import logging

    from paddle_tpu.observability.stepstats import BLOCK, MAX_LINES

    step, clock, hook = by_hand
    with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
        for _ in range(BLOCK):
            step()
        for _ in range(MAX_LINES + 3):
            hook["wait"] = lambda: clock.sleep(0.02)
            step()
            hook["wait"] = None
            step()
        stalled = [r.getMessage() for r in caplog.records]
        assert len(stalled) == MAX_LINES
        assert all(" stalled: 20.50 ms " in ln for ln in stalled)
        assert obs.step_stats().stalls_seen == MAX_LINES + 3
        report = obs.export_run(str(tmp_path))
    assert len(report["steps"]["stalls"]) == MAX_LINES + 3
    (rest,) = [r.getMessage() for r in caplog.records[MAX_LINES:]]
    assert rest.startswith("3 more steps stalled than were logged")


def test_values_already_placed_are_not_counted_as_moved(tmp_path):
    """`moved` on the stage span is exact in both executors: host values
    count, and so does a device array that is somewhere else (on another
    device; single-device under a mesh, where it is resharded); arrays in
    place do not."""
    import jax

    exe, loss = _build_step(name="obs_moved_w")
    from paddle_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    pe = fluid.ParallelExecutor(loss_name=loss.name, mesh=mesh)
    host = {"x": np.ones((4, 4), "float32")}
    scope = fluid.global_scope()
    here = fluid.CPUPlace().jax_device()
    fluid.set_flags({"FLAGS_observability": True})
    obs.reset()
    try:
        exe.run(feed=host, fetch_list=[loss])  # state as startup left it
        scope.set_var("obs_moved_w", jax.device_put(
            np.asarray(scope.find_var("obs_moved_w")), jax.devices()[1]))
        exe.run(feed=host, fetch_list=[loss])  # the feed and the stray one
        exe.run(feed=jax.device_put(host, here), fetch_list=[loss])
        pe.run(feed=host, fetch_list=[loss])   # state on the serial device
        pe.run(feed=host, fetch_list=[loss])   # state in place, feed not
        pe.run(feed=jax.device_put(host, mesh.batch_sharding()),
               fetch_list=[loss])
        moved = [s.args["moved"] for s in obs.default_tracer().spans()
                 if s.name == "executor.stage"]
        n = [s.args["n"] for s in obs.default_tracer().spans()
             if s.name == "executor.stage"]
    finally:
        fluid.set_flags({"FLAGS_observability": False})
        obs.reset()
    # serial: the host feed; with it the array on another device; nothing.
    # On the mesh the first step places every state array, the key and the
    # feed
    assert moved == [1, 2, 0, n[3], 1, 0]


# -----------------------------------------------------------------------
# resilience / elastic accounting (satellite: surfaced, not dropped)
# -----------------------------------------------------------------------
def test_retry_stats_filled_on_success_and_exhaustion(obs_on):
    from paddle_tpu.resilience import retry_with_backoff

    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise ConnectionError("down")
        return "ok"

    stats = {}
    out = retry_with_backoff(flaky, retries=5, base_delay=0.001,
                             sleep=lambda s: None, stats=stats,
                             label="test")
    assert out == "ok"
    assert stats["attempts"] == 3 and stats["retries"] == 2
    assert stats["backoff_s"] > 0
    assert obs.default_registry().counter(
        "paddle_tpu_resilience_retries", "").value(
            label="test", error="ConnectionError") == 2

    stats2 = {}
    with pytest.raises(TimeoutError):
        retry_with_backoff(lambda: (_ for _ in ()).throw(TimeoutError()),
                           retries=2, base_delay=0.001,
                           sleep=lambda s: None, stats=stats2)
    assert stats2["attempts"] == 3 and stats2["retries"] == 2

    # third path: a NON-retryable error after transient retries still
    # fills stats (the retried attempts must not be undercounted)
    attempts3 = []

    def then_fatal():
        attempts3.append(1)
        if len(attempts3) < 3:
            raise ConnectionError("transient")
        raise ValueError("application error")

    stats3 = {}
    with pytest.raises(ValueError):
        retry_with_backoff(then_fatal, retries=5, base_delay=0.001,
                           sleep=lambda s: None, stats=stats3)
    assert stats3["attempts"] == 3 and stats3["retries"] == 2
    assert stats3["backoff_s"] > 0


def test_checkpoint_manager_save_durations(obs_on, tmp_path):
    from paddle_tpu.resilience import CheckpointManager

    exe, loss = _build_step(name="obs_ck_w")
    exe.run(feed=_feed(0), fetch_list=[loss])
    mgr = CheckpointManager(str(tmp_path / "run"), keep_last=2)
    h = mgr.save(1)
    assert h is not None and h.done()
    assert h.stats["step"] == 1
    assert h.stats["save_seconds"] > 0
    assert h.stats["gc_seconds"] >= 0
    assert h.stats["total_seconds"] >= h.stats["save_seconds"]
    # async: stats complete after wait()
    h2 = mgr.save(2, asynchronous=True)
    h2.wait()
    assert h2.stats["save_seconds"] > 0
    reg = obs.default_registry()
    assert reg.counter("paddle_tpu_checkpoint_saves", "").value(
        result="ok") == 2
    assert reg.histogram("paddle_tpu_checkpoint_save_seconds",
                         "").series_summary()["count"] == 2
    assert "ckpt.save" in [s.name for s in obs.default_tracer().spans()]


def test_remote_master_retry_stats_accumulate(obs_on, monkeypatch):
    from paddle_tpu.elastic.rpc import RemoteMaster

    rm = RemoteMaster("127.0.0.1:1")  # nothing listens; no connect yet
    calls = []

    def call_once(req):
        calls.append(1)
        if len(calls) < 2:
            raise ConnectionError("transient")
        return {"ok": True, "counts": {"cur_pass": 0}}

    monkeypatch.setattr(rm, "_call_once", call_once)
    monkeypatch.setattr(rm, "_retry_base_delay", 0.0)
    assert rm.counts() == {"cur_pass": 0}
    assert rm.retry_stats["calls"] == 1
    assert rm.retry_stats["retries"] == 1
    assert rm.last_call_retries == 1


# -----------------------------------------------------------------------
# run artifacts + obsdump + bench integration
# -----------------------------------------------------------------------
def test_export_run_artifacts_and_obsdump(obs_on, tmp_path, monkeypatch):
    import jax

    exe, loss = _build_step(name="obs_art_w")
    obs.reset()
    for i in range(4):
        exe.run(feed=_feed(i), fetch_list=[loss])
    old_report = obs.export_run(str(tmp_path / "few"))
    assert old_report["steps"]["stalls"] == []  # no reference, no stall
    # a step that waits 40 ms longer, once the log has a reference
    feed = jax.device_put(_feed(0), exe.place.jax_device())
    for i in range(64):
        exe.run(feed=feed, fetch_list=[loss])
    ready = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready", lambda x: (ready(x), time.sleep(0.04))[0])
    exe.run(feed=feed, fetch_list=[loss])
    monkeypatch.setattr(jax, "block_until_ready", ready)
    exe.run(feed=feed, fetch_list=[loss])
    base = str(tmp_path / "base.json")
    json.dump({"toy_metric": 100.0}, open(base, "w"))
    d = str(tmp_path / "run")
    report = obs.export_run(
        d, results=[{"metric": "toy_metric", "value": 99.0}],
        baseline_path=base)
    assert sorted(os.listdir(d)) == [
        "metrics.json", "metrics.prom", "report.json", "trace.json"]
    assert report["step_time"]["count"] == 70
    assert set(report["step_time"]) == {
        "count", "window", "mean_s", "min_s", "max_s", "last_s", "p50_s",
        "p90_s", "p99_s"}
    steps = report["steps"]
    assert steps["count"] == 70 and steps["dropped"] == 0
    assert len(steps["records"]) == 70
    assert all(len(r) == len(steps["fields"]) for r in steps["records"])
    slow = int(steps["records"][68][steps["fields"].index("seq")])
    (mine,) = [st for st in steps["stalls"] if st["seq"] == slow]
    assert mine["wait_s"] > 0.04 and mine["wait_cpu_s"] < 0.03  # asleep
    assert report["regression"][0]["verdict"] == "pass"
    prom = open(os.path.join(d, "metrics.prom")).read()
    assert "paddle_tpu_executor_step_seconds_bucket" in prom
    assert "paddle_tpu_compile_cache_total" in prom
    with open(os.path.join(d, "trace.json")) as f:
        doc = json.load(f)
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in doc["traceEvents"])

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obsdump.py"), d],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-500:]
    assert "p50" in out.stdout
    assert f"step {slow} stalled: " in out.stdout
    assert "paddle_tpu_executor_step_seconds" in out.stdout
    assert "[PASS]" in out.stdout
    # a report from before the step log renders as it did
    older = str(tmp_path / "older")
    os.makedirs(older)
    with open(os.path.join(older, "report.json"), "w") as f:
        json.dump({k: v for k, v in report.items() if k != "steps"}, f)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obsdump.py"), older],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-500:]
    assert "p50" in out.stdout and "stalled" not in out.stdout
    # --gate turns a fail verdict into a nonzero exit
    json.dump({"toy_metric": 1000.0}, open(base, "w"))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obsdump.py"), d,
         "--baseline", base, "--gate"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 3
    assert "[FAIL]" in out.stdout


def _bench_obs_env(monkeypatch, tmp_path, model, bs):
    monkeypatch.setenv("BENCH_MODELS", model)
    monkeypatch.setenv("BENCH_BS", bs)
    monkeypatch.setenv("BENCH_STEPS", "2")
    monkeypatch.setenv("BENCH_TUNE", "0")
    monkeypatch.setenv("BENCH_AMP", "0")
    monkeypatch.setenv("BENCH_DEADLINE_S", "0")
    monkeypatch.setenv("BENCH_COMPILE_CACHE", "0")
    monkeypatch.setenv("BENCH_CKPT_DIR", "")
    monkeypatch.setenv("BENCH_OBS_DIR", str(tmp_path / "obs"))
    monkeypatch.setenv("BENCH_BASELINE", str(tmp_path / "base.json"))


def _assert_bench_obs_artifacts(rec, tmp_path, metric):
    # (c) report with p50/p99 + baseline delta verdict
    assert rec["observability"]["steps_recorded"] >= 2
    assert rec["observability"]["step_time_p50_s"] > 0
    assert rec["regression"][0]["metric"] == metric
    assert rec["regression"][0]["verdict"] == "pass"
    d = str(tmp_path / "obs")
    report = json.load(open(os.path.join(d, "report.json")))
    assert report["step_time"]["p99_s"] > 0
    assert report["regression"][0]["verdict"] == "pass"
    # (a) Prometheus snapshot with step-time histogram + compile-cache
    # counters
    prom = open(os.path.join(d, "metrics.prom")).read()
    assert "paddle_tpu_executor_step_seconds_bucket" in prom
    assert 'paddle_tpu_compile_cache_total{result="miss"}' in prom
    # (b) merged chrome trace with named threads
    doc = json.load(open(os.path.join(d, "trace.json")))
    metas = [e for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert metas
    xs = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "executor.step" in xs and "bench.model" in xs


def _run_bench_obs(monkeypatch, capsys, tmp_path, model, bs, metric):
    import bench

    _bench_obs_env(monkeypatch, tmp_path, model, bs)
    json.dump({metric: 0.001}, open(str(tmp_path / "base.json"), "w"))
    fluid.set_flags({"FLAGS_observability": True})
    obs.reset()
    try:
        bench.main()
    finally:
        fluid.set_flags({"FLAGS_observability": False})
        fluid.disable_amp()
        line = capsys.readouterr().out.strip().splitlines()[-1]
        obs.reset()  # artifacts are on disk; keep later tests clean
    rec = json.loads(line)
    assert rec["metric"] == metric, rec
    # a declared CPU run: the row says so, and claims no MFU
    assert rec["platform"] == "cpu" and rec["device_count"] >= 1
    assert rec["mfu"] is None
    _assert_bench_obs_artifacts(rec, tmp_path, metric)


def test_bench_observability_smoke_lenet(monkeypatch, capsys, tmp_path):
    """Tier-1 shape of the acceptance run: FLAGS_observability on, a
    bench smoke produces (a) Prometheus metrics with the step-time
    histogram + compile-cache counters, (b) a merged named-thread chrome
    trace, (c) a report with p50/p99 + baseline verdict."""
    _run_bench_obs(monkeypatch, capsys, tmp_path, "lenet", "4",
                   "mnist_train_images_per_sec_per_chip")


@pytest.mark.slow
def test_bench_observability_smoke_resnet50(monkeypatch, capsys, tmp_path):
    """The literal acceptance criterion (ResNet-50), CPU-sized; slow —
    tier-1 proves the same path on lenet."""
    _run_bench_obs(monkeypatch, capsys, tmp_path, "resnet50", "2",
                   "resnet50_train_images_per_sec_per_chip")
