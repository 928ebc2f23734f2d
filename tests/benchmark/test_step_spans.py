"""The split of the first device's idle time by the executors' spans
(benchmark/harness/step_spans.py) and the six readers on top of it, held
exactly on a hand-made trace."""

import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import manifest, step_spans, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1e3  # the reduction counts in ns

# us of device 0's idle time under each span of trace_step_spans.textproto,
# by hand (the picture is in the file):
#   [2,8):     plan [2,3) 1, stage [3,6) 3, dispatch [6,8) 2
#   [12,12.5): under executor.step alone 0.5
#   [16,27.5): fetch [16,18) 2, step [18,19) 1, outside [19,21) 2,
#              plan [21,23) 2, stage [23,26) 3, dispatch [26,27.5) 1.5
#   [33.5,40): commit [33.5,34) 0.5, fetch [34,38) 4, step [38,39) 1,
#              outside [39,40) 1
BY_SPAN = {"executor.plan": 3.0, "executor.stage": 6.0,
           "executor.dispatch": 3.5, "executor.fetch": 6.0,
           "executor.commit": 0.5, "executor.step": 2.5}
IDLE_US = 24.5
GAPS_MS = {"plan": 3.0e-3 / 2, "stage": 6.0e-3 / 2, "dispatch": 3.5e-3 / 2,
           "fetch": 6.0e-3 / 2, None: 6.0e-3 / 2}
OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 4}}
READERS = {"gap_plan_ms.train": "plan", "gap_stage_ms.train": "stage",
           "gap_dispatch_ms.train": "dispatch",
           "gap_fetch_ms.train": "fetch", "gap_unattributed_ms.train": None}


def _text(name):
    return open(os.path.join(DATA, name)).read()


def _profile(name="trace_step_spans.textproto"):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(_text(name))


@pytest.fixture
def trace_root(tmp_path, monkeypatch):
    """bench_out/trace as the harness leaves it: one .xplane.pb a cell."""
    from jax.profiler import ProfileData

    def write(name, cell="transformer-train"):
        d = tmp_path / cell / "plugins" / "profile" / "2026_01_01"
        d.mkdir(parents=True, exist_ok=True)
        (d / "vm.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(_text(name)))

    monkeypatch.setattr(step_spans, "TRACE_ROOT", str(tmp_path))
    step_spans._parsed.clear()
    yield write
    step_spans._parsed.clear()


def _host_only(spans):
    """A trace as a CPU rehearsal leaves it: no device plane."""
    events = [types.SimpleNamespace(name=n, start_ns=s, duration_ns=e - s)
              for n, s, e in spans]
    return types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(
            name="python", events=events)])])


def test_each_idle_piece_goes_to_the_span_that_covers_it():
    red = step_spans.reduce(_profile())
    assert red["window_ns"] == pytest.approx(40 * US)
    assert red["idle_ns"] == pytest.approx(IDLE_US * US)
    assert set(red["by_span"]) == set(BY_SPAN)
    for name, us in BY_SPAN.items():
        assert red["by_span"][name] == pytest.approx(us * US), name
    assert red["steps"] == 2
    # the spans' own durations, whatever the device did under them
    assert red["host_ns"]["executor.step"] == pytest.approx(36 * US)
    assert red["host_ns"]["executor.stage"] == pytest.approx(6 * US)


def test_the_split_is_of_device_0_alone_and_sums_to_its_idle_time():
    prof = _profile()
    red = step_spans.reduce(prof)
    # device 1 is busy all through the window: read, it would halve this
    outside = red["idle_ns"] - sum(red["by_span"].values())
    assert outside == pytest.approx(3.0 * US)  # [19,21) and [39,40)
    # the same idle time as the benchmark's own reduction groups by
    # bench.step and the operation before
    assert red["idle_ns"] == pytest.approx(
        sum(s for _, s in trace.reduce(prof)["idle_gaps"]) * 1e9)


def test_a_piece_under_nested_spans_goes_to_the_innermost():
    spans = [("executor.step", 0.0, 100.0, {}),
             ("executor.dispatch", 10.0, 90.0, {}),
             ("executor.step", 20.0, 60.0, {}),   # a nested executor's step
             ("executor.fetch", 30.0, 40.0, {})]
    got = step_spans.split_idle([(5.0, 95.0)], spans)
    assert got == {"executor.step": pytest.approx(5 + 5 + 10 + 20),
                   "executor.dispatch": pytest.approx(10 + 30),
                   "executor.fetch": pytest.approx(10)}
    # by intersection, not by the gap's midpoint: a gap that straddles two
    # spans is cut at the boundary between them
    got = step_spans.split_idle(
        [(0.0, 10.0)], [("executor.plan", 0.0, 1.0, {}),
                        ("executor.stage", 1.0, 10.0, {})])
    assert got == {"executor.plan": pytest.approx(1.0),
                   "executor.stage": pytest.approx(9.0)}


@pytest.mark.parametrize("reader", sorted(READERS))
def test_gap_reader_reads_its_phase_per_traced_step(reader, trace_root):
    trace_root("trace_step_spans.textproto")
    mod = manifest.load_py(os.path.join(
        REPO, "benchmark", "layer_metrics", reader + ".py"))
    assert mod.read(OBS) == pytest.approx(GAPS_MS[READERS[reader]])
    # nothing to read: no traced run, no steps, another kind
    assert mod.read({}) is None
    assert mod.read({**OBS, "trace_steps": 0}) is None
    assert mod.read({k: v for k, v in OBS.items() if k != "trace"}) is None
    assert mod.read({**OBS, "kind": "serve"}) is None


def test_the_five_gaps_sum_to_the_idle_time_per_traced_step(trace_root):
    trace_root("trace_step_spans.textproto")
    total = sum(step_spans.gap_ms(OBS, p) for p in READERS.values())
    assert total == pytest.approx(IDLE_US * 1e-3 / 2)
    # device_idle.train x window / traced steps, as the acceptance holds it
    red = trace.reduce(_profile())
    dev0_idle_share = IDLE_US * US / 1e9 / red["window_s"]
    assert total == pytest.approx(
        dev0_idle_share * red["window_s"] * 1e3 / 2)


def test_values_moved_reads_counts_from_suffix_and_from_stats(trace_root):
    trace_root("trace_step_spans.textproto")
    mod = manifest.load_py(os.path.join(
        REPO, "benchmark", "layer_metrics", "values_moved_per_step.train.py"))
    # step 1's `#n=1003,moved=2#` and step 2's stats (moved 0); the warm-up
    # step's 1003 lie before the window
    assert mod.read(OBS) == pytest.approx(2 / 2)
    assert mod.read({}) is None
    assert mod.read({**OBS, "kind": "serve"}) is None


def test_without_a_device_plane_only_the_count_is_read(monkeypatch):
    prof = _host_only([
        ("bench.window", 0.0, 100.0),
        ("executor.step#kind=spmd#", 10.0, 90.0),
        ("executor.stage#n=7,moved=3#", 20.0, 30.0),
        ("executor.fetch", 80.0, 90.0)])
    red = step_spans.reduce(prof)
    assert red["idle_ns"] is None and red["by_span"] == {}
    assert red["moved"] == 3 and red["steps"] == 1
    monkeypatch.setattr(step_spans, "newest", lambda: red)
    obs = {**OBS, "trace_steps": 1}
    assert step_spans.values_moved(obs) == pytest.approx(3.0)
    for phase in READERS.values():
        assert step_spans.gap_ms(obs, phase) is None


@pytest.mark.parametrize("name", ["trace_small.textproto", None])
def test_a_program_without_the_spans_reports_nothing(name, trace_root):
    """The parent of the PR that added the spans, and a run with no trace
    on disk: every reader returns None and none raises."""
    if name:
        trace_root(name)
        assert step_spans.reduce(_profile(name)) is None
    for phase in READERS.values():
        assert step_spans.gap_ms(OBS, phase) is None
    assert step_spans.values_moved(OBS) is None


def test_the_newest_trace_is_found_and_parsed_once(trace_root, monkeypatch):
    trace_root("trace_small.textproto", cell="resnet50-train")
    older = step_spans.newest_trace()
    os.utime(older, (1, 1))
    trace_root("trace_step_spans.textproto", cell="transformer-train")
    assert step_spans.newest_trace() != older
    first = step_spans.newest()
    assert first["moved"] == 2
    from jax.profiler import ProfileData

    monkeypatch.setattr(ProfileData, "from_file", lambda path: 1 / 0)
    assert step_spans.newest() is first


@pytest.mark.parametrize("raw,name,counts", [
    ("executor.plan", "executor.plan", {}),
    ("executor.stage#n=1003,moved=2#", "executor.stage",
     {"n": 1003, "moved": 2}),
    ("executor.plan#cache=hit#", "executor.plan", {"cache": "hit"}),
    ("executor.step#kind=spmd,n_state=9#", "executor.step",
     {"kind": "spmd", "n_state": 9}),
])
def test_span_name_and_counts(raw, name, counts):
    ev = types.SimpleNamespace(name=raw)
    assert step_spans.span_name(raw) == name
    assert step_spans.span_counts(ev) == counts
    # stats win over the suffix: they are what the profiler parsed
    ev.stats = [("moved", 5)]
    assert step_spans.span_counts(ev) == {**counts, "moved": 5}


def test_clock_check_pairs_steps_with_the_modules_runs():
    prof = _profile()
    red = step_spans.reduce(prof)
    assert red["clock"] == {
        "steps": 2, "module_runs": 2, "steps_paired": 2,
        "dispatch_after_first_op": 0, "fetch_before_last_op": 0,
        "min_dispatch_lead_us": pytest.approx(1.5),
        "min_fetch_lag_us": pytest.approx(2.0)}
    # a device clock 3 us behind the host's: both dispatches appear to
    # begin after their module did
    spans = [(n, s + 3 * US, e + 3 * US, c)
             for n, s, e, c in step_spans.executor_spans(prof)]
    t0, t1 = step_spans.window(prof)
    skew = step_spans.clock_check(prof, spans, t0, t1 + 3 * US)
    assert skew["dispatch_after_first_op"] == 2
    assert skew["fetch_before_last_op"] == 0
    assert skew["min_dispatch_lead_us"] == pytest.approx(-1.5)
