"""The program's spans on the host plane (benchmark/harness/step_spans.py:
names, counts, host durations, the values a step moved) and the reader on
top of them, held exactly on a hand-made trace; and the idle gaps of
trace.reduce named by the innermost of those spans."""

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

# us of device 0's idle gaps in trace_step_spans.textproto and the innermost
# span over each gap's middle, by hand (the picture is in the file):
#   [2,8)      middle 5      stage [3,6)      after copy-done.6
#   [12,12.5)  middle 12.25  executor.step    after fusion.1
#   [16,27.5)  middle 21.75  plan [21,23)     after fusion.2
#   [33.5,40)  middle 36.75  fetch [34,38)    after fusion.1
IDLE_GAPS_US = {"executor.stage|after:copy-done.6": 6.0,
                "executor.step|after:fusion.1": 0.5,
                "executor.plan|after:fusion.2": 11.5,
                "executor.fetch|after:fusion.1": 6.5}
IDLE_US = 24.5
OBS = {"kind": "train", "trace_steps": 2, "trace": {"n_ops": 4}}


def _profile(name="trace_step_spans.textproto"):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(open(os.path.join(DATA, name)).read())


def _host_only(spans):
    """A trace as a CPU rehearsal leaves it: no device plane."""
    events = [types.SimpleNamespace(name=n, start_ns=s, duration_ns=e - s)
              for n, s, e in spans]
    return types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(
            name="python", events=events)])])


def test_the_spans_sum_to_their_own_host_durations():
    red = step_spans.reduce(_profile())
    assert set(red) == {"window_ns", "host_ns", "steps", "moved"}
    assert red["window_ns"] == pytest.approx(40 * US)
    assert red["steps"] == 2 and red["moved"] == 2
    # the spans' own durations, whatever the device did under them
    assert red["host_ns"]["executor.step"] == pytest.approx(36 * US)
    assert red["host_ns"]["executor.stage"] == pytest.approx(6 * US)
    assert red["host_ns"]["executor.fetch"] == pytest.approx(7 * US)


def test_host_spans_keeps_the_benchmarks_and_the_executors_annotations():
    spans = trace.host_spans(_profile())
    names = {n for n, _, _ in spans}
    # both prefixes, the counts the profiler appends cut off, nothing else
    assert names == {"bench.window", "bench.step", "executor.step",
                     "executor.plan", "executor.stage", "executor.dispatch",
                     "executor.commit", "executor.fetch"}
    assert not {"not.ours", "compile"} & names
    assert all(n.startswith(trace.SPAN_PREFIXES) for n in names)
    assert trace.window(_profile()) == (1000.0, 1000.0 + 40 * US)
    assert step_spans.window(_profile()) == trace.window(_profile())


def test_an_idle_gap_is_named_by_the_innermost_executor_span_over_it():
    red = trace.reduce(_profile())
    gaps = dict(red["idle_gaps"])
    assert set(gaps) == set(IDLE_GAPS_US)
    for key, us in IDLE_GAPS_US.items():
        assert gaps[key] == pytest.approx(us * US / 1e9), key
    # device 0 alone (device 1 is busy all through), and all its idle time
    assert sum(gaps.values()) == pytest.approx(IDLE_US * US / 1e9)
    # largest first, `<span>|after:<operation before the gap>`
    assert [k for k, _ in red["idle_gaps"]][0] == \
        "executor.plan|after:fusion.2"


@pytest.mark.parametrize("t,want", [
    (5.0, "bench.step"), (15.0, "executor.dispatch"), (35.0, "executor.wait"),
    (45.0, "executor.fetch"), (60.0, "executor.step"), (95.0, "bench.step"),
    (150.0, "outside-spans")])
def test_covering_is_the_shortest_span_over_the_moment(t, want):
    spans = [("bench.step", 0.0, 100.0), ("executor.step", 10.0, 90.0),
             ("executor.dispatch", 10.0, 20.0),
             ("executor.fetch", 30.0, 50.0), ("executor.wait", 30.0, 40.0)]
    assert trace._covering(spans, t) == want


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
    assert red["moved"] == 3 and red["steps"] == 1
    assert red["host_ns"]["executor.fetch"] == 10.0
    monkeypatch.setattr(step_spans, "newest", lambda: red)
    assert step_spans.values_moved({**OBS, "trace_steps": 1}) == \
        pytest.approx(3.0)
    # and trace.reduce of it is empty: no device ran anything
    assert trace.reduce(prof)["idle_gaps"] == []


@pytest.mark.parametrize("name", ["trace_small.textproto", None])
def test_a_program_without_the_spans_reports_nothing(name, trace_root):
    """The parent of the PR that added the spans, and a run with no trace
    on disk: every reader returns None and none raises."""
    if name:
        trace_root(name)
        assert step_spans.reduce(_profile(name)) is None
    assert step_spans.values_moved(OBS) is None


def test_the_newest_trace_is_found_and_parsed_once(trace_root, monkeypatch):
    trace_root("trace_small.textproto", cell="resnet50-train")
    older = trace.newest_trace()
    os.utime(older, (1, 1))
    trace_root("trace_step_spans.textproto", cell="transformer-train")
    assert trace.newest_trace() != older
    first = step_spans.newest()
    assert first["moved"] == 2
    from jax.profiler import ProfileData

    monkeypatch.setattr(ProfileData, "from_serialized_xspace",
                        lambda raw: 1 / 0)
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
