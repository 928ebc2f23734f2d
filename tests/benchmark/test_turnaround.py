"""The step's turnaround on clocks that cannot disagree
(benchmark/harness/turnaround.py) and the seven readers on top of it, held
exactly on a hand-made trace; and that a shift of the device's clock moves
none of it but the skew."""

import json
import os
import re
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import manifest, step_spans, turnaround

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = "trace_turnaround.textproto"
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
US = 1e3  # the reduction counts in ns

# us, by hand (the table is in the trace's file)
PARTS_US = {"host": 2000.0, "runtime": 1500.0, "copy": 600.0,
            "release": 400.0, "caller": 200.0, "entry": 800.0}
GAP_US, LO_US, HI_US = 3500.0, -500.0, 800.0
READERS = {"turnaround_host_ms.train": "host",
           "turnaround_runtime_ms.train": "runtime",
           "turnaround_copy_ms.train": "copy",
           "turnaround_release_ms.train": "release",
           "turnaround_caller_ms.train": "caller",
           "turnaround_entry_ms.train": "entry"}
SKEW = "clock_skew_us.train"
TRAIN_CELLS = ["transformer-train", "resnet50-train", "transformer-train-dp4",
               "ouro-train-loop4", "moonlight-train-ep8share",
               "keye-train-dsa16k"]
OBS = {"kind": "train", "trace_steps": 4, "trace": {"n_ops": 4}}


def _text(name=TRACE, device_shift_us=0, drop=None):
    """The trace's text; with every line of the device planes moved by
    `device_shift_us` (what a skew between the profiler's two clocks is);
    without the events of the span whose metadata id is `drop`."""
    text = open(os.path.join(DATA, name)).read()
    if device_shift_us:
        devices, host = text.split('planes {\n  name: "/host:CPU"')
        devices = re.sub(
            r"timestamp_ns: (\d+)",
            lambda m: "timestamp_ns: %d" % (
                int(m.group(1)) + device_shift_us * 1000), devices)
        text = devices + 'planes {\n  name: "/host:CPU"' + host
    if drop is not None:
        text = re.sub(r" *events \{ metadata_id: %d .*\n" % drop, "", text)
    return text


def _profile(**kw):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(_text(**kw))


def _reader(name):
    return manifest.load_py(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))


def test_every_part_is_held_exactly():
    red = turnaround.reduce(_profile())
    assert red["steps"] == 3 and red["boundaries"] == 2
    for part, us in PARTS_US.items():
        assert red[part + "_ns"] == pytest.approx(us * US), part
    assert red["gap_ns"] == pytest.approx(GAP_US * US)
    assert red["lo_ns"] == pytest.approx(LO_US * US)
    assert red["hi_ns"] == pytest.approx(HI_US * US)
    assert red["shift_ns"] == 0.0
    # the spans' own durations a step, which no device has a say in
    assert red["phase_ns"] == {
        "executor.plan": pytest.approx(200 * US),
        "executor.stage": pytest.approx(400 * US),
        "executor.dispatch": pytest.approx(2000 / 3 * US)}


def test_the_parts_are_contiguous_and_sum_to_the_gap():
    red = turnaround.reduce(_profile())
    parts = sum(red[p + "_ns"] for p in turnaround.PARTS)
    assert parts == pytest.approx(red["host_ns"], abs=1e-6)
    assert red["host_ns"] + red["runtime_ns"] == red["gap_ns"]


@pytest.mark.parametrize("reader", sorted(READERS))
def test_reader_reads_its_part_per_boundary(reader, trace_root):
    trace_root(_text())
    mod = _reader(reader)
    assert mod.read(OBS) == pytest.approx(PARTS_US[READERS[reader]] * 1e-3)
    # nothing to read: no traced run, no steps, another kind, and a trace in
    # which no operation ran on a device (a CPU rehearsal)
    assert mod.read({}) is None
    assert mod.read({**OBS, "trace_steps": 0}) is None
    assert mod.read({k: v for k, v in OBS.items() if k != "trace"}) is None
    assert mod.read({**OBS, "kind": "serve"}) is None
    assert mod.read({**OBS, "trace": {"n_ops": 0}}) is None


@pytest.mark.parametrize("shift_us,skew_us,sign", [
    (0, 0.0, 0), (2000, 1200.0, -1), (-2000, 1500.0, 1)])
def test_a_shift_of_the_devices_clock_moves_the_skew_and_nothing_else(
        shift_us, skew_us, sign, trace_root):
    """The device plane 2 ms late (every module appears to end 1.2 ms after
    its wait returned) and 2 ms early (to start 1.5 ms before its dispatch
    began, the records' mode A): the six parts are bit-equal, the skew
    reads the violation and its sign the direction that repairs it."""
    base = turnaround.reduce(_profile())
    red = turnaround.reduce(_profile(device_shift_us=shift_us))
    for part in PARTS_US:
        assert red[part + "_ns"] == base[part + "_ns"], part
    assert red["gap_ns"] == base["gap_ns"]
    assert red["room_ns"] == base["room_ns"] == (HI_US - LO_US) * US
    assert red["shift_ns"] == sign * skew_us * US
    trace_root(_text(device_shift_us=shift_us))
    assert _reader(SKEW).read(OBS) == skew_us
    for reader, part in READERS.items():
        assert _reader(reader).read(OBS) == base[part + "_ns"] / 1e6


def test_the_edge_gaps_are_left_out():
    prof = _profile()
    spans = step_spans.executor_spans(prof)
    t0, t1 = step_spans.window(prof)
    assert [s["seq"] for s in turnaround.host_steps(spans, t0, t1)] == [
        7, 8, 9]
    # the warm-up step before the window and the one whose executor.run
    # outlasts it are steps like the others: a window over all five
    # takes 6 -> 7 (the host idle for 51.7 ms) and 9 -> 10 in
    every = turnaround.host_steps(spans, float("-inf"), float("inf"))
    assert [s["seq"] for s in every] == [6, 7, 8, 9, 10]
    assert every[1]["dispatch"] - every[0]["wait"] == pytest.approx(
        51700 * US)
    assert every[4]["dispatch"] - every[3]["wait"] == pytest.approx(
        2000 * US)
    assert every[3]["run"][1] - every[3]["step"][1] == pytest.approx(
        200 * US)


@pytest.mark.parametrize("name", ["trace_step_spans.textproto",
                                  "trace_small.textproto", None])
def test_a_program_without_the_spans_reports_nothing(name, trace_root):
    """The parent of the PR that added executor.wait and executor.run (its
    trace has executor.step over the five phases), a trace with no span of
    the program at all, no trace on disk: every reader returns None."""
    if name:
        trace_root(_text(name))
        from jax.profiler import ProfileData

        assert turnaround.reduce(
            ProfileData.from_text_proto(_text(name))) is None
    for reader in list(READERS) + [SKEW]:
        assert _reader(reader).read(OBS) is None


@pytest.mark.parametrize("drop,why", [
    (17, "return_numpy=False: no executor.wait, no mark of the device's end"),
    (10, "no executor.run: the parent's tree with a wait alone"),
    (108, "step 8 is not in the trace: 7 and 9 are not neighbours")])
def test_no_boundary_without_both_marks_of_two_neighbouring_steps(drop, why):
    assert turnaround.reduce(_profile(drop=drop)) is None, why


def test_without_the_devices_runs_only_the_host_side_is_read():
    """No device plane (the reducer on a CPU rehearsal's trace): the host
    parts stand, G, the runtime's share and the skew are None; and a device
    whose module ran twice a step pairs with nothing."""
    prof = _profile()
    host_only = types.SimpleNamespace(
        planes=[p for p in prof.planes if p.name.startswith("/host:")])
    red = turnaround.reduce(host_only)
    assert red["host_ns"] == pytest.approx(PARTS_US["host"] * US)
    assert red["entry_ns"] == pytest.approx(PARTS_US["entry"] * US)
    for key in ("gap_ns", "runtime_ns", "lo_ns", "hi_ns", "room_ns",
                "shift_ns"):
        assert red[key] is None, key
    steps = turnaround.host_steps(step_spans.executor_spans(prof),
                                  *step_spans.window(prof))
    runs = turnaround.module_runs(prof)
    assert turnaround.pair_runs(steps, runs) == [1, 2, 3]  # 0: the warm-up's
    assert turnaround.pair_runs(steps, runs[:2]) is None
    assert turnaround.pair_runs(steps, []) is None


def test_the_modules_runs_are_the_first_devices_and_the_steps_own():
    runs = turnaround.module_runs(_profile())
    # not jit_small's 50 us between two steps, not device 1's half a step on
    assert [(s - 5e8) / US for s, _ in runs] == [
        -148500.0, 2300.0, 103400.0, 205200.0, 306500.0]
    assert [(e - s) / US for s, e in runs][1:3] == [97900.0, 98000.0]


def test_the_newest_trace_is_parsed_once(trace_root, monkeypatch):
    trace_root(_text())
    first = turnaround.newest()
    assert first["boundaries"] == 2
    from jax.profiler import ProfileData

    monkeypatch.setattr(ProfileData, "from_serialized_xspace",
                        lambda raw: 1 / 0)
    assert turnaround.newest() is first


def test_the_seven_readers_are_in_the_manifest_for_every_training_cell(
        manifest_holds):
    entries = manifest_holds(
        "per_layer", list(READERS) + [SKEW], cells=TRAIN_CELLS,
        better="lower", source="program_span", layer="program to step",
        moves="train_samples_per_s")
    for m in entries:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["unit"] == ("us" if m["name"] == SKEW else "ms")
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py"))
    for cell in TRAIN_CELLS:
        reported = {m["name"] for m in
                    manifest.Cell(MANIFEST, cell).metrics("per_layer")}
        assert set(READERS) | {SKEW} <= reported, cell


def test_the_split_that_intersected_the_clocks_is_gone():
    """The five `gap_*_ms.train` set host spans beside device intervals and
    walked with the skew (PERF.md 6, PR 35); PR 42 retired them, entries,
    readers and the code under them."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert not [n for n in names if n.startswith("gap_")]
    readers = os.listdir(os.path.join(REPO, "benchmark", "layer_metrics"))
    assert not [f for f in readers if f.startswith("gap_")]
    for gone in ("gap_ms", "split_idle", "idle_intervals", "clock_check",
                 "innermost_segments", "first_device_ops"):
        assert not hasattr(step_spans, gone), gone
