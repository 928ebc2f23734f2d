"""The yardstick's arithmetic: percentiles, the gap between tokens, the loss
check, the peaks table, FLOP counts, the traffic generator, the per-layer
readers, the reduction of a trace, and the plain references against the
programs they are references of."""

import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import (device, flops, manifest, reference, stats,
                               trace, traffic)

PARKED = "decoder2048-batch"

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([5, 1, 4, 2, 3], 95, 4.8),
    ([10.0], 95, 10.0),
    ([1, 2], 50, 1.5),
    (list(range(101)), 95, 95.0),
])
def test_percentile_is_numpys(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_tpot_is_time_after_the_first_token_over_tokens_after_it():
    # admitted at 10.0, first token 0.5 s later, finished at 12.5 with 5
    # tokens: 2.0 s for the 4 tokens after the first
    assert stats.tpot_s(10.0, 0.5, 12.5, 5) == pytest.approx(0.5)
    with pytest.raises(ValueError):
        stats.tpot_s(0.0, 0.1, 1.0, 1)


@pytest.mark.parametrize("losses,want", [
    ([5.0, 4.0, 3.0, 2.0], True),
    ([2.0, 3.0], False),
    ([5.0, 4.0, float("nan"), 1.0], False),
    ([5, 5, 4, 4, 3, 3, 2, 6], True),       # mean(2, 6) below mean(5, 5)
    ([1, 1, 4, 4, 3, 3, 2, 2], False),
    ([], False),
])
def test_loss_fell(losses, want):
    assert stats.loss_fell(losses) is want


@pytest.mark.parametrize("losses,start,want", [
    ([0.9, 2.3, 2.7], 5.2, True),       # few steps: held to the start
    ([0.9, 2.3, 5.7], 5.2, False),
    ([0.9, 0.5], float("nan"), False),
    ([0.9], 5.2, True),
    ([1, 1, 4, 4, 3, 3, 2, 2], 9.0, False),   # 8 steps: the quarters
])
def test_loss_fell_over_few_steps_is_held_to_the_start(losses, start, want):
    assert stats.loss_fell(losses, start=start) is want


@pytest.mark.parametrize("losses,start,want", [
    # a loss fetched in bf16 that falls by under one step of bf16 a window
    # (the four-chip Transformer cell): both quarters read the same
    ([10.1875] * 8, 10.375, True),
    ([10.25] * 4 + [10.1875] * 4, 10.4375, True),
    ([10.1875] * 4 + [10.25] * 4, 10.4375, True),   # one step up: rounding
    ([10.0] * 4 + [10.25] * 4, 10.4375, False),     # four steps up
    ([10.375] * 8, 10.375, False),                  # never left the start
    ([5, 5, 4, 4, 3, 3, 2, 2], 1.5, False),         # fell, but above start
    ([5, 5, 4, 4, 3, 3, 2, float("inf")], 9.0, False),
])
def test_loss_fell_is_held_to_the_start_and_one_bf16_step(losses, start,
                                                          want):
    assert stats.loss_fell(losses, start=start) is want


@pytest.mark.parametrize("x,want", [
    (10.4, 0.0625), (7.6, 0.03125), (1.0, 0.0078125), (-10.4, 0.0625),
    (0.0037, 2.0 ** -16), (0.0, 0.0),
])
def test_bf16_step(x, want):
    assert stats.bf16_step(x) == want


@pytest.mark.parametrize("copies,want", [
    ("same", (True, 0.0)),
    ("apart", (True, 0.5)),
    ("sharded", (False, None)),
])
def test_copies_apart_over_four_devices(copies, want):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmark.harness import kind_train

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("dp",))
    x = np.arange(8, dtype=np.float32).reshape(4, 2)
    if copies == "sharded":
        arr = jax.device_put(x, NamedSharding(mesh, PartitionSpec("dp")))
    else:
        # the last chip's copy half a unit off: what a gradient that was
        # not reduced over the chips leaves behind
        off = [0.0, 0.0, 0.0, 0.5 if copies == "apart" else 0.0]
        arr = jax.make_array_from_single_device_arrays(
            x.shape, NamedSharding(mesh, PartitionSpec()),
            [jax.device_put(x + o, d) for o, d in zip(off, devs)])
    assert kind_train.copies_apart(arr) == want
    assert 0.0 <= kind_train.COPIES_ATOL < 5e-4    # under Adam's step


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9 and "v5e" in p["source"]
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


def test_resnet_flops_are_counted_from_the_shapes():
    # torchvision's and the paper's figure for ResNet-50 at 224x224 is
    # multiply-adds: 4.09e9.  bench.py took it for FLOPs
    import bench

    assert flops.resnet_forward_macs(50, 224, 1000) == 4089184256
    assert flops.resnet_train_flops_per_image(50, 224, 1000) == \
        pytest.approx(2 * bench.RESNET50_TRAIN_FLOPS_PER_IMG, rel=1e-3)
    # the 7x7 stem alone at 224: 112 x 112 outputs of 7 x 7 x 3 x 64
    assert flops.resnet_forward_macs(50, 224, 1000) > 118013952
    assert flops.resnet_forward_macs(50, 112, 1000) < \
        flops.resnet_forward_macs(50, 224, 1000) / 3.7


def test_transformer_flops_match_bench_py():
    import bench

    cfg = types.SimpleNamespace(d_model=512, d_inner=2048, n_layer=6,
                                max_length=256, trg_vocab_size=32000)
    assert flops.transformer_train_flops_per_token(
        512, 2048, 6, 256, 32000) == \
        bench._transformer_train_flops_per_token(cfg)


def test_every_seed_gets_the_mixs_set_of_lengths_in_another_order():
    mix = manifest.read_json(os.path.join(
        REPO, "benchmark", "traffic", "batch-decode.json"))
    a = traffic.serve_requests(mix, 32000, 1)
    b = traffic.serve_requests(mix, 32000, 3000000019)
    again = traffic.serve_requests(mix, 32000, 1)
    assert a == again
    assert len(a) == mix["requests"]
    shapes_a = [(len(p), o) for p, o in a]
    shapes_b = [(len(p), o) for p, o in b]
    assert sorted(shapes_a) == sorted(shapes_b) and shapes_a != shapes_b
    assert [p for p, _ in a] != [p for p, _ in b]
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    assert all(lo <= len(p) <= hi for p, _ in a)
    olo, ohi = mix["output_len"]["lo"], mix["output_len"]["hi"]
    assert all(olo <= o <= ohi for _, o in a)
    # drawn, not laid on a grid: many distinct lengths of both kinds
    assert len({o for _, o in a}) > 30 and len({len(p) for p, _ in a}) > 60
    assert all(1 <= t < 32000 for p, _ in a for t in p)
    assert traffic.longest_context(mix) == hi + ohi


def test_a_table_of_lengths_is_a_distribution_given_as_data():
    rng = np.random.RandomState(0)
    spec = {"dist": "table", "values": [8, 64, 512], "weights": [6, 3, 1]}
    got = traffic.draw_lengths(rng, spec, 2000)
    assert set(got) == {8, 64, 512}
    assert 0.55 < got.count(8) / 2000 < 0.65
    assert 0.07 < got.count(512) / 2000 < 0.13
    even = traffic.draw_lengths(rng, {"dist": "table", "values": [1, 2]}, 400)
    assert 150 < even.count(1) < 250


def test_traffic_shared_prefix_and_fixed_lengths():
    mix = {"requests": 3, "shape_seed": 1, "shared_prefix_tokens": 4,
           "prompt_len": {"dist": "fixed", "value": 6},
           "output_len": {"dist": "uniform", "lo": 2, "hi": 3}}
    reqs = traffic.serve_requests(mix, 50, 7)
    assert all(len(p) == 6 for p, _ in reqs)
    assert len({tuple(p[:4]) for p, _ in reqs}) == 1
    assert all(2 <= o <= 3 for _, o in reqs)
    with pytest.raises(ValueError):
        traffic.draw_lengths(np.random.RandomState(0), {"dist": "zipf"}, 1)


def _profile():
    from jax.profiler import ProfileData

    text = open(os.path.join(DATA, "trace_small.textproto")).read()
    return ProfileData.from_text_proto(text)


def test_trace_reduction_busy_union_gaps_top_ops_and_all_reduce():
    red = trace.reduce(_profile())
    # the window is the bench.window span: 1000 ns .. 11000 ns
    assert red["window_s"] == pytest.approx(10000e-9)
    assert red["devices"] == 2
    # device 0: [0,3) u [5,6) u [8,9) = 5 us (overlap counted once);
    # device 1: 5 us; mean 5 us
    assert red["busy_s"] == pytest.approx(5000e-9)
    ops = dict(red["device_ops"])
    # summed over devices, per device: fusion.1 = (2 + 1 + 5) / 2 us
    assert ops["fusion.1"] == pytest.approx(4000e-9)
    assert ops["convolution.7"] == pytest.approx(1000e-9)
    assert "jit_step" not in ops  # the coarser modules line is not counted
    assert red["collective_s"] == pytest.approx(1000e-9)
    gaps = dict(red["idle_gaps"])
    # device 0 idle: [3,5) after convolution.7, [6,8) after all-reduce.3,
    # [9,10) after fusion.1, each under the bench.step that covers it
    assert gaps["bench.step|after:convolution.7"] == pytest.approx(2000e-9)
    assert gaps["bench.step|after:all-reduce.3"] == pytest.approx(2000e-9)
    assert gaps["bench.step|after:fusion.1"] == pytest.approx(1000e-9)
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - 5000e-9)
    assert red["n_ops"] == 5


def test_trace_reduction_of_a_trace_without_device_planes_is_empty():
    prof = types.SimpleNamespace(planes=[types.SimpleNamespace(
        name="/host:CPU", lines=[types.SimpleNamespace(
            name="python", events=[types.SimpleNamespace(
                name="bench.step", start_ns=0.0, duration_ns=10.0)])])])
    red = trace.reduce(prof)
    assert red["busy_s"] == 0.0 and red["n_ops"] == 0
    assert red["device_ops"] == [] and red["idle_gaps"] == []


def test_union_merges_overlaps():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == \
        [(0, 4), (5, 6)]


# what the parent of PR 42 read on three fixture traces (busy_s, window_s,
# collective_s, devices, device_ops; the first device's idle time): keeping
# the executors' spans beside the benchmark's own names the idle gaps (the
# keys, largest first) and moves nothing else
HELD = {
    "trace_turnaround.textproto": (
        0.3908, 0.42, 0.0, 1, [["fusion.1", 0.3908]],
        ["executor.run|after:fusion.1", "executor.step|after:fusion.1",
         "executor.run|after:window-start"], 0.42 - 0.3908),
    "trace_moe_scopes.textproto": (
        9.7e-05, 0.0001, 0.0, 1,
        [["conditional.2", 3.2e-05], ["conditional.1", 3e-05],
         ["gmm.1", 1.2e-05], ["tgmm.1", 1.2e-05], ["fusion.5", 1e-05],
         ["gmm.2", 1e-05], ["fusion.1", 8e-06], ["fusion.4", 8e-06],
         ["fusion.7", 8e-06], ["fusion.6", 6e-06]],
        ["executor.step|after:window-start", "bench.window|after:fusion.8"],
        3e-06),
    "trace_step_spans.textproto": (
        2.775e-05, 4e-05, 0.0, 2,
        [["fusion.1", 2.5e-05], ["fusion.2", 1.75e-06],
         ["copy-done.6", 1e-06]],
        ["executor.plan|after:fusion.2", "executor.fetch|after:fusion.1",
         "executor.stage|after:copy-done.6", "executor.step|after:fusion.1"],
        2.45e-05),
}


@pytest.mark.parametrize("name", sorted(HELD))
def test_the_executors_spans_name_the_gaps_and_move_no_other_reading(name):
    from jax.profiler import ProfileData

    busy, window, coll, devices, ops, gap_keys, idle = HELD[name]
    red = trace.reduce(ProfileData.from_text_proto(
        open(os.path.join(DATA, name)).read()))
    assert red["busy_s"] == pytest.approx(busy, rel=1e-12)
    assert red["window_s"] == pytest.approx(window, rel=1e-12)
    assert red["collective_s"] == coll and red["devices"] == devices
    assert [k for k, _ in red["device_ops"]] == [k for k, _ in ops]
    for (_, got), (_, want) in zip(red["device_ops"], ops):
        assert got == pytest.approx(want, rel=1e-12)
    assert [k for k, _ in red["idle_gaps"]] == gap_keys
    # the gaps are the first device's, and all of its idle time
    assert sum(v for _, v in red["idle_gaps"]) == pytest.approx(idle)


def test_one_traced_run_is_read_and_parsed_once_for_all_four_modules(
        trace_root, monkeypatch):
    """trace.load (the harness's own reduction), step_spans, turnaround and
    scope_time read the newest .xplane.pb through trace.newest_parsed: the
    bytes are read once and ProfileData is built from them once, and a file
    written anew is read anew."""
    from jax.profiler import ProfileData

    from benchmark.harness import scope_time, step_spans, turnaround

    built = []
    real = ProfileData.from_serialized_xspace
    monkeypatch.setattr(ProfileData, "from_serialized_xspace",
                        lambda raw: built.append(len(raw)) or real(raw))
    monkeypatch.setattr(ProfileData, "from_file", lambda path: 1 / 0)
    trace_root("trace_turnaround.textproto", cell="resnet50-train")
    logdir = os.path.join(trace.TRACE_ROOT, "resnet50-train")
    profile = trace.load(logdir)
    assert trace.reduce(profile)["n_ops"] == 4
    assert step_spans.newest()["steps"] == 4
    assert turnaround.newest()["boundaries"] == 2
    prof, scopes = scope_time.newest()
    assert prof is profile and scopes == {0: {}, 1: {}}
    assert scope_time.newest()[1] is scopes
    assert turnaround.newest() is turnaround.newest()
    assert len(built) == 1
    # the next traced run of the process writes its file anew
    path = trace.newest_trace()
    os.utime(path, (os.path.getmtime(path) + 5,) * 2)
    assert step_spans.newest()["steps"] == 4
    assert len(built) == 2 and len(trace._parsed) == 1
    with pytest.raises(FileNotFoundError):
        trace.load(os.path.join(trace.TRACE_ROOT, "no-such-cell"))


class _FakeExecutable:
    def __init__(self, temp):
        self.temp = temp

    def get_compiled_memory_stats(self):
        if self.temp is None:
            raise RuntimeError("no statistics for this executable")
        return types.SimpleNamespace(temp_size_in_bytes=self.temp)


class _FakeChip:
    """A device whose allocator and client say what the test sets."""

    def __init__(self, limit=16_909_000_000):   # a v5e's: 15.75 GiB
        self.in_use = self.peak = 0
        self.limit = limit
        self.executables = []
        self.client = types.SimpleNamespace(
            live_executables=lambda: list(self.executables))

    def memory_stats(self):
        return {"bytes_in_use": self.in_use, "peak_bytes_in_use": self.peak,
                "bytes_limit": self.limit}


GB = 10 ** 9


@pytest.mark.parametrize("first,window,want,which", [
    # state 6.8 + FirstStep's copy 2.3 at the first step; the window holds
    # the state alone: the first step's sum wins
    (9.1, 6.8, 9.1 + 5.0, "first"),
    # something the window keeps beside the state (a staged batch) that the
    # first step had not yet: the window's sum wins
    (6.9, 7.4, 7.4 + 5.0, "window"),
])
def test_memory_peak_is_a_sum_of_two_numbers_of_one_moment(first, window,
                                                            want, which):
    chip = _FakeChip()
    startup, copies = _FakeExecutable(1 * GB), _FakeExecutable(None)
    chip.executables = [startup, copies]
    mem = device.StepMemory([chip])
    chip.in_use = int(first * GB)
    mem.before_first_step()
    # the first step compiles the step's program (5.0 GB of temporaries)
    # and something small; the plain reference (9.0 GB) comes after it
    step = _FakeExecutable(5 * GB)
    chip.executables += [step, _FakeExecutable(2 * 10 ** 6)]
    mem.after_first_step()
    chip.executables.append(_FakeExecutable(9 * GB))
    # the reference's moment: the allocator's peak of the whole run
    chip.peak = int(13.7 * GB)
    chip.in_use = int(window * GB)
    mem.between_window_steps()
    assert mem.step_temp == 5 * GB
    assert mem.peak() == pytest.approx(want * GB)
    assert (mem.first_in_use > mem.window_in_use) == (which == "first")
    # never the parent's sum of two peaks of different moments, which is
    # over what the chip holds though the run did not fail
    old = device.allocator_peak_bytes([chip]) \
        + device.largest_program_temp_bytes([chip])
    assert old == pytest.approx(22.7 * GB) and not device.fits(old, chip.limit)
    assert mem.peak() < old and device.fits(mem.peak(), chip.limit)
    assert device.memory_limit_bytes([chip]) == chip.limit


def test_memory_peak_takes_the_fullest_chip_and_a_backend_without_stats():
    a, b = _FakeChip(), _FakeChip()
    mem = device.StepMemory([a, b])
    a.in_use, b.in_use = 3 * GB, 4 * GB
    mem.before_first_step()
    a.executables.append(_FakeExecutable(2 * GB))
    mem.after_first_step()
    a.in_use, b.in_use = 3 * GB, 2 * GB
    mem.between_window_steps()
    assert (mem.first_in_use, mem.window_in_use) == (4 * GB, 3 * GB)
    assert mem.peak() == 6 * GB
    # the CPU of a rehearsal keeps no statistics: the temporaries alone,
    # no limit, and any reading fits
    held = [_FakeExecutable(7)]
    cpu = types.SimpleNamespace(
        memory_stats=lambda: None, client=types.SimpleNamespace(
            live_executables=lambda: list(held)))
    mem = device.StepMemory([cpu])
    mem.before_first_step()
    mem.after_first_step()          # nothing new: no step program known
    mem.between_window_steps()
    assert mem.peak() == 0 and device.memory_limit_bytes([cpu]) is None
    assert device.fits(10 ** 12, None)


READERS = {
    "compiles_in_window.train": ({"kind": "train", "compiles_in_window": 0},
                                 0.0),
    "compiles_in_window.serve": ({"kind": "serve", "compiles_in_window": 2},
                                 2.0),
    "mfu.train": ({"kind": "train", "platform": "tpu",
                   "device_kind": "TPU v5 lite", "chips": 2, "steps": 10,
                   "samples_per_step": 197, "window_s": 10.0,
                   "flops_per_sample": 1e12}, 50.0),
    "collective_ms.train": ({"kind": "train", "chips": 4, "trace_steps": 4,
                             "trace": {"n_ops": 9, "collective_s": 0.02}},
                            5.0),
    "loop_step_ms.serve": ({"kind": "serve", "window_s": 3.0,
                            "loop_steps": 30}, 100.0),
    "prefill_step_share.serve": ({"kind": "serve", "loop_steps": 40,
                                  "loop_prefill_steps": 10}, 25.0),
    "hbm_peak_gb.serve": ({"kind": "serve", "memory_peak_bytes": 9.5e9},
                          9.5),
    "batch_occupancy.serve": ({"kind": "serve", "occupancy": 0.75}, 75.0),
    "paged_fallbacks.serve": ({"kind": "serve", "paged_fallbacks": 0}, 0.0),
    "device_idle.train": ({"kind": "train", "trace": {
        "n_ops": 3, "busy_s": 0.9, "window_s": 1.0}}, 10.0),
    "device_idle.serve": ({"kind": "serve", "trace": {
        "n_ops": 3, "busy_s": 0.6, "window_s": 1.0}}, 40.0),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_layer_metric_reader(name):
    reader = manifest.load_py(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))
    obs, want = READERS[name]
    assert reader.read(obs) == pytest.approx(want)
    # a reader that finds nothing to read returns nothing
    assert reader.read({}) is None
    other = "serve" if obs["kind"] == "train" else "train"
    assert reader.read({**obs, "kind": other}) is None


def test_every_reader_tested_here_is_in_the_manifest_or_parked():
    # a later PR's readers bring their cases in a test file of their own
    man = manifest.load_manifest(PARKED)
    assert set(READERS) <= {m["name"] for m in man["per_layer"]}
    assert {n for n in READERS if n.endswith(".train")} <= {
        m["name"] for m in manifest.load_manifest()["per_layer"]}


def test_mfu_reader_refuses_an_unknown_tpu_and_skips_a_cpu():
    reader = manifest.load_py(os.path.join(
        REPO, "benchmark", "layer_metrics", "mfu.train.py"))
    obs = dict(READERS["mfu.train"][0])
    with pytest.raises(KeyError):
        reader.read({**obs, "device_kind": "TPU v9 imaginary"})
    assert reader.read({**obs, "platform": "cpu", "device_kind": "cpu"}) \
        is None


def test_cell_overlays_rehearsal_sizes_only_on_request():
    man = manifest.load_manifest(PARKED)
    real = manifest.Cell(man, "decoder2048-batch")
    tiny = manifest.Cell(man, "decoder2048-batch", rehearse=True)
    assert real.config["d_model"] == 2048 and real.config["n_layer"] == 12
    assert real.sizing["pool_pages"] == 1408 and real.kind == "serve"
    assert tiny.config["d_model"] < 2048 and tiny.sizing["pool_pages"] < 1408
    assert {"decode_tokens_per_s", "tpot_p95_ms", "ttft_p95_ms",
            "setup_s"} == {m["name"] for m in real.metrics("end_to_end")}
    with pytest.raises(KeyError):
        manifest.Cell(man, "no-such-cell")
    # the parked cell is not in the manifest the driver reads
    with pytest.raises(KeyError):
        manifest.Cell(manifest.load_manifest(), "decoder2048-batch")
    dp4 = manifest.Cell(man, "transformer-train-dp4")
    assert dp4.sizing["mesh"] == {"dp": 4}
    assert dp4.traffic["per_chip_batches"] == 4 == dp4.chips


def test_on_device_weights_have_init_decode_params_structure():
    import jax
    from paddle_tpu import serving

    man = manifest.load_manifest(PARKED)
    cell = manifest.Cell(man, "decoder2048-batch", rehearse=True)
    mod = cell.config_module
    dcfg = mod.decode_config(cell.config, 32)
    want = serving.init_decode_params(dcfg, seed=0)
    got = mod.build_params(dcfg, 3000000019, jax.devices()[0])
    flat_w, tree_w = jax.tree_util.tree_flatten(want)
    flat_g, tree_g = jax.tree_util.tree_flatten(got)
    assert tree_w == tree_g
    for w, g in zip(flat_w, flat_g):
        assert w.shape == g.shape and w.dtype == g.dtype
    np.testing.assert_array_equal(np.asarray(got["pos"]), want["pos"])
    # the same scale: unit-variance rows after the 1/sqrt(fan_in)
    wq = np.asarray(got["layers"][0]["wq"])
    assert abs(wq.std() * np.sqrt(wq.shape[0]) - 1.0) < 0.1
    assert float(np.asarray(got["layers"][0]["ln1_g"]).min()) == 1.0
    # the same seed gives the same weights, another seed others
    again = mod.build_params(dcfg, 3000000019, jax.devices()[0])
    np.testing.assert_array_equal(np.asarray(again["embed"]),
                                  np.asarray(got["embed"]))
    other = mod.build_params(dcfg, 1, jax.devices()[0])
    assert not np.array_equal(np.asarray(other["embed"]),
                              np.asarray(got["embed"]))


def test_plain_reference_agrees_with_the_programs_oracle_at_a_small_size():
    import jax
    from paddle_tpu import serving

    man = manifest.load_manifest(PARKED)
    cell = manifest.Cell(man, "decoder2048-batch", rehearse=True)
    mod = cell.config_module
    dcfg = mod.decode_config(cell.config, 32)
    params = mod.build_params(dcfg, 5, jax.devices()[0])
    toks = np.random.RandomState(0).randint(1, dcfg.vocab_size, size=(2, 20))
    ref = mod.reference_forward(params, dcfg, toks)
    for i in range(2):
        want = serving.full_forward(params, dcfg, toks[i])
        np.testing.assert_allclose(ref[i], want, atol=2e-5)
    # padding at the end changes no earlier row
    short = mod.reference_forward(params, dcfg, toks[:, :12])
    np.testing.assert_allclose(short, ref[:, :12], atol=2e-5)


def _first_step(cell_name, dropout=None, batch=4):
    """The cell's program at its rehearsal size on the CPU: one step, and
    what harness/reference.py makes of it."""
    import jax
    import paddle_tpu as fluid

    cell = manifest.Cell(manifest.load_manifest(), cell_name, rehearse=True)
    if dropout is not None:
        cell.config["dropout"] = dropout
    spec = cell.config_module.build(cell.config, 7)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = jax.device_put(cell.config_module.make_batch(
        cell.config, spec, batch, 7))
    first = reference.FirstStep(cell, spec)
    loss = float(np.ravel(np.asarray(
        exe.run(feed=feed, fetch_list=[spec.loss])[0]))[0])
    return first.compare(loss, feed, batch)


@pytest.mark.parametrize("cell_name,dropout", [
    ("resnet50-train", None), ("transformer-train", 0.0)])
def test_plain_reference_agrees_with_the_program_where_nothing_is_random(
        cell_name, dropout):
    found, problems = _first_step(cell_name, dropout)
    assert problems == []
    assert found["loss_rel"] < 1e-5
    assert found["grad_cos"] > 1 - 1e-6
    assert abs(found["grad_norm_ratio"] - 1) < 1e-4
    assert found["param_norm_far"] < 1.001


def test_plain_reference_takes_dropout_at_its_mean():
    found, problems = _first_step("transformer-train", batch=8)
    assert problems == []               # the rehearsal's loose tolerances
    assert 1e-5 < found["loss_rel"] < 0.05
    assert 0.5 < found["grad_cos"] < 1 - 1e-6


TOL = {"loss_rtol": 0.01, "grad_cos_min": 0.9, "grad_norm_rtol": 0.1,
       "param_norm_factor": 1.5}


@pytest.mark.parametrize("loss,prods,words", [
    # the same gradient: nothing to say
    (2.0, {"a": (4.0, 4.0, 4.0), "b": (1.0, 1.0, 1.0)}, []),
    # a loss 5% off
    (2.1, {"a": (4.0, 4.0, 4.0)}, ["loss"]),
    # half of the backward pass skipped: b's gradient is 0
    (2.0, {"a": (4.0, 4.0, 4.0), "b": (0.0, 0.0, 4.0)},
     ["cosine", "norm", "gradient norm of b"]),
    # a sum over four chips where a mean was due
    (2.0, {"a": (16.0, 64.0, 4.0)}, ["norm", "gradient norm of a"]),
    # the opposite sign
    (2.0, {"a": (-4.0, 4.0, 4.0)}, ["cosine"]),
    # a parameter with next to no gradient is not judged alone
    (2.0, {"a": (4.0, 4.0, 4.0), "b": (0.0, 1e-3, 1e-5)}, []),
])
def test_first_step_judgement(loss, prods, words):
    found = reference.judge(loss, 2.0, prods)
    said = reference.problems(found, TOL)
    assert len(said) == len(words)
    for line, word in zip(said, words):
        assert word in line
