"""BENCHMARK.json against the benchmark's contract, and every name in it
against a file of its own."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
PARKED = [json.load(open(os.path.join(REPO, "benchmark", "parked", f)))
          for f in sorted(os.listdir(os.path.join(REPO, "benchmark",
                                                  "parked")))]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def _path(*parts):
    return os.path.join(REPO, *parts)


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    # 2 + 14 x cells runs of run_seconds + 60 s, 180 s a cell to compile and
    # 1200 s spare fit 43200 s with the full 24 cells
    assert (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configurations_entry_and_its_files(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"])
    assert cfg["file"].startswith("benchmark/configs/")
    data = json.load(open(_path(cfg["file"])))
    # the file states source, reduced, assumed and the deployment
    for key in ("source", "reduced", "assumed", "deployment", "kind"):
        assert key in data, key
    assert data["reduced"] == cfg["reduced"]
    for key in cfg["reduced"]:
        assert NAME.match(key)
        # `num_hidden_layers` is the source's own name for the depth
        assert not re.search(
            r"(_dim|_rank|hidden_size|d_model|d_inner|head)", key), \
            f"{key} is a width"
    assert os.path.isfile(os.path.splitext(_path(cfg["file"]))[0] + ".py")
    # its plain reference beside it, and the tolerances it is held to
    if data["kind"] == "train":
        assert os.path.isfile(
            os.path.splitext(_path(cfg["file"]))[0] + ".reference.py")
        assert {"loss_rtol", "grad_cos_min", "grad_norm_rtol",
                "param_norm_factor"} <= set(data["reference"])
    if cfg["reduced"]:
        assert len(data["reduced_why"]) > 40
    assert any(w["config"] == cfg["name"] for w in MANIFEST["workloads"])
    for text in (cfg["why"], cfg["source"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_stop_gap_says_so():
    data = json.load(open(_path("benchmark/configs/decoder2048.json")))
    assert "STOP-GAP" in data["stop_gap"] and "OLMoE" in data["stop_gap"]


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda c: c["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    sizing = json.load(open(_path("benchmark/cells", cell["name"] + ".json")))
    assert sizing["config"] == cell["config"]
    assert sizing["traffic"] == cell["traffic"]
    assert sizing["chips"] == cell["chips"]
    mix = json.load(open(_path("benchmark/traffic",
                               cell["traffic"] + ".json")))
    assert mix["name"] == cell["traffic"]
    # every cell reports setup_s, another end-to-end metric and a per-layer
    e2e = [m["name"] for m in MANIFEST["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell["name"] in m.get("workloads", [cell["name"]])
               for m in MANIFEST["per_layer"])


@pytest.mark.parametrize(
    "cell,man", [(w["name"], m) for m in [MANIFEST] + PARKED
                 for w in m["workloads"]], ids=lambda v: v if isinstance(
                     v, str) else "")
def test_every_reader_named_for_a_cell_exists_and_reads_nothing_from_nothing(
        cell, man):
    """What a configuration's own test file need not repeat: every per-layer
    metric that names the cell (or names no cell, and so every one) has its
    reader, which returns nothing where it finds nothing to read."""
    # conftest.py put the repository on the path
    from benchmark.harness import manifest

    mine = [m for m in man["per_layer"]
            if cell in m.get("workloads", [cell])]
    assert mine, f"{cell} reports no per-layer metric"
    e2e = {m["name"] for m in man["end_to_end"]
           if cell in m.get("workloads", [cell])}
    for m in mine:
        path = _path("benchmark/layer_metrics", m["name"] + ".py")
        assert os.path.isfile(path), m["name"]
        assert manifest.load_py(path).read({}) is None, m["name"]
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_cells_are_distinct_and_few_take_four_chips():
    cells = MANIFEST["workloads"]
    assert len({c["name"] for c in cells}) == len(cells) <= 24
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    four = sum(c["chips"] == 4 for c in cells)
    assert four <= max(1, len(cells) // 4)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in MANIFEST["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {c["name"] for c in MANIFEST["workloads"]}
    where = set(metric.get("workloads", cells))
    assert where and where <= cells
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        # its own reader, found by name
        assert os.path.isfile(_path("benchmark/layer_metrics",
                                    metric["name"] + ".py"))
        moved = [m for m in MANIFEST["end_to_end"]
                 if m["name"] == metric["moves"]]
        assert len(moved) == 1
        # the moved metric is reported wherever this one is
        assert where <= set(moved[0].get("workloads", cells))
        assert 1 <= len(metric["layer"]) <= 200


def test_metric_names_are_distinct_and_setup_is_there():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    setup = [m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.1


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in MANIFEST["paths"]:
        for root, dirs, files in os.walk(_path(base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), REPO)
                assert ok.match(rel), rel


def test_every_reader_and_traffic_file_is_named_by_the_manifest_or_parked():
    def named(group, key):
        return {e[key] for m in [MANIFEST] + PARKED for e in m[group]}

    readers = {f[:-3] for f in os.listdir(_path("benchmark/layer_metrics"))
               if f.endswith(".py")}
    assert readers == named("per_layer", "name")
    mixes = {f[:-5] for f in os.listdir(_path("benchmark/traffic"))}
    assert mixes == named("workloads", "traffic")
    sizings = {f[:-5] for f in os.listdir(_path("benchmark/cells"))}
    assert sizings == named("workloads", "name")
    configs = {f[:-5] for f in os.listdir(_path("benchmark/configs"))
               if f.endswith(".json")}
    assert configs == named("configs", "name")


@pytest.mark.parametrize("parked", PARKED,
                         ids=lambda p: p["workloads"][0]["name"])
def test_a_parked_cell_is_whole_and_has_no_bound_yet(parked):
    """Entries a later PR adds to BENCHMARK.json as they are, but for the
    bounds it measures; nothing of them is in the manifest today."""
    assert "PARKED" in parked["what"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        mine = {e["name"] for e in MANIFEST[group]}
        assert not mine & {e["name"] for e in parked[group]}
    assert all(m["bound"] is None for m in parked["end_to_end"])
    cells = {w["name"] for w in parked["workloads"]}
    for w in parked["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200
        assert os.path.isfile(_path("benchmark/cells", w["name"] + ".json"))
        assert os.path.isfile(_path("benchmark/traffic",
                                    w["traffic"] + ".json"))
    for c in parked["configs"]:
        assert os.path.isfile(_path(c["file"]))
    e2e = {m["name"] for m in parked["end_to_end"]}
    for m in parked["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert os.path.isfile(_path("benchmark/layer_metrics",
                                    m["name"] + ".py"))
