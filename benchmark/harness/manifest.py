"""Finds everything by name: BENCHMARK.json names cells, configurations and
metrics; each has a file of its own under benchmark/.  A later PR adds files
and manifest entries and edits none of these."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(parked: str | None = None) -> dict:
    """BENCHMARK.json; with `parked`, the entries of
    benchmark/parked/<parked>.manifest.json (a cell that is not proved yet,
    for rehearsals and tests) added to its lists."""
    man = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    if parked:
        extra = read_json(os.path.join(BENCH, "parked",
                                       parked + ".manifest.json"))
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            man[key] = man[key] + extra[key]
    return man


def load_py(path: str):
    """A module from a file whose name need not be an identifier
    (`transformer-base.py`, `step_ms.train.py`)."""
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _entry(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json names no {what} {name!r}")


def overlay(data: dict, rehearse: bool) -> dict:
    """The file's sizes; under --rehearse its `rehearsal` group on top."""
    out = {k: v for k, v in data.items() if k != "rehearsal"}
    if rehearse:
        out.update(data.get("rehearsal", {}))
    return out


class Cell:
    """One entry of `workloads` with the files it names."""

    def __init__(self, manifest: dict, name: str, rehearse: bool = False):
        self.manifest = manifest
        self.entry = _entry(manifest["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        self.sizing = overlay(read_json(
            os.path.join(BENCH, "cells", name + ".json")), rehearse)
        cfg_entry = _entry(manifest["configs"], self.entry["config"],
                           "configuration")
        cfg_path = os.path.join(ROOT, cfg_entry["file"])
        self.config = overlay(read_json(cfg_path), rehearse)
        self.config_module = load_py(os.path.splitext(cfg_path)[0] + ".py")
        self.traffic = overlay(read_json(os.path.join(
            BENCH, "traffic", self.entry["traffic"] + ".json")), rehearse)
        self.kind = self.config["kind"]
        if self.traffic["kind"] != self.kind:
            raise ValueError(
                f"cell {name}: configuration {self.entry['config']} is of "
                f"kind {self.kind}, traffic {self.entry['traffic']} of kind "
                f"{self.traffic['kind']}")

    def metrics(self, group: str) -> list:
        """The manifest's metrics of `group` that this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def runner(self):
        return importlib.import_module(
            f"benchmark.harness.kind_{self.kind}")


def read_layer_metrics(cell: Cell, obs: dict) -> dict:
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        reader = load_py(os.path.join(BENCH, "layer_metrics",
                                      m["name"] + ".py"))
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
