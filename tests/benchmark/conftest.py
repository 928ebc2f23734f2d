"""What the benchmark's test files share: a trace directory as the harness
leaves it, and how a file asks whether the manifest still holds its entries.

A configuration's or a metric's test file holds ITS OWN manifest entries:
that they are there, in their own relative order, with at least its cells in
their `workloads`.  It says nothing of what stands behind or between them,
nor of what other cells report: every later PR appends entries and cells,
and a test that pins "mine are last" or "mine alone" trips on the next one
(twelve such cases were strict expected failures until PR 42).
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def trace_root(tmp_path, monkeypatch):
    """bench_out/trace as the harness leaves it, one .xplane.pb a cell:
    `write(trace, cell=...)` puts one there, `trace` the name of a file
    under data/ or a trace's text."""
    from benchmark.harness import trace as trace_mod
    from jax.profiler import ProfileData

    def write(trace, cell="transformer-train"):
        if trace.endswith(".textproto"):
            trace = open(os.path.join(DATA, trace)).read()
        d = tmp_path / cell / "plugins" / "profile" / "2026_01_01"
        d.mkdir(parents=True, exist_ok=True)
        (d / "vm.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(trace))

    monkeypatch.setattr(trace_mod, "TRACE_ROOT", str(tmp_path))
    trace_mod._parsed.clear()
    yield write
    trace_mod._parsed.clear()


def _held(group: str, names, cells=(), **fields) -> list:
    """The entries of BENCHMARK.json's `group` called `names`: each there
    once, in the order given (whatever stands between them), with every one
    of `cells` in its `workloads` where it has the key, and equal to
    `fields` key by key."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = json.load(f)[group]
    order = [e["name"] for e in entries]
    names = list(names)
    for name in names:
        assert order.count(name) == 1, f"{group}: {name} x {order.count(name)}"
    assert sorted(names, key=order.index) == names, \
        f"{group}: {names} stand in another order"
    found = [entries[order.index(name)] for name in names]
    for e in found:
        assert set(cells) <= set(e.get("workloads", cells)), e["name"]
        for key, want in fields.items():
            assert e[key] == want, (e["name"], key)
    return found


@pytest.fixture
def manifest_holds():
    return _held
