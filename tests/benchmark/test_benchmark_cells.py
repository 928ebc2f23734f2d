"""Every cell end to end at its rehearsal size on the CPU, through the
benchmark's own command, and the refusal to measure without the chips."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
PARKED = {"decoder2048-batch": json.load(open(os.path.join(
    REPO, "benchmark", "parked", "decoder2048-batch.manifest.json")))}
CHIPS = {c["name"]: c["chips"] for m in [MANIFEST, *PARKED.values()]
         for c in m["workloads"]}
CELLS = sorted(CHIPS)
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(cell, *extra, devices=1, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "BENCH_RUN")}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="ignored",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable if c == "python3" else os.path.join(REPO, c)
           for c in MANIFEST["command"]]
    if cell in PARKED:
        extra = ("--parked", cell, *extra)
    return subprocess.run(
        cmd + ["--workload", cell, "--seed", "3000000019",
               "--seconds", "1", *extra],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env)


def _metrics_of(cell, group):
    entries = MANIFEST[group] + PARKED.get(cell, {}).get(group, [])
    return {m["name"]: m["unit"] for m in entries
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_end_to_end_on_the_cpu(cell, trace):
    """A declared CPU rehearsal: tiny sizes, the line marked as one, exit
    code 3, and only the contract's keys besides the mark."""
    chips = CHIPS[cell]
    out = _run(cell, "--trace", str(trace), "--rehearse", devices=chips)
    assert out.returncode == 3, out.stdout[-2000:] + out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line.pop("rehearsal") is True
    # each number `correct` compared beside its limit: the line's last key
    # and the last lines of stderr
    assert list(line)[-1] == "compared"
    compared = line.pop("compared")
    if cell not in PARKED:
        assert {"loss_rel", "grad_cos_min", "grad_norm_off_1",
                "param_norm_far", "compiles_in_window"} <= set(compared)
        assert ("copies_apart" in compared) == (chips > 1)
    said = out.stderr.strip().splitlines()[-len(compared):] if compared \
        else []
    for (name, (value, limit)), text in zip(compared.items(), said):
        assert text == f"[bench] compared {name}: {value} limit {limit}"
    if trace:
        breakdown = line.pop("breakdown")
        assert set(breakdown) == {"device_ops", "idle_gaps"}
        assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line) == LINE_KEYS
    assert line["correct"] is True, out.stdout[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert "memory_peak_bytes" in line["device"]
    allowed = _metrics_of(cell, "per_layer" if trace else "end_to_end")
    assert line["metrics"], "no metric at all"
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == allowed[name]
        assert isinstance(m["value"], float)
    if trace:
        # counts the CPU can give are there; device shares are not
        assert not any(n.startswith(("device_idle", "mfu", "collective"))
                       for n in line["metrics"])
        assert any(n.startswith("compiles_in_window")
                   for n in line["metrics"])
        zero = [n for n in line["metrics"]
                if n.startswith(("compiles_in_window", "paged_fallbacks"))]
        assert all(line["metrics"][n]["value"] == 0 for n in zero)
    else:
        assert set(line["metrics"]) == set(allowed)
        assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_refuses_to_measure_without_its_chips(cell):
    out = _run(cell, "--trace", "0", devices=4, timeout=120)
    assert out.returncode not in (0, 3), out.stdout + out.stderr
    assert "no TPU" in out.stderr
    assert '"metrics"' not in out.stdout and '"device"' not in out.stdout


def test_an_unknown_cell_is_an_error():
    out = _run("no-such-cell", "--trace", "0", "--rehearse", timeout=120)
    assert out.returncode not in (0, 3)
    assert '"metrics"' not in out.stdout
