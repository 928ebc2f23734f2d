"""Tracing, lowering and building or loading of the executables inside NO first
run of the program before the window: the plain reference and the harness's
helpers, the benchmark's own cost (kind train).

One key of benchmark/harness/setup_log.py::summary, which cuts the program's
set-up log at the window's start."""

from benchmark.harness import setup_log


def read(obs):
    return setup_log.reading(obs, "other_compile_s")
