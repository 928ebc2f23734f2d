"""jax's tracing and lowering to StableHLO of the program's executables (those
inside the start-up program's and the step program's first runs): paid warm
and cold alike (kind train).

One key of benchmark/harness/setup_log.py::summary, which cuts the program's
set-up log at the window's start."""

from benchmark.harness import setup_log


def read(obs):
    return setup_log.reading(obs, "trace_lower_s")
