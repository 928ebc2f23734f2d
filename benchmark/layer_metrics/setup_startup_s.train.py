"""The start-up program's first run, from before its block is built to its
last value written: the program traced, lowered, built or loaded from the
persistent cache, the weights made (kind train).

One key of benchmark/harness/setup_log.py::summary, which cuts the program's
set-up log at the window's start."""

from benchmark.harness import setup_log


def read(obs):
    return setup_log.reading(obs, "startup_s")
