"""The step program's first run, from before its block is built to the
fetched loss on the host: the step traced, lowered, built or loaded from the
persistent cache, and one step (kind train).

One key of benchmark/harness/setup_log.py::summary, which cuts the program's
set-up log at the window's start."""

from benchmark.harness import setup_log


def read(obs):
    return setup_log.reading(obs, "first_step_s")
