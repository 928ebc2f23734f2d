"""The backend's compile of the program's executables that the persistent
cache did not have (or was not asked for): 0 in a warm run (kind train).

One key of benchmark/harness/setup_log.py::summary, which cuts the program's
set-up log at the window's start."""

from benchmark.harness import setup_log


def read(obs):
    return setup_log.reading(obs, "compile_s")
