"""The bytes of the persistent cache's entries this run loaded or wrote before
the window, the reference's among them: what the cell holds of the cache
(kind train); None where the cache is off.

One key of benchmark/harness/setup_log.py::summary, which cuts the program's
set-up log at the window's start."""

from benchmark.harness import setup_log


def read(obs):
    return setup_log.reading(obs, "cache_entries_mb")
