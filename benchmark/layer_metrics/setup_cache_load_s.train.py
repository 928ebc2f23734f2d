"""The persistent cache's retrieval of the program's executables it had: read,
decompress, deserialize, load (kind train); None where the cache is off.

One key of benchmark/harness/setup_log.py::summary, which cuts the program's
set-up log at the window's start."""

from benchmark.harness import setup_log


def read(obs):
    return setup_log.reading(obs, "cache_load_s")
