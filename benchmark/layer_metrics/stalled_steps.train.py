"""Steps of the measured window whose period is over the window's median by
more than max(5 ms, 5% of it) (kind train).

One key of benchmark/harness/step_log.py::summary, which cuts the program's
always-on step log to the measured window."""

from benchmark.harness import step_log


def read(obs):
    return step_log.reading(obs, "stalled_steps")
